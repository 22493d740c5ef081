"""Sender recovery from the wire bytes, inside the native batch.

``crypto.native.recover_senders_wire`` (``coreth_recover_wire`` in
native/secp256k1.cc) takes a segment's transactions as ONE buffer of
wire bytes and derives each signing hash, r, s and recovery id itself.
The reference it is held to, transaction by transaction, is the
per-transaction path it takes the work from and leaves refusals to:
``LatestSigner.sig_hash`` + ``LatestSigner.sender``.  These tests pin:

- every wire shape the walk branches on recovers the address
  ``signer.sender`` answers (so the hash is ``signer.sig_hash``'s);
- every refusal is ``ok = 0`` for that lane alone — its neighbours in
  the batch recover — and ``signer.sender`` raises for it as before;
- the engine's seam: packing a segment calls no ``sig_hash``, a
  transaction built in process rides its ``encode()``, and
  ``ReplayStats.sigs_left_to_signer`` counts exactly the refused lanes.
"""

import dataclasses
import os
import random
import sys
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import pytest

from coreth_tpu import rlp
from coreth_tpu.crypto import native, secp256k1
from coreth_tpu.types import (
    AccessListTx, DynamicFeeTx, LegacyTx, Transaction, sign_tx)
from coreth_tpu.types import transaction as txmod

import fuzz_sender_wire as F
import test_batch_recovery as T

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="no native library")

CID = F.CHAIN_ID
SIGNER = F.SIGNER
N = secp256k1.N
GWEI = 10**9


def _legacy(**kw):
    base = dict(nonce=3, gas_price=25 * GWEI, gas=21_000, to=F.TO, value=7)
    return LegacyTx(**{**base, **kw})


def _type1(**kw):
    base = dict(chain_id_=CID, nonce=3, gas_price=25 * GWEI, gas=60_000,
                to=F.TO, value=7)
    return AccessListTx(**{**base, **kw})


def _type2(**kw):
    base = dict(chain_id_=CID, nonce=3, gas_tip_cap_=GWEI,
                gas_fee_cap_=300 * GWEI, gas=60_000, to=F.TO, value=7)
    return DynamicFeeTx(**{**base, **kw})


def _signed(inner, cid=CID, parity=None):
    """``inner`` signed; with ``parity``, by the first key whose
    signature has that recovery id."""
    for key in range(0xA11CE, 0xA11CE + 64):
        tx = sign_tx(inner, key, cid)
        if parity is None or tx.inner.raw_signature()[2] == parity:
            return tx
    raise AssertionError("no key gave that parity")


def _with_sig(tx, **sig):
    """The same payload under an edited v / r / s: built in process."""
    return Transaction(dataclasses.replace(tx.inner, **sig))


def _small_scalar_sig(inner):
    """A signature whose r and s both have leading zero bytes (31 and 30
    bytes on the wire): not made by signing — any (r, s) with r a curve
    x-coordinate recovers SOME key, and the reference answers which."""
    for r in range(1 << 240, (1 << 240) + 64):
        tx = Transaction(dataclasses.replace(
            inner, v=inner.with_signature(1, 1, 0, CID).v, r=r,
            s=(1 << 232) + 5))
        try:
            SIGNER.sender(Transaction(tx.inner))
            return tx
        except ValueError:
            continue
    raise AssertionError("no small r on the curve")


ACCEPTED = {
    "legacy v 27": lambda: _signed(_legacy(), None, 0),
    "legacy v 28": lambda: _signed(_legacy(), None, 1),
    "eip155 parity 0": lambda: _signed(_legacy(), CID, 0),
    "eip155 parity 1": lambda: _signed(_legacy(), CID, 1),
    "type 1": lambda: _signed(_type1()),
    "type 2 parity 0": lambda: _signed(_type2(), CID, 0),
    "type 2 parity 1": lambda: _signed(_type2(), CID, 1),
    "type 1, access list": lambda: _signed(_type1(al=F.ACCESS)),
    "type 2, access list": lambda: _signed(_type2(al=F.ACCESS)),
    "type 2, access list past 55 bytes": lambda: _signed(_type2(
        al=[(bytes([i]) * 20, [bytes([i]) * 32] * 3) for i in range(9)])),
    "legacy, data of 56 bytes": lambda: _signed(_legacy(data=bytes(56))),
    "eip155, data of 300 bytes": lambda: _signed(
        _legacy(data=bytes(range(256)) + bytes(44))),
    "type 2, data of 70,000 bytes": lambda: _signed(
        _type2(data=bytes(range(250)) * 280, gas=5_000_000)),
    "legacy, one data byte under 0x80": lambda: _signed(
        _legacy(data=b"\x05")),
    "legacy, contract creation": lambda: _signed(
        _legacy(to=None, data=bytes(100)), None),
    "eip155, contract creation": lambda: _signed(
        _legacy(to=None, data=bytes(100))),
    "type 2, contract creation": lambda: _signed(
        _type2(to=None, data=bytes(100))),
    "eip155, zero nonce, value and price": lambda: _signed(
        _legacy(nonce=0, value=0, gas_price=0)),
    "eip155, 32-byte value": lambda: _signed(_legacy(value=(1 << 256) - 1)),
    "eip155, r and s with leading zero bytes":
        lambda: _small_scalar_sig(_legacy()),
    "type 2, r and s with leading zero bytes":
        lambda: _small_scalar_sig(_type2()),
    "eip155, s = n / 2": lambda: _with_sig(
        _small_scalar_sig(_legacy()), s=N // 2),
}


@pytest.mark.parametrize("case", list(ACCEPTED))
def test_native_walk_recovers_what_the_signer_recovers(case):
    """ok = 1 and the address ``signer.sender`` answers, for the
    transaction as built (the signer re-encodes its fields) and as
    decoded (the signer slices ``_wire``); alone, and between
    neighbours of other shapes."""
    tx = ACCEPTED[case]()
    wire = tx.encode()
    built = SIGNER.sender(Transaction(tx.inner))
    decoded = Transaction.decode(wire)
    assert decoded.encode() == wire
    assert SIGNER.sender(decoded) == built
    if tx.cached_sender() is not None:     # signed here: the key's own
        assert tx.cached_sender() == built
    assert F.recover([wire]) == [built]
    others = [t.encode() for t in F.corpus().values()]
    got = F.recover(others[:2] + [wire] + others[2:])
    assert got[2] == built and None not in got


def _high_s(tx):
    r, s, recid = tx.inner.raw_signature()
    flipped = tx.inner.with_signature(r, N - s, recid ^ 1, CID)
    return Transaction(flipped)


def _raw_wire(tx, edit):
    """``tx``'s RLP items edited and re-encoded: encodings the object
    model cannot build (a v that is no integer, too few items)."""
    wire = tx.encode()
    typed = wire[0] < 0xC0
    items = rlp.decode(wire[1:] if typed else wire)
    return wire[:1] * typed + rlp.encode(edit(items))


def _long_form_data(wire):
    """A legacy wire's 3-byte data item under 0xB8 0x03 instead of
    0x83, the list's length one more."""
    at = wire.index(b"\x83abc")
    assert wire[0] == 0xF8
    return (wire[:1] + bytes([wire[1] + 1]) + wire[2:at] + b"\xb8\x03abc"
            + wire[at + 4:])


# name -> (the lane's wire bytes, what signer.sender raises for the
# transaction those bytes decode to; None: they do not decode)
REFUSED = {
    "eip155, foreign chain id": lambda: (
        _signed(_legacy(), CID + 1).encode(), "invalid chain id"),
    "type 2, foreign chain id": lambda: (
        _signed(_type2(chain_id_=CID + 1), CID + 1).encode(),
        "invalid chain id"),
    "type 1, foreign chain id": lambda: (
        _signed(_type1(chain_id_=7), 7).encode(), "invalid chain id"),
    "eip155, high s": lambda: (
        _high_s(_signed(_legacy())).encode(), "invalid signature values"),
    "type 2, high s": lambda: (
        _high_s(_signed(_type2())).encode(), "invalid signature values"),
    "eip155, s = n / 2 + 1": lambda: (
        _with_sig(_small_scalar_sig(_legacy()), s=N // 2 + 1).encode(),
        "invalid signature values"),
    "type 2, recovery id 2": lambda: (
        _with_sig(_signed(_type2()), v=2).encode(), "y-parity"),
    "legacy, v 29": lambda: (
        _with_sig(_signed(_legacy(), None), v=29).encode(),
        "invalid chain id"),
    "legacy, v 0 (unsigned)": lambda: (
        _with_sig(_signed(_legacy()), v=0).encode(), "invalid chain id"),
    "eip155, r = 0": lambda: (
        _with_sig(_signed(_legacy()), r=0).encode(),
        "invalid signature values"),
    "type 2, s = 0": lambda: (
        _with_sig(_signed(_type2()), s=0).encode(),
        "invalid signature values"),
    "eip155, r = n": lambda: (
        _with_sig(_signed(_legacy()), r=N).encode(),
        "invalid signature values"),
    "eip155, a 33-byte s": lambda: (
        _with_sig(_signed(_legacy()), s=(1 << 256) + 5).encode(),
        "invalid signature values"),
    "type 2, a 33-byte r": lambda: (
        _with_sig(_signed(_type2()), r=(1 << 256) + 5).encode(),
        "invalid signature values"),
    "eip155, r is no x-coordinate": lambda: (
        _with_sig(_signed(_legacy()), r=5).encode(),
        "invalid signature"),
    "a truncated encoding": lambda: (
        _signed(_legacy()).encode()[:-7], None),
    "an item that overruns its offset": lambda: (
        (lambda w: w[:-33] + b"\xa1" + w[-32:])(_signed(_type2()).encode()),
        None),
    "a list that overruns its offset": lambda: (
        (lambda w: w[:1] + bytes([w[1] + 1]) + w[2:])(
            _signed(_legacy()).encode()), None),
    "bytes after the list": lambda: (
        _signed(_legacy()).encode() + b"\x80", None),
    "an empty lane": lambda: (b"", None),
    "an unknown type byte": lambda: (
        b"\x03" + _signed(_type2()).encode()[1:], None),
    "eight items": lambda: (
        _raw_wire(_signed(_legacy()), lambda it: it[:8]), None),
    "ten items": lambda: (
        _raw_wire(_signed(_legacy()), lambda it: it + [b""]), None),
    "a nonce with a leading zero byte": lambda: (
        _raw_wire(_signed(_legacy()),
                  lambda it: [b"\x00\x03"] + it[1:]), None),
    "a wrapped single byte": lambda: (
        (lambda w: w[:1] + bytes([w[1] + 1]) + b"\x81" + w[2:])(
            _signed(_legacy()).encode()), None),
    "a long-form length where the short fits": lambda: (
        _long_form_data(_signed(_legacy(data=b"abc")).encode()), None),
    "an r with a leading zero byte": lambda: (
        _raw_wire(_signed(_legacy()),
                  lambda it: it[:7] + [b"\x00" + it[7][1:]] + it[8:]),
        None),
    "a list where v belongs": lambda: (
        _raw_wire(_signed(_type2()),
                  lambda it: it[:9] + [[]] + it[10:]), None),
    "a string where the access list belongs": lambda: (
        _raw_wire(_signed(_type2()),
                  lambda it: it[:8] + [b""] + it[9:]), None),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_refused_lane_is_left_to_the_signer_alone(case):
    """ok = 0 for the lane, every neighbour recovered, and the
    per-transaction path refuses it by its own rule, as before."""
    wire, raises = REFUSED[case]()
    txs = F.corpus()
    others = [t.encode() for t in txs.values()]
    want = [t.cached_sender() for t in txs.values()]
    got = F.recover(others[:3] + [wire] + others[3:])
    assert got[3] is None
    assert got[:3] + got[4:] == want
    assert F.recover([wire]) == [None]
    if raises is None:
        with pytest.raises(ValueError):
            Transaction.decode(wire)
    else:
        with pytest.raises(ValueError, match=raises):
            SIGNER.sender(Transaction.decode(wire))


def test_empty_batch_and_bad_arguments():
    assert native.recover_senders_wire(b"", [0], CID) == (b"", b"")
    assert native.recover_senders_wire(b"\x01\x02", [2], CID) == (b"", b"")
    with pytest.raises(ValueError):
        native.recover_senders_wire(b"", [], CID)
    with pytest.raises(ValueError):
        native.recover_senders_wire(b"", [0], 1 << 64)


@pytest.mark.parametrize("chain_id", [0, 1, 127, 128, 1 << 32,
                                      (1 << 63) - 19, (1 << 64) - 1])
def test_chain_ids_by_width(chain_id):
    """The chain id is re-encoded by the walk for an EIP-155 hash
    (single byte, short string) and compared for a typed one; past
    2**63 - 19, where v no longer fits 64 bits, EIP-155 lanes are
    left to the signer — which recovers them."""
    signer = txmod.LatestSigner(chain_id)
    legacy = sign_tx(_legacy(), 0xBEEF, chain_id)
    typed = sign_tx(_type2(chain_id_=chain_id), 0xBEEF, chain_id)
    want = legacy.cached_sender()
    assert signer.sender(Transaction(legacy.inner)) == want
    got = F.recover([legacy.encode(), typed.encode()], chain_id)
    fits = 35 + 2 * chain_id + 1 < 1 << 64
    assert got == [want if fits else None, want]


def test_mutants_cuts_and_threads_agree_with_the_python_decoder():
    """The hostile corpus the sanitizer suites replay (truncations,
    lying lengths, byte edits, two threads at once), here against the
    production library for its assertions alone."""
    txs = F.corpus()
    assert F.cuts(txs) > 1000
    assert F.mutations(txs, random.Random(39), rounds=150) > 100
    assert F.threads(txs, rounds=5) == 10


# ------------------------------------------------------- the engine's seam
_engine = T._engine


def _block(txs):
    return SimpleNamespace(transactions=list(txs))


def _engine_chain_txs(n=6):
    return [sign_tx(_type2(chain_id_=T.CFG.chain_id, nonce=i), 0x7A00 + i,
                    T.CFG.chain_id) for i in range(n)]


@pytest.mark.parametrize("path", ["pipeline", "warm_senders"])
def test_packing_a_segment_calls_no_sig_hash(monkeypatch, path):
    """Neither form of the batch walks a transaction's fields: no
    ``sig_hash`` (signer's or a payload's), no ``raw_signature``."""
    from coreth_tpu.replay.engine import _SenderPipeline

    def boom(*_a, **_k):
        raise AssertionError("per-transaction path entered")

    signed = _engine_chain_txs()
    txs = [Transaction.decode(t.encode()) for t in signed]
    eng = _engine()
    monkeypatch.setattr(txmod.LatestSigner, "sig_hash", boom)
    for cls in (LegacyTx, AccessListTx, DynamicFeeTx):
        monkeypatch.setattr(cls, "sig_hash", boom)
        monkeypatch.setattr(cls, "raw_signature", boom)
    blocks = [_block(txs[:4]), _block(txs[4:])]
    if path == "pipeline":
        _SenderPipeline(eng, blocks).ensure(1)
    else:
        eng.warm_senders(blocks)
    assert [t.cached_sender() for t in txs] == \
        [t.cached_sender() for t in signed]
    st = eng.stats
    assert st.sigs_host == len(txs) and st.recover_degraded == 0
    assert st.sigs_left_to_signer == 0


def test_transaction_without_wire_recovers_through_encode():
    """Built in process, never decoded: no ``_wire`` to collect, so the
    segment's buffer holds ``encode()`` — beside decoded neighbours."""
    signed = _engine_chain_txs()
    built = [Transaction(t.inner) for t in signed[:3]]
    decoded = [Transaction.decode(t.encode()) for t in signed[3:]]
    assert not any(hasattr(t.inner, "_wire") for t in built)
    assert all(hasattr(t.inner, "_wire") for t in decoded)
    eng = _engine()
    todo, wire, offsets = eng._pack_sigs([_block(built + decoded)])
    assert todo == built + decoded
    assert wire == b"".join(t.encode() for t in signed)
    assert offsets[0] == 0 and offsets[-1] == len(wire)
    assert len(offsets) == len(signed) + 1
    eng.warm_senders([_block(built + decoded)])
    assert [t.cached_sender() for t in built + decoded] == \
        [t.cached_sender() for t in signed]
    # all cached now: nothing left to pack
    assert eng._pack_sigs([_block(built + decoded)]) == ([], b"", [0])


def test_transaction_that_does_not_encode_costs_its_lane_only():
    signed = _engine_chain_txs(3)
    broken = Transaction(dataclasses.replace(signed[1].inner, value=-1))
    txs = [Transaction(signed[0].inner), broken, Transaction(signed[2].inner)]
    eng = _engine()
    eng.warm_senders([_block(txs)])
    assert txs[0].cached_sender() == signed[0].cached_sender()
    assert txs[2].cached_sender() == signed[2].cached_sender()
    assert broken.cached_sender() is None
    assert eng.stats.sigs_left_to_signer == 1
    assert eng.stats.recover_degraded == 0


def test_sigs_left_to_signer_counts_exactly_the_refused_lanes():
    """Three refusals among nine lanes, over two batches: the counter
    reads 3 in the stats row, the stream's report, the live /report
    payload and the metrics registry, beside ``sigs_host`` — which counts every lane of a
    batch that completed."""
    from coreth_tpu.metrics import Registry
    from coreth_tpu.replay.engine import _SenderPipeline
    from coreth_tpu.serve import ChainFeed, StreamingPipeline

    cid = T.CFG.chain_id
    signed = _engine_chain_txs(9)
    txs = [Transaction.decode(t.encode()) for t in signed]
    refused = {
        1: _high_s(signed[1]),
        4: sign_tx(_type2(chain_id_=cid + 1, nonce=4), 0x7A04, cid + 1),
        7: _with_sig(signed[7], v=2),
    }
    for i, bad in refused.items():
        txs[i] = Transaction.decode(bad.encode())
    eng = _engine()
    pipe = _SenderPipeline(eng, [_block(txs[:5])])
    pipe.ensure(0)
    assert eng.stats.sigs_left_to_signer == 2
    eng.warm_senders([_block(txs[5:])])
    st = eng.stats
    assert st.sigs_left_to_signer == 3 and st.sigs_host == 9
    assert st.recover_degraded == 0
    assert st.row()["sigs_left_to_signer"] == 3
    for i, tx in enumerate(txs):
        if i in refused:
            assert tx.cached_sender() is None
            with pytest.raises(ValueError):
                eng.signer.sender(tx)
        else:
            assert tx.cached_sender() == signed[i].cached_sender()
    reg = Registry()
    eng.publish_metrics(reg)
    assert reg.snapshot()["replay/sigs_left_to_signer"]["value"] == 3
    stream = StreamingPipeline(eng, ChainFeed([]))
    assert stream.run().lanes["sigs_left_to_signer"] == 3
    assert stream._live_report()["lanes"]["sigs_left_to_signer"] == 3
    # the fast path carried every lane it vouched for: the fallback's
    # count rides the same row, registry and report
    assert st.sigs_slow_path == 0
    assert reg.snapshot()["replay/sigs_slow_path"]["value"] == 0
    assert stream._live_report()["lanes"]["sigs_slow_path"] == 0

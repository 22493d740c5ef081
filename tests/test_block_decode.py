"""The one-pass wire decoder against a reference written here.

``Block.decode`` / ``Transaction.decode`` walk the wire bytes by offsets
(``rlp.payload_span`` / ``rlp.span_items``).  The reference below is the
decoder they replaced: ``rlp.decode`` builds the whole item tree, the
dataclasses are filled from it.  It states the accepted SHAPE outright
(a list where a list belongs, a string where a string does), because a
byte flip turns one into the other and the tree-building decoder met
those with ``TypeError`` / ``IndexError`` or an object holding a list
for an address.  Both sides must agree on every input: equal blocks, or
``ValueError`` from both.
"""

import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from coreth_tpu import rlp
from coreth_tpu.crypto import keccak256
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.types import (
    AccessListTx, Block, DynamicFeeTx, Header, LatestSigner, LegacyTx,
    Transaction, sign_tx,
)

CHAIN_ID = 43111
PRIV = 0xA1B2C3D4E5F60718293A4B5C6D7E8F90A1B2C3D4E5F60718293A4B5C6D7E8F90
ADDR = priv_to_address(PRIV)


# --- the reference: rlp.decode's tree + the dataclasses ---------------------

def _strings(items, n=None):
    if not isinstance(items, list) or any(
            not isinstance(x, bytes) for x in items):
        raise ValueError("expected a list of strings")
    if n is not None and len(items) != n:
        raise ValueError("wrong field count")
    return items


def _ref_access_list(items):
    if not isinstance(items, list):
        raise ValueError("access list is not a list")
    out = []
    for tup in items:
        if not isinstance(tup, list) or len(tup) != 2 \
                or not isinstance(tup[0], bytes):
            raise ValueError("malformed access list entry")
        out.append((tup[0], _strings(tup[1])))
    return out


def _ref_legacy(items) -> Transaction:
    it = _strings(items, 9)
    u = rlp.decode_uint
    return Transaction(LegacyTx(
        nonce=u(it[0]), gas_price=u(it[1]), gas=u(it[2]),
        to=it[3] or None, value=u(it[4]), data=it[5],
        v=u(it[6]), r=u(it[7]), s=u(it[8])))


def _ref_typed(data: bytes) -> Transaction:
    if not data:
        raise ValueError("empty tx bytes")
    if data[0] not in (1, 2):
        raise ValueError("unknown tx type")
    items = rlp.decode(data[1:])
    n, al_at = (11, 7) if data[0] == 1 else (12, 8)
    if not isinstance(items, list) or len(items) != n:
        raise ValueError("malformed typed tx")
    al = _ref_access_list(items[al_at])
    it = _strings(items[:al_at] + items[al_at + 1:])
    u = rlp.decode_uint
    if data[0] == 1:
        return Transaction(AccessListTx(
            chain_id_=u(it[0]), nonce=u(it[1]), gas_price=u(it[2]),
            gas=u(it[3]), to=it[4] or None, value=u(it[5]), data=it[6],
            al=al, v=u(it[7]), r=u(it[8]), s=u(it[9])))
    return Transaction(DynamicFeeTx(
        chain_id_=u(it[0]), nonce=u(it[1]), gas_tip_cap_=u(it[2]),
        gas_fee_cap_=u(it[3]), gas=u(it[4]), to=it[5] or None,
        value=u(it[6]), data=it[7], al=al, v=u(it[8]), r=u(it[9]),
        s=u(it[10])))


def ref_tx_decode(data: bytes) -> Transaction:
    if data and data[0] >= 0xC0:
        return _ref_legacy(rlp.decode(data))
    return _ref_typed(data)


def ref_block_decode(data: bytes) -> Block:
    items = rlp.decode(data)
    if not isinstance(items, list) or len(items) != 5:
        raise ValueError("malformed block RLP")
    head, txs, uncles, version, extdata = items
    if not isinstance(txs, list) or not isinstance(uncles, list):
        raise ValueError("malformed block RLP")
    _strings([version, extdata])
    return Block(
        Header.from_rlp_items(_strings(head)),
        [_ref_legacy(t) if isinstance(t, list) else _ref_typed(t)
         for t in txs],
        [Header.from_rlp_items(_strings(u)) for u in uncles],
        rlp.decode_uint(version), extdata or None)


def outcome(decode, data):
    """("ok", value) or ("ValueError",); anything else propagates and
    fails the test."""
    try:
        return ("ok", decode(data))
    except ValueError:
        return ("ValueError",)


def tx_fields(tx: Transaction):
    return (type(tx.inner), tx.inner)


def block_fields(b: Block):
    return (b.header, [tx_fields(t) for t in b.transactions], b.uncles,
            b.version, b.extdata)


# --- the blocks --------------------------------------------------------------

TO = b"\x11" * 20
AL = [(b"\x22" * 20, [b"\x00" * 32, b"\x01" * 32]), (b"\x23" * 20, [])]
CALL = bytes.fromhex("a9059cbb") + b"\x00" * 12 + b"\x44" * 20 \
    + (10**18).to_bytes(32, "big")          # 68 bytes: the long-string form
INIT = bytes(range(1, 90))                   # 89 bytes of init code


def _legacy(nonce, **kw):
    kw = {"gas_price": 225 * 10**9, "gas": 21000, "to": TO, "value": 5,
          "data": b"", **kw}
    return sign_tx(LegacyTx(nonce=nonce, **kw), PRIV, CHAIN_ID)


def _access(nonce, **kw):
    kw = {"gas_price": 225 * 10**9, "gas": 100_000, "to": TO, "value": 5,
          "data": b"\xde\xad", "al": AL, **kw}
    return sign_tx(AccessListTx(chain_id_=CHAIN_ID, nonce=nonce, **kw),
                   PRIV, CHAIN_ID)


def _dynamic(nonce, **kw):
    kw = {"gas_tip_cap_": 10**9, "gas_fee_cap_": 300 * 10**9, "gas": 21000,
          "to": TO, "value": 123456789, "data": b"", **kw}
    return sign_tx(DynamicFeeTx(chain_id_=CHAIN_ID, nonce=nonce, **kw),
                   PRIV, CHAIN_ID)


def _header(**kw):
    kw = {"number": 42, "gas_limit": 8_000_000, "gas_used": 21000,
          "time": 1_700_000_000, "coinbase": b"\x77" * 20,
          "extra": b"\x00" * 80, "base_fee": 25 * 10**9,
          "ext_data_gas_used": 0, "block_gas_cost": 100_000, **kw}
    return Header(**kw)


MIXED = [_legacy(0), _access(1), _dynamic(2),
         _legacy(3, to=None, data=INIT), _dynamic(4, data=CALL, al=AL),
         _access(5, al=[]), _legacy(6, value=0, data=b"\x7f")]

BLOCKS = {
    "empty": Block(_header()),
    "legacy": Block(_header(), [_legacy(i) for i in range(4)]),
    "legacy_pre155": Block(_header(), [Transaction(LegacyTx(
        nonce=1, gas_price=1, gas=21000, to=TO, value=1, v=27, r=5, s=7))]),
    "legacy_creation": Block(_header(), [_legacy(0, to=None, data=INIT)]),
    "access_list": Block(_header(), [_access(0), _access(1, to=None)]),
    "access_list_empty": Block(_header(), [_access(0, al=[])]),
    "dynamic_fee": Block(_header(), [_dynamic(i) for i in range(3)]),
    "dynamic_fee_calldata": Block(_header(), [_dynamic(0, data=CALL)]),
    "dynamic_fee_access_list": Block(_header(), [
        _dynamic(0, al=AL), _dynamic(1, to=None, data=INIT, al=AL)]),
    "mixed": Block(_header(), MIXED),
    "uncle": Block(_header(), MIXED[:3],
                   uncles=[_header(number=41), _header(
                       number=40, base_fee=None, ext_data_gas_used=None,
                       block_gas_cost=None)]),
    "extdata": Block(_header(), MIXED[:2], version=1,
                     extdata=b"\x00\x01" + b"\xee" * 70),
    "header_16": Block(_header(base_fee=None, ext_data_gas_used=None,
                               block_gas_cost=None), [_legacy(0)]),
    "header_17": Block(_header(ext_data_gas_used=None, block_gas_cost=None),
                       [_dynamic(0)]),
    "header_18": Block(_header(block_gas_cost=None), [_dynamic(0)]),
    "header_19": Block(_header(), [_dynamic(0)]),
}
HEADER_FIELDS = {"header_16": 16, "header_17": 17, "header_18": 18,
                 "header_19": 19}
TXS = {"legacy": _legacy(0), "legacy_creation": _legacy(1, to=None,
                                                         data=INIT),
       "access_list": _access(2), "access_list_empty": _access(3, al=[]),
       "dynamic_fee": _dynamic(4), "dynamic_fee_calldata": _dynamic(
           5, data=CALL, al=AL)}


@pytest.mark.parametrize("name", list(BLOCKS))
def test_block_decodes_as_the_reference_does(name):
    built = BLOCKS[name]
    wire = built.encode()
    new, ref = Block.decode(wire), ref_block_decode(wire)
    assert block_fields(new) == block_fields(ref) == block_fields(built)
    assert new.encode() == wire
    assert new.hash() == built.hash()
    assert [t.hash() for t in new.transactions] == \
        [t.hash() for t in built.transactions]
    if name in HEADER_FIELDS:
        assert len(rlp.decode(wire)[0]) == HEADER_FIELDS[name]
    # the wire slices serve the signing hash: senders recover from them
    signer = LatestSigner(CHAIN_ID)
    for tx in new.transactions:
        if tx.inner.r != 5:  # the hand-made pre-EIP-155 signature
            assert signer.sender(tx) == ADDR
    # any bytes-like input is the same block
    assert block_fields(Block.decode(bytearray(wire))) == block_fields(new)


@pytest.mark.parametrize("name", list(TXS))
def test_transaction_decodes_as_the_reference_does(name):
    wire = TXS[name].encode()
    new, ref = Transaction.decode(wire), ref_tx_decode(wire)
    assert tx_fields(new) == tx_fields(ref) == tx_fields(TXS[name])
    assert new.encode() == wire
    assert LatestSigner(CHAIN_ID).sender(new) == ADDR


# --- the same rejections -------------------------------------------------------

def _tree(wire: bytes):
    """A block's item tree with each typed transaction opened up as
    ("typed", type byte, payload tree), so a mutation can reach inside
    its string."""
    items = rlp.decode(wire)
    items[1] = [t if isinstance(t, list) else ("typed", t[:1],
                                               rlp.decode(t[1:]))
                for t in items[1]]
    return items


def _paths(node, at=()):
    """Every node of a tree, by index path."""
    yield at
    if isinstance(node, tuple):
        yield from _paths(node[2], at + (2,))
    elif isinstance(node, list):
        for i, x in enumerate(node):
            yield from _paths(x, at + (i,))


def _prefix(length: int, offset: int, long_form: bool) -> bytes:
    if length < 56 and not long_form:
        return bytes([offset + length])
    blen = rlp.encode_uint(length) or b"\x00"
    return bytes([offset + 55 + len(blen)]) + blen


def _encode(node, fault=None, at=()):
    """``rlp.encode`` of a tree, with one node (``fault = (path, kind)``)
    written wrongly and every enclosing length right: ``long`` = its
    length in the long form, ``swap`` = a list's prefix on a string and
    the other way round, ``trail`` = one byte after a typed
    transaction's payload, inside its string."""
    kind = fault[1] if fault and fault[0] == at else None
    if isinstance(node, tuple):
        payload = node[1] + _encode(node[2], fault, at + (2,))
        if kind == "trail":
            payload += b"\x00"
        is_list = False
    elif isinstance(node, list):
        payload = b"".join(_encode(x, fault, at + (i,))
                           for i, x in enumerate(node))
        is_list = True
    else:
        payload, is_list = node, False
        if len(node) == 1 and node[0] < 0x80 and kind is None:
            return node
    if kind == "swap":
        is_list = not is_list
    return _prefix(len(payload), 0xC0 if is_list else 0x80,
                   kind == "long") + payload


def _flip(rng, wire):
    i = rng.randrange(len(wire))
    return wire[:i] + bytes([wire[i] ^ (1 << rng.randrange(8))]) \
        + wire[i + 1:]


def _byte(rng, wire):
    i = rng.randrange(len(wire))
    return wire[:i] + bytes([rng.randrange(256)]) + wire[i + 1:]


def _truncate(rng, wire):
    return wire[:rng.randrange(len(wire))]


def _insert(rng, wire):
    i = rng.randrange(len(wire) + 1)
    return wire[:i] + bytes([rng.randrange(256)]) + wire[i:]


def _drop(rng, wire):
    i = rng.randrange(len(wire))
    return wire[:i] + wire[i + 1:]


def _structural(kind):
    def mutate(rng, wire, tree):
        if kind == "trail":   # the typed transactions of the block
            paths = [(1, i) for i, t in enumerate(tree[1])
                     if isinstance(t, tuple)]
        else:
            paths = list(_paths(tree))
        if not paths:
            return None
        bad = wire
        while bad == wire:   # a length of 56 or more is in the long form
            bad = _encode(tree, (rng.choice(paths), kind))
        return bad
    return mutate


BYTE_MUTATIONS = {"bit_flip": _flip, "byte_rewrite": _byte,
                  "truncate": _truncate, "insert_byte": _insert,
                  "drop_byte": _drop}
TREE_MUTATIONS = {"long_form_length": _structural("long"),
                  "list_string_swap": _structural("swap"),
                  "trailing_byte_in_typed_string": _structural("trail")}
MUTATED_BLOCKS = ["legacy", "legacy_creation", "access_list",
                  "dynamic_fee_calldata", "dynamic_fee_access_list",
                  "mixed", "uncle", "extdata", "header_16"]
PER_CASE = 24   # 9 blocks x 8 mutations x 24 = 1,728 mutated blocks


def test_tree_encoder_of_this_file_is_rlp_encode():
    for name, block in BLOCKS.items():
        wire = block.encode()
        assert _encode(_tree(wire)) == wire, name


@pytest.mark.parametrize("name", MUTATED_BLOCKS)
@pytest.mark.parametrize("mutation", list(BYTE_MUTATIONS) + list(
    TREE_MUTATIONS))
def test_mutated_block_same_rejection(mutation, name):
    wire = BLOCKS[name].encode()
    tree = _tree(wire)
    rng = random.Random(f"{mutation}/{name}")
    seen = {"ok": 0, "ValueError": 0}
    for _ in range(PER_CASE):
        if mutation in BYTE_MUTATIONS:
            bad = BYTE_MUTATIONS[mutation](rng, wire)
        else:
            bad = TREE_MUTATIONS[mutation](rng, wire, tree)
            if bad is None:   # no typed transaction to put a byte into
                assert mutation == "trailing_byte_in_typed_string"
                return
        new, ref = outcome(Block.decode, bad), outcome(ref_block_decode, bad)
        assert new[0] == ref[0], bad.hex()
        seen[new[0]] += 1
        if new[0] == "ok":
            assert block_fields(new[1]) == block_fields(ref[1]), bad.hex()
            assert new[1].encode() == ref[1].encode()
    if mutation in ("truncate", "long_form_length",
                    "trailing_byte_in_typed_string", "list_string_swap"):
        assert seen["ok"] == 0, seen   # never canonical, whatever the seed


@pytest.mark.parametrize("name", list(TXS))
@pytest.mark.parametrize("mutation", list(BYTE_MUTATIONS) + [
    "long_form_length", "list_string_swap", "trailing_byte"])
def test_mutated_transaction_same_rejection(mutation, name):
    wire = TXS[name].encode()
    typ, tree = (b"", rlp.decode(wire)) if wire[0] >= 0xC0 else (
        wire[:1], rlp.decode(wire[1:]))
    rng = random.Random(f"{mutation}/{name}")
    for _ in range(PER_CASE):
        if mutation in BYTE_MUTATIONS:
            bad = BYTE_MUTATIONS[mutation](rng, wire)
        elif mutation == "trailing_byte":
            bad = wire + bytes([rng.randrange(256)])
        else:
            kind = "long" if mutation == "long_form_length" else "swap"
            bad = wire
            while bad == wire:   # 56 bytes or more: the long form already
                bad = typ + _encode(
                    tree, (rng.choice(list(_paths(tree))), kind))
        new, ref = outcome(Transaction.decode, bad), outcome(ref_tx_decode,
                                                             bad)
        assert new[0] == ref[0], bad.hex()
        if new[0] == "ok":
            assert tx_fields(new[1]) == tx_fields(ref[1]), bad.hex()
        elif mutation not in BYTE_MUTATIONS:
            assert new[0] == "ValueError"


@pytest.mark.parametrize("bad", [
    b"", b"\xc0", b"\x80", b"\xf8", b"\xf9\x00", b"\xc5\xc0\xc0\xc0\x80\x80",
    bytes.fromhex("c6") + b"\x80" * 6,
], ids=["nothing", "empty_list", "empty_string", "cut_in_the_length",
        "cut_in_a_zero_led_length", "header_without_fields",
        "strings_for_lists"])
def test_degenerate_block_bytes_raise_value_error(bad):
    assert outcome(Block.decode, bad) == ("ValueError",)
    assert outcome(ref_block_decode, bad) == ("ValueError",)
    assert outcome(Transaction.decode, bad) == ("ValueError",)
    assert outcome(ref_tx_decode, bad) == ("ValueError",)


# --- the span readers against rlp.decode ---------------------------------------

def test_span_items_is_decode_of_a_lists_payload():
    rng = random.Random(36)

    def item(depth):
        if depth and rng.random() < 0.3:
            return [item(depth - 1) for _ in range(rng.randrange(4))]
        n = rng.choice([0, 1, 1, 2, 20, 32, 55, 56, 60, 300])
        return bytes(rng.randrange(256) for _ in range(n))

    for _ in range(200):
        items = [item(0) for _ in range(rng.randrange(6))]
        nested = rng.randrange(len(items) + 1)
        items.insert(nested, item(2) if rng.random() < 0.8 else [])
        if not isinstance(items[nested], list):
            nested = -1
        wire = rlp.encode(items)
        start, end = rlp.list_span(wire, 0, len(wire))
        assert end == len(wire)
        assert rlp.span_items(wire, start, end, nested) == items
        if nested >= 0:   # a list where only strings may be
            with pytest.raises(ValueError):
                rlp.span_items(wire, start, end)
        if len(rlp.encode(items[-1])) > 1:   # an item past the span's end
            with pytest.raises(ValueError):
                rlp.span_items(wire, start, end - 1, nested)


# --- the mechanism, in counts ----------------------------------------------------

# built here, by the real codec: the counts below are the decoder's alone
WIRES = {name: block.encode() for name, block in BLOCKS.items()}
WIRE_100_LEGACY = Block(_header(), [
    _legacy(i, to=bytes([i + 1]) * 20, value=10**15 + i)
    for i in range(100)]).encode()


@pytest.fixture
def codec_calls(monkeypatch):
    """Calls of the generic recursive codec from OUTSIDE it: a recursive
    ``_decode_at`` counts once, for the item it was handed."""
    calls = {"encode": 0, "_decode_at": 0}
    depth = [0]
    real_encode, real_decode_at = rlp.encode, rlp._decode_at

    def encode(item):
        calls["encode"] += 1
        return real_encode(item)

    def _decode_at(data, pos):
        calls["_decode_at"] += depth[0] == 0
        depth[0] += 1
        try:
            return real_decode_at(data, pos)
        finally:
            depth[0] -= 1

    monkeypatch.setattr(rlp, "encode", encode)
    monkeypatch.setattr(rlp, "_decode_at", _decode_at)
    return calls


def test_legacy_block_decodes_without_the_generic_codec(codec_calls):
    block = Block.decode(WIRE_100_LEGACY)
    assert len(block.transactions) == 100
    assert codec_calls == {"encode": 0, "_decode_at": 0}


@pytest.mark.parametrize("name,expected", [
    ("access_list", 2), ("dynamic_fee_access_list", 2), ("mixed", 2),
    ("access_list_empty", 0), ("dynamic_fee", 0), ("uncle", 2)])
def test_decode_at_runs_once_per_nested_list(codec_calls, name, expected):
    """Once per non-empty access list; the ``uncle`` block has one
    (``MIXED[1]``) and its uncle list is the other."""
    Block.decode(WIRES[name])
    assert codec_calls == {"encode": 0, "_decode_at": expected}


# --- _wire -----------------------------------------------------------------------

@pytest.mark.parametrize("name", ["legacy", "access_list", "dynamic_fee"])
def test_every_decoded_transaction_keeps_its_wire_slice(name):
    built = TXS[name]
    assert not hasattr(built.inner, "_wire")   # signed here, never decoded
    assert built.hash() == keccak256(built.encode())
    alone = Transaction.decode(built.encode())
    in_block = Block.decode(Block(_header(), [MIXED[0], built]).encode()
                            ).transactions[1]
    for tx in (alone, in_block):
        assert tx.inner._wire == built.encode()
        assert keccak256(tx.inner._wire) == tx.hash() == built.hash()
        assert type(tx.inner._wire) is bytes

#!/usr/bin/env python
"""Hostile input for the native sender batch's wire walk.

``coreth_recover_wire`` (native/secp256k1.cc; bound as
``crypto.native.recover_senders_wire``) parses UNTRUSTED bytes —
transactions' wire encodings end to end, cut by caller-supplied offsets
— on a worker thread, and derives signing hash, r, s and recovery id
from them.  This script throws three things at it:

- ``cuts``: every prefix of a transaction as the LAST lane of a batch
  whose buffer ends where the prefix does, and length prefixes that lie
  (a list or an item claiming more bytes than its offsets give it),
  offsets that run backwards or past the buffer.  Run under the ASan
  build (tests/test_sanitize.py: CORETH_NATIVE_SANITIZE=1 + LD_PRELOAD)
  any read past the buffer aborts the process: that is the oracle; the
  assertions alone (the hostile lane refused, its neighbours recovered)
  would pass on a walk that over-reads silently.
- ``mutations``: seeded byte edits of valid transactions, each held to
  the Python decoder and ``LatestSigner``: a lane the walk vouches for
  (ok = 1) decodes, and ``signer.sender`` of the decoded transaction
  answers the same address; a mutant Python accepts, the walk accepts.
- ``threads``: two threads inside the entry at once, as the tip's
  prefetcher and the replay thread's worker can be (tests/test_tsan.py
  runs this under the TSan build: any shared write inside reports).

Deterministic (seeded PRNG), a few seconds: a regression corpus, not a
discovery campaign.
"""

import itertools
import os
import random
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from coreth_tpu.crypto import native  # noqa: E402
from coreth_tpu.types import (  # noqa: E402
    AccessListTx, DynamicFeeTx, LegacyTx, Transaction, sign_tx)
from coreth_tpu.types.transaction import LatestSigner  # noqa: E402

CHAIN_ID = 43112
SIGNER = LatestSigner(CHAIN_ID)
TO = bytes(range(1, 21))
ACCESS = [(bytes([7]) * 20, [bytes([8]) * 32, bytes([9]) * 32]),
          (bytes([10]) * 20, [])]


def corpus():
    """One signed transaction of each wire shape the walk branches on."""
    inners = {
        "legacy": (LegacyTx(nonce=5, gas_price=25 * 10**9, gas=21_000,
                            to=TO, value=10**18), None),
        "eip155": (LegacyTx(nonce=6, gas_price=25 * 10**9, gas=90_000,
                            to=TO, value=3, data=bytes(70)), CHAIN_ID),
        "type1": (AccessListTx(chain_id_=CHAIN_ID, nonce=7,
                               gas_price=10**11, gas=60_000, to=TO,
                               value=9, al=ACCESS), CHAIN_ID),
        "type2": (DynamicFeeTx(chain_id_=CHAIN_ID, nonce=8,
                               gas_tip_cap_=10**9, gas_fee_cap_=10**11,
                               gas=21_000, to=TO, value=10**15),
                  CHAIN_ID),
        "type2-create": (DynamicFeeTx(chain_id_=CHAIN_ID, nonce=9,
                                      gas_tip_cap_=1, gas_fee_cap_=10**11,
                                      gas=500_000, to=None,
                                      data=bytes(range(256)) * 2,
                                      al=ACCESS), CHAIN_ID),
    }
    return {name: sign_tx(inner, 0xC0FFEE + i, cid)
            for i, (name, (inner, cid)) in enumerate(inners.items())}


def recover(wires, chain_id=CHAIN_ID):
    """The native entry over ``wires``: one address or None a lane."""
    out, ok = native.recover_senders_wire(
        b"".join(wires),
        list(itertools.accumulate(map(len, wires), initial=0)), chain_id)
    return [out[20 * i:20 * i + 20] if ok[i] else None
            for i in range(len(wires))]


def python_sender(wire):
    """(decodes, signer.sender's answer or None) for one lane's bytes."""
    try:
        tx = Transaction.decode(wire)
    except Exception:  # noqa: BLE001 — the decoder's refusals, any of them
        return False, None
    try:
        return True, SIGNER.sender(tx)
    except ValueError:
        return True, None


def cuts(txs):
    """Truncated and overrunning encodings, last in their buffer."""
    good = [tx.encode() for tx in txs.values()]
    want = [tx.cached_sender() for tx in txs.values()]
    refused = 0
    for wire in good:
        for k in range(len(wire)):            # every proper prefix
            got = recover(good + [wire[:k]])
            assert got[:-1] == want and got[-1] is None, (wire.hex(), k)
            refused += 1
        # length prefixes that claim more than the lane holds: the
        # list's, and each byte that could be an item's prefix
        at = 1 if wire[0] < 0xC0 else 0
        for pos in range(at, len(wire)):
            for claim in (0xB8, 0xBF, 0xF8, 0xFF, wire[pos] + 1 & 0xFF):
                lie = wire[:pos] + bytes([claim]) + wire[pos + 1:]
                got = recover(good + [lie])
                assert got[:-1] == want, (lie.hex(), pos)
                refused += got[-1] is None
    # offsets the walk may not follow: backwards, and past the buffer
    wire = b"".join(good)
    ends = list(itertools.accumulate(map(len, good), initial=0))
    for offsets in (ends[:2] + [ends[1] - 1] + ends[3:],
                    ends[:-1] + [ends[-1] + 1],
                    ends[:-1] + [1 << 63], [5, 4, 3, 2, 1, 0]):
        out, ok = native.recover_senders_wire(wire, offsets, CHAIN_ID)
        for i in range(len(offsets) - 1):
            whole = offsets[i] == ends[i] and offsets[i + 1] == ends[i + 1]
            assert bool(ok[i]) == whole, (offsets, i)
            refused += not whole
    return refused


def mutations(txs, rng, rounds=400):
    """Seeded byte edits, held to the Python decoder and signer."""
    accepted = 0
    for name, tx in txs.items():
        wire = tx.encode()
        lanes = [wire]
        for _ in range(rounds):
            m = bytearray(wire)
            for _ in range(rng.choice((1, 1, 1, 2, 3))):
                edit = rng.random()
                pos = rng.randrange(len(m))
                if edit < 0.6:
                    m[pos] = rng.randrange(256)
                elif edit < 0.8:
                    m[pos:pos] = bytes([rng.randrange(256)])
                elif len(m) > 1:
                    del m[pos]
            lanes.append(bytes(m))
        got = recover(lanes)
        assert got[0] == tx.cached_sender(), name
        for lane, addr in zip(lanes, got):
            decodes, sender = python_sender(lane)
            if addr is not None:
                # an access list is stepped over, never entered: what
                # is wrong INSIDE one is the decoder's alone to refuse
                assert decodes or "type" in name, (name, lane.hex())
                assert not decodes or sender == addr, (name, lane.hex())
                accepted += 1
            else:
                assert sender is None, (name, lane.hex())
    return accepted


def threads(txs, rounds=40):
    """Two threads in the entry at once, each on its own batch."""
    wires = [tx.encode() for tx in txs.values()]
    want = [tx.cached_sender() for tx in txs.values()]
    batches = [wires * 8, wires[::-1] * 8 + [b"\xc0"]]
    wants = [want * 8, want[::-1] * 8 + [None]]
    wrong = []

    def run(k):
        for _ in range(rounds):
            if recover(batches[k]) != wants[k]:
                wrong.append(k)

    workers = [threading.Thread(target=run, args=(k,)) for k in (0, 1)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    assert not wrong, wrong
    return 2 * rounds


def main(argv):
    txs = corpus()
    if argv[1:] == ["threads"]:
        print(f"OK calls={threads(txs)}")
        return
    refused = cuts(txs)
    accepted = mutations(txs, random.Random(39))
    print(f"OK refused={refused} mutants_accepted={accepted}")


if __name__ == "__main__":
    main(sys.argv)

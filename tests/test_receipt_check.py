"""A device block's receipts checked from columns.

``native.receipt_check`` derives the receipt root, the header bloom and
the block's gas from the classifier's columns in one C++ pass; it is
held bit for bit to ``derive_sha(receipts, StackTrie())`` and
``create_bloom`` across the rlp(i) key boundaries, the receipt types
and the 0 / 1 Transfer-shaped log.  ``verify_block_fee_columns`` takes
the tips from the fee column and is held to ``verify_block_fee`` over
``Receipt`` objects, rejection for rejection.  The engine builds no
``Receipt`` on that path unless ``keep_receipts`` asks for them, and
counts the device blocks whose receipts took the Python path
(``ReplayStats.receipt_blocks_python``).
"""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu.consensus.engine import ConsensusError, DummyEngine, Mode
from coreth_tpu.crypto import native
from coreth_tpu.mpt import StackTrie
from coreth_tpu.types import (
    AccessListTx, DynamicFeeTx, LegacyTx, Log, Receipt, Transaction,
    create_bloom, derive_sha)

needs_native = pytest.mark.skipif(native.load() is None,
                                  reason="native lib unavailable")

GWEI = 10**9
UINT64_MAX = 2**64 - 1
SIZES = (0, 1, 2, 127, 128, 129, 255, 256, 257, 713, 1000, 4096)
# receipt types and how many receipts carry a log
SHAPES = {
    "legacy_no_logs": ((0,), 0.0),
    "eip2930_all_logs": ((1,), 1.0),
    "eip1559_some_logs": ((2,), 0.3),
    "mixed_some_logs": ((0, 1, 2), 0.5),
}


def _columns(n, shape, seed=0):
    """(gas, types, has_log, log_blob, receipts) of n random receipts."""
    types, log_share = SHAPES[shape]
    rng = random.Random(seed * 7919 + n)
    gas, tx_types, has_log, blob, receipts = [], [], [], [], []
    cum = 0
    for _ in range(n):
        g = rng.choice((21_000, 21_000, 51_234, 0, 2**33 + 5))
        cum += g
        t = rng.choice(types)
        logs = []
        if rng.random() < log_share:
            lg = Log(address=rng.randbytes(20),
                     topics=[rng.randbytes(32) for _ in range(3)],
                     data=rng.randbytes(32))
            logs.append(lg)
            blob.append(lg.address + b"".join(lg.topics) + lg.data)
        gas.append(g)
        tx_types.append(t)
        has_log.append(1 if logs else 0)
        receipts.append(Receipt(tx_type=t, status=1,
                                cumulative_gas_used=cum, logs=logs))
    return gas, bytes(tx_types), bytes(has_log), b"".join(blob), receipts


@needs_native
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("n", SIZES)
def test_receipt_check_matches_stacktrie_and_bloom(n, shape):
    gas, types, has_log, blob, receipts = _columns(n, shape)
    root, bloom, total = native.receipt_check(gas, types, has_log, blob)
    assert root == derive_sha(receipts, StackTrie())
    assert bloom == create_bloom(receipts)
    assert total == sum(gas)


@needs_native
@pytest.mark.parametrize("at", (0, 1, 126, 127, 128, 255, 256))
def test_receipt_check_one_gas_changes_the_root(at):
    """One gas more in any receipt moves its cumulative gas and every
    later one's: the root and the total change, the bloom does not."""
    gas, types, has_log, blob, _ = _columns(257, "mixed_some_logs", seed=3)
    root, bloom, total = native.receipt_check(gas, types, has_log, blob)
    gas[at] += 1
    root2, bloom2, total2 = native.receipt_check(gas, types, has_log, blob)
    assert root2 != root
    assert bloom2 == bloom
    assert total2 == total + 1


@needs_native
@pytest.mark.parametrize("fault", (
    "gas_short", "has_log_short", "blob_short", "blob_long",
    "has_log_not_a_flag", "gas_overflow"))
def test_receipt_check_refuses_malformed_columns(fault):
    gas, types, has_log, blob, _ = _columns(300, "mixed_some_logs", seed=5)
    if fault == "gas_short":
        gas = gas[:-1]
    elif fault == "has_log_short":
        has_log = has_log[:-1]
    elif fault == "blob_short":
        blob = blob[:-1]
    elif fault == "blob_long":
        blob = blob + bytes(148)
    elif fault == "has_log_not_a_flag":
        has_log = has_log[:7] + b"\x02" + has_log[8:]
    else:
        gas[10] = UINT64_MAX
    with pytest.raises(ValueError):
        native.receipt_check(gas, types, has_log, blob)


# ----------------------------------------------------- the block fee

BASE_FEE = 25 * GWEI


def _fee_block(n=40):
    """Transactions of every type, each fee cap at or above the base
    fee (the classifier refuses a lower one); tips that bind and caps
    that bind.  Returns (txs, used, paid): ``paid`` sums the classifier's
    fee column, used x min(base fee + tip, fee cap)."""
    rng = random.Random(11)
    txs, used = [], []
    for i in range(n):
        kind = i % 4
        if kind == 0:
            inner = LegacyTx(gas_price=BASE_FEE + rng.randrange(3 * GWEI))
        elif kind == 1:
            inner = AccessListTx(
                gas_price=BASE_FEE + rng.randrange(3 * GWEI))
        elif kind == 2:     # the tip binds
            inner = DynamicFeeTx(gas_tip_cap_=rng.randrange(2 * GWEI),
                                 gas_fee_cap_=BASE_FEE + 5 * GWEI)
        else:               # the cap binds
            inner = DynamicFeeTx(gas_tip_cap_=9 * GWEI,
                                 gas_fee_cap_=BASE_FEE
                                 + rng.randrange(4 * GWEI))
        txs.append(Transaction(inner))
        used.append(rng.choice((21_000, 51_234)))
    paid = sum(u * min(BASE_FEE + tx.gas_tip_cap, tx.gas_fee_cap)
               for tx, u in zip(txs, used))
    return txs, used, paid


def _outcome(check):
    try:
        check()
    except ConsensusError as exc:
        return str(exc)
    return None


def _both(engine, base_fee, required, contribution):
    """The outcome of the per-receipt and of the column form."""
    txs, used, paid = _fee_block()
    receipts = [Receipt(gas_used=u) for u in used]
    old = _outcome(lambda: engine.verify_block_fee(
        base_fee, required, txs, receipts, contribution))
    new = _outcome(lambda: engine.verify_block_fee_columns(
        base_fee, required, paid, sum(used), contribution))
    return old, new


def _tips():
    txs, used, _ = _fee_block()
    return sum(tx.effective_gas_tip(BASE_FEE) * u
               for tx, u in zip(txs, used))


@pytest.mark.parametrize("skip", (False, True), ids=("checked", "skip"))
@pytest.mark.parametrize("side", ("at", "below"))
@pytest.mark.parametrize("contribution", ("none", "zero", "positive"))
def test_block_fee_columns_match_receipts(contribution, side, skip):
    """The fee total one wei either side of required_block_gas_cost x
    base fee (a positive contribution tops the tips up to it), or —
    with no contribution to tune — the required cost one gas either
    side of what the tips pay for."""
    engine = DummyEngine(mode=Mode(skip_block_fee=skip))
    tips = _tips()
    if contribution == "positive":
        required = tips // BASE_FEE + 1000
        extra = required * BASE_FEE - tips - (side == "below")
    else:
        extra = None if contribution == "none" else 0
        required = tips // BASE_FEE + (side == "below")
    old, new = _both(engine, BASE_FEE, required, extra)
    assert new == old
    assert (old is None) == (skip or side == "at")


@pytest.mark.parametrize("base_fee, required, extra", (
    (None, 0, None), (0, 0, None), (-1, 0, None),
    (BASE_FEE, None, None), (BASE_FEE, UINT64_MAX + 1, None),
    (BASE_FEE, 0, -1)),
    ids=("no_base_fee", "zero_base_fee", "negative_base_fee",
         "no_required", "required_past_u64", "negative_contribution"))
def test_block_fee_columns_refuse_what_receipts_refuse(base_fee, required,
                                                       extra):
    old, new = _both(DummyEngine(), base_fee, required, extra)
    assert old is not None and new == old


# ------------------------------------------------------ in the engine

def _replay(blocks, genesis, keep_receipts=False):
    from test_replay import CFG
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64)
    engine.keep_receipts = keep_receipts
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_fallback == 0
    assert engine.stats.blocks_device == len(blocks)
    return engine


@needs_native
def test_receipt_blocks_python_counts_the_python_path(monkeypatch):
    """A ring of transfers adds 0.  A token block whose log the
    classifier does not pack (as if outside the uniform Transfer shape)
    takes the Python StackTrie path, commits at its header's root, and
    adds 1 a block — in the stats row, the registry, the stream's
    report and the live /report payload."""
    from test_replay import build_sized_chain, build_token_chain
    from coreth_tpu.metrics import Registry
    from coreth_tpu.replay import engine as engine_mod
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    genesis, _, blocks = build_sized_chain([8, 8], cross=True)
    ring = _replay(blocks, genesis)
    assert ring.stats.receipt_blocks_python == 0

    genesis, _, blocks, _ = build_token_chain(2, 4)
    monkeypatch.setattr(engine_mod, "transfer_log_record",
                        lambda log: None)
    eng = _replay(blocks, genesis)
    assert eng.stats.receipt_blocks_python == 2
    assert eng.stats.row()["receipt_blocks_python"] == 2
    reg = Registry()
    eng.publish_metrics(reg)
    assert reg.snapshot()["replay/receipt_blocks_python"]["value"] == 2
    stream = StreamingPipeline(eng, ChainFeed([]))
    assert stream.run().lanes["receipt_blocks_python"] == 2
    assert stream._live_report()["lanes"]["receipt_blocks_python"] == 2


@needs_native
@pytest.mark.parametrize("chain", ("transfers", "tokens"))
def test_uniform_path_builds_no_receipt(monkeypatch, chain):
    """Off, the engine constructs no Receipt for a device block; on
    (the bootstrap cells' DeviceProcessor), it builds one a transaction
    and they hash to the header's root."""
    from test_replay import build_sized_chain, build_token_chain
    from coreth_tpu.replay import engine as engine_mod
    if chain == "transfers":
        genesis, _, blocks = build_sized_chain([6, 6], cross=True)
    else:
        genesis, _, blocks, _ = build_token_chain(2, 6)
    made = []

    class Counted(Receipt):
        def __init__(self, *a, **kw):
            made.append(1)
            super().__init__(*a, **kw)

    monkeypatch.setattr(engine_mod, "Receipt", Counted)
    eng = _replay(blocks, genesis)
    assert made == []
    assert eng.stats.receipt_blocks_python == 0
    eng = _replay(blocks, genesis, keep_receipts=True)
    assert len(made) == sum(len(b.transactions) for b in blocks)
    assert derive_sha(eng.last_receipts, StackTrie()) \
        == blocks[-1].header.receipt_hash
    assert eng.stats.receipt_blocks_python == 0

"""Batched replay engine: u256 limb math + device/host parity on roots."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

from coreth_tpu.chain import BlockChain, Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.ops import u256
from coreth_tpu.params import TEST_CHAIN_CONFIG
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.state import Database
from coreth_tpu.types import DynamicFeeTx, create_bloom, derive_sha, sign_tx

GWEI = 10**9
KEYS = [0x1000 + i for i in range(8)]
ADDRS = [priv_to_address(k) for k in KEYS]
CFG = TEST_CHAIN_CONFIG


# ---------------------------------------------------------------- u256 math

def test_u256_roundtrip():
    vals = [0, 1, 0xFFFF, 2**255 + 12345, 2**256 - 1, 10**24]
    arr = u256.from_ints(vals)
    assert u256.to_ints(arr) == vals


def test_u256_add_sub_gte():
    import random
    rng = random.Random(7)
    a_vals = [rng.randrange(2**250) for _ in range(64)]
    b_vals = [rng.randrange(2**250) for _ in range(64)]
    a = u256.from_ints(a_vals)
    b = u256.from_ints(b_vals)
    add = u256.to_ints(u256.add(a, b))
    assert add == [(x + y) % 2**256 for x, y in zip(a_vals, b_vals)]
    big = u256.from_ints([max(x, y) for x, y in zip(a_vals, b_vals)])
    small = u256.from_ints([min(x, y) for x, y in zip(a_vals, b_vals)])
    sub = u256.to_ints(u256.sub(big, small))
    assert sub == [abs(x - y) for x, y in zip(a_vals, b_vals)]
    gte = np.asarray(u256.gte(a, b))
    assert list(gte) == [x >= y for x, y in zip(a_vals, b_vals)]


def test_u256_segment_headroom():
    # sum 4096 maxed values then normalize — no overflow in int32 limbs
    import jax.numpy as jnp
    vals = u256.from_ints([2**256 - 1] * 4096)
    summed = jnp.sum(vals, axis=0)
    norm = u256.normalize(summed[None, :])
    expect = (4096 * (2**256 - 1)) % 2**256
    assert u256.to_ints(norm)[0] == expect


# ------------------------------------------------------------ replay parity

def build_sized_chain(sizes, cross=False):
    """Transfers from the eight keys in turn, ``sizes[i]`` of them in
    block i: to fresh addresses, or (``cross``) to the next key."""
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={a: GenesisAccount(balance=10**24)
                             for a in ADDRS})
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)
    sent = [0]

    def gen(i, bg):
        for j in range(sizes[i]):
            k = sent[0] % len(KEYS)
            sent[0] += 1
            to = ADDRS[(k + 1) % len(KEYS)] if cross \
                else bytes([0x40 + k]) * 20
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=21_000,
                to=to, value=1000 + j,
            ), KEYS[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, gblock, db, len(sizes), gen, gap=2)
    return genesis, gblock, blocks


def build_transfer_chain(n_blocks, txs_per_block, cross=False):
    return build_sized_chain([txs_per_block] * n_blocks, cross)


def test_replay_disjoint_transfers():
    genesis, gblock, blocks = build_transfer_chain(4, 16)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header, capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].header.root
    assert engine.stats.blocks_device == 4
    assert engine.stats.blocks_fallback == 0
    assert engine.stats.txs == 64


def test_replay_cross_transfers_sender_is_recipient():
    """Senders send to each other; engine must stay exact (solvency is
    checked conservatively, these accounts are well funded)."""
    genesis, gblock, blocks = build_transfer_chain(3, 8, cross=True)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header, capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].header.root
    assert engine.stats.blocks_device == 3


def test_replay_fallback_on_contract_block():
    """Blocks with contract txs route through the host processor and the
    engine keeps going, bit-identically."""
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={ADDRS[0]: GenesisAccount(balance=10**24)})
    db = Database()
    gblock = genesis.to_block(db)
    runtime = bytes.fromhex("60003560005500")
    init = b"\x66" + runtime + bytes.fromhex("60005260076019f3")

    def gen(i, bg):
        if i == 1:
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=i, gas_tip_cap_=GWEI,
                gas_fee_cap_=300 * GWEI, gas=200_000, to=None, value=0,
                data=init), KEYS[0], CFG.chain_id))
        else:
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=i, gas_tip_cap_=GWEI,
                gas_fee_cap_=300 * GWEI, gas=21_000, to=b"\x77" * 20,
                value=5), KEYS[0], CFG.chain_id))

    blocks, _ = generate_chain(CFG, gblock, db, 3, gen, gap=2)
    db2 = Database()
    gb2 = genesis.to_block(db2)
    engine = ReplayEngine(CFG, db2, gb2.root, parent_header=gb2.header, capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].header.root
    assert engine.stats.blocks_device == 2
    assert engine.stats.blocks_fallback == 1


def test_replay_matches_blockchain_insert():
    """Replay and the canonical BlockChain.insert path land on identical
    state (cross-engine parity)."""
    genesis, gblock, blocks = build_transfer_chain(3, 10)
    # path A: replay engine
    db_a = Database()
    gb_a = genesis.to_block(db_a)
    engine = ReplayEngine(CFG, db_a, gb_a.root, parent_header=gb_a.header, capacity=256, batch_pad=64)
    root_a = engine.replay(blocks)
    # path B: full blockchain insert
    chain = BlockChain(genesis)
    chain.insert_chain(blocks)
    assert root_a == chain.last_accepted.root


def test_replay_windows_multiple_blocks_per_device_call(monkeypatch):
    """replay() must batch consecutive device-replayable blocks into
    ONE device call (the lax.scan window), not issue per-block round
    trips."""
    from coreth_tpu.replay import engine as engine_mod
    genesis, gblock, blocks = build_transfer_chain(6, 8)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64, window=8)
    calls = []
    orig = engine._issue_window

    def spy(items):
        calls.append(len(items))
        return orig(items)

    monkeypatch.setattr(engine, "_issue_window", spy)
    root = engine.replay(blocks)
    assert root == blocks[-1].header.root
    assert engine.stats.blocks_device == 6
    # all six consecutive transfer blocks must ride one window
    assert calls == [6], calls


def test_prepare_window_pads_to_pow2_not_full_window():
    """A 1-block window must not pad out to `window` scan slots
    (a 16-slot scan for a single block)."""
    genesis, gblock, blocks = build_transfer_chain(3, 8)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64, window=16)
    engine.warm_senders(blocks[0])
    batch = engine._classify(blocks[0])
    txds, t_idxs, *_ = engine._prepare_window([(blocks[0], batch)])
    assert txds.shape[0] == 1
    txds2, *_ = engine._prepare_window(
        [(blocks[0], batch),
         (blocks[1], engine._classify(blocks[1])),
         (blocks[2], engine._classify(blocks[2]))])
    assert txds2.shape[0] == 4  # 3 blocks -> pow2 bucket of 4


# consecutive blocks either side of every lane-bucket edge a C-Chain
# block can reach (714 plain transfers fill 15M gas)
LANE_SIZES = [1, 16, 17, 64, 65, 714]


@pytest.fixture(scope="module")
def sized_chain():
    genesis, _, blocks = build_sized_chain(LANE_SIZES)
    return genesis, blocks


def _classified_window(genesis, blocks, lead=(), **engine_kw):
    """A fresh engine that replayed ``lead``, and ``blocks`` classified
    as ONE window's items."""
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, window=16, **engine_kw)
    if lead:
        engine.replay(list(lead))
    items = []
    for block in blocks:
        engine.warm_senders(block)
        items.append((block, engine._classify(block)))
    return engine, items


def _prepared_shapes(genesis, blocks, **engine_kw):
    """(txds, t_idxs) shapes of ONE window holding ``blocks``."""
    engine, items = _classified_window(genesis, blocks, **engine_kw)
    txds, t_idxs, *_ = engine._prepare_window(items)
    return txds.shape, t_idxs.shape


@pytest.mark.parametrize("n_txs,lanes", [
    (1, 16), (16, 16), (17, 64), (64, 64), (65, 256), (714, 1024)])
def test_prepare_window_lane_bucket(sized_chain, n_txs, lanes):
    """The lane axis of a window is the x4 bucket (floor 16) of its
    largest block — not a fixed 1,024."""
    genesis, blocks = sized_chain
    block = blocks[LANE_SIZES.index(n_txs)]
    assert len(block.transactions) == n_txs
    txds_shape, _ = _prepared_shapes(genesis, [block])
    assert txds_shape == (1, lanes, 72)


def test_prepare_window_largest_block_decides(sized_chain):
    """A window that mixes 1-tx and 65-tx blocks pads every block to
    the 65-tx block's bucket, whatever the order."""
    genesis, blocks = sized_chain
    one, big = blocks[0], blocks[4]
    assert (len(one.transactions), len(big.transactions)) == (1, 65)
    for window in ([one, big], [big, one]):
        txds_shape, _ = _prepared_shapes(genesis, window)
        assert txds_shape == (2, 256, 72)


@pytest.mark.parametrize("batch_pad", [8, 1024])
def test_batch_pad_is_inert(sized_chain, batch_pad):
    """The constructor still takes ``batch_pad``; nothing reads it."""
    genesis, blocks = sized_chain
    for window in ([blocks[0]], [blocks[2]], blocks[:5]):
        assert _prepared_shapes(genesis, window, batch_pad=batch_pad) \
            == _prepared_shapes(genesis, window)


def test_device_rehash_parity():
    """device_rehash == host hash on a large dirty set."""
    from coreth_tpu.mpt import SecureTrie
    from coreth_tpu.mpt.rehash import device_rehash
    t1 = SecureTrie()
    t2 = SecureTrie()
    for i in range(3000):
        k = i.to_bytes(20, "big")
        v = (b"\x01" + i.to_bytes(8, "big")) * 4
        t1.update(k, v)
        t2.update(k, v)
    assert device_rehash(t1, min_batch=64) == t2.hash()
    # incremental dirty batch
    for i in range(500):
        k = i.to_bytes(20, "big")
        t1.update(k, b"\x99" * 40)
        t2.update(k, b"\x99" * 40)
    assert device_rehash(t1, min_batch=64) == t2.hash()


# ------------------------------------------------------------ ERC-20 device

TOKEN = bytes([0x77]) * 20


def build_token_chain(n_blocks, txs_per_block, gen_tx=None):
    """Chain whose blocks are transfer() calls on the workloads/erc20
    token (BASELINE config[1] shape); headers/receipts come from the
    bit-exact host processor via generate_chain."""
    from coreth_tpu.workloads.erc20 import (
        token_genesis_account, transfer_calldata)
    alloc = {a: GenesisAccount(balance=10**24) for a in ADDRS}
    alloc[TOKEN] = token_genesis_account(
        {a: 10**18 for a in ADDRS})
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=alloc)
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def default_gen(i, bg):
        for j in range(txs_per_block):
            k = (i * txs_per_block + j) % len(KEYS)
            # mix fresh recipients (SSTORE set) and token holders (reset)
            if j % 3 == 0:
                to = ADDRS[(k + 1) % len(KEYS)]
            else:
                to = bytes([0x50 + (j % 40)]) * 20
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=100_000,
                to=TOKEN, value=0,
                data=transfer_calldata(to, 10 + j),
            ), KEYS[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks,
                               gen_tx or default_gen, gap=2)
    return genesis, gblock, blocks, nonces


def test_replay_token_transfers_on_device():
    """M2 slice: token blocks replay on device with bit-identical roots
    (the root check inside _validate_and_advance), zero fallbacks."""
    genesis, gblock, blocks, _ = build_token_chain(4, 16)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_device == 4
    assert engine.stats.blocks_fallback == 0
    # committed state is readable by a host StateDB, including slots
    from coreth_tpu.state import StateDB
    from coreth_tpu.workloads.erc20 import balance_slot
    engine.commit()
    statedb = StateDB(root, db)
    total = sum(
        int.from_bytes(statedb.get_state(TOKEN, balance_slot(a)), "big")
        for a in ADDRS)
    assert total <= len(ADDRS) * 10**18  # senders paid out to fresh addrs


def test_replay_token_zero_amount_noop_variant():
    from coreth_tpu.workloads.erc20 import transfer_calldata

    def gen(i, bg):
        for j in range(6):
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=i * 6 + j,
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=100_000,
                to=TOKEN, value=0,
                data=transfer_calldata(ADDRS[1], 0 if j % 2 else 7),
            ), KEYS[0], CFG.chain_id))

    genesis, gblock, blocks, _ = build_token_chain(2, 6, gen_tx=gen)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_device == 2


def test_replay_mixed_native_and_token_block():
    """Native value transfers and token calls batch into ONE device
    step (unified txd layout)."""
    from coreth_tpu.workloads.erc20 import transfer_calldata

    def gen(i, bg):
        for j in range(8):
            k = j % 4
            nonce = i * 2 + j // 4
            if j % 2 == 0:
                bg.add_tx(sign_tx(DynamicFeeTx(
                    chain_id_=CFG.chain_id, nonce=nonce,
                    gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI,
                    gas=21_000, to=bytes([0x60 + j]) * 20, value=123,
                ), KEYS[k], CFG.chain_id))
            else:
                bg.add_tx(sign_tx(DynamicFeeTx(
                    chain_id_=CFG.chain_id, nonce=nonce,
                    gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI,
                    gas=100_000, to=TOKEN, value=0,
                    data=transfer_calldata(bytes([0x61 + j]) * 20, 5),
                ), KEYS[k], CFG.chain_id))

    genesis, gblock, blocks, _ = build_token_chain(2, 8, gen_tx=gen)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_device == 2
    assert engine.stats.blocks_fallback == 0


def test_replay_token_insufficient_falls_back_then_resumes():
    """A would-revert transfer is not token-fast-path classifiable;
    since round 5 it rides the GENERAL step machine (receipt status 0
    computed on device) instead of the host fallback, and later token
    blocks return to the fast path with refreshed slot values."""
    from coreth_tpu.workloads.erc20 import transfer_calldata

    def gen(i, bg):
        if i == 1:
            # overdraw KEYS[6]'s token balance to force the host-path
            # fallback (classifier sees the sequential revert)
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=0,
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=100_000,
                to=TOKEN, value=0,
                data=transfer_calldata(ADDRS[0], 10**30),
            ), KEYS[6], CFG.chain_id))
        else:
            n = {0: 0, 2: 1}[i]
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=n,
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=100_000,
                to=TOKEN, value=0,
                data=transfer_calldata(ADDRS[1], 1000),
            ), KEYS[0], CFG.chain_id))

    genesis, gblock, blocks, _ = build_token_chain(3, 1, gen_tx=gen)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_fallback == 0
    assert engine.stats.blocks_device == 3
    assert engine._machine.blocks == 1        # the overdraw block


def test_native_receipt_root_parity():
    """The C++ receipt-root builder (native.receipt_root — the
    DeriveSha + CreateBloom fast path) must be bit-identical to the
    Python StackTrie/bloom path across the rlp-key length boundary
    (127/129) and mixed typed/legacy receipts."""
    from coreth_tpu.crypto import native
    from coreth_tpu.mpt import StackTrie
    from coreth_tpu.types import Receipt, Log
    if native.load() is None:
        pytest.skip("native lib unavailable")
    for ntx in (1, 127, 129, 260):
        receipts, cums, types, haslog = [], [], [], []
        blob = b""
        cum = 0
        for i in range(ntx):
            cum += 21000 + i
            tx_type = 2 if i % 2 else 0
            if i % 3 == 0:
                lg = Log(address=bytes([i % 256]) * 20,
                         topics=[bytes([7]) * 32, bytes([i % 251]) * 32,
                                 bytes([3]) * 32],
                         data=i.to_bytes(32, "big"))
                logs = [lg]
                haslog.append(1)
                blob += lg.address + b"".join(lg.topics) + lg.data
            else:
                logs = []
                haslog.append(0)
            receipts.append(Receipt(tx_type=tx_type, status=1,
                                    cumulative_gas_used=cum, logs=logs))
            cums.append(cum)
            types.append(tx_type)
        root, bloom = native.receipt_root(
            cums, bytes(types), bytes(haslog), blob)
        assert root == derive_sha(receipts, StackTrie())
        assert bloom == create_bloom(receipts)


def _coinbase_spends_its_fees_chain(first_to: int = 0x52):
    """Block 1 is sequentially valid and the DEVICE REFUSES it: its
    coinbase is ADDRS[1], whose second transaction spends the fee the
    first one just paid it.  Fees credited to the coinbase are outside
    the transfer step's in-order solvency check (_transfer_step), so the
    block comes back ok=False and goes to the host path.  (Until the
    check went in-order these tests used "A -> B big, then B -> C more
    than B held before the block", which the device now commits:
    tests/test_transfer_order.py has that block.)"""
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={ADDRS[0]: GenesisAccount(balance=10**24),
                             ADDRS[1]: GenesisAccount(balance=10**12),
                             ADDRS[2]: GenesisAccount(balance=10**24)})
    db0 = Database()
    gblock = genesis.to_block(db0)

    def gen(i, bg):
        if i == 1:
            bg.set_coinbase(ADDRS[1])
            # A pays the coinbase 21,000 x (base fee + tip) in fees ...
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=1, gas_tip_cap_=GWEI,
                gas_fee_cap_=300 * GWEI, gas=21_000, to=ADDRS[2],
                value=5 * 10**23), KEYS[0], CFG.chain_id))
            # ... and the coinbase, which held 10^12 wei before the
            # block, needs all of that to buy its own gas
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=0, gas_tip_cap_=GWEI,
                gas_fee_cap_=bg.base_fee + GWEI, gas=21_000,
                to=ADDRS[2], value=777), KEYS[1], CFG.chain_id))
        else:
            nonce = {0: 0, 2: 2}[i]
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonce, gas_tip_cap_=GWEI,
                gas_fee_cap_=300 * GWEI, gas=21_000,
                to=bytes([first_to + i]) * 20, value=777),
                KEYS[0], CFG.chain_id))

    blocks, _ = generate_chain(CFG, gblock, db0, 3, gen, gap=2)
    return genesis, blocks


def test_replay_speculative_window_discard():
    """The pipelined replay issues window k+1 before validating window
    k.  With window=1, block 1's validation failure must discard the
    already-issued speculative window for block 2 (computed on the
    now-stale device state), rewind, run block 1 on the host path, and
    re-derive block 2 — landing on the exact sequential root."""
    genesis, blocks = _coinbase_spends_its_fees_chain()
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, batch_pad=64, window=1)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert engine.stats.blocks_fallback == 1
    assert engine.stats.blocks_device == 2


def _insolvent_mid_block_chain():
    """Block 1 is sequentially valid but fails the device's solvency
    check (its second sender, the block's coinbase, spends fees it
    earned earlier in the same block)."""
    return _coinbase_spends_its_fees_chain(first_to=0x42)


@pytest.mark.parametrize("shape", ["insolvent_two_tx", "one_tx_blocks"])
def test_replay_mid_window_failure_recovery(monkeypatch, shape):
    """A block that fails the device path at k>0 of its window triggers
    the rewind/re-apply/fallback/resume path (_recover_window),
    producing the exact sequential result.  ``insolvent_two_tx``: the
    device's own solvency check refuses block 1 (fees earned in the
    block are outside it).  ``one_tx_blocks``:
    a one-tx-a-block chain, every window in the 16-lane floor bucket,
    block 3 made to fail its device validation once — the valid prefix
    [0, 3) is re-applied through _prepare_window in that bucket."""
    from coreth_tpu.replay.engine import ReplayError
    if shape == "insolvent_two_tx":
        genesis, blocks = _insolvent_mid_block_chain()
        failed = 1
    else:
        genesis, _, blocks = build_sized_chain([1] * 6)
        failed = 3
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, window=16)
    lanes, recovered, reapply_uploads = [], [], []
    prepare, recover = engine._prepare_window, engine._recover_window
    fallback = engine._fallback
    validate = engine._validate_and_advance

    def spy_prepare(items):
        out = prepare(items)
        lanes.append(out[0].shape[:2])
        return out

    def spy_recover(win, arr, k, *rest):
        recovered.append(k)
        reapply_uploads.append(engine.stats.window_uploads)
        return recover(win, arr, k, *rest)

    def spy_fallback(block, *rest):
        # _recover_window re-applies the prefix, THEN falls back
        reapply_uploads.append(engine.stats.window_uploads)
        return fallback(block, *rest)

    def fail_once(block, *rest):
        if block is blocks[failed] and not recovered:
            raise ReplayError("forced device validation failure")
        return validate(block, *rest)

    monkeypatch.setattr(engine, "_prepare_window", spy_prepare)
    monkeypatch.setattr(engine, "_recover_window", spy_recover)
    monkeypatch.setattr(engine, "_fallback", spy_fallback)
    if shape == "one_tx_blocks":
        monkeypatch.setattr(engine, "_validate_and_advance", fail_once)
    root = engine.replay(blocks)
    assert root == blocks[-1].root
    assert recovered == [failed]
    assert engine.stats.blocks_fallback == 1   # the failed block
    assert engine.stats.blocks_device == len(blocks) - 1  # prefix + tail
    # the window, the prefix re-apply and the resumed tail: K is the
    # pow2 of each run, every one of them 16 lanes wide
    assert [l[1] for l in lanes] == [16, 16, 16], lanes
    assert lanes[1][0] >= failed
    # the re-apply went through the shared upload-and-call helper: ONE
    # transfer between _recover_window's entry and its fallback, and
    # one a prepared window over the whole replay
    assert reapply_uploads[1] - reapply_uploads[0] == 1
    assert engine.stats.window_uploads == len(lanes) == 3
    assert engine.account.row()["n"]["window/upload"] == 3


# ------------------------------------------ one staging buffer a window

def _packed_window(shape, sized_chain):
    """(engine, items) of the three windows the benchmark's cells and
    the token fast path meet, each on the state its blocks expect."""
    if shape == "one_tx_blocks":      # valuetx: 16 x 16 lanes
        genesis, _, blocks = build_sized_chain([1] * 16)
        return _classified_window(genesis, blocks)
    if shape == "full_block":         # p2p-1k: 714 txs, 1,024 lanes
        genesis, blocks = sized_chain
        assert len(blocks[-1].transactions) == 714
        return _classified_window(genesis, blocks[-1:], lead=blocks[:-1])
    genesis, _, blocks, _ = build_token_chain(4, 16)
    return _classified_window(genesis, blocks)


PACKED_SHAPES = {
    # shape: (K, pad, t_pad, s_pad, L, SL) of its window
    "one_tx_blocks": (16, 16, 256, 8, 256, 8),
    "full_block": (1, 1024, 256, 8, 256, 8),
    "token_window": (4, 16, 256, 32, 256, 32),
}


@pytest.mark.parametrize("shape", list(PACKED_SHAPES))
def test_packed_window_equals_eight_argument_body(sized_chain, shape):
    """The entry the engine calls — one staging buffer, cut inside the
    jitted program — returns bit for bit what the eight-argument
    _transfer_window returns on the same _prepare_window output."""
    from coreth_tpu.replay import engine as E
    engine, items = _packed_window(shape, sized_chain)
    (txds, t_idxs, s_idxs, acct_gids, slot_gids, _, slot_lists, _,
     (buf, dims)) = engine._prepare_window(items)
    assert dims == PACKED_SHAPES[shape]
    st = engine.state
    tables = (st.balances, st.nonces, st.slot_vals)
    ref = E._transfer_window(*tables, acct_gids, slot_gids, txds,
                             t_idxs, s_idxs)
    got = E._transfer_window_packed(*tables, buf, dims=dims)
    for r, g in zip(ref, got):
        assert r.shape == g.shape and r.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # and it is the real thing: every block passed the device's checks,
    # the tables moved, the token window has slot locals of its own
    assert (np.asarray(got[3])[:len(items), -1, 0] == 1).all()
    assert not np.array_equal(np.asarray(got[0]), np.asarray(tables[0]))
    assert any(slot_lists) == (shape == "token_window")
    if shape == "token_window":
        assert (slot_gids < st.slot_capacity).sum() > 1
        assert not np.array_equal(np.asarray(got[2]),
                                  np.asarray(tables[2]))


@pytest.mark.parametrize("shape", list(PACKED_SHAPES))
def test_window_views_tile_one_fresh_buffer(sized_chain, shape):
    """The five inputs are views that tile the staging buffer end to
    end — acct_gids, slot_gids, txds, t_idxs, s_idxs: no gap, no
    overlap, no copy — and every _prepare_window call allocates its
    own: window k+1 is packed while window k's upload may still be
    read by the runtime."""
    from coreth_tpu.replay import engine as E
    engine, items = _packed_window(shape, sized_chain)
    out = engine._prepare_window(items)
    txds, t_idxs, s_idxs, acct_gids, slot_gids = out[:5]
    buf, dims = out[-1]
    K, pad, t_pad, s_pad, L, SL = dims
    assert buf.dtype == np.int32 and buf.ndim == 1
    assert buf.size == L + SL + K * (pad * E.TXD_COLS + t_pad + s_pad)
    at = buf.__array_interface__["data"][0]
    for view, shp in ((acct_gids, (L,)), (slot_gids, (SL,)),
                      (txds, (K, pad, E.TXD_COLS)), (t_idxs, (K, t_pad)),
                      (s_idxs, (K, s_pad))):
        assert view.shape == shp and view.dtype == np.int32
        assert view.flags["C_CONTIGUOUS"] and view.base is buf
        assert view.__array_interface__["data"][0] == at
        at += view.nbytes
    assert at == buf.__array_interface__["data"][0] + buf.nbytes
    # what the device cuts is what the host filled
    for mine, cut in zip((acct_gids, slot_gids, txds, t_idxs, s_idxs),
                         E.window_views(np.array(buf), dims)):
        np.testing.assert_array_equal(mine, cut)
    with pytest.raises(ValueError, match="window buffer"):
        E.window_views(buf[:-1], dims)
    again = engine._prepare_window(items)
    assert not np.shares_memory(again[-1][0], buf)
    np.testing.assert_array_equal(again[-1][0], buf)
    assert again[-1][1] == dims


def test_eager_flush_waits_on_the_one_buffer(monkeypatch):
    """CORETH_EAGER_FLUSH makes the transfer explicit so that it has a
    device buffer to wait on: ONE a window, the whole staging buffer."""
    import jax
    from coreth_tpu.replay import engine as E
    genesis, _, blocks = build_transfer_chain(5, 8)
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256, window=4)
    waited = []
    ready = jax.block_until_ready

    def spy_ready(x):
        waited.append(x)
        return ready(x)

    monkeypatch.setattr(E, "_EAGER_FLUSH", True)
    monkeypatch.setattr(E.jax, "block_until_ready", spy_ready)
    assert engine.replay(blocks) == blocks[-1].header.root
    assert engine.stats.blocks_fallback == 0
    assert len(waited) == engine.stats.window_uploads == 2
    assert all(isinstance(x, jax.Array) and x.ndim == 1 for x in waited)
    assert sum(x.nbytes for x in waited) \
        == engine.stats.window_upload_bytes

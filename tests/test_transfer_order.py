"""The transfer window's solvency rule is the reference's own: every
transaction held, in block order, to what its sender holds at its own
place in the block (replay/engine.py _transfer_step, _order_solvent).

Kernel level: _transfer_step against a sequential loop over Python
ints — a balance book, one transaction at a time, buyGas's
``balance >= gas * fee_cap + value`` — with nothing of the program in
it.  Engine level: ring chains (every sender funded by the transaction
before it, upstream's genTxRing) replay on the device path with no
fallback, and ReplayStats.blocks_order_dependent counts the blocks the
pre-block rule would have refused.
"""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.ops import u256
from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.replay import engine as E
from coreth_tpu.state import Database
from coreth_tpu.types import DynamicFeeTx, LegacyTx, sign_tx

GWEI = 10**9
TX_GAS = 21_000
LANES = [16, 64]
ACCOUNTS = 32
COINBASE = ACCOUNTS - 1   # never a sender below, but for one case
FEE = TX_GAS * 25 * GWEI


# ----------------------------------------------- the sequential reference
def sequential(balances, nonces, txs, coinbase=COINBASE):
    """The block applied one transaction at a time on Python ints.
    ``txs``: (sender, recipient, value, fee, required, nonce).  Returns
    (ok, balances, nonces); on the first transaction its sender cannot
    pay for, or whose nonce is not the sender's, ok is False."""
    bal, non = list(balances), list(nonces)
    for s, r, value, fee, required, nonce in txs:
        if nonce != non[s] or bal[s] < required:
            return False, None, None
        non[s] += 1
        bal[s] -= value + fee
        bal[r] += value
        bal[coinbase] += fee
    return True, bal, non


def pre_block_rule(balances, nonces, txs):
    """The rule the step had before: every sender's PRE-BLOCK balance
    against the sum of all it will need in the block."""
    need, seen = {}, {}
    for s, _r, _v, _f, required, nonce in txs:
        if nonce != nonces[s] + seen.get(s, 0):
            return False
        seen[s] = seen.get(s, 0) + 1
        need[s] = need.get(s, 0) + required
    return all(balances[s] >= n for s, n in need.items())


def run_step(balances, nonces, txs, lanes, coinbase=COINBASE):
    """_transfer_step on the block, padded to ``lanes`` as the window
    pads it: masked-out lanes are all zeros."""
    n = len(txs)
    assert n <= lanes
    pad = [0] * (lanes - n)
    col = lambda i: [t[i] for t in txs] + pad  # noqa: E731
    offsets, seen = [], {}
    for s, *_ in txs:
        offsets.append(seen.get(s, 0))
        seen[s] = offsets[-1] + 1
    i32 = lambda xs: jnp.asarray(xs, dtype=jnp.int32)  # noqa: E731
    nb, nn, ok, pre_ok = E._transfer_step(
        u256.from_ints(balances), i32(nonces), i32(col(0)), i32(col(1)),
        u256.from_ints(col(2)), u256.from_ints(col(3)),
        u256.from_ints(col(4)), i32(col(5)), i32(offsets + pad),
        jnp.asarray([True] * n + [False] * (lanes - n)), coinbase,
        num_accounts=len(balances))
    return (bool(ok), bool(pre_ok), u256.to_ints(nb),
            [int(x) for x in np.asarray(nn)])


def check(balances, nonces, txs, lanes, want_ok=None, want_pre=None):
    want, bal, non = sequential(balances, nonces, txs)
    ok, pre_ok, got_bal, got_non = run_step(balances, nonces, txs, lanes)
    assert ok is want
    if want_ok is not None:
        assert ok is want_ok
    assert pre_ok is pre_block_rule(balances, nonces, txs)
    if want_pre is not None:
        assert pre_ok is want_pre
    assert ok or not pre_ok          # the old rule implies the new one
    if ok:
        assert got_bal == bal and got_non == non
    return ok


def tx(s, r, value, nonce=0, fee=FEE, required=None):
    return (s, r, value, fee,
            value + fee if required is None else required, nonce)


def empty_book():
    return [0] * ACCOUNTS, [0] * ACCOUNTS


# ------------------------------------------------------ the kernel's cases
@pytest.mark.parametrize("lanes", LANES)
def test_ring_block_every_sender_funded_by_the_lane_before(lanes):
    """genTxRing: each transfer moves the sender's whole balance less
    the fee to the next account, which sends it on — equality in every
    lane, and only lane 0's sender held anything before the block."""
    bal, non = empty_book()
    bal[0] = 2**100
    n = min(lanes, ACCOUNTS - 2)
    txs, have = [], bal[0]
    for j in range(n):
        have -= FEE
        txs.append(tx(j, j + 1, have))
    assert check(bal, non, txs, lanes, want_ok=True, want_pre=False)


@pytest.mark.parametrize("lanes", LANES)
def test_ring200_like_block_senders_repeat(lanes):
    """A short ring walked three times in one block: every account
    sends three times (nonce offsets 0-2), funded between its sends."""
    ring = 5
    bal, non = empty_book()
    bal[0] = 10**30
    non[:ring] = [7, 0, 3, 0, 1]
    sent = [0] * ring
    txs, have = [], bal[0]
    for j in range(3 * ring):
        s = j % ring
        have -= FEE
        txs.append(tx(s, (s + 1) % ring, have, nonce=non[s] + sent[s]))
        sent[s] += 1
    assert check(bal, non, txs, lanes, want_ok=True, want_pre=False)
    # one wrong nonce in the walk fails it, under both rules
    bad = list(txs)
    bad[7] = bad[7][:5] + (bad[7][5] + 1,)
    assert not check(bal, non, bad, lanes, want_ok=False, want_pre=False)


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("short", [0, 1])
def test_balance_equal_to_the_requirement_passes_one_wei_short_fails(
        lanes, short):
    """The requirement is gas * fee_cap + value, above value + fee: a
    balance EQUAL to it passes, one wei less does not — both for a
    pre-block balance (lane 0) and for one that arrived in the block."""
    cap_room = TX_GAS * 5 * GWEI          # fee cap above the price paid
    bal, non = empty_book()
    v0, v1 = 10**18, 4 * 10**17
    bal[0] = v0 + FEE + cap_room
    txs = [tx(0, 1, v0, required=v0 + FEE + cap_room),
           tx(1, 2, v1, required=v0 + short)]
    assert check(bal, non, txs, lanes, want_pre=False) is (short == 0)
    bal[0] -= short
    assert check(bal, non, txs[:1], lanes) is (short == 0)


@pytest.mark.parametrize("lanes", LANES)
def test_credit_in_a_later_lane_does_not_count(lanes):
    """Order, not totals: the same two transfers commit when the credit
    comes first and fail when the spender comes first."""
    bal, non = empty_book()
    bal[0] = 10**20
    fund, spend = tx(0, 1, 10**19), tx(1, 2, 10**18)
    assert check(bal, non, [fund, spend], lanes, want_ok=True)
    assert not check(bal, non, [spend, fund], lanes, want_ok=False)


@pytest.mark.parametrize("lanes", LANES)
def test_sums_that_carry_out_of_256_bits(lanes):
    """Neither side of the compare may wrap at 2^256.  Need: three
    sends of ~2^255 each add up past 2^256 — wrapped, the third would
    read as cheap and pass.  Have: 2^255 held plus 2^255 paid in is
    2^256 — wrapped, it would read as 0 and fail."""
    half = 2**255
    bal, non = empty_book()
    bal[0] = 2**256 - 1
    most = half - 1 - FEE            # two of them: 2^256 - 2 with fees
    txs = [tx(0, 1, most, nonce=0), tx(0, 2, most, nonce=1),
           tx(0, 3, most, nonce=2)]
    assert not check(bal, non, txs, lanes, want_ok=False, want_pre=False)
    assert check(bal, non, txs[:2], lanes, want_ok=True, want_pre=True)
    bal, non = empty_book()
    bal[0], bal[1] = half + FEE, half
    txs = [tx(0, 1, half), tx(1, 2, 2**256 - 2 * FEE, required=2**256 - 1)]
    ok, pre_ok, got_bal, _ = run_step(bal, non, txs, lanes)
    assert ok and not pre_ok
    assert sequential(bal, non, txs)[0]
    assert got_bal[1] == FEE and got_bal[2] == 2**256 - 2 * FEE


@pytest.mark.parametrize("lanes", LANES)
def test_coinbase_fees_stay_outside_the_check(lanes):
    """A coinbase that sends what it earned earlier in the block is
    sequentially valid and REFUSED (the engine falls back): the one
    place the rule stays conservative."""
    bal, non = empty_book()
    bal[0] = 10**20
    txs = [tx(0, 1, 10**18), tx(COINBASE, 2, FEE // 2, fee=FEE // 4)]
    assert sequential(bal, non, txs)[0]
    ok, pre_ok, _, _ = run_step(bal, non, txs, lanes)
    assert not ok and not pre_ok


def _random_block(rng, lanes):
    """A mixed block over a few accounts, so that senders repeat, are
    paid and run dry: about half of the blocks are valid."""
    accounts = rng.randrange(3, 9)
    bal, non = empty_book()
    for a in range(accounts):
        bal[a] = rng.choice([0, 0, rng.randrange(10**18), 10**19])
        non[a] = rng.randrange(4)
    book, nonce = list(bal), list(non)
    txs = []
    for _ in range(rng.randrange(1, lanes + 1)):
        fee = TX_GAS * rng.randrange(25, 30) * GWEI
        room = rng.choice([0, 0, TX_GAS * GWEI])
        rich = [a for a in range(accounts) if book[a] > fee + room]
        s = rng.choice(rich) if rich and rng.random() < 0.97 \
            else rng.randrange(accounts)
        r = rng.randrange(accounts)
        can = book[s] - fee - room
        if can > 0 and rng.random() < 0.98:
            value = rng.choice([can, rng.randrange(can + 1)])
        else:  # overdraws: a later credit to s does not help
            value = book[s] + rng.randrange(1, 10**17)
        use = nonce[s] if rng.random() < 0.995 else nonce[s] + 1
        txs.append((s, r, value, fee, value + fee + room, use))
        nonce[s] += 1
        if book[s] >= value + fee + room:
            book[s] -= value + fee
            book[r] += value
    return bal, non, txs


@pytest.mark.parametrize("lanes", LANES)
def test_random_mixed_blocks_agree_with_the_sequential_reference(lanes):
    rng = random.Random(20261003 + lanes)
    verdicts = [check(*_random_block(rng, lanes), lanes)
                for _ in range(200)]
    # the generator makes both kinds, or the test shows nothing
    assert 40 <= sum(verdicts) <= 160, sum(verdicts)


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("shape", ["valuetx", "p2p", "token"])
def test_accepted_cells_shapes_pass_under_both_rules(lanes, shape):
    """What the three accepted cells send: one 1-wei transfer a block;
    payments between well-funded accounts, senders repeating; token
    calls, which reach this step as value-0 transfers that pay gas."""
    rng = random.Random(7)
    bal, non = empty_book()
    for a in range(16):
        bal[a] = 10**27
    if shape == "valuetx":
        txs = [tx(0, 17, 1)]
    else:
        txs, sent = [], [0] * 16
        for _ in range(lanes):
            s, r = rng.sample(range(16), 2)
            value = rng.randrange(1, 10**15) if shape == "p2p" else 0
            txs.append(tx(s, r, value, nonce=sent[s],
                          required=value + FEE + TX_GAS * GWEI))
            sent[s] += 1
    assert check(bal, non, txs, lanes, want_ok=True, want_pre=True)


def test_order_check_is_in_the_jitted_step_and_bounded():
    """The scope the device trace finds the check by is inside
    coreth/transfer_step's program, and past ORDER_CHECK_MAX_LANES the
    step builds no [B, B] mask (the pre-block rule alone)."""
    def lowered(lanes):
        i = jax.ShapeDtypeStruct((lanes,), jnp.int32)
        w = jax.ShapeDtypeStruct((lanes, 16), jnp.int32)
        return E._transfer_step.lower(
            jax.ShapeDtypeStruct((64, 16), jnp.int32),
            jax.ShapeDtypeStruct((64,), jnp.int32), i, i, w, w, w, i, i,
            jax.ShapeDtypeStruct((lanes,), jnp.bool_), 0,
            num_accounts=64).as_text(debug_info=True)
    assert "coreth/transfer_order_check" in lowered(64)
    assert E.ORDER_CHECK_MAX_LANES == 4096
    assert "coreth/transfer_order_check" not in lowered(
        4 * E.ORDER_CHECK_MAX_LANES)


# ------------------------------------------------------------ engine level
KEYS = [0x7000 + i for i in range(12)]
ADDRS = [priv_to_address(k) for k in KEYS]


def ring_chain(n_blocks, txs_per_block, ring=12, funds=2**100):
    """genTxRing at toy size: account 0 alone funded; every transfer
    moves its sender's whole balance less the fee at the block's base
    fee to the next ring account; ``from`` and the funds carry on
    across blocks."""
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={ADDRS[0]: GenesisAccount(balance=funds)})
    db = Database()
    gblock = genesis.to_block(db)
    walk = {"from": 0, "funds": funds, "nonces": [0] * ring}

    def gen(_i, bg):
        for _ in range(txs_per_block):
            src = walk["from"]
            dst = (src + 1) % ring
            walk["funds"] -= TX_GAS * bg.base_fee
            bg.add_tx(sign_tx(LegacyTx(
                nonce=walk["nonces"][src], gas_price=bg.base_fee,
                gas=TX_GAS, to=ADDRS[dst], value=walk["funds"]),
                KEYS[src], CFG.chain_id))
            walk["nonces"][src] += 1
            walk["from"] = dst

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=10)
    return genesis, blocks


def fresh_engine(genesis, **kw):
    db = Database()
    gb = genesis.to_block(db)
    return ReplayEngine(CFG, db, gb.root, parent_header=gb.header, **kw)


@pytest.mark.parametrize("txs_per_block", [8, 30])
def test_ring_chain_replays_on_the_device_path(txs_per_block):
    """A 12-account ring of 5 blocks at the engine's defaults: the lead
    block through replay_block, the rest through replay, every header
    root met, no block to the host.  30 a block walks the ring 2.5
    times: senders repeat inside a block (ring200's shape)."""
    genesis, blocks = ring_chain(5, txs_per_block)
    engine = fresh_engine(genesis)
    engine.replay_block(blocks[0])
    assert engine.root == blocks[0].header.root
    assert engine.replay(blocks[1:]) == blocks[-1].header.root
    st = engine.stats
    assert st.blocks_fallback == 0 and st.blocks_device == 5
    assert st.blocks_order_dependent == 5
    assert st.row()["blocks_order_dependent"] == 5
    # the book: one account holds the money, every other one it passed
    # through is left at exactly 0 with its nonces spent
    from coreth_tpu.state import StateDB
    engine.commit()
    sdb = StateDB(engine.root, engine.db)
    held = [sdb.get_balance(a) for a in ADDRS]
    assert sum(1 for b in held if b) == 1
    assert sum(sdb.get_nonce(a) for a in ADDRS) == 5 * txs_per_block


def _credit_then_spend_chain():
    """tests/test_replay.py's old "device refuses, host accepts" block:
    block 1 is A -> B big, then B -> C more than B held before the
    block.  Under the in-order rule the device commits it."""
    keys, addrs = KEYS[:3], ADDRS[:3]
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={addrs[0]: GenesisAccount(balance=10**24),
                             addrs[1]: GenesisAccount(balance=10**17),
                             addrs[2]: GenesisAccount(balance=10**24)})
    db = Database()
    gblock = genesis.to_block(db)
    big = 5 * 10**23

    def pay(bg, key, nonce, to, value):
        bg.add_tx(sign_tx(DynamicFeeTx(
            chain_id_=CFG.chain_id, nonce=nonce, gas_tip_cap_=GWEI,
            gas_fee_cap_=300 * GWEI, gas=TX_GAS, to=to, value=value),
            key, CFG.chain_id))

    def gen(i, bg):
        if i == 1:
            pay(bg, keys[0], 1, addrs[1], big)
            pay(bg, keys[1], 0, addrs[2], big // 2)
        else:
            pay(bg, keys[0], {0: 0, 2: 2}[i], bytes([0x52 + i]) * 20, 777)

    blocks, _ = generate_chain(CFG, gblock, db, 3, gen, gap=2)
    return genesis, blocks


@pytest.mark.parametrize("window", [1, 16])
def test_credit_then_spend_block_commits_on_the_device(window):
    genesis, blocks = _credit_then_spend_chain()
    engine = fresh_engine(genesis, capacity=256, window=window)
    assert engine.replay(blocks) == blocks[-1].root
    assert engine.stats.blocks_fallback == 0
    assert engine.stats.blocks_device == 3
    assert engine.stats.blocks_order_dependent == 1


def test_counter_is_zero_on_a_p2p_chain_and_rides_the_stream_report():
    """Well-funded senders paying each other (p2p's shape): the
    pre-block rule would have committed every block, so the counter
    stays 0 — in stats.row(), the StreamReport and publish_metrics."""
    from test_replay import build_transfer_chain
    from coreth_tpu.metrics import Registry
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    genesis, _, blocks = build_transfer_chain(3, 8, cross=True)
    engine = fresh_engine(genesis, capacity=256)
    report = StreamingPipeline(engine, ChainFeed(blocks)).run()
    assert engine.root == blocks[-1].header.root
    assert engine.stats.blocks_device == 3
    assert engine.stats.row()["blocks_order_dependent"] == 0
    assert report.lanes["blocks_order_dependent"] == 0
    # and a ring through the same pipeline counts its blocks there
    genesis, blocks = ring_chain(4, 6)
    engine = fresh_engine(genesis, capacity=256)
    pipe = StreamingPipeline(engine, ChainFeed(blocks))
    report = pipe.run()
    assert engine.root == blocks[-1].header.root
    assert engine.stats.blocks_fallback == 0
    assert report.lanes["blocks_order_dependent"] == 4
    assert pipe._live_report()["lanes"] == report.lanes
    reg = Registry()
    engine.publish_metrics(reg)
    assert reg.snapshot()["replay/blocks_order_dependent"]["value"] == 4

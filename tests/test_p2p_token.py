"""Block-STM's p2p payment as a token call (tier-1, CPU, small sizes).

The benchmark's ``blockstm-p2p-token-1k`` deployment at toy size: the
program — the fused device OCC path at the engine's defaults — against
the plain reference (``benchlib/plainevm.py``, nothing of the program):
state root, every nonce, native balance and token slot, and the gas of
every call; the plain reference's gas against sums worked out by hand
from the opcode schedule; the selection (the token fast path does not
know this contract, the serial short-circuit leaves computed keys
alone); and the machine's phases in the engine's self-time account.
"""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from benchlib import chains, names, plainevm, plainref  # noqa: E402

SEED = 2**31 + 31
ENGINE_KW = dict(window=2, capacity=256, slot_capacity=64)
TOKEN_BUILDER, _ = names.load_named("chains", "p2p_token")
CODE = TOKEN_BUILDER.TOKEN_RUNTIME
A, B = bytes([0x11] * 20), bytes([0x22] * 20)
HELD = 10**24


def toy_config(accounts, txs, blocks):
    _cell, _entry, config, traffic = names.resolve_cell(
        names.load_spec(), "p2p-token-1k.catchup")
    config = copy.deepcopy(config)
    config["chain_blocks"] = blocks
    config["chain"]["accounts"] = accounts
    config["txs_per_block"] = txs
    return config, traffic


def plan_of(config, seed):
    plan = TOKEN_BUILDER._plan(config, seed)
    return [plan(i) for i in range(config["chain_blocks"])]


def conflicts(rows):
    """Payments of one block that touch a slot an earlier one wrote."""
    seen, n = set(), 0
    for src, dst, _amt in rows:
        n += bool({src, dst} & seen)
        seen |= {src, dst}
    return n


# ------------------------------------------------ the plain reference
def _exec_gas(data, caller=A, storage=None, gas=100_000, code=CODE):
    if storage is None:
        storage = {plainevm.mapping_slot(A): HELD,
                   plainevm.mapping_slot(B): HELD}
    out = plainevm.call(code, caller, data, gas, storage)
    return out, out.gas_used - plainevm.intrinsic_gas(data)


# by hand, from the opcode schedule: the dispatcher up to the JUMPI
# into transfer (PUSH1 CALLDATALOAD PUSH1 SHR DUP1 PUSH4 EQ PUSH2 = 8 x
# 3, JUMPI 10); the zero-recipient check (JUMPDEST 1, PUSH1
# CALLDATALOAD DUP1 ISZERO PUSH2 = 5 x 3, JUMPI 10); the revert block
# (JUMPDEST 1, PUSH1 PUSH1 2 x 3, REVERT 0)
DISPATCH, ZERO_CHECK, REVERT_BLOCK = 34, 26, 7
# amount and the sender's key: PUSH1 CALLDATALOAD 6, CALLER 2, two
# PUSH1 + MSTORE with a word of memory each (3 + 3 + 3 and 3 + 3 + 3 +
# 3), PUSH1 PUSH1 6, KECCAK256 of two words 30 + 12
SENDER_KEY = 6 + 2 + 9 + 12 + 6 + 42
# DUP1 3, the cold SLOAD 2,100, DUP3 DUP2 LT PUSH2 4 x 3, JUMPI 10
BALANCE_CHECK = 3 + 2_100 + 12 + 10
# DUP3 SWAP1 SUB SWAP1 4 x 3 and the warm nonzero-to-nonzero SSTORE
DEBIT = 12 + 2_900
# DUP2 PUSH1 MSTORE 9, PUSH1 PUSH1 KECCAK256 6 + 42, DUP1 3, the cold
# SLOAD 2,100, DUP3 ADD SWAP1 9, SSTORE 2,900
CREDIT = 9 + 48 + 3 + 2_100 + 9 + 2_900
# DUP1 PUSH1 MSTORE 9, DUP2 3, CALLER 2, PUSH32 PUSH1 PUSH1 9, LOG3 375
# + 3 x 375 + 8 x 32; PUSH1 PUSH1 MSTORE 9, PUSH1 PUSH1 6, RETURN 0
EVENT_AND_RETURN = 9 + 3 + 2 + 9 + 1_756 + 9 + 6


def test_plainevm_gas_of_a_payment_by_hand():
    data = plainevm.transfer_data(B, 999_999_999_999_999)
    out, exec_gas = _exec_gas(data)
    assert out.status == plainevm.STOP_OK
    assert exec_gas == (DISPATCH + ZERO_CHECK + SENDER_KEY
                        + BALANCE_CHECK + DEBIT + CREDIT
                        + EVENT_AND_RETURN) == 12_037
    # 4 selector + 20 address + 7 amount bytes nonzero, 37 zero
    assert plainevm.intrinsic_gas(data) == 21_000 + 31 * 16 + 37 * 4
    # the configuration's tx_gas is this worst case, and 445 fit
    config, _ = toy_config(16, 8, 1)
    assert out.gas_used == config["chain"]["tx_gas"] == 33_681
    full = names.resolve_cell(names.load_spec(),
                              "p2p-token-1k.catchup")[2]
    assert full["txs_per_block"] == 15_000_000 // 33_681 == 445
    assert out.refund == 0 and out.output == (1).to_bytes(32, "big")
    assert out.writes == {
        plainevm.mapping_slot(A): HELD - 999_999_999_999_999,
        plainevm.mapping_slot(B): HELD + 999_999_999_999_999}
    (topics, logdata), = out.logs
    assert topics == [
        plainref.keccak256(b"Transfer(address,address,uint256)"),
        b"\x00" * 12 + A, b"\x00" * 12 + B]
    assert int.from_bytes(logdata, "big") == 999_999_999_999_999


@pytest.mark.parametrize("data,storage,exec_gas", [
    # zero recipient: reverts before any storage is touched
    (plainevm.transfer_data(b"\x00" * 20, 5), None,
     DISPATCH + ZERO_CHECK + REVERT_BLOCK),
    # the sender holds less than it sends: one cold SLOAD, then revert
    (plainevm.transfer_data(B, 5), {plainevm.mapping_slot(A): 4},
     DISPATCH + ZERO_CHECK + SENDER_KEY + BALANCE_CHECK + REVERT_BLOCK),
])
def test_plainevm_reverts_give_back_the_gas_left(data, storage, exec_gas):
    out, used = _exec_gas(data, storage=storage)
    assert out.status == plainevm.REVERTED and used == exec_gas
    assert out.writes == {} and out.logs == [] and out.output == b""


@pytest.mark.parametrize("code,storage,exec_gas,refund", [
    # PUSH1 0 SLOAD twice: cold 2,100, then warm 100
    ("6000546000 54", {}, 3 + 2_100 + 3 + 100, 0),
    # PUSH1 5 PUSH1 0 SSTORE on a slot that holds 9: cold 2,100 + 2,900
    ("60056000 55", {bytes(32): 9}, 6 + 2_100 + 2_900, 0),
    # the same slot read first: the SSTORE is warm, 2,900
    ("600054 60056000 55", {bytes(32): 9}, 3 + 2_100 + 6 + 2_900, 0),
    # writing what is there: 100; from zero: 20,000
    ("60096000 55", {bytes(32): 9}, 6 + 2_100 + 100, 0),
    ("60096000 55", {}, 6 + 2_100 + 20_000, 0),
    # clearing a slot: EIP-3529's 4,800 on the counter (never paid)
    ("60006000 55", {bytes(32): 9}, 6 + 2_100 + 2_900, 4_800),
])
def test_plainevm_storage_gas_by_hand(code, storage, exec_gas, refund):
    out, used = _exec_gas(b"", storage=storage,
                          code=bytes.fromhex(code.replace(" ", "")))
    assert out.status == plainevm.STOP_OK
    assert (used, out.refund) == (exec_gas, refund)


def test_plainevm_knows_the_tokens_opcodes_and_no_other():
    used = set()
    pc = 0
    while pc < len(CODE):
        used.add(CODE[pc])
        pc += 1 + (CODE[pc] - 0x5F if 0x60 <= CODE[pc] <= 0x7F else 0)
    assert used == set(plainevm.OPCODES)
    for op in (0x50, 0x5F, 0xF1, 0x02):         # POP PUSH0 CALL MUL
        with pytest.raises(plainevm.UnknownOpcode):
            plainevm.call(bytes([op]), A, b"", 30_000, {})
    # failing takes all the gas; an SSTORE inside the stipend fails
    out = plainevm.call(bytes.fromhex("6005600055"), A, b"", 23_000, {})
    assert out.status == plainevm.FAILED and out.gas_used == 23_000
    out = plainevm.call(CODE, A, plainevm.transfer_data(B, 1), 33_000,
                        {plainevm.mapping_slot(A): HELD})
    assert out.status == plainevm.FAILED and out.gas_used == 33_000


# ----------------------------------------------------- the selection
def test_the_fast_path_does_not_know_this_token():
    from coreth_tpu.crypto import keccak256
    from coreth_tpu.evm.census import static_storage_keys
    from coreth_tpu.workloads.erc20 import TOKEN_CODE_HASH
    config, traffic = toy_config(16, 8, 1)
    assert keccak256(CODE) != TOKEN_CODE_HASH
    assert keccak256(CODE).hex() == config["chain"]["token_code_hash"] \
        == plainref.keccak256(CODE).hex()
    # computed keys: the serial short-circuit has nothing to prove
    assert static_storage_keys(CODE) is None
    from benchlib import replay_pass
    from coreth_tpu.types import Block
    genesis, wire = chains.build_wire(config, traffic, SEED)
    engine = replay_pass.fresh_engine(genesis, ENGINE_KW)
    block = Block.decode(wire[0])
    tx = block.transactions[0]
    rules = engine.config.rules(block.number, block.time)
    ctx = engine._token_block_ctx(rules, block)
    assert engine._classify_token(
        tx, engine.signer.sender(tx), engine._account(tx.to), ctx,
        {}) is None
    assert engine._classify(block) is None
    plans = engine._machine.classify(block)
    assert [pl.kind for pl in plans] == ["call"] * 8
    assert not engine._machine._serial_eligible(plans)


# --------------------------------- the program against the reference
def _replay(config, traffic, seed=SEED):
    """The host processor's chain (with its receipts), one pass of the
    engine at the harness's entry, and the plain reference's book."""
    from benchlib import replay_pass
    from coreth_tpu.chain import generate_chain
    from coreth_tpu.state import Database
    from coreth_tpu.types import Block
    genesis, state = TOKEN_BUILDER.genesis(config, traffic, seed)
    db = Database()
    gblock = genesis.to_block(db)
    blocks, receipts = generate_chain(
        genesis.config, gblock, db, config["chain_blocks"],
        TOKEN_BUILDER.gen(config, traffic, seed, genesis, state),
        gap=config["chain"]["block_gap_s"])
    engine = replay_pass.fresh_engine(genesis, ENGINE_KW)
    replay_pass.run_engine(engine,
                           [Block.decode(b.encode()) for b in blocks])
    assert engine.root == blocks[-1].header.root
    book = TOKEN_BUILDER.ledger(config, traffic, seed)
    return engine, receipts, book


@pytest.mark.parametrize("accounts,txs,blocks,dense", [
    (16, 8, 5, True),       # 8 pairs of 16 holders: chains of conflicts
    (1000, 8, 3, False),    # 8 pairs of 1,000: every payment on its own
])
def test_program_against_the_plain_reference(accounts, txs, blocks,
                                             dense):
    config, traffic = toy_config(accounts, txs, blocks)
    plan = plan_of(config, SEED)
    if dense:
        assert all(conflicts(rows) >= 2 for rows in plan)
        # a sender that sends twice in one block: consecutive nonces
        assert any(len({s for s, _d, _a in rows}) < txs for rows in plan)
    else:
        assert all(conflicts(rows) == 0 for rows in plan)
    engine, receipts, book = _replay(config, traffic)
    # the fused device path, and nothing else
    mx = engine._machine
    assert (mx.blocks, mx.host_txs, mx.serial_blocks, mx.dirty_blocks) \
        == (blocks, 0, 0, 0)
    assert engine.stats.blocks_fallback == 0
    mc = mx.machine_counters()
    assert mc["specialize_escapes"] == 0 and mc["kernel_retraces"] == 0
    assert mc["lanes_specialized"] == blocks * txs
    assert (mx.rounds > 0) is dense         # re-execution rounds ran
    # every call's gas: the host processor's receipts (the engine is
    # held to their root block by block) against the plain reference
    assert [r.gas_used for rs in receipts for r in rs] == book.gas_used
    assert all(r.status == 1 and len(r.logs) == 1
               for rs in receipts for r in rs)
    # every nonce, native balance and token slot, and the state root
    back = TOKEN_BUILDER.read_back(engine, book)
    assert back["wrong"] == []
    assert back["compared"] == 2 * accounts + 2   # + token + coinbase
    assert back["root"] == bytes(engine.root)
    moved = sum(a for rows in plan for _s, _d, a in rows)
    assert sum(book.slots.values()) == accounts * HELD and moved > 0


def test_one_unit_more_is_seen_by_the_plain_reference_alone():
    config, traffic = toy_config(16, 8, 3)
    _g, clean = chains.build_wire(config, traffic, SEED)
    _g, altered = chains.build_wire(config, traffic, SEED, alter=(1, 0))
    assert clean[0] == altered[0] and clean[1] != altered[1]
    book = TOKEN_BUILDER.ledger(config, traffic, SEED)
    from coreth_tpu.types import Block
    assert Block.decode(clean[-1]).header.root == book.state_root()
    assert Block.decode(altered[-1]).header.root != book.state_root()


# ------------------------------------------------- the self-time account
MACHINE_PHASES = ("machine", "machine/prepare", "machine/upload",
                  "machine/dispatch", "machine/fetch_wait",
                  "machine/fold")


def test_machine_phases_sum_to_the_engines_age():
    config, traffic = toy_config(16, 8, 11)
    _replay(config, traffic)            # compiles, learns the recipes
    engine, _receipts, _book = _replay(config, traffic)
    row, mx, st = engine.account.row(), engine._machine, engine.stats
    for phase in MACHINE_PHASES:
        assert row["n"].get(phase, 0) > 0, phase
    # every instant belongs to one phase
    assert sum(row["self_s"].values()) == pytest.approx(
        row["t_last"] - row["t_open"], rel=1e-9)
    # no machine phase recurs per block or per round: one fold a
    # window, one read and one upload/dispatch a dispatch, one
    # ``machine`` a run (the lead block's, and the rest: windows of the
    # executor's default 8 blocks, here 8 + 2)
    assert mx.WINDOW == 8 and mx.windows == 1 + 2
    assert mx.window_attempts == mx.windows
    assert row["n"]["machine/fold"] == mx.windows
    for phase in ("machine/upload", "machine/dispatch",
                  "machine/fetch_wait"):
        assert row["n"][phase] == mx.window_attempts, phase
    # prepare: once a dispatch, and once more for the one window whose
    # lanes were built ahead, while the window before it was in flight
    assert row["n"]["machine/prepare"] == mx.window_attempts + 1
    assert row["n"]["machine"] == 2
    assert row["n"]["commit/flush"] >= mx.windows
    for phase in ("machine/serial", "machine/host_occ", "fallback",
                  "window/dispatch"):
        assert phase not in row["n"], phase
    # the machine's phases hold what ReplayStats.t_device and t_trie
    # bracket on this path (prepare, upload, dispatch, the read, and
    # the fold's unpacking; the blocks' account sweep and staging)
    machine_s = sum(v for k, v in row["self_s"].items()
                    if k.startswith("machine"))
    assert machine_s >= st.t_device > 0
    assert machine_s <= st.t_device + st.t_trie + 0.05
    # lanes: every call packed once (no re-dispatch), in windows
    # bucketed to 8 blocks x 8 lanes
    assert st.machine_lanes_real == 11 * 8
    assert st.machine_lanes_padded == mx.windows * 8 * 8
    assert (st.lanes_real, st.lanes_padded) == (0, 0)
    mc = mx.machine_counters()
    assert (mc["lanes_real"], mc["lanes_padded"]) \
        == (st.machine_lanes_real, st.machine_lanes_padded)

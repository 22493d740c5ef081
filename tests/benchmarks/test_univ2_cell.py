"""The Uniswap V2 cell at toy size on the CPU (tier-1).

``univ2-swap.catchup``: the builder's chain (2 blocks, 4 pairs)
replayed through ``ReplayEngine`` at the engine's defaults commits
every header root on the device with no transaction left to the host
and six call frames a swap; ``plainamm``'s book agrees, slot for slot
and account for account, with the state ``BlockChain.insert_chain``
(the host processor) leaves; each of the four execution gases
``plainamm`` charges is what ``native/evm.cc`` spends on that path; the
cell's entries in ``BENCHMARK.json`` and its run through the harness;
and the cell's two readers on hand-made rows.
"""

import copy

import pytest

from test_harness import SEED, SPEC, TOY_BLOCKS, run_toy, toy_cell  # noqa: F401 — toy_cell is a fixture

from benchlib import chains, names, plainamm, replay_pass

CELL = "univ2-swap.catchup"
CONFIG_FILE = "benchmarks/configs/univ2-swap.json"
# the cell's own per-layer metrics, and the metrics of every catch-up
# cell (or of every cell) whose lists it joins
OWN = ("call_frames_per_tx", "machine_steps_per_tx")
ALSO_IN = ("decode_share", "sender_share", "recover_worker_busy_share_acct",
           "recover_us_per_sig")


def _small():
    _cell, _entry, config, traffic = names.resolve_cell(SPEC, CELL)
    config = copy.deepcopy(config)
    config["chain_blocks"] = 2
    config["txs_per_block"] = 12
    c = config["chain"]
    c["accounts"], c["tokens"], c["pair_offsets"] = 16, 4, [1]
    return config, traffic


@pytest.fixture(scope="module")
def small_chain():
    from coreth_tpu.types import Block
    config, traffic = _small()
    genesis, wire = chains.build_wire(config, traffic, SEED)
    return config, traffic, genesis, [Block.decode(w) for w in wire]


def test_swaps_commit_on_the_device_six_frames_a_swap(small_chain):
    config, traffic, genesis, blocks = small_chain
    engine = replay_pass.fresh_engine(genesis, {})
    replay_pass.run_engine(engine, blocks)       # raises off a header
    assert engine.root == blocks[-1].header.root
    row = replay_pass.engine_row(engine)
    assert row["blocks_fallback"] == 0
    assert row["blocks_device"] == len(blocks)
    m = row["machine"]
    assert m["host_txs"] == 0 and m["dirty_blocks"] == 0
    assert m["machine_blocks"] == len(blocks)
    st = engine.stats
    txs = sum(len(b.transactions) for b in blocks)
    assert st.txs == txs and st.machine_frame_host_txs == {}
    assert st.machine_frames == 6 * txs
    assert st.machine_lane_steps > 0
    # the window's code and callee tables are a phase of their own
    assert "machine/callees" in engine.account.row()["self_s"]
    # the book reads back under the engine's root
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    out = builder.read_back(engine, builder.ledger(config, traffic, SEED))
    assert out["wrong"] == [] and out["compared"] > 0


def test_a_block_wider_than_a_chunk_commits_on_the_device():
    """40 swaps a block over 4 pairs: more lanes than a call kernel
    steps at once (``machine.CALL_LANES``), so a round runs them in
    chunks, and the swaps of one pair wait round after round for the
    one before them.  Every header root commits on the device, no
    transaction goes to the host, six frames a swap; the footprint
    pre-run leaves no discovery dispatch."""
    from coreth_tpu.evm.device import machine as M
    from coreth_tpu.types import Block
    config, traffic = _small()
    config["txs_per_block"] = 40
    assert config["txs_per_block"] > M.CALL_LANES
    genesis, wire = chains.build_wire(config, traffic, SEED)
    blocks = [Block.decode(w) for w in wire]
    engine = replay_pass.fresh_engine(genesis, {})
    replay_pass.run_engine(engine, blocks)
    assert engine.root == blocks[-1].header.root
    row = replay_pass.engine_row(engine)
    m = row["machine"]
    assert row["blocks_fallback"] == 0 and m["host_txs"] == 0
    assert m["machine_blocks"] == len(blocks) and m["dirty_blocks"] == 0
    assert m["discovery_dispatches"] == 0
    assert engine.stats.machine_frames == 6 * 40 * len(blocks)
    assert engine.stats.machine_frame_host_txs == {}


def test_plainamm_agrees_with_the_host_chain(small_chain):
    """The plain reference against BlockChain.insert_chain, the host
    processor's own insert path: every slot of every token and pair and
    every account, as the book has them."""
    from coreth_tpu.chain import BlockChain
    config, traffic, genesis, blocks = small_chain
    chain = BlockChain(genesis)
    assert chain.insert_chain(blocks) == len(blocks)
    sdb = chain.state_at(blocks[-1].header.root)
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    book = builder.ledger(config, traffic, SEED)
    n = 0
    for contract, slots in book.storage().items():
        for key, want in slots.items():
            assert int.from_bytes(sdb.get_state(contract, key),
                                  "big") == want, (contract.hex(), key)
            n += 1
    for addr, (nonce, balance) in book.accounts().items():
        assert (sdb.get_nonce(addr), sdb.get_balance(addr)) \
            == (nonce, balance), addr.hex()
        n += 1
    w = builder.world(config)
    assert n >= len(w.pairs) * 7 + len(w.tokens) * 16
    # every swap moved the reserves it should, and the accumulators of
    # a pair grew once a block it was traded in
    moved = [pa for pa in w.pairs
             if book.reserves[pa.address] != [config["chain"]["reserve"]] * 2]
    assert moved
    for pa in moved:
        assert book.price[pa.address][0] \
            > config["chain"]["price_cumulative_start"]


def test_plainamm_formulas():
    # getAmountOut: 0.3% off the input, floor
    assert plainamm.amount_out(1000, 10**6, 10**6) == \
        1000 * 997 * 10**6 // (10**9 + 1000 * 997)
    with pytest.raises(ValueError):
        plainamm.amount_out(0, 1, 1)
    w = plainamm.World(4, [1], b"\x01" * 20, b"\x02" * 32)
    assert len(w.pairs) == 4 and len(set(p.address for p in w.pairs)) == 4
    for pa in w.pairs:
        assert pa.token0 < pa.token1


def test_held_entries_resolve():
    """The cell's entries in BENCHMARK.json pass test_harness's checks
    of a cell's names, the configuration keeps its gas-full blocks (only
    the chain's length is cut, to 33, 17 or 9 blocks), and the cell
    joins the lists of the catch-up cells' per-layer metrics."""
    _c, entry, config, traffic = names.resolve_cell(SPEC, CELL)
    cell = {w["name"]: w for w in SPEC["workloads"]}[CELL]
    assert cell["config"] == "univ2-swap" and cell["chips"] == 1
    assert cell["traffic"] == "catchup-frames"
    assert entry["file"] == CONFIG_FILE
    assert config["name"] == entry["name"] and config["chips"] == 1
    assert set(entry["reduced"]) == set(config["reduced"]) \
        <= {"chain_blocks"}
    assert config["chain_blocks"] in (33, 17, 9)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert len(entry["source"]) <= 200
    assert entry["source"] == config["source"]
    c = config["chain"]
    assert config["txs_per_block"] == c["gas_limit"] // c["tx_gas"]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    for name in OWN:
        m = listed[name]
        assert m["workloads"] == [CELL] and m["layer"] == "step machine"
        assert m["source"] == "program_counter"
        assert m["moves"] == "committed_txs_per_s"
        reader, _ = names.load_named("metrics", name)
        assert callable(reader.read)
    for name in ALSO_IN:
        assert CELL in listed[name]["workloads"], name
    driver, _ = names.load_named("drivers", traffic["driver"])
    assert callable(driver.drive)


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_univ2_run_reads_six_frames(toy_cell):
    result = run_toy(CELL, trace=1)
    compared = result["compared"]
    assert result["correct"] is True and result["failed"] == 0, compared
    assert result["attempted"] >= TOY_BLOCKS
    assert all(n["value"] == 0 for n in compared.values())
    assert compared["machine.host_txs"]["value"] == 0
    metrics = result["metrics"]
    wanted = {m["name"] for m in names.cell_metrics(
        SPEC, "per_layer", CELL)}
    # the CPU has no device plane: the idle share is left out, never 0
    assert set(metrics) == wanted - {"device_idle_share"}
    assert metrics["call_frames_per_tx"]["value"] == 6.0
    assert metrics["machine_steps_per_tx"]["value"] > 0


def test_exec_gas_is_what_native_evm_spends(small_chain):
    """The four execution gases plainamm charges, each spent by
    native/evm.cc (the C++ executor, not the interpreter they were
    measured with) on one swap of that path from the genesis state; and
    ``tx_gas`` less the worst case's intrinsic gas is the least
    execution gas with which the costliest path succeeds."""
    from coreth_tpu.evm import hostexec
    if not hostexec.available():
        pytest.skip("native library not built")
    from coreth_tpu.evm.device import machine as M
    from coreth_tpu.evm.device.tables import fork_key
    from coreth_tpu.evm.hostexec.backend import HostExecBackend
    from coreth_tpu.state import Database, StateDB
    config, traffic, genesis, _blocks = small_chain
    c = config["chain"]
    builder, _ = names.load_named("chains", c["builder"])
    _g, state = builder.genesis(config, traffic, SEED)
    db = Database()
    g = genesis.to_block(db)
    sdb = StateDB(g.root, db)
    w = builder.world(config)
    trader = state["traders"][0]
    coinbase = b"\x01" + b"\x00" * 19
    price = c["fee_cap"]

    def run(side, now, gas):
        book = plainamm.AmmBook(w, state["traders"], c, c["funded"])
        data = book.swap_data(0, side, trader, 10**15, now)
        be = HostExecBackend(
            fork_key(genesis.config.rules(1, now)), genesis.config.chain_id,
            lambda a, k: sdb.get_state(a, k), lambda a: sdb.get_code(a))
        try:
            be.set_env(coinbase, now, 1, c["gas_limit"], 0)
            res = be.call(trader, w.router, 0, price, data, gas,
                          warm_addrs=[trader, w.router, coinbase])
        finally:
            be.close()
        return res

    # genesis leaves blockTimestampLast at 0: a swap at time 0 finds no
    # time elapsed, one at the first block's time pays the accumulators
    gap = c["block_gap_s"]
    for path, side, now in (("in0", 0, 0), ("in1", 1, 0),
                            ("in0_elapsed", 0, gap),
                            ("in1_elapsed", 1, gap)):
        gas = c["tx_gas"]
        res = run(side, now, gas)
        assert res.status == M.STOP, (path, res.status)
        assert gas - res.gas_left == c["exec_gas"][path], path
    worst = c["tx_gas"] - 23_048      # the worst case's intrinsic gas
    assert run(1, gap, worst).status == M.STOP
    assert run(1, gap, worst - 1).status != M.STOP


def test_readers_find_nothing_without_frames():
    for name in ("call_frames_per_tx", "machine_steps_per_tx"):
        reader, _ = names.load_named("metrics", name)
        # a parent's rows carry no ``frames``: nothing to read
        assert reader.read({"passes": [{"txs_committed": 10}]}) is None
        rows = [{"txs_committed": 4, "frames": {
            "frames": 24, "lane_steps": 4000, "host_txs": {}}}] * 2
        want = 6.0 if name == "call_frames_per_tx" else 1000.0
        assert reader.read({"passes": rows}) == want


def test_mesh_engine_routes_call_frame_blocks_to_the_host(small_chain):
    """The sharded machine has no call frames: on a mesh engine a block
    whose code makes them takes the host path, counted under "mesh",
    and still commits at its header's root."""
    import jax
    from coreth_tpu import parallel
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    _config, _traffic, genesis, blocks = small_chain
    db = Database()
    g = genesis.to_block(db)
    engine = ReplayEngine(genesis.config, db, g.root, parent_header=g.header,
                          mesh=parallel.make_mesh(jax.devices("cpu")[:2]))
    replay_pass.run_engine(engine, blocks)
    assert engine.root == blocks[-1].header.root
    st = engine.stats
    assert st.blocks_fallback == len(blocks)
    assert st.machine_frame_host_txs == {
        "mesh": sum(len(b.transactions) for b in blocks)}

"""Sharded multi-device replay: cross-device equivalence + the
exchange-overlap contract.

The PR-8 tentpole shards replay state over the dp mesh (per-shard
account/slot row arenas in DeviceState, per-shard OCC slot tables in
evm/device/shard.py) and exchanges cross-shard effects with packed
psum collectives (replay/shard.py; the exchange step of the OCC path).
These tests pin:

- bit-identical state roots at 1 / 2 / 4 virtual devices across the
  transfer, erc20-via-machine, and swap (full-conflict) shapes, for
  BOTH trie backends — including a window whose txs cross account
  buckets and a chain containing a host-escape block;
- the exchange-overlap dispatch ordering: when a window's collective
  exchange reports clean, the NEXT window's per-shard dispatch goes
  out BEFORE the current window's packed results are fetched (the PR-4
  execute/fold overlap applied to the exchange phase);
- the serve prefetcher warms a mesh engine's senders through
  ``warm_senders`` like any other;
- two 2-device smokes that hold the COUNTS a scaling collapse would
  move (dispatches a window, shard occupancy, retraces), equal or
  bounded across widths — no clock: a wall-time ratio of two CPU-mesh
  runs reads the box's other tenants, and took turns failing tier-1.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest
import jax

from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
from coreth_tpu.parallel import make_mesh
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.state import Database
from coreth_tpu.types import DynamicFeeTx, sign_tx
from coreth_tpu.workloads.erc20 import (
    token_genesis_account, transfer_calldata,
)
from coreth_tpu.workloads.swap import pool_genesis_account, swap_calldata

GWEI = 10**9
KEYS = [0x5100 + i for i in range(8)]
ADDRS = [priv_to_address(k) for k in KEYS]
POOL = b"\x74" * 20
TOKEN = b"\x75" * 20
# device-eligible code that escapes at runtime (MSTORE past mem_cap)
ESCAPER = b"\x76" * 20
ESCAPER_CODE = bytes.fromhex("600061138852" + "00")

_trie_backends = ["py"]
from coreth_tpu.crypto import native as _native  # noqa: E402
if _native.load() is not None:
    _trie_backends.append("native")


def _mappings() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


@pytest.fixture(autouse=True)
def map_headroom():
    """Every XLA:CPU executable maps its code sections, and a process
    may hold 65,530 mappings here (vm.max_map_count).  This file's
    cases compile ~60,000 mappings' worth between them, on top of what
    the worker's earlier files left: past the limit LLVM cannot
    allocate and the worker dies mid-compile.  So a case that starts
    past 40,000 drops the compiled programs first (each case warms its
    own)."""
    if _mappings() > 40_000:
        jax.clear_caches()
    yield


def _alloc(extra=None):
    alloc = {a: GenesisAccount(balance=10**24) for a in ADDRS}
    alloc[POOL] = pool_genesis_account(10**15, 10**15)
    alloc[TOKEN] = token_genesis_account({a: 10**21 for a in ADDRS})
    if extra:
        alloc.update(extra)
    return alloc


def _tx(k, nonces, to, data=b"", gas=200_000, value=0):
    t = sign_tx(DynamicFeeTx(
        chain_id_=CFG.chain_id, nonce=nonces[k], gas_tip_cap_=GWEI,
        gas_fee_cap_=300 * GWEI, gas=gas, to=to, value=value,
        data=data), KEYS[k], CFG.chain_id)
    nonces[k] += 1
    return t


def _build_chain(n_blocks, gen_txs, extra=None):
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc=_alloc(extra))
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def gen(i, bg):
        for t in gen_txs(i, nonces):
            bg.add_tx(t)

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=2)
    return blocks


def _replay(blocks, mesh, extra=None, window=4):
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc=_alloc(extra))
    db = Database()
    g = genesis.to_block(db)
    eng = ReplayEngine(CFG, db, g.root, parent_header=g.header,
                       window=window, capacity=256, batch_pad=64,
                       mesh=mesh)
    root = eng.replay(blocks)
    return root, eng


def _meshes():
    devs = jax.devices("cpu")
    return [None, make_mesh(devs[:2]), make_mesh(devs[:4])]


# ------------------------------------------------- cross-device roots
def _gen_transfer(i, nonces):
    # transfers between accounts in DIFFERENT buckets (8 keccak-spread
    # senders to fresh recipients) — the cross-shard credit exchange
    return [_tx(k, nonces, bytes([0x41 + i]) + bytes([k]) * 19,
                gas=21_000, value=1000 + 7 * i + k) for k in range(6)]


def _gen_erc20(i, nonces):
    return [_tx(k, nonces, TOKEN,
                transfer_calldata(ADDRS[(k + 1) % 8], 5 + k))
            for k in range(6)]


def _gen_swap(i, nonces):
    return [_tx(k, nonces, POOL, swap_calldata(1000 + 17 * i + k))
            for k in range(6)]


def _gen_mixed(i, nonces):
    # machine window containing cross-shard txs: two contracts (two
    # buckets when they split) + plain transfers crossing account
    # buckets, all in one block
    return [
        _tx(0, nonces, POOL, swap_calldata(500 + i)),
        _tx(1, nonces, TOKEN, transfer_calldata(ADDRS[(i + 3) % 8], 7)),
        _tx(2, nonces, bytes([0x46]) * 20, gas=21_000, value=5 + i),
        _tx(3, nonces, POOL, swap_calldata(900 + i)),
    ]


@pytest.mark.parametrize("trie", _trie_backends)
@pytest.mark.parametrize(
    "gen,machine", [(_gen_transfer, False), (_gen_erc20, True),
                    (_gen_swap, True), (_gen_mixed, True)],
    ids=["transfer", "erc20", "swap", "mixed"])
def test_cross_device_roots_bit_identical(monkeypatch, gen, machine,
                                          trie):
    """The same chain replays to bit-identical roots at 1/2/4 virtual
    devices under both trie backends; machine shapes are forced through
    the (sharded) OCC machine path."""
    monkeypatch.setenv("CORETH_TRIE", trie)
    if machine:
        monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
        monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
        monkeypatch.setenv("CORETH_MACHINE_WINDOW", "2")
    blocks = _build_chain(4, gen)
    roots = []
    for mesh in _meshes():
        root, eng = _replay(blocks, mesh)
        assert eng.stats.blocks_fallback == 0
        roots.append(root)
    assert roots[0] == roots[1] == roots[2] == blocks[-1].root


def test_cross_device_roots_with_host_escape(monkeypatch):
    """A host-escape block (lane exceeding mem_cap) inside a machine
    run: every width escalates it to the exact host path and still
    lands the chain root."""
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    extra = {ESCAPER: GenesisAccount(balance=0, nonce=1,
                                     code=ESCAPER_CODE)}

    def gen(i, nonces):
        if i == 1:
            return [_tx(0, nonces, POOL, swap_calldata(321)),
                    _tx(1, nonces, ESCAPER, gas=100_000)]
        return [_tx(k, nonces, POOL, swap_calldata(100 + 13 * i + k))
                for k in range(4)]

    blocks = _build_chain(3, gen, extra)
    for mesh in _meshes():
        root, eng = _replay(blocks, mesh, extra)
        assert root == blocks[-1].root
        assert eng.stats.blocks_fallback == 1
        assert eng._machine.blocks == 2


def test_sharded_runner_vs_single_chip_runner(monkeypatch):
    """CORETH_SHARD_OCC=0 keeps the replicated single-chip window
    runner on a mesh engine; both runners land the same roots (the
    sharded runner's per-shard tables and exchange change nothing
    about results)."""
    monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    blocks = _build_chain(3, _gen_mixed)
    mesh = make_mesh(jax.devices("cpu")[:2])
    root_sharded, es = _replay(blocks, mesh)
    monkeypatch.setenv("CORETH_SHARD_OCC", "0")
    root_single, eu = _replay(blocks, mesh)
    assert root_sharded == root_single == blocks[-1].root
    from coreth_tpu.evm.device.shard import ShardedWindowRunner
    assert isinstance(es._machine._runner, ShardedWindowRunner)
    assert not isinstance(eu._machine._runner, ShardedWindowRunner)


# --------------------------------------------- exchange-overlap order
def test_exchange_overlaps_next_window_dispatch(monkeypatch):
    """THE overlap contract (ISSUE 8 acceptance): when the collective
    exchange reports a window clean, the next window's per-shard OCC
    dispatch is issued BEFORE the current window's packed results are
    fetched — pinned on the EVENT_LOG dispatch/fetch trace, analogous
    to the PR-4 execute/fold overlap test."""
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    monkeypatch.setenv("CORETH_MACHINE_WINDOW", "2")
    from coreth_tpu.evm.device import shard as SH
    blocks = _build_chain(6, _gen_swap)
    SH.EVENT_LOG.clear()
    try:
        root, eng = _replay(blocks, make_mesh(jax.devices("cpu")[:2]))
        assert root == blocks[-1].root
        ev = list(SH.EVENT_LOG)
    finally:
        SH.EVENT_LOG.clear()
    assert eng._machine.windows >= 3
    # at least one steady-state window: exchange fetched, then the
    # NEXT dispatch, and only then the packed-result fetch (seq is
    # module-global, so candidates come from the trace itself)
    seqs = sorted({int(e.split(":")[1]) for e in ev})
    overlapped = [
        s for s in seqs
        if f"exchange_fetch:{s}" in ev and f"dispatch:{s + 1}" in ev
        and f"result_fetch:{s}" in ev
        and ev.index(f"exchange_fetch:{s}")
        < ev.index(f"dispatch:{s + 1}") < ev.index(f"result_fetch:{s}")]
    assert overlapped, f"no overlapped window in {ev}"


# ------------------------------------------------- prefetch recovery
def test_prefetcher_warms_a_mesh_engine_through_warm_senders(monkeypatch):
    """The prefetcher has no recovery of its own: with a dp mesh too it
    hands the chunk to ``engine.warm_senders`` (the native batch) and
    counts what it handed over."""
    from coreth_tpu.serve.prefetch import Prefetcher
    from coreth_tpu.types import Block
    # fresh decode: chain generation already cached the senders
    blocks = [Block.decode(b.encode())
              for b in _build_chain(1, _gen_transfer)]
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=_alloc())
    db = Database()
    g = genesis.to_block(db)
    eng = ReplayEngine(CFG, db, g.root, parent_header=g.header,
                       capacity=256, batch_pad=64,
                       mesh=make_mesh(jax.devices("cpu")[:2]))
    warmed = []
    real = eng.warm_senders
    monkeypatch.setattr(eng, "warm_senders",
                        lambda bs: (warmed.append(list(bs)), real(bs))[1])
    pf = Prefetcher(eng)
    pf.warm(blocks)
    n = sum(len(b.transactions) for b in blocks)
    assert warmed == [blocks]
    assert pf.sigs == n == eng.stats.sigs_host
    assert eng.stats.sigs_device == 0
    assert all(tx.cached_sender() in ADDRS
               for b in blocks for tx in b.transactions)


# --------------------------------------------------- row-arena growth
def test_sharded_occ_table_growth_pads_on_device():
    """A per-shard table-cap re-bucket pads the resident arenas IN
    PLACE on device (rows s*G_old+g -> s*G+g) — the grown tables must
    be bit-identical to a from-scratch host rebuild at the new cap."""
    import numpy as np
    from coreth_tpu.evm.device.shard import ShardedWindowRunner
    mesh = make_mesh(jax.devices("cpu")[:2])
    vals = {}
    contracts = [bytes([0x10 + i]) * 20 for i in range(6)]

    def fill(runner, per_contract):
        for c in contracts:
            for j in range(per_contract):
                key = bytes([j]) + b"\x01" * 31
                vals[(c, key)] = 1 + j + c[0]
                runner._gid(c, key)

    runner = ShardedWindowRunner(
        "durango", lambda c, k: vals.get((c, k), 0), mesh)
    fill(runner, 10)                       # worst shard <= 60 rows
    runner._device_tables(64)
    assert runner.table_cap == 64 and not runner._stale
    fill(runner, 20)                       # worst shard may exceed 64
    t, k = runner._device_tables(128)      # pad path (not a rebuild)
    assert runner.table_cap == 128
    t, k = np.asarray(t).copy(), np.asarray(k).copy()

    # reference: a full host rebuild of the SAME runner state
    runner._stale = True
    tf, kf = runner._device_tables(128)
    np.testing.assert_array_equal(t, np.asarray(tf))
    np.testing.assert_array_equal(k, np.asarray(kf))


def test_sharded_row_arena_growth_remaps():
    """Arena growth in shard mode moves every row (shard-major layout);
    values must survive the device-table rebuild."""
    from coreth_tpu.replay.engine import DeviceState
    from coreth_tpu.types import StateAccount
    st = DeviceState(capacity=16, slot_capacity=16, n_shards=4)
    addrs = [bytes([i]) * 20 for i in range(12)]
    for i, a in enumerate(addrs):
        st.ensure(a, StateAccount(balance=10**18 + i, nonce=i))
    st.flush_staged()
    before = dict(zip(addrs, st.read_accounts(
        [st.index[a] for a in addrs])))
    # force growth: one shard's arena (16/4 = 4 rows) must overflow
    grown = 0
    i = 0
    while st.capacity == 16:
        a = bytes([0x80 + i]) * 20
        st.ensure(a, StateAccount(balance=5, nonce=0))
        grown += 1
        i += 1
    st.flush_staged()
    after = dict(zip(addrs, st.read_accounts(
        [st.index[a] for a in addrs])))
    assert after == before
    # rows are unique and land inside the owning shard's arena
    assert len(set(st.row_of)) == len(st.row_of)
    from coreth_tpu.parallel import account_bucket
    arena = st.capacity // st.n_shards
    for idx, row in enumerate(st.row_of):
        assert row // arena == account_bucket(st.addr_hashes[idx], 4)


# ----------------------------------------------- 2-device smoke (CI)
def _count_window_kernel_calls(monkeypatch):
    """Calls of the transfer-window kernel, single-device and sharded:
    counted at the jitted callables themselves, beside the account's
    phase entries."""
    from coreth_tpu.replay import engine as E, shard as S
    calls = []

    def counted(fn):
        def call(*a, **k):
            calls.append(fn)
            return fn(*a, **k)
        return call

    real_sharded = S.sharded_transfer_window
    monkeypatch.setattr(E, "_transfer_window_packed",
                        counted(E._transfer_window_packed))
    monkeypatch.setattr(S, "sharded_transfer_window",
                        lambda mesh, mode: counted(real_sharded(mesh,
                                                                mode)))
    return calls


def test_two_device_scaling_smoke(monkeypatch):
    """Tier-1 scaling regression gate on a small transfer shape.  The
    2-device mesh was 67x slower than one device while it dispatched,
    and blocked on, every BLOCK; the sharded window kernel made it one
    dispatch and one read a WINDOW.  That is what is held here, as
    counts, at both widths: ceil(blocks / window) kernel calls,
    dispatch phases, blocking reads and async fetches a run — a
    per-block-dispatch regression reads 6 where this reads 2 — and the
    same lanes packed and scanned at 2 devices as at 1 (a mesh path
    that padded every block to a shard multiple would scan more)."""
    n_blocks, n_txs, window = 6, 64, 4
    keys = [0x6200 + i for i in range(16)]
    addrs = [priv_to_address(k) for k in keys]
    genesis = Genesis(config=CFG, gas_limit=30_000_000,
                      alloc={a: GenesisAccount(balance=10**24)
                             for a in addrs})
    db0 = Database()
    g0 = genesis.to_block(db0)
    nonces = [0] * len(keys)

    def gen(i, bg):
        for j in range(n_txs):
            k = (i * n_txs + j) % len(keys)
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI,
                gas=21_000, to=b"\xe1" + (i * n_txs + j).to_bytes(
                    4, "big") * 4 + b"\xe1" * 3, value=10**12 + j),
                keys[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, g0, db0, n_blocks, gen, gap=10)
    calls = _count_window_kernel_calls(monkeypatch)
    windows = -(-n_blocks // window)

    def run(mesh):
        db = Database()
        gb = genesis.to_block(db)
        eng = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                           capacity=1024, batch_pad=64, window=window,
                           mesh=mesh)
        del calls[:]
        root = eng.replay(blocks)
        assert root == blocks[-1].header.root
        assert eng.stats.blocks_fallback == 0
        assert eng.stats.blocks_device == n_blocks
        n = eng.account.row()["n"]
        assert len(calls) == windows, (
            f"{len(calls)} window kernel calls for {n_blocks} blocks "
            f"in windows of {window}: dispatching per block again?")
        assert n["window/dispatch"] == n["window/fetch_wait"] == windows
        assert eng.stats.reads_prefetched == windows
        return eng.stats.lanes_real, eng.stats.lanes_padded

    lanes1 = run(None)
    lanes2 = run(make_mesh(jax.devices("cpu")[:2]))
    assert lanes1 == lanes2 == (n_blocks * n_txs, n_blocks * n_txs)


# ===================================================== key-range (ISSUE 14)
# One hot ERC-20-shaped contract taking 100% of lanes: contract-bucket
# placement serialized this shape onto one shard; key-range placement
# (slot_bucket + conflict-component co-location + the per-block replica
# sync exchange) must keep roots bit-identical at every width and both
# exchange modes, and keep the 2-device curve flat.

def _hot_chain(n_blocks=6, txs=6, n_keys=8, seed=20260804):
    from coreth_tpu.workloads.hot_contract import build_hot_chain
    return build_hot_chain(CFG, n_blocks, txs, n_keys=n_keys,
                           seed=seed)


def _force_machine(monkeypatch, threshold="3"):
    monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    monkeypatch.setenv("CORETH_MACHINE_WINDOW", "2")
    monkeypatch.setenv("CORETH_KEYRANGE_THRESHOLD", threshold)


def _replay_hot(genesis, blocks, mesh, window=4):
    db = Database()
    g = genesis.to_block(db)
    eng = ReplayEngine(CFG, db, g.root, parent_header=g.header,
                       window=window, capacity=256, batch_pad=64,
                       mesh=mesh)
    root = eng.replay(list(blocks))
    return root, eng


@pytest.mark.parametrize("trie", _trie_backends)
def test_keyrange_exchange_mode_equivalence(monkeypatch, trie):
    """THE ISSUE-14 equivalence matrix: the single-hot-contract chain
    replays to bit-identical roots across CORETH_EXCHANGE=psum|ppermute
    x 1/2/4 devices x both trie backends, with key-range placement
    active (kr_lanes > 0) and the selected collective actually used."""
    monkeypatch.setenv("CORETH_TRIE", trie)
    _force_machine(monkeypatch)
    genesis, blocks = _hot_chain()
    want = blocks[-1].root
    root1, _e1 = _replay_hot(genesis, blocks, None)
    assert root1 == want
    for mode in ("psum", "ppermute"):
        monkeypatch.setenv("CORETH_EXCHANGE", mode)
        for nd in (2, 4):
            mesh = make_mesh(jax.devices("cpu")[:nd])
            root, eng = _replay_hot(genesis, blocks, mesh)
            assert root == want, (mode, nd)
            assert eng.stats.blocks_fallback == 0
            mc = eng._machine.machine_counters()
            assert mc["kr_lanes"] > 0
            used = mc["exchange_psum" if mode == "psum"
                      else "exchange_ppermute"]
            other = mc["exchange_ppermute" if mode == "psum"
                       else "exchange_psum"]
            assert used > 0 and other == 0, (mode, nd, mc)
            assert eng.stats.load_imbalance > 0


@pytest.mark.parametrize(
    "gen,machine", [(_gen_transfer, False), (_gen_erc20, True)],
    ids=["transfer", "erc20"])
def test_exchange_mode_equivalence_classic_paths(monkeypatch, gen,
                                                 machine):
    """CORETH_EXCHANGE on the pre-existing exchanges: the transfer
    window's packed effect reduce and the contract-bucket machine
    path's flags exchange produce identical roots in both modes."""
    if machine:
        # high threshold: the token stays contract-bucketed, so this
        # pins the FLAGS exchange, not the key-range sync
        _force_machine(monkeypatch, threshold="64")
    blocks = _build_chain(3, gen)
    want = blocks[-1].root
    root1, _ = _replay(blocks, None)
    assert root1 == want
    mesh = make_mesh(jax.devices("cpu")[:2])
    for mode in ("psum", "ppermute"):
        monkeypatch.setenv("CORETH_EXCHANGE", mode)
        root, eng = _replay(blocks, mesh)
        assert root == want, mode
        assert eng.stats.blocks_fallback == 0


def test_keyrange_empty_sync_is_ppermute_degenerate(monkeypatch):
    """A hot-contract run whose lanes never share keys: the exchange
    kernel is active (key-range placement on) but the cross-range set
    stays EMPTY every window — the ppermute degenerate case — and
    roots stay exact."""
    from coreth_tpu.chain import Genesis, generate_chain
    from coreth_tpu.workloads.hot_contract import (
        HOT_CONTRACT, hot_genesis_alloc)
    from coreth_tpu.workloads.erc20 import transfer_calldata
    _force_machine(monkeypatch)
    monkeypatch.setenv("CORETH_EXCHANGE", "ppermute")
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc=hot_genesis_alloc(ADDRS))
    db = Database()
    g = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def gen(i, bg):
        # every lane: distinct sender -> a UNIQUE fresh recipient, so
        # no two lanes (in any block) ever share a storage key
        for k in range(6):
            to = bytes([0x51 + i]) + bytes([k]) * 15 + b"\x51" * 4
            bg.add_tx(_tx(k, nonces, HOT_CONTRACT,
                          transfer_calldata(to, 3 + k)))

    blocks, _ = generate_chain(CFG, g, db, 4, gen, gap=2)
    mesh = make_mesh(jax.devices("cpu")[:2])
    root, eng = _replay_hot(genesis, blocks, mesh)
    assert root == blocks[-1].root
    assert eng.stats.blocks_fallback == 0
    runner = eng._machine._runner
    assert runner._xchg_hw > 0          # exchange kernel compiled in
    assert runner._sync_last == 0       # ... with an empty sync set
    assert eng._machine.machine_counters()["exchange_ppermute"] > 0


def test_keyrange_dense_forces_psum_fallback(monkeypatch):
    """Auto mode with the density threshold at 0: any nonempty sync
    set reads as dense, so the selector must fall back to the full
    psum — and roots stay exact."""
    _force_machine(monkeypatch)
    monkeypatch.delenv("CORETH_EXCHANGE", raising=False)
    monkeypatch.setenv("CORETH_EXCHANGE_DENSITY", "0.0")
    genesis, blocks = _hot_chain()
    mesh = make_mesh(jax.devices("cpu")[:2])
    root, eng = _replay_hot(genesis, blocks, mesh)
    assert root == blocks[-1].root
    runner = eng._machine._runner
    mc = eng._machine.machine_counters()
    if runner._sync_last or runner._xchg_locked:
        assert runner._xchg_mode == "psum"
        assert mc["exchange_psum"] > 0


def test_keyrange_specialize_retrace_gate(monkeypatch):
    """ISSUE-14 acceptance: kernel_retraces == 0 holds with key-range
    sharding AND per-contract specialization both on, load_imbalance
    reaches ReplayStats + the metrics registry, and the placement
    instant lands on the tracer ring (the Perfetto surface)."""
    from coreth_tpu.metrics import Registry
    from coreth_tpu.obs.trace import SpanTracer, install, uninstall
    _force_machine(monkeypatch)
    monkeypatch.setenv("CORETH_SPECIALIZE", "1")
    genesis, blocks = _hot_chain()
    mesh = make_mesh(jax.devices("cpu")[:2])
    tr = SpanTracer()
    install(tr)
    try:
        root, eng = _replay_hot(genesis, blocks, mesh)
    finally:
        uninstall()
    assert root == blocks[-1].root
    mc = eng._machine.machine_counters()
    assert mc["kernel_retraces"] == 0, mc
    assert mc["kr_lanes"] > 0
    assert mc["lanes_specialized"] > 0  # spec programs per key-range shard
    assert eng.stats.load_imbalance > 0
    reg = Registry()
    eng.publish_metrics(reg)
    g = reg.get("replay/load_imbalance")
    assert g is not None and g.value > 0
    assert any(e.get("name") == "shard/load_imbalance"
               for e in list(tr._ring)), "placement instant not traced"


def test_two_device_hot_contract_smoke(monkeypatch):
    """Tier-1 ISSUE-14 scaling gate: the single-hot-contract shape on
    the machine path at the DEFAULT key-range env, one device and two.
    Contract-bucket placement put every lane of the one hot contract on
    ONE shard; key-range placement spreads its conflict components.
    Held as counts (the chain and the placement are deterministic, so
    every run reads the same):

    - ``load_imbalance`` (max/mean shard occupancy) reads 1.868 here —
      this Zipf chain's largest conflict component is irreducible
      serial work — and exactly 2.0, with 0 key-range lanes and 0
      multi-shard blocks, when the hot contract collapses onto one
      shard (CORETH_KEYRANGE=0 reads that): so under 1.9, every lane
      placed by key range, every block on both shards;
    - ``kernel_retraces`` and ``host_txs`` 0 at both widths: the mesh
      must not pay mid-run compiles or host escapes the single device
      does not;
    - ``window_attempts`` (dispatches of the fused window) and the
      account's ``machine/dispatch`` entries no higher at 2 devices
      than at 1, OCC rounds equal: sharding must not buy re-dispatches.
    """
    monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    # realistic-pool shape: Zipf over a sender population comparable
    # to the block size
    n_blocks, txs = 6, 96
    genesis, blocks = _hot_chain(n_blocks=n_blocks, txs=txs,
                                 n_keys=128)

    def run(mesh):
        db = Database()
        gb = genesis.to_block(db)
        eng = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                           capacity=1024, batch_pad=64, window=4,
                           mesh=mesh)
        root = eng.replay(list(blocks))
        assert root == blocks[-1].header.root
        assert eng.stats.blocks_fallback == 0
        mx = eng._machine
        mc = mx.machine_counters()
        assert mx.blocks == n_blocks
        assert mc["kernel_retraces"] == 0 and mx.host_txs == 0
        return eng, mc

    run(None)          # the process's first engine learns the premap
    #                    recipes by discovery: one more dispatch
    eng1, _mc1 = run(None)
    eng2, mc2 = run(make_mesh(jax.devices("cpu")[:2]))
    mx1, mx2 = eng1._machine, eng2._machine
    assert mx2.window_attempts <= mx1.window_attempts
    assert eng2.account.row()["n"]["machine/dispatch"] \
        <= eng1.account.row()["n"]["machine/dispatch"]
    assert mx2.rounds == mx1.rounds > 0
    assert eng1.stats.load_imbalance == 0.0   # no sharded window ran
    assert 1.0 <= eng2.stats.load_imbalance < 1.9, (
        f"load_imbalance {eng2.stats.load_imbalance}: the hot "
        f"contract's lanes are back on one shard")
    assert mc2["kr_lanes"] == n_blocks * txs
    assert mx2._runner.multi_shard_blocks == n_blocks


# ------------------------------------------- lane buckets (PR 30)
# blocks either side of the 16- and 64-lane bucket edges, in a row
EDGE_SIZES = [1, 16, 17, 64, 65, 1]


@pytest.fixture(scope="module")
def edge_chain():
    def gen(i, nonces):
        return [_tx(j % 8, nonces,
                    bytes([0x61 + i]) + bytes([j % 8]) * 19,
                    gas=21_000, value=1000 + j)
                for j in range(EDGE_SIZES[i])]
    return _build_chain(len(EDGE_SIZES), gen)


@pytest.fixture
def fresh_jit_caches():
    """No compiled program before the test, so the count of variants
    it compiles is exact, and none after it (map_headroom above)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("window,shapes", [
    # (K, lanes) of the windows issued: K the pow2 of the run, lanes
    # the bucket of its largest block
    (4, [(4, 64), (2, 256)]),
    # a window a block: EVERY header root is checked
    (1, [(1, 16), (1, 16), (1, 64), (1, 64), (1, 256), (1, 16)]),
], ids=["window4", "window1"])
@pytest.mark.parametrize("n_dev", [1, 2])
def test_lane_buckets_exact_across_edges(monkeypatch, fresh_jit_caches,
                                         edge_chain, n_dev, window,
                                         shapes):
    """One chain whose consecutive blocks cross the lane-bucket edges
    replays to the header roots on one device and on a 2-device mesh,
    wholly on the device, and the lane counters say what was packed
    and what was scanned.  On one device the same run counts the
    variants it compiled of the entry the engine calls,
    _transfer_window_packed (PERF.md §7, Speed 8)."""
    from coreth_tpu.replay import engine as engine_mod
    blocks = edge_chain
    mesh = make_mesh(jax.devices("cpu")[:n_dev]) if n_dev > 1 else None
    seen = []
    prepare = ReplayEngine._prepare_window

    def spy(self, items):
        out = prepare(self, items)
        seen.append(tuple(a.shape for a in out[:5]))
        return out

    monkeypatch.setattr(ReplayEngine, "_prepare_window", spy)
    before = engine_mod._transfer_window_packed._cache_size()
    root, eng = _replay(blocks, mesh, window=window)
    compiled = engine_mod._transfer_window_packed._cache_size() - before
    assert root == blocks[-1].root
    assert eng.stats.blocks_device == len(blocks)
    assert eng.stats.blocks_fallback == 0
    assert [s[0][:2] for s in seen] == shapes
    assert eng.stats.lanes_real == sum(EDGE_SIZES) == eng.stats.txs
    assert eng.stats.lanes_padded == sum(k * pad for k, pad in shapes)
    # one executable per distinct input shape: 2 for this chain at
    # window 4, 3 a block at a time (the mesh path has a jit of its own)
    assert len(set(seen)) == len(set(shapes))
    assert compiled == (len(set(shapes)) if n_dev == 1 else 0)


@pytest.mark.parametrize("n_dev", [1, 2])
def test_window_upload_counters(monkeypatch, edge_chain, n_dev):
    """ONE host->device transfer a window on a single device —
    window_uploads == windows issued, window_upload_bytes the sum of
    their staging buffers — and on a mesh five a window of the same
    bytes (txds sharded over dp, the other four replicated: an upload
    of its own), to the same roots."""
    blocks = edge_chain
    mesh = make_mesh(jax.devices("cpu")[:n_dev]) if n_dev > 1 else None
    bufs = []
    prepare = ReplayEngine._prepare_window

    def spy(self, items):
        out = prepare(self, items)
        bufs.append(out[-1][0])
        return out

    monkeypatch.setattr(ReplayEngine, "_prepare_window", spy)
    root, eng = _replay(blocks, mesh, window=4)
    assert root == blocks[-1].root
    assert eng.stats.blocks_fallback == 0
    windows = eng.stats.reads_prefetched
    assert windows == len(bufs) == 2
    assert eng.account.row()["n"]["window/upload"] == windows
    assert eng.stats.window_uploads == (windows if n_dev == 1
                                        else 5 * windows)
    assert eng.stats.window_upload_bytes == sum(b.nbytes for b in bufs)
    # fresh buffers: the second window was packed while the first was
    # in flight
    assert not np.shares_memory(bufs[0], bufs[1])

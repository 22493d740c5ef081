"""The main path's kernels, compiled by the TPU's own compiler.

The suite runs on the CPU, so nothing else in it asks what the chip's
compiler makes of these programs.  The compiler is installed here and
compiles for a chip that is DESCRIBED, not attached (a v5e 2x2 host):
each case lowers one jitted program of the replay path at the static
shapes ``chip_smoke.py`` runs (bench.py's defaults) and compiles it —
what the chip's compiler would refuse (a shape it cannot tile, a
program that does not fit, a collective it cannot partition) fails
here, at no chip time.  Nothing runs: a compile that passes says
nothing about results or times.

Rules this file keeps (one process may load the TPU library at a time,
and xdist workers each import every test file): the topology is
described inside a module-scoped fixture — never at import, in a
``skipif``/``parametrize`` argument or in conftest — the fixture is not
autouse, everything compiles in the test's own process, and these cases
live in ONE file so one worker owns the library.  The persistent
compile cache is off around them: an executable compiled for a
described chip cannot be read back without one.

``parallel.sharded_recover`` (the ladder under shard_map, ~48 s) is not
kept here — the single-chip ladder below is the expensive case; it was
compiled by hand for the four-chip run recorded in CHANGES.md.
"""

import os
import re
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as PS, SingleDeviceSharding)

from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device import shard as SH
from coreth_tpu.evm.device.adapter import MachineWindowRunner
from coreth_tpu.evm.device.specialize import SpecProgram
from coreth_tpu.ops import keccak, secp
from coreth_tpu.replay import engine as E
from coreth_tpu.replay.shard import sharded_transfer_window
from coreth_tpu.workloads.erc20 import TOKEN_RUNTIME

# chip_smoke.FULL / bench.py defaults
ACCOUNTS, SLOTS = 1 << 17, 1 << 14
WINDOW, TXS, ERC20_TXS = 128, 128, 256
FORK = "durango"
# what the token runtime compiles in (the ERC-20 and hot-contract sets)
FEATURES = frozenset(["keccak", "log", "shift", "storage"])
TOKEN_SPEC = (SpecProgram(code=TOKEN_RUNTIME, fork=FORK),)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    env = pytest.MonkeyPatch()
    env.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here: skip, never fail
        env.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    env.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(dims), dtype, sharding=sharding)
    return shape


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices), ("dp",))


@pytest.fixture(scope="module")
def on_mesh(mesh):
    def shape(dims, spec=PS(), dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(dims), dtype,
                                    sharding=NamedSharding(mesh, spec))
    return shape


def _collectives(compiled) -> dict:
    text = compiled.as_text()
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
            for op in ("all-reduce", "reduce-scatter", "all-gather",
                       "collective-permute")}


def _like(arrays, shape):
    """ShapeDtypeStructs (via ``shape``) of a pytree of warm arrays."""
    return jax.tree_util.tree_map(
        lambda a: shape(a.shape, dtype=a.dtype), arrays)


# ------------------------------------------------------------- one chip
def _lower_window(s, accounts, slots, dims):
    """The entry the engine calls (engine._transfer_window_packed: ONE
    staging buffer, cut inside the program) lowered for a window of
    ``dims`` = (K, pad, t_pad, s_pad, L, SL)."""
    return E._transfer_window_packed.lower(
        s((accounts, 16)), s((accounts,)), s((slots, 16)),
        s((E.window_words(dims),)), dims=dims)


def test_transfer_window_full_width(one_chip):
    """The transfer window at the steady window of the transfer
    chain: 128 blocks x 128 txs over a 2^17-row account table, the
    window's touched set bucketed to 16384 locals."""
    compiled = _lower_window(
        one_chip, ACCOUNTS, SLOTS,
        (WINDOW, TXS, 512, 8, 16384, 8)).compile()
    mem = compiled.memory_analysis()
    # the three tables in, the three tables + the fetch tensor out
    assert mem.argument_size_in_bytes >= ACCOUNTS * 17 * 4
    assert mem.temp_size_in_bytes < 1 << 30


def test_transfer_window_one_tx_blocks(one_chip):
    """The transfer window at the steady window of a one-tx-a-block
    chain at the engine's defaults (the benchmark's ``valuetx`` cell):
    16 blocks in the 16-lane floor bucket (engine.LANE_FLOOR), 256
    account locals, the 2^14-row tables."""
    cap = 1 << 14
    compiled = _lower_window(
        one_chip, cap, cap, (16, E.LANE_FLOOR, 256, 8, 256, 8)).compile()
    mem = compiled.memory_analysis()
    # the three tables and ONE 92 KB staging buffer (73 KB of it the
    # 16 x 16 x 72 int32 tx rows), not the 4.7 MB of a 1,024-lane one
    assert mem.argument_size_in_bytes < cap * 33 * 4 + (1 << 17)


def test_transfer_window_gas_full_blocks_order_check(one_chip):
    """The transfer window of a gas-full ring or p2p block at the
    engine's defaults (the benchmark's ``ring1k`` / ``p2p-1k`` cells):
    16 blocks x 1,024 lanes, 1,024 account locals.  The in-order
    solvency check's two [1,024, 1,024] lane masks go into int32
    products under their own scope (engine._order_solvent), and the
    program's temporaries stay a few MB: the masks are per scanned
    block, not per window, and the compiler fuses them into the
    products."""
    cap = 1 << 14
    compiled = _lower_window(
        one_chip, cap, cap, (16, 1024, 1024, 8, 1024, 8)).compile()
    text = compiled.as_text()
    check = [line for line in text.split("\n")
             if "coreth/transfer_order_check" in line]
    assert any("convolution(" in line and "s32[1024,16]" in line
               for line in check)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes


def test_erc20_window_and_block_steps(one_chip):
    """The ERC-20 fast path's window (256-tx blocks, 4096 slot locals)
    and the per-block _transfer_step / _slot_step it is built from
    (__graft_entry__.entry() calls those directly)."""
    s = one_chip
    _lower_window(s, ACCOUNTS, SLOTS,
                  (WINDOW, ERC20_TXS, 512, 512, 2048, 4096)).compile()
    b = TXS
    E._transfer_step.lower(
        s((ACCOUNTS, 16)), s((ACCOUNTS,)), s((b,)), s((b,)),
        s((b, 16)), s((b, 16)), s((b, 16)), s((b,)), s((b,)),
        s((b,), jnp.bool_), s(()), num_accounts=ACCOUNTS).compile()
    b = ERC20_TXS
    E._slot_step.lower(
        s((SLOTS, 16)), s((b,)), s((b,)), s((b, 16)),
        s((b,), jnp.bool_), num_slots=SLOTS).compile()


def test_recover_kernel_one_chunk(one_chip):
    """ops.secp.recover_kernel at the 4096-signature chunk
    (secp_device.MAX_CHUNK): the 256-step Shamir ladder is the largest
    program of the path (~35 s to compile, ~60 MB of code)."""
    s = one_chip
    n = 4096
    compiled = secp.recover_kernel.lower(
        s((n, 33), jnp.uint8), s((n,)), s((n, 8)), s((n, 8))).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= n * 102
    assert mem.generated_code_size_in_bytes < 256 << 20


def test_keccak_blocks(one_chip):
    s = one_chip
    keccak.keccak256_blocks.lower(
        s((4096, 2, 34), jnp.uint32), s((4096,))).compile()


# the benchmark's own token (benchmarks/chains/p2p_token.py), as the
# window runner specializes it: a file loaded by path, no device touched
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks"))
from benchlib import names as _bench_names  # noqa: E402
BENCH_TOKEN_SPEC = (SpecProgram(code=_bench_names.load_named(
    "chains", "p2p_token")[0].TOKEN_RUNTIME, fork=FORK),)


@pytest.mark.parametrize("lanes,table_cap,spec", [
    (ERC20_TXS, 4096, TOKEN_SPEC),   # ERC-20 chain, specialized program
    (128, 2048, ()),                 # hot-contract width, generic kernel
    # p2p-token-1k.catchup: a gas-full block of 445 calls buckets to
    # 512 lanes, 1,000 holders' slots to the 8,192-row arena the lead
    # window projects
    (512, 8192, BENCH_TOKEN_SPEC),
], ids=["erc20-specialized", "hot-generic", "p2p-token-cell"])
def test_occ_machine_donates_its_table(one_chip, lanes, table_cap, spec):
    """The fused OCC machine (lax.while_loop step machine inside the
    block scan) as the window runner buckets it, with the slot table
    DONATED: the compiled program must alias it, or every
    window->window handoff copies the table."""
    p = M.MachineParams(fork=FORK, batch=lanes, code_cap=256,
                        data_cap=128, scache_cap=16, features=FEATURES)
    occ = M.OccParams(blocks=8, table_cap=table_cap, rounds=lanes + 1)
    runner = MachineWindowRunner(FORK, lambda _c, _k: 0)
    args = _like(runner._warm_args(p, occ), one_chip)
    compiled = M.get_occ_machine(p, occ, spec).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= table_cap * 16 * 4, mem


# ------------------------------------------------------------ four chips
@pytest.mark.parametrize("mode,expect", [
    ("psum", "all-reduce"), ("ppermute", "collective-permute")])
def test_sharded_transfer_window(mesh, on_mesh, mode, expect):
    """replay/shard.py's window on a 4-device mesh: tables sharded by
    row over dp, the tx axis sharded, one effect exchange per block —
    an all-reduce, or the ppermute ring (what the engine picks at these
    sizes: the touched set is sparse against the tables)."""
    s = on_mesh
    tab2, tab1 = PS("dp", None), PS("dp")
    compiled = sharded_transfer_window(mesh, mode).lower(
        s((ACCOUNTS, 16), tab2), s((ACCOUNTS,), tab1),
        s((SLOTS, 16), tab2), s((16384,)), s((8,)),
        s((WINDOW, TXS, E.TXD_COLS), PS(None, "dp", None)),
        s((WINDOW, 512)), s((WINDOW, 8))).compile()
    assert _collectives(compiled)[expect] > 0
    # per-device bytes: each device holds a quarter of the tables
    quarter = (ACCOUNTS * 17 + SLOTS * 16) * 4 // 4
    mem = compiled.memory_analysis()
    assert quarter <= mem.argument_size_in_bytes < 2 * quarter, mem


def test_sharded_block_steps_reduce_into_the_row_sharding(mesh, on_mesh):
    """parallel/mesh.py's per-block steps ask for reduce-scatter
    (psum_scatter onto the account-row sharding).  The program lowered
    from jax carries it; the v5e compiler is free to realise it as an
    all-reduce plus a slice at these sizes (it does), so the compiled
    text is only required to hold a cross-device reduction."""
    from coreth_tpu.parallel import sharded_slot_step, sharded_transfer_step
    s = on_mesh
    t2, t1 = PS("dp", None), PS("dp")
    b = TXS
    lowered = sharded_transfer_step(mesh, ACCOUNTS).lower(
        s((ACCOUNTS, 16), t2), s((ACCOUNTS,), t1), s((b,), t1),
        s((b,), t1), s((b, 16), t2), s((b, 16), t2), s((b, 16), t2),
        s((b,), t1), s((b,), t1), s((b,), t1, jnp.bool_), s(()))
    assert "reduce_scatter" in lowered.as_text()
    got = _collectives(lowered.compile())
    assert got["reduce-scatter"] + got["all-reduce"] > 0, got
    b = ERC20_TXS
    lowered = sharded_slot_step(mesh, SLOTS).lower(
        s((SLOTS, 16), t2), s((b,), t1), s((b,), t1), s((b, 16), t2),
        s((b,), t1, jnp.bool_))
    assert "reduce_scatter" in lowered.as_text()
    got = _collectives(lowered.compile())
    assert got["reduce-scatter"] + got["all-reduce"] > 0, got


@pytest.mark.parametrize("xchg,mode,expect", [
    (0, "psum", None),                       # contract-bucket windows
    (256, "ppermute", "collective-permute"),  # key-range sync, ring
    (256, "psum", "all-reduce"),
], ids=["no-sync", "keyrange-ring", "keyrange-psum"])
def test_sharded_occ_machine(mesh, on_mesh, xchg, mode, expect):
    """evm/device/shard.py's per-shard OCC at the hot-contract width
    (128 lanes and a 1024-row arena PER SHARD): the while-loop machine
    inside shard_map, the table donated and sharded over dp, and — for
    key-range windows — the replica-sync exchange between blocks."""
    p = M.MachineParams(fork=FORK, batch=128, code_cap=256,
                        data_cap=128, scache_cap=16, features=FEATURES)
    occ = M.OccParams(blocks=8, table_cap=1024, rounds=129)
    runner = SH.ShardedWindowRunner(FORK, lambda _c, _k: 0, mesh)
    table, key_tab, inputs, *rows = runner._warm_args(p, occ, xchg=xchg)
    lane = PS(None, "dp")
    args = [on_mesh(table.shape, PS("dp")),
            on_mesh(key_tab.shape, PS("dp")),
            {k: on_mesh(v.shape, lane if k in SH._LANE_KEYS else PS(),
                        v.dtype) for k, v in inputs.items()}]
    args += [on_mesh(r.shape) for r in rows]
    compiled = SH.get_sharded_occ_machine(
        p, occ, mesh, TOKEN_SPEC, xchg, mode).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= occ.table_cap * 16 * 4, mem
    got = _collectives(compiled)
    if expect is None:
        assert not any(got.values()), got  # per-shard OCC: no collective
    else:
        assert got[expect] > 0, got

#!/usr/bin/env python
"""chip_smoke.py — the replay engine's main path, once, on the chip.

Signed C-Chain blocks in, bit-identical state roots out, through
``ReplayEngine.replay`` and the ``serve/`` ``StreamingPipeline``, in ONE
process on ONE directly attached TPU, at the static shapes ``bench.py``
uses by default (the repo's rendering of upstream ``core/bench_test.go``:
value-transfer chain, BASELINE.json config[2]; ERC-20 spam, config[1]).
Widths are never cut; chain LENGTH is, and every phase says by how much
under ``reduced``.  The plain reference is the Python host processor:
it built the chains, so its roots are in the headers.

    python chip_smoke.py             # one chip, every phase
    python chip_smoke.py --phase recover   # one chip, that phase only
    python chip_smoke.py --chips 4   # ONLY the mesh-vs-single-device
                                     # comparison, on four chips

Every phase prints one JSON line; the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``
and the exit code is 0 only if every phase passed.  Without a TPU the
script refuses to start (exit 2, no result line): there is no CPU
carry-on.  ``tests/test_chip_smoke.py`` calls the phase functions at a
toy size on the CPU so the script cannot rot between chip runs.

Times and rates printed here are information (a host clock around work
that ends in a device read), not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from contextlib import contextmanager
from dataclasses import dataclass
from itertools import islice
from typing import Callable, List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _DIR)

GWEI = 10**9
TOKEN = bytes([0x77]) * 20


@dataclass(frozen=True)
class Sizes:
    """Static shapes (bench.py defaults) and the chain lengths this
    run replays.  ``*_default_blocks`` is what bench.py replays by
    default; where a phase replays fewer, that is its ``reduced``."""
    n_keys: int = 1024            # funded senders (bench.py N_KEYS)
    txs: int = 128                # transfer txs/block
    window: int = 128             # engine window (BENCH_WINDOW)
    capacity: int = 1 << 17       # account table rows (bench._fresh_engine)
    slot_capacity: int = 1 << 14  # slot table rows
    erc20_txs: int = 256          # ERC-20 txs/block (machine lanes)
    machine_window: int = 8       # blocks per fused OCC dispatch
    hot_keys: int = 256           # bench.run_hot_contract
    hot_txs: int = 128
    hot_capacity: int = 1 << 13
    hot_window: int = 16
    stream_window: int = 32       # BENCH_STREAM_WINDOW
    # lengths: two full windows plus the lead block everywhere, so the
    # window->window handoff (donated tables, prefetch overlap, commit
    # pipeline) happens at least once
    windows: int = 2
    hot_blocks: int = 65
    transfer_default_blocks: int = 1024
    stream_default_blocks: int = 512

    @property
    def chain_blocks(self) -> int:
        return self.windows * self.window + 1

    @property
    def machine_blocks(self) -> int:
        return self.windows * self.machine_window + 1


FULL = Sizes()


# ------------------------------------------------------------ measurement
class CompileMeter:
    """Counts what jax compiled or loaded from the persistent cache
    (jax.monitoring events), so each phase can say how many compiles it
    paid, how long they took, and whether the cache served them."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self):
        import jax.monitoring as mon
        self.n = dict(compiles=0, compile_s=0.0, cache_hits=0,
                      cache_misses=0)
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        key = self._EVENTS.get(event)
        if key:
            self.n[key] += 1

    def _on_duration(self, event, duration, **_kw):
        if event == self._BACKEND:
            self.n["compiles"] += 1
            self.n["compile_s"] += duration

    def since(self, before: dict) -> dict:
        out = {k: self.n[k] - before[k] for k in self.n}
        out["compile_s"] = round(out["compile_s"], 2)
        return out

    def mark(self) -> dict:
        return dict(self.n)


def identity() -> dict:
    """Versions and the device, as jax reports them."""
    import jax
    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    dev = jax.devices()
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
            "device": {"platform": dev[0].platform,
                       "kind": dev[0].device_kind, "count": len(dev)}}


# ----------------------------------------------------------------- chains
def _cached_chain(name: str, build: Callable[[], list]) -> list:
    """Chains are generated in the run from fixed keys; a copy under
    .bench_cache/ (gitignored) only speeds a rerun, the run never
    depends on one being there.  The name carries every parameter."""
    from coreth_tpu import rlp
    from coreth_tpu.types import Block
    path = os.path.join(_DIR, ".bench_cache", f"smoke_{name}.bin")
    if os.path.exists(path):
        with open(path, "rb") as f:
            return [Block.decode(b) for b in rlp.decode(f.read())]
    blocks = build()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(rlp.encode([b.encode() for b in blocks]))
    os.replace(tmp, path)
    return blocks


def _funded_genesis(sizes: Sizes, token: bool):
    """bench._genesis: n_keys funded senders (+ the ERC-20 token)."""
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    keys = [0xC0FFEE + i for i in range(sizes.n_keys)]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {a: GenesisAccount(balance=10**27) for a in addrs}
    if token:
        from coreth_tpu.workloads.erc20 import token_genesis_account
        alloc[TOKEN] = token_genesis_account({a: 10**24 for a in addrs})
    genesis = Genesis(config=TEST_CHAIN_CONFIG, gas_limit=8_000_000,
                      alloc=alloc)
    return genesis, keys, addrs


def _generate(genesis, n_blocks: int, gen) -> list:
    from coreth_tpu.chain import generate_chain
    from coreth_tpu.state import Database
    db = Database()
    gblock = genesis.to_block(db)
    # gap=10s: one block per fee window keeps the base fee bounded
    blocks, _ = generate_chain(genesis.config, gblock, db, n_blocks, gen,
                               gap=10)
    return blocks


def transfer_chain(sizes: Sizes, n_blocks: int):
    """bench.py's value-transfer chain (gen_transfer): half of every
    block's recipients are fresh addresses, so the account table grows
    all chain."""
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    genesis, keys, _addrs = _funded_genesis(sizes, token=False)
    cid = genesis.config.chain_id
    nonces = [0] * sizes.n_keys

    def gen(i, bg):
        for j in range(sizes.txs):
            n = i * sizes.txs + j
            k = n % sizes.n_keys
            if j % 2 == 0:
                to = b"\xf0" + n.to_bytes(4, "big") * 4 + b"\xf0" * 3
            else:
                to = bytes([0x10 + (j % 199)]) * 20
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=cid, nonce=nonces[k], gas_tip_cap_=GWEI,
                gas_fee_cap_=2000 * GWEI, gas=21_000, to=to,
                value=10**12 + j), keys[k], cid))
            nonces[k] += 1

    name = f"transfer_{n_blocks}x{sizes.txs}k{sizes.n_keys}"
    return genesis, _cached_chain(
        name, lambda: _generate(genesis, n_blocks, gen))


def erc20_chain(sizes: Sizes, n_blocks: int):
    """bench.py's ERC-20 spam (gen_erc20): transfer() calls on the
    workloads/erc20 token, repeat holders and a rotating pool of fresh
    recipients."""
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    from coreth_tpu.workloads.erc20 import transfer_calldata
    genesis, keys, addrs = _funded_genesis(sizes, token=True)
    cid = genesis.config.chain_id
    nk = sizes.n_keys
    nonces = [0] * nk

    def gen(i, bg):
        for j in range(sizes.erc20_txs):
            k = (i * sizes.erc20_txs + j) % nk
            if j % 3 == 0:
                to = addrs[(k + 1) % nk]
            else:
                to = (0x5000 + (i * 7 + j) % 1999).to_bytes(2, "big") * 10
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=cid, nonce=nonces[k], gas_tip_cap_=GWEI,
                gas_fee_cap_=2000 * GWEI, gas=100_000, to=TOKEN, value=0,
                data=transfer_calldata(to, 10 + j)), keys[k], cid))
            nonces[k] += 1

    name = f"erc20_{n_blocks}x{sizes.erc20_txs}k{nk}"
    return genesis, _cached_chain(
        name, lambda: _generate(genesis, n_blocks, gen))


HOT_SEED, HOT_ALPHA = 20260804, 1.1


def hot_chain(sizes: Sizes):
    """bench.run_hot_contract's chain: ONE ERC-20-shaped contract takes
    every tx, Zipf-skewed senders and recipients — repeated senders and
    credited senders inside one block are read-write conflicts on
    computed (keccak) keys, which stay on device OCC."""
    from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
    from coreth_tpu.workloads import hot_contract as HC
    genesis, _k, _a = HC.hot_genesis(CFG, sizes.hot_keys)
    name = (f"hot_{sizes.hot_blocks}x{sizes.hot_txs}k{sizes.hot_keys}"
            f"s{HOT_SEED}a{HOT_ALPHA}")
    return genesis, _cached_chain(name, lambda: HC.build_hot_chain(
        CFG, sizes.hot_blocks, sizes.hot_txs, n_keys=sizes.hot_keys,
        alpha=HOT_ALPHA, seed=HOT_SEED)[1])


# ----------------------------------------------------------------- replay
def fresh_engine(genesis, *, batch_pad: int, capacity: int,
                 slot_capacity: int, window: int, mesh=None):
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    db = Database()
    gblock = genesis.to_block(db)
    return ReplayEngine(genesis.config, db, gblock.root,
                        parent_header=gblock.header, batch_pad=batch_pad,
                        capacity=capacity, slot_capacity=slot_capacity,
                        window=window, mesh=mesh)


@contextmanager
def _env(**kv):
    saved = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def replay_once(genesis, wire: List[bytes], engine_kw: dict,
                after_lead: Optional[Callable] = None) -> dict:
    """One replay as bench.run_tpu drives it: blocks decoded fresh from
    wire (no cached senders), the lead block through replay_block, the
    rest through replay().  Returns the engine's own counters."""
    from coreth_tpu.evm.device import adapter
    from coreth_tpu.types import Block
    blocks = [Block.decode(w) for w in wire]
    engine = fresh_engine(genesis, **engine_kw)
    d0 = adapter.DISPATCH_COUNT
    t0 = time.monotonic()
    engine.replay_block(blocks[0])
    if after_lead is not None:
        after_lead(engine)
    engine.replay(blocks[1:])
    wall = time.monotonic() - t0
    row = {"wall_s": round(wall, 3), "blocks": len(blocks),
           "txs": sum(len(b.transactions) for b in blocks),
           "root_ok": engine.root == blocks[-1].header.root,
           "dispatches": adapter.DISPATCH_COUNT - d0}
    row.update(_engine_row(engine))
    row["_engine"] = engine
    return row


def _engine_row(engine) -> dict:
    st = engine.stats
    sup = engine.supervisor.snapshot()
    row = {
        "blocks_device": st.blocks_device,
        "blocks_fallback": st.blocks_fallback,
        "sigs_device": st.sigs_device, "sigs_host": st.sigs_host,
        "t_sender_device_s": round(st.t_sender_device, 3),
        "t_sender_host_s": round(st.t_sender_host, 3),
        "recover_degraded": st.recover_degraded,
        "t_s": {k: round(getattr(st, k), 3) for k in (
            "t_classify", "t_sender", "t_device", "t_trie",
            "t_fallback")},
        "supervisor": {k: sup[k] for k in ("retries", "strikes",
                                           "demotions")},
    }
    mx = getattr(engine, "_machine", None)
    if mx is not None:
        mc = mx.machine_counters()
        row["machine"] = {
            "machine_blocks": mx.blocks, "host_txs": mx.host_txs,
            "occ_rounds": mx.rounds, "occ_windows": mx.windows,
            "window_attempts": mx.window_attempts,
            "serial_blocks": mx.serial_blocks,
            "dirty_blocks": mx.dirty_blocks,
            "kernel_retraces": mc["kernel_retraces"],
            "warm_failures": mc["warm_failures"],
            "specialize_escapes": mc["specialize_escapes"],
            "lanes_specialized": mc["lanes_specialized"],
            "programs_traced": mc["programs_traced"],
            "discovery_dispatches": mc["discovery_dispatches"],
            "kr_lanes": mc["kr_lanes"],
        }
    return row


def replay_failures(row: dict, *, machine: bool = False,
                    conflicts: bool = False,
                    retraces_ok: bool = False) -> List[str]:
    """What a healthy device run must show, by the engine's own
    counters.  Anything listed here means the run LOOKED fine (the
    root may even match) while the device did less than claimed.
    ``retraces_ok`` reports kernel_retraces without failing on it.
    Senders are recovered by the native batch alone (the device
    ladder's proof is phase_recover): a run whose signatures did not
    all complete there fell to per-tx recovery."""
    bad = []
    n = row["blocks"]
    if not row["root_ok"]:
        bad.append("state root != header root")
    if row["blocks_fallback"] != 0:
        bad.append(f"blocks_fallback={row['blocks_fallback']}")
    if row["blocks_device"] != n:
        bad.append(f"blocks_device={row['blocks_device']} != {n}")
    if row["sigs_host"] <= 0 or row["sigs_device"] != 0:
        bad.append(f"sigs_host={row['sigs_host']} sigs_device="
                   f"{row['sigs_device']}: senders not recovered by "
                   f"the native batch")
    if row["recover_degraded"] != 0:
        bad.append(f"recover_degraded={row['recover_degraded']}")
    for k, v in row["supervisor"].items():
        if v != 0:
            bad.append(f"supervisor.{k}={v}")
    if machine:
        m = row.get("machine")
        if m is None:
            return bad + ["machine path never ran"]
        if m["machine_blocks"] != n:
            bad.append(f"machine_blocks={m['machine_blocks']} != {n}")
        zero = ["host_txs", "specialize_escapes", "dirty_blocks",
                "serial_blocks", "warm_failures"]
        if not retraces_ok:
            zero.append("kernel_retraces")
        bad += [f"{k}={m[k]}" for k in zero if m[k] != 0]
        if row["dispatches"] <= 0:
            bad.append("no device dispatch")
        if conflicts and m["occ_rounds"] <= 0:
            bad.append("occ_rounds=0: no conflict re-executed on device")
    return bad


def run_replay_phase(meter: CompileMeter, genesis, blocks, engine_kw,
                     **expect) -> dict:
    """A cold pass (pays the compiles), then warm passes on fresh
    engines until one compiles nothing.  The machine path needs two:
    the first engine of a process learns the contract's premap recipes
    by discovery, the second starts from them and lands its first
    window in a table bucket the first never used.  The row is the
    last pass's; a third pass that still compiles fails the phase."""
    from coreth_tpu.evm.device.adapter import wait_warm_compiles
    wire = [b.encode() for b in blocks]
    failures, compiles, walls = [], [], []
    for n_pass in range(1, 4):
        m0 = meter.mark()
        row = replay_once(genesis, wire, engine_kw)
        wait_warm_compiles()  # a background pre-warm belongs to its pass
        compiles.append(meter.since(m0))
        walls.append(row["wall_s"])
        failures += [f"pass {n_pass}: {f}"
                     for f in replay_failures(row, **expect)]
        if n_pass > 1 and compiles[-1]["compiles"] == 0:
            break
    else:
        failures.append(f"pass 3 still compiled "
                        f"{compiles[-1]['compiles']} programs")
    row.update(wall_s_by_pass=walls, compile=compiles[0],
               compile_warm=compiles[1:],
               cache_hit=compiles[0]["cache_hits"] > 0,
               failures=failures)
    return row


def recover_probe(chain_id: int, blocks, n: int, runs: int = 3) -> dict:
    """The device ECDSA ladder against the native C++ batch on the same
    ``n`` signatures (one launch of bucket ``pad``): equal addresses —
    the ladder's proof; the program itself never calls it — and each
    side's wall seconds over ``runs`` warm runs, after a first that
    loads or compiles the bucket's executable (a report: nothing reads
    the seconds)."""
    from coreth_tpu.crypto import native
    from coreth_tpu.crypto.secp_device import (
        _pad_pow2, recover_addresses_device)
    from coreth_tpu.types import LatestSigner
    signer = LatestSigner(chain_id)
    hashes, rs, ss, recids = [], [], [], bytearray()
    for tx in islice((tx for b in blocks for tx in b.transactions), n):
        r, s, recid = tx.inner.raw_signature()
        hashes.append(signer.sig_hash(tx))
        rs.append(r.to_bytes(32, "big"))
        ss.append(s.to_bytes(32, "big"))
        recids.append(recid)
    args = (b"".join(hashes), b"".join(rs), b"".join(ss), bytes(recids))

    def timed(fn):
        t0 = time.monotonic()
        out = fn(*args)
        return out, round(time.monotonic() - t0, 5)

    dev, first_s = timed(recover_addresses_device)
    device_s, host_s, equal = [], [], True
    for _ in range(runs):
        dev, dt = timed(recover_addresses_device)
        device_s.append(dt)
        host, dt = timed(native.recover_addresses_batch)
        host_s.append(dt)
        equal &= dev == host and all(host[1])
    return {"n": len(recids), "pad": _pad_pow2(len(recids)),
            "device_first_s": first_s, "device_s": device_s,
            "host_s": host_s, "host_cores": os.cpu_count(),
            "equal": equal}


# ----------------------------------------------------------------- phases
def phase_build() -> dict:
    """Rebuild the native runtime from native/*.cc IN THIS RUN (the .so
    is gitignored; whatever sits on disk was built on another machine)
    and require every native seam: a failed build must fail here, not
    put the pure-Python crypto/trie/EVM on the clock."""
    from coreth_tpu import nativebuild
    t0 = time.monotonic()
    path = nativebuild.rebuild()
    build_s = round(time.monotonic() - t0, 2)
    from coreth_tpu.crypto import native
    from coreth_tpu.evm import hostexec
    from coreth_tpu.mpt import native_trie
    failures = []
    if native.load() is None:
        failures.append("crypto.native.load() is None")
    if not hostexec.available():
        failures.append("hostexec.available() is False")
    if native_trie.backend() != "native":
        failures.append(f"trie backend is {native_trie.backend()}")
    return {"native_lib": os.path.relpath(path, _DIR),
            "build_s": build_s, "failures": failures}


def _transfer_kw(sizes: Sizes, **over) -> dict:
    kw = dict(batch_pad=sizes.txs, capacity=sizes.capacity,
              slot_capacity=sizes.slot_capacity, window=sizes.window)
    kw.update(over)
    return kw


def _length_cut(smoke: int, default: int) -> dict:
    """The ``reduced`` entry of a phase: chain length against what
    bench.py replays by default (widths are never cut)."""
    return {"chain_blocks": {"smoke": smoke, "bench_default": default}} \
        if smoke < default else {}


def phase_transfer(meter, sizes: Sizes = FULL) -> dict:
    genesis, blocks = transfer_chain(sizes, sizes.chain_blocks)
    out = run_replay_phase(meter, genesis, blocks, _transfer_kw(sizes))
    out["reduced"] = _length_cut(sizes.chain_blocks,
                                 sizes.transfer_default_blocks)
    return out


def phase_recover(meter, sizes: Sizes = FULL) -> dict:
    """The device ladder's proof, once per pow2 bucket the transfer
    chain can fill (64 ... MAX_CHUNK): equal addresses against the
    native batch.  The replay phases prove nothing about the ladder:
    the engine recovers on the native batch alone.  ``table`` is the
    seconds a launch and a native batch took in this run, as a report."""
    from statistics import median
    from coreth_tpu.crypto.secp_device import MAX_CHUNK
    genesis, blocks = transfer_chain(sizes, sizes.chain_blocks)
    have = sum(len(b.transactions) for b in blocks)
    m0 = meter.mark()
    probes, failures = [], []
    n = 64
    while n <= MAX_CHUNK and (n <= have or not probes):
        probe = recover_probe(genesis.config.chain_id, blocks, n)
        probes.append(probe)
        if not probe["equal"]:
            failures.append(f"device recovery != native recovery "
                            f"at {probe['n']} signatures")
        n *= 2
    return {"probes": probes, "failures": failures,
            "table": {"launch_s": {p["pad"]: median(p["device_s"])
                                   for p in probes},
                      "host_s": {p["n"]: median(p["host_s"])
                                 for p in probes},
                      "host_cores": os.cpu_count()},
            "compile": meter.since(m0), "reduced": {}}


def phase_erc20(meter, sizes: Sizes = FULL) -> dict:
    """ERC-20 spam through the token fast path (_slot_step)."""
    genesis, blocks = erc20_chain(sizes, sizes.chain_blocks)
    out = run_replay_phase(
        meter, genesis, blocks, _transfer_kw(sizes,
                                             batch_pad=sizes.erc20_txs))
    out["reduced"] = {}
    return out


def phase_erc20_machine(meter, sizes: Sizes = FULL) -> dict:
    """The same ERC-20 chain through the GENERAL step machine — the
    path every other contract takes — forced the way bench.py does.
    The head of the fast-path chain is reused (two machine windows
    plus the lead block)."""
    genesis, blocks = erc20_chain(sizes, sizes.chain_blocks)
    with _env(CORETH_NO_TOKEN_FASTPATH="1",
              CORETH_MACHINE_WINDOW=str(sizes.machine_window)):
        out = run_replay_phase(
            meter, genesis, blocks[:sizes.machine_blocks],
            _transfer_kw(sizes, batch_pad=sizes.erc20_txs),
            machine=True)
    out["reduced"] = {}
    return out


def phase_conflicts(meter, sizes: Sizes = FULL) -> dict:
    """Conflicts ON THE DEVICE: the Zipf hot-contract chain with the
    serial short-circuit left at its default (computed-key conflicts
    stay on device OCC), so re-execution rounds must be > 0."""
    genesis, blocks = hot_chain(sizes)
    with _env(CORETH_NO_TOKEN_FASTPATH="1",
              CORETH_MACHINE_WINDOW=str(sizes.machine_window)):
        out = run_replay_phase(
            meter, genesis, blocks,
            dict(batch_pad=sizes.hot_txs, capacity=sizes.hot_capacity,
                 slot_capacity=sizes.hot_capacity,
                 window=sizes.hot_window),
            machine=True, conflicts=True)
    out["reduced"] = {}
    return out


def stream_once(genesis, wire: List[bytes], sizes: Sizes):
    """One backlog-mode run of the serve pipeline on a fresh engine."""
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.types import Block
    fresh = [Block.decode(w) for w in wire]
    engine = fresh_engine(genesis, **_transfer_kw(
        sizes, window=sizes.stream_window))
    t0 = time.monotonic()
    rep = StreamingPipeline(engine, ChainFeed(fresh),
                            window_wait=0.005).run()
    row = {"wall_s": round(time.monotonic() - t0, 3),
           "blocks": rep.blocks, "txs": rep.txs,
           "root_ok": engine.root == fresh[-1].header.root,
           "latency_ms": rep.latency_ms,
           "sustained_txs_s": rep.sustained_txs_s,
           "prefetch": rep.prefetch}
    row.update(_engine_row(engine))
    failures = []
    if rep.blocks != len(fresh):
        failures.append(f"committed {rep.blocks} of {len(fresh)} blocks")
    if rep.feed_drops != 0:
        failures.append(f"feed_drops={rep.feed_drops}")
    if rep.quarantined:
        failures.append(f"quarantined={len(rep.quarantined)}")
    if rep.halted is not None:
        failures.append(f"halted={rep.halted}")
    for k in ("retries", "strikes", "demotions"):
        if rep.supervisor.get(k, 0) != 0:
            failures.append(f"report.supervisor.{k}={rep.supervisor[k]}")
    return row, failures


def phase_streaming(meter, sizes: Sizes = FULL) -> dict:
    """The transfer chain through StreamingPipeline(engine,
    ChainFeed(...)) in backlog mode, as bench.run_streaming does:
    once cold and once warm, whose latencies are the ones worth
    reading."""
    genesis, blocks = transfer_chain(sizes, sizes.chain_blocks)
    wire = [b.encode() for b in blocks]
    m0 = meter.mark()
    cold, failures = stream_once(genesis, wire, sizes)
    failures = ["cold: " + f
                for f in replay_failures(cold) + failures]
    comp_cold = meter.since(m0)
    m1 = meter.mark()
    row, warm_failures = stream_once(genesis, wire, sizes)
    failures += ["warm: " + f
                 for f in replay_failures(row) + warm_failures]
    row.update(cold={k: cold[k] for k in ("wall_s", "latency_ms",
                                          "sustained_txs_s")},
               compile=comp_cold, compile_warm=meter.since(m1),
               cache_hit=comp_cold["cache_hits"] > 0, failures=failures,
               reduced=_length_cut(sizes.chain_blocks,
                                   sizes.stream_default_blocks))
    return row


def _shard_rows(arr) -> list:
    """(device id, rows, bytes) of each addressable shard."""
    return [{"device": sh.device.id, "rows": int(sh.data.shape[0]),
             "bytes": int(sh.data.nbytes)}
            for sh in arr.addressable_shards]


def _quartered(name: str, arr, n_dev: int) -> List[str]:
    """The table must sit on n_dev distinct devices, a 1/n_dev row
    block each — "everything on the first device" is the failure."""
    shards = _shard_rows(arr)
    want = arr.shape[0] // n_dev
    if len({s["device"] for s in shards}) != n_dev \
            or any(s["rows"] != want for s in shards):
        return [f"{name} not sharded {n_dev} ways: {shards}"]
    return []


def phase_mesh(meter, sizes: Sizes = FULL, devices=None) -> dict:
    """The four-chip path and what it is compared with, nothing else:
    the transfer chain and the machine-path hot-contract chain through
    ReplayEngine(mesh=make_mesh(4 devices)) and through the
    single-device engine; all roots equal the headers, and after the
    lead window the device tables sit on four devices, a quarter of
    the rows each."""
    import jax
    from coreth_tpu.parallel import make_mesh
    devices = devices if devices is not None else jax.devices()
    n_dev = 4
    if len(devices) < n_dev:
        return {"failures": [f"need {n_dev} devices, have "
                             f"{len(devices)}"]}
    mesh = make_mesh(devices[:n_dev])
    out: dict = {"n_devices": n_dev, "failures": []}

    def compare(tag, genesis, blocks, kw, probe, **exp):
        wire = [b.encode() for b in blocks]
        m0 = meter.mark()
        single = replay_once(genesis, wire, kw)
        fails, tables = [], {}

        def placement(eng):
            for name, arr in probe(eng).items():
                fails.extend(_quartered(name, arr, n_dev))
                tables[name] = _shard_rows(arr)

        meshed = replay_once(genesis, wire, dict(kw, mesh=mesh),
                             after_lead=placement)
        e_mesh = meshed.pop("_engine")
        single.pop("_engine")
        placement(e_mesh)  # and again at the tip
        fails += ["single: " + f for f in replay_failures(single, **exp)]
        fails += ["mesh: " + f for f in replay_failures(meshed, **exp)]
        if e_mesh.stats.load_imbalance:
            meshed["load_imbalance"] = e_mesh.stats.load_imbalance
        out[tag] = {"single": single, "mesh": meshed, "tables": tables,
                    "compile": meter.since(m0)}
        out["failures"] += [f"{tag}: {f}" for f in fails]

    genesis, blocks = transfer_chain(sizes, sizes.chain_blocks)
    compare("transfer", genesis, blocks, _transfer_kw(sizes),
            lambda e: {"balances": e.state.balances,
                       "nonces": e.state.nonces,
                       "slot_vals": e.state.slot_vals})
    genesis, blocks = hot_chain(sizes)
    with _env(CORETH_NO_TOKEN_FASTPATH="1",
              CORETH_MACHINE_WINDOW=str(sizes.machine_window)):
        compare("hot_contract", genesis, blocks,
                dict(batch_pad=sizes.hot_txs,
                     capacity=sizes.hot_capacity,
                     slot_capacity=sizes.hot_capacity,
                     window=sizes.hot_window),
                lambda e: ({"occ_table": e._machine._runner.table}
                           if getattr(e, "_machine", None) is not None
                           and e._machine._runner is not None else {}),
                # the sharded runner's exchange bucket ratchets 64 ->
                # 512 over this chain's first windows and outruns its
                # pre-warm once (also on the virtual CPU mesh): the
                # count is printed, the comparison is about roots and
                # placement
                machine=True, conflicts=True, retraces_ok=True)
    out["reduced"] = _length_cut(sizes.chain_blocks,
                                 sizes.transfer_default_blocks)
    return out


# ------------------------------------------------------------------- main
def _emit(name: str, row: dict) -> bool:
    row = {k: v for k, v in row.items() if not k.startswith("_")}
    ok = not row.get("failures")
    print(json.dumps(dict({"phase": name, "ok": ok}, **row)), flush=True)
    return ok


def _run_phase(name: str, fn, *args) -> bool:
    t0 = time.monotonic()
    try:
        row = fn(*args)
    except Exception as exc:  # noqa: BLE001 — a phase that raises is a failed phase; the later phases still run so one chip call reports every fault
        row = {"failures": [f"{type(exc).__name__}: {exc}"],
               "traceback": traceback.format_exc()[-3000:]}
    row["phase_s"] = round(time.monotonic() - t0, 2)
    return _emit(name, row)


PHASES = {"transfer": phase_transfer, "recover": phase_recover,
          "erc20": phase_erc20, "erc20_machine": phase_erc20_machine,
          "conflicts": phase_conflicts, "streaming": phase_streaming}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the mesh-vs-single-device "
                         "comparison on four chips")
    ap.add_argument("--phase", action="append", choices=sorted(PHASES),
                    help="one chip: run only this phase (repeatable); "
                         "default: all of them")
    args = ap.parse_args(argv)
    t0 = time.monotonic()

    ident = identity()
    device = ident["device"]
    if device["platform"] != "tpu" or device["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); jax reports "
              f"{device} — refusing to run", file=sys.stderr)
        return 2

    from coreth_tpu import compile_cache
    ident["compile_cache_dir"] = compile_cache.configure()
    ident["compile_cache_from_env"] = bool(
        os.environ.get(compile_cache.ENV_VAR))
    meter = CompileMeter()
    ok = _emit("identity", ident)
    ok &= _run_phase("build", phase_build)
    if args.chips == 4:
        ok &= _run_phase("mesh", phase_mesh, meter)
    else:
        for name in args.phase or PHASES:
            ok &= _run_phase(name, PHASES[name], meter)
    print(json.dumps({"phase": "total",
                      "wall_s": round(time.monotonic() - t0, 1)}),
          flush=True)
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Mesh scaling curve: engine replay txs/s at n_devices in {1,2,4,8}
on the VIRTUAL CPU mesh (round-4 verdict #8 — attach a number to the
psum_scatter design in parallel/mesh.py).

CAVEAT, recorded in the output: virtual CPU devices all live on ONE
host core, so the collectives are memcpy emulations and the curve
measures SHARDING OVERHEAD, not ICI speedup — on real multi-chip
hardware the dp-sharded segment sums scale with chip count while this
harness can only show that the sharded program stays correct and how
much partitioning costs when the hardware underneath is serial.

This is the CPU REHEARSAL of the mesh path: JAX_PLATFORMS is pinned to
cpu and the mesh is built from ``jax.devices("cpu")`` on purpose (it
also keeps this script safe to start as a child of a process that
holds the chip).  The run on real chips is ``python chip_smoke.py
--chips 4``.

Writes MULTICHIP_SCALING.json at the repo root and prints it.
"""

import json
import os
import sys
import time

_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _DIR)

N_MAX = 8
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={N_MAX}"
    ).strip()

import jax  # noqa: E402

from coreth_tpu import compile_cache  # noqa: E402

compile_cache.configure()

from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain  # noqa: E402
from coreth_tpu.crypto.secp256k1 import priv_to_address  # noqa: E402
from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG  # noqa: E402
from coreth_tpu.parallel import make_mesh  # noqa: E402
from coreth_tpu.replay import ReplayEngine  # noqa: E402
from coreth_tpu.state import Database  # noqa: E402
from coreth_tpu.types import Block, DynamicFeeTx, sign_tx  # noqa: E402

GWEI = 10**9
TXS = int(os.environ.get("SCALE_TXS", "512"))
N_BLOCKS = int(os.environ.get("SCALE_BLOCKS", "16"))
REPS = int(os.environ.get("SCALE_REPS", "3"))
# transfer (default) or hot_contract: ONE ERC-20-shaped contract
# taking 100% of txs with Zipf sender/recipient skew (the ISSUE-14
# key-range acceptance shape — forced through the machine path)
WORKLOAD = os.environ.get("SCALE_WORKLOAD", "transfer")
# which mesh widths to measure, e.g. SCALE_POINTS=1,2
POINTS = tuple(int(x) for x in os.environ.get(
    "SCALE_POINTS", "1,2,4,8").split(","))


def build_chain():
    if WORKLOAD == "hot_contract":
        from coreth_tpu.workloads.hot_contract import build_hot_chain
        # the hot path must exercise the general machine-OCC path (the
        # token fast path already shards work by tx and would mask the
        # placement ceiling this harness measures)
        os.environ["CORETH_NO_TOKEN_FASTPATH"] = "1"
        # population sizes matter: Zipf over a tiny sender pool makes
        # the head cartoonishly heavy and the per-block conflict graph
        # percolates into one giant (irreducibly serial) component —
        # realistic millions-of-users traffic has heavy heads over
        # LARGE populations, so scale the pools with the block size
        genesis, blocks = build_hot_chain(
            CFG, N_BLOCKS, TXS, n_keys=min(512, max(32, 2 * TXS)))
        return genesis, [b.encode() for b in blocks]
    keys = [0xD00D + i for i in range(64)]
    addrs = [priv_to_address(k) for k in keys]
    genesis = Genesis(config=CFG, gas_limit=30_000_000,
                      alloc={a: GenesisAccount(balance=10**27)
                             for a in addrs})
    db = Database()
    g0 = genesis.to_block(db)
    nonces = [0] * len(keys)

    def gen(i, bg):
        for j in range(TXS):
            k = (i * TXS + j) % len(keys)
            to = b"\xe0" + (i * TXS + j).to_bytes(4, "big") * 4 \
                + b"\xe0" * 3
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI,
                gas=21_000, to=to, value=10**12 + j),
                keys[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, g0, db, N_BLOCKS, gen, gap=10)
    return genesis, [b.encode() for b in blocks]


def run_once(genesis, wire, mesh):
    blocks = [Block.decode(w) for w in wire]
    db = Database()
    gb = genesis.to_block(db)
    eng = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                       capacity=4096, batch_pad=TXS, window=8,
                       mesh=mesh)
    t0 = time.monotonic()
    root = eng.replay(blocks)
    dt = time.monotonic() - t0
    assert root == blocks[-1].header.root
    assert eng.stats.blocks_fallback == 0
    return N_BLOCKS * TXS / dt, dt, eng.stats.load_imbalance


def _emit_partial(result, out):
    """Unconditional per-point emission (the bench.py pattern, PR 6): a
    wedged later point cannot lose the already-measured curve — each
    completed point flushes a partial JSON line to stderr AND the state
    file next to the artifact."""
    line = json.dumps(dict(result, partial=True))
    print(line, file=sys.stderr, flush=True)
    try:
        with open(out + ".partial", "w") as f:
            f.write(line + "\n")
    except OSError:
        pass


def main():
    genesis, wire = build_chain()
    devices = jax.devices("cpu")
    result = {
        "harness": "virtual CPU mesh (xla_force_host_platform_"
                   "device_count) on ONE physical core",
        "caveat": "collectives are host-memory emulations: this curve "
                  "measures partitioning overhead and correctness, NOT "
                  "ICI scaling; real multi-chip speedup requires real "
                  "chips",
        "workload": f"{N_BLOCKS} blocks x {TXS} {WORKLOAD} txs, "
                    f"full ReplayEngine incl. sender recovery + trie",
        "reps": REPS,
        "points": [],
    }
    out = os.environ.get(
        "SCALE_OUT", os.path.join(_DIR, "MULTICHIP_SCALING.json"))
    for n in POINTS:
        mesh = make_mesh(devices[:n]) if n > 1 else None
        runs = []
        cold_s = 0.0
        imb = 0.0
        for r in range(REPS + 1):
            tps, dt, imb = run_once(genesis, wire, mesh)
            if r > 0:          # rep 0 = compile warm-up, excluded
                runs.append(tps)
            else:
                cold_s = dt
        runs.sort()
        median = runs[len(runs) // 2]
        # compile cost = the cold rep's wall time beyond a warm rep
        warm_s = N_BLOCKS * TXS / median
        result["points"].append({
            "n_devices": n,
            "txs_s_median": round(median, 1),
            "txs_s_spread": [round(runs[0], 1), round(runs[-1], 1)],
            "compile_ms": round(max(0.0, cold_s - warm_s) * 1000, 1),
            # max/mean per-shard lane occupancy (sharded machine
            # windows only; 0.0 on the transfer path / single device)
            "load_imbalance": imb,
        })
        print(f"n={n}: {runs}", file=sys.stderr)
        _emit_partial(result, out)
    # SCALE_OUT redirects the artifact (bench.py's deadline-budgeted
    # truncated run must not clobber the standalone curve)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    try:
        # the final artifact supersedes the crash-recovery state; a
        # leftover .partial would read as a live truncated curve
        os.remove(out + ".partial")
    except OSError:
        pass
    print(json.dumps(result))


if __name__ == "__main__":
    main()

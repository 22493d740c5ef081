"""One run of one cell: set-up, the timed window, the comparison that
decides ``correct``, the metrics, the result line.

Order of a run (``main``):

1. find the cell, its configuration and its traffic mix by name;
2. refuse to go on without the TPU chips the cell asks for;
3. set-up: the configuration's ``env``, the chain from ``--seed``
   (written by a child process while this one reaches the chip), the
   compile cache, the native library, warm passes on fresh engines
   until one compiles nothing.  ``setup_s`` is all of it but the time
   this process stood still for the chain: the chain is the reference's
   work, not the system's, and a cached seed would otherwise read a
   minute less than a new one;
4. the window: the traffic mix's driver runs for ``--seconds``;
   nothing is traced;
5. ``--trace 1`` only: one more pass after the window has closed, a few
   seconds of it under the profiler;
6. the device's peak memory is read; then the plain reference
   (``plainref``, through the chain builder's ``ledger``) adds the chain
   up, every account of it is read back from the state the last pass
   committed, and every pass's root is held to the last header's AND to
   the reference's own state root; the engine's own counters say
   whether the passes stayed on the path the cell stands for;
7. the metrics (end-to-end for ``--trace 0``, per-layer readers for
   ``--trace 1``) and the one result line, last on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import sys
import threading
import time
from typing import Callable, List, Optional

from benchlib import names

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")
TRACE_DIR = os.path.join(names.REPO, ".bench_cache", "trace")
MAX_WARM_PASSES = 3


def log(tag: str, row: dict) -> None:
    """An earlier line: information, never the result."""
    print(json.dumps({tag: row}, default=_jsonable), file=sys.stderr,
          flush=True)


def _jsonable(v):
    if isinstance(v, (bytes, bytearray)):
        return bytes(v).hex()
    return str(v)


# ------------------------------------------------------------------ device
def device_identity() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, as the backend reports."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ------------------------------------------------------------------ set-up
def apply_env(config: dict, strict: bool) -> None:
    """Only what the configuration file lists; a ``CORETH_*`` variable
    from outside would make the cell measure something else (a CPU
    rehearsal needs some, so only a real run is strict)."""
    listed = config.get("env", {})
    stray = sorted(k for k in os.environ
                   if k.startswith("CORETH_") and k not in listed)
    if strict and stray:
        raise SystemExit(f"unlisted CORETH_* variables set: {stray}; "
                         "the cell measures the program's defaults")
    os.environ.update(listed)


def require_native() -> None:
    """Builds the native library when it is missing (the loader's own
    staleness rule) and fails when any native seam did not load: the
    pure-Python crypto, trie or EVM must never be on the clock."""
    from coreth_tpu.crypto import native
    from coreth_tpu.evm import hostexec
    from coreth_tpu.mpt import native_trie
    bad = []
    if native.load() is None:
        bad.append("crypto.native.load() is None")
    if not hostexec.available():
        bad.append("hostexec.available() is False")
    if native_trie.backend() != "native":
        bad.append(f"trie backend is {native_trie.backend()}")
    if bad:
        raise SystemExit("native library: " + "; ".join(bad))


def warm_up(meter, pass_fn: Callable[[], dict]) -> dict:
    """Whole passes on fresh engines until one compiles nothing.  The
    machine path needs two before that: the first engine of a process
    learns the contract's premap recipes by discovery, the second
    starts from them and lands its first window in a table bucket the
    first never used.  A third pass that still compiles fails the
    run."""
    from coreth_tpu.evm.device.adapter import wait_warm_compiles
    compiles, walls, errors = [], [], []
    for n_pass in range(1, MAX_WARM_PASSES + 1):
        m0 = meter.mark()
        row = pass_fn()
        wait_warm_compiles()  # a background pre-warm belongs to its pass
        compiles.append(meter.since(m0))
        walls.append(row["t_end"] - row["t_start"])
        if row["error"]:
            errors.append(f"warm pass {n_pass}: {row['error']}")
        if n_pass > 1 and compiles[-1]["compiles"] == 0:
            break
    else:
        raise SystemExit(f"warm pass {MAX_WARM_PASSES} still compiled "
                         f"{compiles[-1]['compiles']} programs")
    return {"passes": len(walls), "wall_s": walls, "compile": compiles,
            "errors": errors}


# -------------------------------------------------------------- comparison
def read_back(builder, engine, config: dict, traffic: dict,
              seed: int) -> dict:
    """The plain reference adds the chain up (the builder's ``ledger``:
    ``plainref`` alone, nothing of the program) and the builder reads
    every account of that book back from the state the last timed pass
    committed.  ``{"compared", "wrong", "root"}``; a state that cannot
    be read back is a wrong answer, not a crash of the benchmark."""
    book = builder.ledger(config, traffic, seed)
    try:
        return builder.read_back(engine, book)
    except Exception as exc:  # noqa: BLE001 — see above
        return {"compared": 1, "root": None,
                "wrong": [f"{type(exc).__name__}: {exc}"[:300]]}


def compare(rows: List[dict], header_root: bytes, ledger: dict,
            violations: dict) -> dict:
    """``correct``, by what the timed passes themselves committed.

    Two references.  The program's host processor wrote the chain: the
    engine holds each block to its header's receipt root and gas and
    each commit window to its header's state root, a block it cannot
    commit so makes the pass raise, and the root every pass ended on is
    compared here with the last header's.  The plain reference
    (``plainref``) shares nothing with the program: the same roots are
    compared with the state root it folds out of its own book, and every
    account of that book with the state the last pass committed.  The
    engine's counters (``replay_pass.path_violations``) say whether the
    passes took the path the cell stands for.  Every number is exact,
    so every limit is 0.
    """
    attempted = sum(r["blocks"] for r in rows)
    off_header = [r for r in rows
                  if r["error"] or r["root"] != header_root]
    off_ledger = [r for r in rows if r["root"] != ledger["root"]] \
        if ledger["root"] is not None else []
    short = sum(max(0, r["blocks"] - r["blocks_device"]
                    - r["blocks_fallback"]) for r in rows)
    numbers = {
        "passes_off_header_root": {"value": len(off_header), "limit": 0},
        "passes_off_ledger_root": {"value": len(off_ledger), "limit": 0},
        "blocks_uncommitted": {"value": short, "limit": 0},
        "accounts_off_ledger": {"value": len(ledger["wrong"]), "limit": 0,
                                "of": ledger["compared"]},
    }
    for name, count in violations.items():
        numbers[name] = {"value": count, "limit": 0}
    # no reading finer than a pass exists (no per-block commit hook):
    # every block of a pass that ended off a root counts as failed
    bad = {id(r): r for r in off_header + off_ledger}
    failed = sum(r["blocks"] for r in bad.values())
    if ledger["wrong"] and rows:
        failed = max(failed, rows[-1]["blocks"])
    ok = bool(rows) and all(n["value"] <= n["limit"]
                            for n in numbers.values())
    return {"correct": ok, "attempted": attempted,
            "failed": max(failed, short), "numbers": numbers}


# ------------------------------------------------------------------- trace
# The TPU's trace holds every XLA op (1.2-1.6 million a second in these
# cells), stop_trace() digests ~35 thousand a second, and the device's
# trace buffer ends at ~6.28M events: a whole hot-token pass took 172 s to
# stop and, its tail dropped, read 49% idle where shorter stretches read
# 12-25%.  The traced span is therefore ``TRACE_SPAN_S`` seconds of one
# pass: the whole pass where it is shorter, and otherwise a stretch that
# begins at a phase drawn from the seed, so that no part of a pass is
# favoured.
TRACE_SPAN_S = 2.5


def _profiler_options():
    """No Python tracer and no HLO protos: with the defaults the traced
    pass ran at half speed and the file was twice the size."""
    import jax
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 1
    po.enable_hlo_proto = False
    return po


def traced_pass(pass_fn: Callable[[], dict], expected_s: float,
                span_s: float, seed: int) -> Optional[dict]:
    """One more pass, ``span_s`` seconds of it under the JAX profiler,
    reduced to busy/idle/breakdown.  ``expected_s`` is what a pass took
    in the window.  The pass runs in a worker thread; this thread
    starts the trace at the drawn phase, holds the ``bench/pass``
    annotation — the span the idle share is taken over — for ``span_s``
    seconds or until the pass has ended, and stops the trace
    (``stop_trace`` called from a helper thread took four times as
    long).  The trace is removed once read: a run writes little."""
    import jax
    from benchlib import trace_reduce
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR, exist_ok=True)
    delay = random.Random(seed).uniform(0.0, max(0.0, expected_s - span_s))
    info = {"trace_after_s": delay, "traced": False}
    out = {}
    worker = threading.Thread(
        target=lambda: out.update(row=pass_fn(annotate=True)),
        name="bench-traced-pass")
    if delay > 0.0:
        worker.start()
        worker.join(delay)
    if delay == 0.0 or worker.is_alive():
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profiler_options())
        try:
            with jax.profiler.TraceAnnotation("bench/pass"):
                if delay == 0.0:
                    worker.start()
                worker.join(span_s)
        finally:
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            info.update(traced=True, stop_s=time.monotonic() - t_stop)
    worker.join()
    t_read = time.monotonic()
    if "row" in out:
        info["pass_wall_s"] = out["row"]["t_end"] - out["row"]["t_start"]
    path = trace_reduce.find_xplane(TRACE_DIR)
    summary = None
    if path is not None:
        info["xplane_bytes"] = os.path.getsize(path)
        summary = trace_reduce.summarize(trace_reduce.read_xplane(path))
        info["read_s"] = time.monotonic() - t_read
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    log("trace", dict(info, summary=summary))
    return summary


# -------------------------------------------------------------------- main
def parse_args(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(args, t_start: float, require_tpu: bool = True) -> dict:
    """Everything but printing; returns the result object.  The control
    and the tests call this with ``require_tpu=False`` to drive a whole
    run on the CPU."""
    spec = names.load_spec()
    cell, entry, config, traffic = names.resolve_cell(spec, args.workload)
    apply_env(config, strict=require_tpu)
    sys.path.insert(0, names.REPO)
    from benchlib import chains
    require_native()  # before the child: two makes must never race
    child = chains.start_build(
        os.path.join(names.REPO, entry["file"]),
        names.traffic_file(cell["traffic"]), config, traffic, args.seed)
    try:
        return _run_cell(args, t_start, require_tpu, spec, cell, config,
                         traffic, child)
    finally:
        if child is not None and child.poll() is None:
            child.kill()  # the run gave up before it needed the chain
            child.wait()


def _run_cell(args, t_start, require_tpu, spec, cell, config, traffic,
              child) -> dict:
    device = device_identity()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); jax reports {device} - refusing to run",
              file=sys.stderr)
        raise SystemExit(2)

    from coreth_tpu import compile_cache
    from benchlib import chains, replay_pass
    from benchlib.compile_meter import CompileMeter
    cache_dir = compile_cache.configure()
    meter = CompileMeter()
    t_native = time.monotonic()  # imports, native library, the chip
    genesis, wire, how = chains.chain_for(config, traffic, args.seed, child)
    from coreth_tpu.types import Block
    header_root = bytes(Block.decode(wire[-1]).header.root)
    engine_kw = dict(config["engine"])

    last = {}  # the newest pass's engine, kept for the read-back

    def pass_fn(annotate: bool = False) -> dict:
        last.clear()  # the engine before dies here, inside the window
        row = replay_pass.one_pass(genesis, wire, engine_kw,
                                   annotate=annotate)
        last["engine"] = row.pop("_engine")
        return row

    warm = warm_up(meter, pass_fn)
    log("setup", {"workload": args.workload, "seed": args.seed,
                  "device": device, "compile_cache_dir": cache_dir,
                  "reached_chip_s": t_native - t_start,
                  "chain": how, "warm": warm,
                  "reduced": config.get("reduced")})

    driver, _ = names.load_named("drivers", traffic["driver"])
    ctx = {"genesis": genesis, "wire": wire, "engine_kw": engine_kw,
           "pass_fn": pass_fn}
    m0 = meter.mark()
    window = driver.drive(ctx, args.seconds, traffic)
    in_window = meter.since(m0)
    rows = window["rows"]
    setup_s = window["t_open"] - t_start - how["wait_s"]
    window_s = window["t_close"] - window["t_open"]

    window_engine = last.get("engine")
    pass_s = sum(r["t_end"] - r["t_start"] for r in rows) / len(rows)
    trace = traced_pass(pass_fn, pass_s, TRACE_SPAN_S,
                        args.seed) if args.trace else None
    peak = memory_peak_bytes()
    t_check = time.monotonic()
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    ledger = read_back(builder, window_engine, config, traffic, args.seed)
    verdict = compare(rows, header_root, ledger,
                      replay_pass.path_violations(
                          rows, config.get("expect", {})))
    log("check", {"check_s": time.monotonic() - t_check,
                  "accounts_read_back": ledger["compared"],
                  "ledger_root": ledger["root"],
                  "header_root": header_root,
                  "wrong": ledger["wrong"][:5]})

    for i, r in enumerate(rows):
        log("pass", {"i": i, **{k: v for k, v in r.items()
                                if k not in ("t_start", "t_end")}})
    log("window", {"window_s": window_s, "passes": len(rows),
                   "compile": in_window})

    run = {"spec": spec, "cell": cell, "config": config,
           "traffic": traffic, "passes": rows, "window_s": window_s,
           "setup_s": setup_s, "compile": in_window, "trace": trace}
    txs = sum(r["txs_committed"] for r in rows
              if not r["error"] and r["root"] == header_root)
    values = {"committed_txs_per_s": txs / window_s, "setup_s": setup_s,
              **window.get("values", {})}
    metrics = {}
    group = "per_layer" if args.trace else "end_to_end"
    for m in names.cell_metrics(spec, group, args.workload):
        if m["name"] not in values:
            reader, _ = names.load_named("metrics", m["name"])
            values[m["name"]] = reader.read(run)
        if values[m["name"]] is not None:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": verdict["correct"],
              "attempted": verdict["attempted"],
              "failed": verdict["failed"], "metrics": metrics,
              "device": dev}
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = verdict["numbers"]
    return result


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    result = run_cell(args, t_start)
    # each number compared beside its limit: last on standard error,
    # and last in the result line
    for name, n in result["compared"].items():
        print(f"compared {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0

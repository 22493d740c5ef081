"""From a profiler trace to device busy time, idle share and breakdown.

``reduce_intervals`` is plain arithmetic over ``(start_s, duration_s,
name)`` intervals and is tested on hand-made ones; ``read_xplane`` is
the thin reader of the ``.xplane.pb`` the JAX profiler writes
(``jax.profiler.ProfileData``, on-chip-measurement guide section 6).
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]

# lines of a TPU device plane, in order of preference for "an operation
# ran on the device": single XLA ops, else whole programs
OP_LINES = ("XLA Ops", "XLA Modules")
PROGRAM_LINE = "XLA Modules"


def reduce_intervals(intervals: Iterable[Interval],
                     span: Optional[Tuple[float, float]] = None,
                     top: int = 10) -> Optional[dict]:
    """Busy union, idle share, time by name and the longest gaps.

    ``span`` is the (start, end) the share is taken over; without it,
    the first start to the last end.  Returns None when there is
    nothing to read (no interval, or an empty span): an idle share of
    a trace that holds no device operation is not 100%, it is absent.
    """
    ivs = sorted((s, s + d, n) for s, d, n in intervals if d > 0)
    if not ivs:
        return None
    lo, hi = span if span is not None else (ivs[0][0],
                                           max(e for _s, e, _n in ivs))
    if hi <= lo:
        return None
    by_name: dict = {}
    for s, e, n in ivs:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    busy = 0.0
    gaps: List[Tuple[float, float]] = []
    cur = lo
    for s, e, _n in ivs:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s - cur))
            cur = s
        if e > cur:
            busy += e - cur
            cur = e
    if hi > cur:
        gaps.append((cur, hi - cur))
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy, "span_s": hi - lo,
            "idle_share": 1.0 - busy / (hi - lo),
            "ops": [[n, t] for n, t in ops[:top]],
            "gaps": gaps[:top], "n_intervals": len(ivs)}


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Interval],
               default: str = "host") -> List[list]:
    """Name each gap by what the host was doing: the shortest host span
    that covers the gap's middle."""
    out = []
    for start, dur in gaps:
        mid = start + dur / 2
        best = None
        for s, d, n in host_spans:
            if s <= mid <= s + d and (best is None or d < best[0]):
                best = (d, n)
        out.append([best[1] if best else default, dur])
    return out


def merge_by_name(rows: Sequence[Sequence], top: int = 10) -> List[list]:
    """Sum ``[name, seconds]`` rows that share a name; longest first."""
    acc: dict = {}
    for n, t in rows:
        acc[n] = acc.get(n, 0.0) + t
    return [[n, t] for n, t in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str, host_prefix: str = "bench/") -> dict:
    """Device intervals per chip, and the host spans whose name starts
    with ``host_prefix`` (the runner's own ``TraceAnnotation``s), all in
    seconds on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host, layout = {}, [], {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).append(line)
        layout[plane.name] = sorted(lines)
        if plane.name.startswith("/device:TPU:"):
            dev = {}
            for name in OP_LINES:  # the program line is one of them
                dev[name] = [(ev.start_ns / 1e9, ev.duration_ns / 1e9,
                              ev.name)
                             for line in lines.get(name, ())
                             for ev in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for group in lines.values():
                for line in group:
                    for ev in line.events:
                        if ev.name.startswith(host_prefix):
                            host.append((ev.start_ns / 1e9,
                                         ev.duration_ns / 1e9, ev.name))
    return {"devices": devices, "host": host, "layout": layout}


def summarize(trace: dict, top: int = 10) -> Optional[dict]:
    """What the result line needs from a read trace: busy seconds and
    span averaged over the chips, the idle share, the programs that
    took most device time and the longest idle gaps by host span.
    The span is the runner's ``bench/pass`` annotation where the trace
    has it, so set-up before the pass is not counted as idle."""
    span = None
    for s, d, n in trace["host"]:
        if n == "bench/pass":
            span = (s, s + d)
    per_chip = []
    for dev in trace["devices"].values():
        ivs = next((dev[n] for n in OP_LINES if dev.get(n)), None)
        if not ivs:
            continue
        red = reduce_intervals(ivs, span, top)
        if red is None:
            continue
        progs = dev.get(PROGRAM_LINE) or ivs
        red["programs"] = merge_by_name(
            [(n, d) for _s, d, n in progs], top)
        per_chip.append(red)
    if not per_chip:
        return None
    n = len(per_chip)
    first = per_chip[0]
    return {"busy_s": sum(r["busy_s"] for r in per_chip) / n,
            "window_s": sum(r["span_s"] for r in per_chip) / n,
            "idle_share": sum(r["idle_share"] for r in per_chip) / n,
            "device_ops": first["programs"],
            "idle_gaps": merge_by_name(
                label_gaps(first["gaps"], trace["host"]), top),
            "chips": n, "n_intervals": first["n_intervals"]}

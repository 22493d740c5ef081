"""End-to-end span tracing: per-block latency attribution + Perfetto.

The stack runs six concurrent actors (feed, prefetch, execute, device
dispatch, commit, flat exporter) plus a supervisor that silently
reroutes work between backends; this module is the shared evidence
layer that says WHERE a block's enqueue->committed time went.

Design constraints, in order (the faults-registry / metrics.ENABLED
mold):

1. **Disabled costs ~nothing.**  ``TRACER`` is a module global that is
   ``None`` by default; every instrumentation site goes through
   :func:`span` / :func:`instant` / :func:`block_begin`, which return
   after ONE module-global ``is None`` check — no ring is allocated,
   no event is recorded, no contextvar is touched.  ``CORETH_TRACE=1``
   installs the tracer (:func:`arm_from_env`, called idempotently by
   the pipeline and engine constructors, like ``faults.arm_from_env``).
2. **Bounded.**  Events land in a ring (``CORETH_TRACE_RING``, default
   64k events); a long-running stream overwrites its oldest events
   instead of growing, and ``dropped`` counts the evictions.
3. **Exportable.**  :meth:`SpanTracer.export` renders the ring as
   Chrome trace-event / Perfetto JSON: one row per thread (metadata
   ``thread_name`` events), complete ``X`` spans, ``i`` instants, and
   ``s``/``t``/``f`` flow arrows that follow a block (flow id = block
   number) across the feed, prefetch, execute, and flat-exporter
   threads.  ``CORETH_TRACE_OUT=path`` writes the export at pipeline
   shutdown (:func:`write_out`); a write failure — the
   ``obs/export_fail`` injection point, or a real I/O error — is
   counted, never raised: the trace is diagnostics, losing it must not
   take the pipeline down.
4. **Attributable.**  A :class:`BlockTrace` rides each block from feed
   enqueue to commit; its named stage intervals sum EXACTLY to the
   block's enqueue->committed latency, and the tracer aggregates them
   into ``stage_breakdown()`` — the per-stage share surface
   ``StreamReport.stage_breakdown`` and the bench ``tracing`` section
   publish.

Every span and every phase of ``obs.account`` records an ``id`` and
the ``parent`` that enclosed it on its thread (:data:`PARENT`), so
:func:`self_times` can take a span's children out of its duration.
While the tracer is armed the account's phases also open
``jax.profiler.TraceAnnotation`` (:func:`annotation`), so XLA activity
lines up under the program's own phases in a captured jax profile.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, Optional

from coreth_tpu import faults

# the trace-file write fails mid-export: the pipeline must finish
# unharmed and the failure must be COUNTED (SpanTracer.export_failures)
PT_EXPORT_FAIL = faults.declare(
    "obs/export_fail",
    "trace-file write fails mid-export (pipeline unharmed, counted)")

# THE module global every instrumentation site checks (None = off)
TRACER: Optional["SpanTracer"] = None

# current flow id (block number) for span/instant inheritance: set by
# a span opened with an explicit flow=, read by everything nested under
# it on the same thread — contextvars give per-thread isolation without
# threading the id through every call signature
_FLOW: "contextvars.ContextVar[Optional[int]]" = contextvars.ContextVar(
    "coreth_trace_flow", default=None)

# id of the innermost open span or account phase on this thread: what a
# span opened now records as its parent.  Written only while the tracer
# is armed (the disabled path never reaches a _Span or a phase frame).
PARENT: "contextvars.ContextVar[Optional[int]]" = contextvars.ContextVar(
    "coreth_trace_parent", default=None)
SPAN_IDS = itertools.count(1)

# Stable per-thread trace ids.  threading.get_ident() is the raw
# pthread handle, which the OS RECYCLES the moment a thread exits — a
# fast backlog feed thread can die before the prefetch thread is even
# created, handing both the same ident and merging their timeline rows
# (observed: the prefetch row labeled "serve-feed").  A monotonic
# counter bound to a threading.local never repeats, so every thread
# lifetime gets its own row.
_TID_LOCAL = threading.local()
_TID_COUNTER = itertools.count(1)


def _tid() -> int:
    t = getattr(_TID_LOCAL, "tid", None)
    if t is None:
        t = next(_TID_COUNTER)
        _TID_LOCAL.tid = t
    return t


class _NullSpan:
    """Shared no-op context manager the disabled path hands out."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One recorded span: a complete ``X`` event emitted at exit, with
    flow inheritance through the contextvar while it is open."""

    __slots__ = ("_t", "name", "_flow", "_args", "_t0", "_tok", "_id",
                 "_parent", "_ptok")

    def __init__(self, tracer: "SpanTracer", name: str,
                 flow: Optional[int], args: dict):
        self._t = tracer
        self.name = name
        self._flow = flow
        self._args = args
        self._tok = None

    def __enter__(self):
        t = self._t
        self._t0 = t._now_us()
        self._id = next(SPAN_IDS)
        self._parent = PARENT.get()
        self._ptok = PARENT.set(self._id)
        if self._flow is None:
            self._flow = _FLOW.get()
        else:
            self._tok = _FLOW.set(self._flow)
        if self._flow is not None:
            t._bind_flow(self._flow, self._t0)
        return self

    def __exit__(self, *exc):
        t = self._t
        tid = _tid()
        t._note_thread(tid)
        args = dict(self._args, id=self._id, parent=self._parent)
        if self._flow is not None:
            args["flow"] = self._flow
        t._emit({"ph": "X", "name": self.name, "ts": self._t0,
                 "dur": t._now_us() - self._t0, "tid": tid,
                 "args": args})
        PARENT.reset(self._ptok)
        if self._tok is not None:
            _FLOW.reset(self._tok)
            self._tok = None
        return False


class StageAccumulator:
    """Thread-safe per-consumer sink for block stage attribution.

    Each consumer (a StreamingPipeline run) owns ONE of these, so two
    pipelines sharing the process-global tracer — a builder+replica
    pair, or back-to-back bench reps armed via CORETH_TRACE=1 — never
    blend each other's blocks into one breakdown."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stage_s: Dict[str, float] = {}
        self._latency_s = 0.0
        self._blocks = 0

    def add_block(self, stages: Dict[str, float],
                  total_s: float) -> None:
        """Fold one committed block's stage intervals (seconds; their
        sum equals the block's enqueue->committed latency)."""
        with self._lock:
            self._blocks += 1
            self._latency_s += total_s
            acc = self._stage_s
            for k, v in stages.items():
                acc[k] = acc.get(k, 0.0) + v

    def breakdown(self) -> dict:
        """Per-stage SHARE of total enqueue->committed time across
        every block folded so far (shares sum to ~1.0 by construction;
        ``_blocks``/``_latency_s`` carry the denominators)."""
        with self._lock:
            total = self._latency_s
            if total <= 0 or not self._blocks:
                return {}
            out = {k: round(v / total, 4)
                   for k, v in sorted(self._stage_s.items())}
            out["_blocks"] = self._blocks
            out["_latency_s"] = round(total, 3)
        return out


class SpanTracer:
    """Thread-safe span/instant recorder over a bounded ring."""

    def __init__(self, ring: int = 65536, clock=time.monotonic):
        self._clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self.ring_size = ring
        self._ring: deque = deque(maxlen=ring)
        self.dropped = 0           # events evicted from the full ring
        self.export_failures = 0   # write_out failures (counted, eaten)
        self._thread_names: Dict[int, str] = {}

    # ------------------------------------------------------------ recording
    def _now_us(self) -> int:
        return int((self._clock() - self._t0) * 1e6)

    def _note_thread(self, tid: int) -> None:
        # unlocked fast path for the steady state; the insert itself
        # must hold the lock because export() iterates/prunes this
        # dict under it (an unlocked insert racing that iteration is
        # a RuntimeError out of a live /trace scrape)
        if tid in self._thread_names:
            return
        with self._lock:
            self._thread_names[tid] = threading.current_thread().name

    def _emit(self, ev: dict) -> None:
        with self._lock:
            if len(self._ring) == self.ring_size:
                self.dropped += 1
            self._ring.append(ev)

    def _bind_flow(self, flow: int, ts: int) -> None:
        """One flow-arrow binding at (ts, this thread).  Every binding
        records as ``t``; export() derives ``s``/``f`` from the ring's
        surviving content (first/last binding per id), so pairing needs
        NO cross-run state and survives both ring eviction of a flow's
        head and block numbers recurring across pipeline runs."""
        tid = _tid()
        self._note_thread(tid)
        with self._lock:
            if len(self._ring) == self.ring_size:
                self.dropped += 1
            self._ring.append({"ph": "t", "name": "block", "id": flow,
                               "ts": ts, "tid": tid})

    def span(self, name: str, flow: Optional[int] = None,
             **args) -> _Span:
        return _Span(self, name, flow, args)

    def instant(self, name: str, flow: Optional[int] = None,
                **args) -> None:
        ts = self._now_us()
        tid = _tid()
        self._note_thread(tid)
        if flow is None:
            flow = _FLOW.get()
        if flow is not None:
            self._bind_flow(flow, ts)
        ev = {"ph": "i", "s": "t", "name": name, "ts": ts, "tid": tid}
        if args:
            ev["args"] = args
        self._emit(ev)

    # --------------------------------------------------------------- export
    def export(self) -> dict:
        """The ring as a Chrome trace-event / Perfetto JSON document:
        thread_name metadata rows first, then the events with pid/cat
        stamped.  Flow phases derive from the SURVIVING ring content —
        per id, the first binding becomes ``s`` and the last the
        terminating ``f`` — so arrows pair up even when the ring
        evicted a flow's head or a block number recurred across runs.
        Only the shallow snapshot happens under the recording lock
        (per-event copies outside it: a 64k-ring scrape must not stall
        every instrumented thread)."""
        pid = os.getpid()
        with self._lock:
            snap = list(self._ring)
            # prune names whose threads have no surviving events: a
            # long-lived env-armed tracer spawns fresh pipeline threads
            # (fresh tids — the counter never reuses) every run, and
            # without pruning the name map and every export's metadata
            # rows would grow without bound.  Safe: a still-live thread
            # re-notes its name on its next event.
            live = {e["tid"] for e in snap}
            for tid in [t for t in self._thread_names
                        if t not in live]:
                del self._thread_names[tid]
            names = dict(self._thread_names)
        evs = [dict(e) for e in snap]
        first_bind: Dict[int, int] = {}
        last_bind: Dict[int, int] = {}
        for i, e in enumerate(evs):
            if e["ph"] == "t":
                first_bind.setdefault(e["id"], i)
                last_bind[e["id"]] = i
        out = []
        for tid, nm in sorted(names.items()):
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "ts": 0, "cat": "__metadata",
                        "args": {"name": nm}})
        for i, e in enumerate(evs):
            e["pid"] = pid
            e.setdefault("cat", "coreth")
            if e["ph"] == "t":
                fid = e["id"]
                if first_bind[fid] == i:
                    e["ph"] = "s"
                elif last_bind[fid] == i:
                    e["ph"] = "f"
                    e["bp"] = "e"
            out.append(e)
        return {"traceEvents": out, "displayTimeUnit": "ms"}

    def write_out(self, path: Optional[str] = None) -> Optional[str]:
        """Write the export to ``path`` (default ``CORETH_TRACE_OUT``);
        returns the path written, or None (not configured / failed —
        failures are counted in ``export_failures``, never raised)."""
        path = path or os.environ.get("CORETH_TRACE_OUT")
        if not path:
            return None
        try:
            faults.fire(PT_EXPORT_FAIL)
            # default=str: the open **kwargs span API means one
            # refactor could pass a non-JSON primitive (a numpy int,
            # say) — degrade it to its repr instead of losing the file
            data = json.dumps(self.export(), default=str)
            with open(path, "w", encoding="utf-8") as f:
                f.write(data)
            return path
        except (faults.FaultInjected, OSError, TypeError, ValueError):
            # counted, never raised: this runs in the pipeline's
            # shutdown finally — a failed diagnostic write must not
            # turn a successful stream into a crashed run
            self.export_failures += 1
            return None


class BlockTrace:
    """Per-block trace context: rides one block from feed enqueue to
    commit (carried on the pipeline's queue items), emitting flow-bound
    instants on each thread it crosses and accumulating the stage
    intervals whose sum IS the block's enqueue->committed latency.

    Stages (consecutive, clamped non-negative, summing exactly to the
    total): ``queue_feed`` (enqueue -> prefetch pickup), ``prefetch``
    (per-block share of the chunk warm), ``queue_exec`` (prefetched ->
    execute-stage pickup), ``commit`` (per-block share of the window's
    trie-fold flush), and ``execute`` (the remainder: classify, device
    dispatch/validation, host fallback)."""

    __slots__ = ("_t", "_sink", "number", "t_enqueue", "t_prefetch",
                 "prefetch_s", "t_exec")

    def __init__(self, tracer: SpanTracer, number: int,
                 t_enqueue: Optional[float],
                 sink: StageAccumulator):
        self._t = tracer
        # attribution sink: the owner's per-consumer accumulator, so
        # concurrent/sequential pipelines sharing the global tracer
        # never blend breakdowns
        self._sink = sink
        self.number = number
        self.t_enqueue = tracer._clock() if t_enqueue is None \
            else t_enqueue
        self.t_prefetch: Optional[float] = None
        self.prefetch_s = 0.0
        self.t_exec: Optional[float] = None
        tracer.instant("block/enqueue", flow=number, number=number)

    def prefetched(self, t_start: float, share_s: float) -> None:
        self.t_prefetch = t_start
        self.prefetch_s = share_s
        self._t.instant("block/prefetched", flow=self.number)

    def exec_start(self) -> None:
        self.t_exec = self._t._clock()
        self._t.instant("block/exec_start", flow=self.number)

    def finish(self, t_commit: float, commit_s: float = 0.0) -> None:
        total = max(t_commit - self.t_enqueue, 0.0)
        t_pf = self.t_prefetch if self.t_prefetch is not None \
            else self.t_enqueue
        queue_feed = min(max(t_pf - self.t_enqueue, 0.0), total)
        prefetch = min(self.prefetch_s, total - queue_feed)
        t_ex = self.t_exec if self.t_exec is not None else t_pf
        queue_exec = min(max(t_ex - t_pf - prefetch, 0.0),
                         total - queue_feed - prefetch)
        commit = min(max(commit_s, 0.0),
                     total - queue_feed - prefetch - queue_exec)
        execute = total - queue_feed - prefetch - queue_exec - commit
        self._sink.add_block(
            {"queue_feed": queue_feed, "prefetch": prefetch,
             "queue_exec": queue_exec, "execute": execute,
             "commit": commit}, total)
        self._t.instant("block/committed", flow=self.number)


class EventRing:
    """Small ALWAYS-ON ordered event ring (the evm/device/shard.py
    dispatch-ordering trace).  Appends cost one bounded-deque push when
    tracing is off — the exact semantics the dispatch-ordering test in
    tests/test_shard_replay.py pins — and mirror into the active tracer
    as instant events when it is on, so the Perfetto timeline shows the
    same dispatch/fetch ordering the test asserts."""

    __slots__ = ("name", "_dq")

    def __init__(self, name: str, maxlen: int = 512):
        self.name = name
        self._dq: deque = deque(maxlen=maxlen)

    def append(self, entry: str) -> None:
        self._dq.append(entry)
        t = TRACER
        if t is not None:
            t.instant(f"{self.name}/{entry}")

    def clear(self) -> None:
        self._dq.clear()

    def __iter__(self):
        return iter(self._dq)

    def __len__(self) -> int:
        return len(self._dq)

    def __contains__(self, entry) -> bool:
        return entry in self._dq


# ------------------------------------------------------------- module API

def enabled() -> bool:
    return TRACER is not None


def tracer() -> Optional[SpanTracer]:
    """The active tracer (None when tracing is off) — the accessor for
    callers that hold ``obs`` rather than this module (the re-exported
    ``TRACER`` name would snapshot the binding at import)."""
    return TRACER


def span(name: str, **kw):
    """A recorded span, or the shared no-op when tracing is off (the
    one-module-global-None-check contract every site relies on)."""
    t = TRACER
    if t is None:
        return _NULL_SPAN
    return t.span(name, **kw)


def instant(name: str, **kw) -> None:
    t = TRACER
    if t is None:
        return
    t.instant(name, **kw)


def block_begin(number: int, t_enqueue: Optional[float],
                sink: StageAccumulator) -> Optional[BlockTrace]:
    """A BlockTrace riding block ``number`` (None when tracing is off
    — callers carry the None and skip their marks).  ``sink`` is the
    owner's per-consumer StageAccumulator."""
    t = TRACER
    if t is None:
        return None
    return BlockTrace(t, number, t_enqueue, sink)


def annotation(name: str):
    """An ENTERED ``jax.profiler.TraceAnnotation`` (the caller exits
    it), or None where this jax has no profiler API.  Only the armed
    path of ``obs.account`` calls this."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


def self_times(events) -> Dict[str, float]:
    """Seconds of SELF time by name over exported ``X`` events: each
    event's duration minus the part its children cover (the events
    that name it as ``args.parent``; children of one parent run on its
    thread, one after another).  An event with no ``args.id`` has no
    children to take out."""
    covered: Dict[int, float] = {}
    spans = [e for e in events if e.get("ph") == "X"]
    for e in spans:
        parent = e.get("args", {}).get("parent")
        if parent is not None:
            covered[parent] = covered.get(parent, 0.0) + e["dur"]
    out: Dict[str, float] = {}
    for e in spans:
        own = e["dur"] - covered.get(e.get("args", {}).get("id"), 0.0)
        out[e["name"]] = out.get(e["name"], 0.0) + max(own, 0.0) / 1e6
    return out


def install(tracer: Optional[SpanTracer] = None,
            ring: Optional[int] = None) -> SpanTracer:
    """Install (and return) the global tracer.  Tests and the bench use
    this directly; production opts in through CORETH_TRACE=1."""
    global TRACER
    if tracer is None:
        tracer = SpanTracer(ring=ring) if ring else SpanTracer()
    TRACER = tracer
    return tracer


def uninstall() -> Optional[SpanTracer]:
    """Remove and return the global tracer (instrumentation sites go
    back to the one-None-check no-op)."""
    global TRACER
    t = TRACER
    TRACER = None
    return t


def arm_from_env() -> Optional[SpanTracer]:
    """Install a tracer if CORETH_TRACE=1 and none is active yet
    (idempotent — the pipeline and engine constructors both call this,
    whoever runs first wins, mirroring faults.arm_from_env)."""
    if TRACER is not None:
        return TRACER
    if not bool(int(os.environ.get("CORETH_TRACE", "0") or "0")):
        return None
    ring = int(os.environ.get("CORETH_TRACE_RING", "65536") or "65536")
    return install(ring=ring)


def write_out(path: Optional[str] = None) -> Optional[str]:
    """Write the active tracer's export to CORETH_TRACE_OUT (or
    ``path``); no-op when tracing is off or no path is configured."""
    t = TRACER
    if t is None:
        return None
    return t.write_out(path)

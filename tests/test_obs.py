"""End-to-end span tracing + live telemetry (coreth_tpu/obs).

Five surfaces under test:

1. the tracer core: span nesting with contextvars flow isolation
   across threads, ring bounding under sustained load, and the
   Perfetto/Chrome trace-event schema (every event carries
   ph/ts/pid/tid; flow ids pair up s ... f);
2. the DISABLED contract: with CORETH_TRACE unset an instrumented
   streaming run records zero events, allocates no ring, and the
   report's stage_breakdown stays empty — instrumentation sites cost
   one module-global None check;
3. per-block latency attribution: a traced streaming run's
   stage_breakdown shares sum to ~1.0 of enqueue->committed time and
   its flow spans cover feed -> prefetch -> execute -> commit;
4. the telemetry endpoint: /metrics + /trace + /report scraped from a
   LIVE streaming run (CORETH_TELEMETRY_PORT=0, ephemeral port);
5. the obs/export_fail fault point: a trace-file write failure is
   counted, the pipeline finishes unharmed — plus the metrics
   satellites (# HELP exposition, Meter first-scrape rate guard) and
   the supervisor's last_transition record.
"""

import json
import os
import sys
import threading
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu import faults, obs
from coreth_tpu.faults import FaultPlan, FaultSpec
from coreth_tpu.metrics import (
    Counter, Meter, Registry, render_prometheus,
)
from coreth_tpu.obs.trace import _NULL_SPAN
from coreth_tpu.serve import (
    BlockFeed, ChainFeed, FeedExhausted, StreamingPipeline,
)

from tests.test_serve import (  # noqa: E501 — deterministic chain builders shared with the serve suite
    build_transfer_chain, _fresh_engine,
)


@pytest.fixture(autouse=True)
def _clean_tracer_state():
    """No tracer (or fault plan) may leak across tests: the module
    global is the whole enabled/disabled contract."""
    obs.uninstall()
    yield
    obs.uninstall()
    faults.disarm()


# ------------------------------------------------------------- metrics

def test_meter_rate_guard_at_first_scrape():
    """A scrape right after registration used to divide by ~0 and
    report an absurd rate; now any interval under a microsecond reads
    as rate 0."""
    t = [100.0]
    m = Meter(clock=lambda: t[0])
    m.mark(1000)
    assert m.rate_mean(clock=lambda: t[0]) == 0.0          # dt == 0
    assert m.rate_mean(clock=lambda: t[0] + 1e-9) == 0.0   # dt ~ 0
    assert m.rate_mean(clock=lambda: t[0] + 2.0) == 500.0  # real dt


def test_prometheus_help_lines():
    reg = Registry()
    reg.get_or_register("serve/quarantined", Counter,
                        description="blocks applied but unverified")
    reg.get_or_register("serve/undocumented", Counter)
    reg.get_or_register("serve/events", Meter,
                        description="event arrival meter")
    text = render_prometheus(reg)
    assert ("# HELP serve_quarantined blocks applied but unverified"
            in text)
    assert ("# HELP serve_events_total event arrival meter" in text)
    # no description -> no HELP line for that family
    assert "# HELP serve_undocumented" not in text
    # TYPE lines are unchanged
    assert "# TYPE serve_quarantined counter" in text


# --------------------------------------------------------- tracer core

def test_disabled_mode_is_noop():
    """CORETH_TRACE unset: every API is the one-None-check no-op —
    the SAME shared null span object, no ring, no BlockTrace."""
    assert obs.tracer() is None
    assert obs.span("anything", blocks=3) is _NULL_SPAN
    assert obs.instant("anything") is None
    assert obs.block_begin(7, None, obs.StageAccumulator()) is None
    assert obs.write_out() is None
    assert obs.arm_from_env() is None  # env unset -> stays off
    with obs.span("still-a-noop"):
        pass
    assert obs.tracer() is None


def test_disabled_streaming_run_records_nothing():
    genesis, blocks = build_transfer_chain(4, 4)
    eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(eng, ChainFeed(list(blocks)),
                             window_wait=0.005)
    rep = pipe.run()
    assert eng.root == blocks[-1].header.root
    assert obs.tracer() is None        # nothing installed a tracer
    assert rep.stage_breakdown == {}   # and nothing was attributed


def test_span_nesting_and_thread_flow_isolation():
    """Nested spans inherit the enclosing flow through the contextvar;
    concurrent threads each keep their own flow (contextvars isolate
    per thread)."""
    tr = obs.install()
    seen = {}

    def worker(flow):
        with tr.span("outer", flow=flow):
            with tr.span("inner"):      # no explicit flow: inherits
                pass
        seen[flow] = True

    threads = [threading.Thread(target=worker, args=(f,))
               for f in (101, 202)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.export()["traceEvents"]
    inner = [e for e in evs if e.get("name") == "inner"]
    assert len(inner) == 2
    # each inner span inherited its OWN thread's flow id
    assert sorted(e["args"]["flow"] for e in inner) == [101, 202]
    outer = {e["args"]["flow"]: e["tid"] for e in evs
             if e.get("name") == "outer"}
    for e in inner:
        assert e["tid"] == outer[e["args"]["flow"]]
    # the main thread's context is untouched
    from coreth_tpu.obs.trace import _FLOW
    assert _FLOW.get() is None


def test_ring_bounds_under_sustained_load():
    tr = obs.install(ring=64)
    for i in range(500):
        tr.instant("tick", i=i)
    assert len(tr._ring) == 64
    assert tr.dropped == 500 - 64
    evs = tr.export()["traceEvents"]
    # export = ring + thread metadata; the oldest events are gone
    ticks = [e for e in evs if e["name"] == "tick"]
    assert len(ticks) == 64
    assert ticks[0]["args"]["i"] == 500 - 64


def test_event_ring_mirrors_into_tracer():
    ring = obs.EventRing("unit", maxlen=4)
    ring.append("a:1")            # tracing off: deque only
    assert list(ring) == ["a:1"] and "a:1" in ring
    tr = obs.install()
    ring.append("b:2")            # tracing on: mirrored as an instant
    assert list(ring) == ["a:1", "b:2"]
    names = [e["name"] for e in tr.export()["traceEvents"]]
    assert "unit/b:2" in names and "unit/a:1" not in names
    for i in range(10):
        ring.append(f"c:{i}")
    assert len(ring) == 4         # bounded, exact deque semantics
    ring.clear()
    assert len(ring) == 0


# ----------------------------------------- streaming run: attribution

def _traced_stream(n_blocks=8, txs=6, **pipe_kw):
    genesis, blocks = build_transfer_chain(n_blocks, txs)
    tr = obs.install()
    eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(eng, ChainFeed(list(blocks)),
                             window_wait=0.005, **pipe_kw)
    rep = pipe.run()
    assert eng.root == blocks[-1].header.root
    return tr, rep, blocks


def test_traced_stream_breakdown_and_perfetto_schema():
    tr, rep, blocks = _traced_stream()
    # ---- stage_breakdown: shares of enqueue->committed time, ~1.0
    bd = rep.stage_breakdown
    assert bd["_blocks"] == len(blocks)
    shares = {k: v for k, v in bd.items() if not k.startswith("_")}
    assert set(shares) == {"queue_feed", "prefetch", "queue_exec",
                           "execute", "commit"}
    assert all(v >= 0 for v in shares.values())
    assert 0.98 <= sum(shares.values()) <= 1.02
    # ---- Perfetto schema: every event has ph/ts/pid/tid
    evs = tr.export()["traceEvents"]
    for e in evs:
        assert {"ph", "ts", "pid", "tid"} <= set(e), e
        assert e["ph"] in ("X", "i", "s", "t", "f", "M"), e
    # X spans carry durations; one thread_name row per thread seen
    assert any(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    named = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"serve-feed", "serve-prefetch"} <= named
    # ---- flow arrows pair up: per block number, one s ... one f,
    # crossing at least two threads (feed -> execute)
    flows = {}
    for e in evs:
        if e["ph"] in ("s", "t", "f"):
            flows.setdefault(e["id"], []).append(e)
    assert set(flows) == {b.number for b in blocks}
    for fid, chain in flows.items():
        phs = [e["ph"] for e in chain]
        assert phs[0] == "s" and phs[-1] == "f", (fid, phs)
        assert phs.count("s") == 1 and phs.count("f") == 1
        assert len({e["tid"] for e in chain}) >= 2
        ts = [e["ts"] for e in chain]
        assert ts == sorted(ts)
    # ---- the per-block span chain covers the pipeline stages
    names = {e["name"] for e in evs}
    for want in ("block/enqueue", "block/prefetched",
                 "block/exec_start", "block/committed",
                 "serve/prefetch_warm", "window/dispatch",
                 "window/fetch_wait", "commit/flush"):
        assert want in names, want


def test_two_runs_share_tracer_without_blending(monkeypatch):
    """An env-armed tracer outlives one pipeline (arm_from_env never
    resets it): the SECOND run's stage_breakdown must count only its
    own blocks (per-pipeline StageAccumulator), and its flow arrows —
    block numbers recur across runs — must still pair s..f (export
    derives phases from surviving ring content, no cross-run state)."""
    obs.install()
    genesis, blocks = build_transfer_chain(4, 4)
    for expect_blocks in (4, 4):
        eng, _ = _fresh_engine(genesis)
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)),
                                 window_wait=0.005)
        rep = pipe.run()
        assert eng.root == blocks[-1].header.root
        assert rep.stage_breakdown["_blocks"] == expect_blocks
    flows = {}
    for e in obs.tracer().export()["traceEvents"]:
        if e["ph"] in ("s", "t", "f"):
            flows.setdefault(e["id"], []).append(e["ph"])
    for fid, phs in flows.items():
        assert phs[0] == "s" and phs[-1] == "f", (fid, phs)
        assert phs.count("s") == 1 and phs.count("f") == 1


def test_export_prunes_dead_thread_names():
    """A long-lived tracer must not accumulate thread_name rows for
    threads whose events the ring already evicted (fresh pipeline
    threads get fresh tids every run — the map would otherwise grow
    without bound)."""
    tr = obs.install(ring=8)

    def emit(label):
        threading.current_thread().name = label
        tr.instant("tick")

    for i in range(6):
        t = threading.Thread(target=emit, args=(f"dead-{i}",))
        t.start()
        t.join()
    # flood the ring from this thread: the dead threads' events evict
    for _ in range(16):
        tr.instant("flood")
    doc = tr.export()
    named = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert not any(n.startswith("dead-") for n in named)
    assert len(tr._thread_names) == 1  # only the flooding thread


def test_arm_from_env_tolerates_empty_ring_var(monkeypatch):
    monkeypatch.setenv("CORETH_TRACE", "1")
    monkeypatch.setenv("CORETH_TRACE_RING", "")
    t = obs.arm_from_env()
    assert t is not None and t.ring_size == 65536


def test_trace_out_written_and_loadable(tmp_path, monkeypatch):
    out = tmp_path / "trace.json"
    monkeypatch.setenv("CORETH_TRACE_OUT", str(out))
    _tr, rep, _blocks = _traced_stream(4, 4)
    assert rep.blocks == 4
    doc = json.loads(out.read_text())
    assert doc["traceEvents"], "export must be Perfetto-loadable"


def test_arm_from_env_installs_once(monkeypatch):
    monkeypatch.setenv("CORETH_TRACE", "1")
    monkeypatch.setenv("CORETH_TRACE_RING", "128")
    t1 = obs.arm_from_env()
    t2 = obs.arm_from_env()
    assert t1 is t2 is obs.tracer()
    assert t1.ring_size == 128


# ------------------------------------------------- obs/export_fail

def test_export_fail_fault_counted_pipeline_unharmed(tmp_path,
                                                     monkeypatch):
    """The obs/export_fail point: the trace-file write fails mid-
    export — the streaming run still completes on the right root, and
    the failure is counted instead of raised."""
    out = tmp_path / "trace.json"
    monkeypatch.setenv("CORETH_TRACE_OUT", str(out))
    with faults.armed(FaultPlan({"obs/export_fail": FaultSpec()})):
        tr, rep, blocks = _traced_stream(4, 4)
    assert rep.blocks == len(blocks)       # pipeline unharmed
    assert tr.export_failures == 1         # failure counted
    assert not out.exists()                # and nothing half-written


# ------------------------------------------------- telemetry endpoint

class _GatedFeed(BlockFeed):
    """Serves ``blocks``, parking after ``gate_after`` of them until
    ``gate`` is set — so the endpoint test scrapes a DETERMINISTICALLY
    live run instead of racing the stream's tail."""

    def __init__(self, blocks, gate_after, gate):
        self._blocks = blocks
        self._i = 0
        self._gate_after = gate_after
        self._gate = gate

    def next_block(self, timeout):
        if self._i >= len(self._blocks):
            raise FeedExhausted
        if self._i >= self._gate_after and not self._gate.is_set():
            if not self._gate.wait(timeout):
                return None
        b = self._blocks[self._i]
        self._i += 1
        return b


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode("utf-8")


def test_endpoint_scrapes_live_streaming_run(monkeypatch):
    """CORETH_TELEMETRY_PORT=0: /metrics, /trace, and /report answer
    WHILE the stream runs; the listener is gone after run()."""
    monkeypatch.setenv("CORETH_TELEMETRY_PORT", "0")
    obs.install()
    genesis, blocks = build_transfer_chain(6, 4)
    eng, _ = _fresh_engine(genesis)
    gate = threading.Event()
    pipe = StreamingPipeline(eng, _GatedFeed(list(blocks), 3, gate),
                             window_wait=0.005)
    out = {}

    def drive():
        out["rep"] = pipe.run()

    t = threading.Thread(target=drive)
    t.start()
    try:
        deadline = 10.0
        import time as _t
        t0 = _t.monotonic()
        while pipe._telemetry is None or pipe._telemetry.port is None:
            assert _t.monotonic() - t0 < deadline, "server never started"
            _t.sleep(0.01)
        port = pipe._telemetry.port
        base = f"http://127.0.0.1:{port}"
        metrics = _get(f"{base}/metrics")
        assert "# TYPE" in metrics
        trace = json.loads(_get(f"{base}/trace"))
        assert "traceEvents" in trace and trace["traceEvents"]
        report = json.loads(_get(f"{base}/report"))
        assert "enqueued_blocks" in report
        assert report["enqueued_blocks"] >= 1
        with pytest.raises(urllib.error.HTTPError):
            _get(f"{base}/nope")
    finally:
        gate.set()
        t.join(timeout=30)
    rep = out["rep"]
    assert eng.root == blocks[-1].header.root
    assert rep.blocks == len(blocks)
    assert pipe._telemetry is None  # stopped in run()'s finally
    with pytest.raises(Exception):
        _get(f"http://127.0.0.1:{port}/metrics")


# --------------------------------------------- supervisor transitions

def test_supervisor_last_transition_record():
    from coreth_tpu.replay.supervisor import BackendSupervisor
    t = [0.0]
    sup = BackendSupervisor(clock=lambda: t[0], sleep=lambda s: None)
    sup.strikes_to_demote = 1
    sup.max_retries = 0
    assert sup.snapshot()["last_transition"] is None
    sup.strike("device", RuntimeError("boom"))
    lt = sup.snapshot()["last_transition"]
    assert lt == {"kind": "demote", "scope": "device", "at_s": 0.0}
    # cooldown lapses; a successful probe re-promotes
    t[0] = sup.cooldown + 1
    sup.note_ok("device")
    lt = sup.snapshot()["last_transition"]
    assert lt["kind"] == "promote" and lt["scope"] == "device"
    assert lt["at_s"] == t[0]


def test_supervisor_transitions_reach_event_stream():
    from coreth_tpu.replay.supervisor import BackendSupervisor
    tr = obs.install()
    t = [0.0]
    sup = BackendSupervisor(clock=lambda: t[0], sleep=lambda s: None)
    sup.strikes_to_demote = 1
    sup.strike("native", RuntimeError("boom"))
    t[0] = sup.cooldown + 1
    sup.note_ok("native")
    names = [e["name"] for e in tr.export()["traceEvents"]]
    assert "supervisor/demote" in names
    assert "supervisor/promote" in names

"""The self-time accounts, one a thread (coreth_tpu/obs/account.py).

Five surfaces under test:

1. the account itself on a hand-driven clock: nested phases sum to its
   age EXACTLY, a phase's self time excludes its children, ``switch``
   is one boundary, an exception's open phases unwind at ``end``, a
   public call from another thread gets the null account;
2. the in-flight count: which phase the host was in while the device
   had nothing to do (``starved_s``), in-order retirement by a later
   ticket, a discarded speculative window;
3. the three sinks of one site: with the tracer OFF no ring event, no
   ``TraceAnnotation`` and no contextvar write; ARMED, every phase is
   an ``X`` event with ``id``/``parent``, spans nest under phases, the
   per-block phases open no annotation, and ``self_times`` takes
   children out of their parents;
4. the engine: on the benchmark's toy chain every phase of the
   transfer path is entered, the phases sum to the wall around the
   calls, ``sender/*`` and ``window/*`` add up to ``stats.t_sender``
   and ``stats.t_device`` (whose lines are untouched), the device
   recovery's wait is told from its host finish, the streaming report
   carries the account, and the jitted steps carry their scope names;
5. the other threads of a pass: CPU seconds beside the wall seconds
   (a sleep reads none, a spin reads its own), a thread's own account
   (``thread_account``), roles in the registry, the recovery worker's
   ``sender/native`` a segment, the serve pipeline's feed and prefetch
   accounts and the execute thread's ``stream/wait``.

No host-clock assertion beyond "two readings of one interval agree".
"""

import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import numpy as np
import pytest

from coreth_tpu import obs
from coreth_tpu.obs import account as A
from coreth_tpu.obs import trace as T


class Clock:
    """A clock the test moves by hand (dyadic steps: sums are exact)."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def step(self, dt):
        self.t += dt


@pytest.fixture(autouse=True)
def _no_tracer_leaks():
    obs.uninstall()
    yield
    obs.uninstall()


def fresh(clock=None, **kw):
    clock = clock or Clock()
    dev = A.InFlight()
    return clock, dev, A.Account(clock=clock, device=dev, register=False,
                                 **kw)


# ---------------------------------------------------------- the account
def test_nested_phases_sum_to_age_and_self_excludes_children():
    clock, _dev, acct = fresh()
    clock.step(0.5)                    # idle
    tok = acct.begin()
    clock.step(1.0)                    # loop
    acct.enter("outer")
    clock.step(2.0)                    # outer, before the child
    with acct.enter("inner"):
        clock.step(4.0)                # inner
    clock.step(0.25)                   # outer, after the child
    acct.exit()
    clock.step(0.125)                  # loop again
    acct.end(tok)
    clock.step(8.0)                    # idle, not charged yet
    row = acct.row()
    assert row["self_s"] == {"idle": 0.5, "loop": 1.125, "outer": 2.25,
                             "inner": 4.0}
    assert row["n"] == {"idle": 1, "loop": 1, "outer": 1, "inner": 1}
    assert sum(row["self_s"].values()) == row["t_last"] - row["t_open"]
    # the next boundary charges the idle stretch: still exact
    acct.end(acct.begin())
    row = acct.row()
    assert row["self_s"]["idle"] == 8.5 and row["n"]["loop"] == 2
    assert sum(row["self_s"].values()) == row["t_last"] - row["t_open"]


def test_switch_is_one_boundary_and_replaces_the_top():
    clock, _dev, acct = fresh()
    reads = []
    acct._clock = lambda: (reads.append(1), clock.t)[1]
    tok = acct.begin()
    acct.enter("a")
    clock.step(1.0)
    n0 = len(reads)
    acct.switch("b")
    assert len(reads) == n0 + 1
    clock.step(2.0)
    acct.exit()
    acct.end(tok)
    row = acct.row()
    assert row["self_s"]["a"] == 1.0 and row["self_s"]["b"] == 2.0
    assert row["self_s"]["loop"] == 0.0


def test_move_reclassifies_seconds_and_keeps_the_sum():
    """Staging inside ``validate``: timed by the engine's own clock
    pairs, moved over once a window, starved part in proportion."""
    clock, dev, acct = fresh()
    tok = acct.begin()
    acct.enter("validate")
    clock.step(2.0)                    # nothing in flight: starved
    ticket = dev.issue(acct)
    clock.step(2.0)                    # in flight
    acct.move("validate", "commit/stage", 1.0, entries=16)
    dev.done(ticket, acct)
    acct.exit()
    acct.move("validate", "commit/stage", 0.0)        # nothing to move
    acct.move("never-entered", "commit/stage", 1.0)   # nor from nowhere
    acct.end(tok)
    row = acct.row()
    assert row["self_s"]["validate"] == 3.0
    assert row["self_s"]["commit/stage"] == 1.0
    assert row["starved_s"]["validate"] == 1.5
    assert row["starved_s"]["commit/stage"] == 0.5
    assert row["n"]["commit/stage"] == 16 and row["n"]["validate"] == 1
    assert sum(row["self_s"].values()) == row["t_last"] - row["t_open"]
    acct.move("validate", "commit/stage", 99.0)       # never below zero
    assert acct.row()["self_s"]["validate"] == 0.0


def test_end_unwinds_phases_an_exception_left_open():
    clock, _dev, acct = fresh()
    tok = acct.begin()
    try:
        acct.enter("a")
        acct.enter("b")
        clock.step(1.0)
        raise RuntimeError("mid-phase")
    except RuntimeError:
        pass
    acct.end(tok)
    clock.step(2.0)
    tok = acct.begin()     # back at the root: idle gets the 2 s
    acct.end(tok)
    row = acct.row()
    assert row["self_s"]["b"] == 1.0 and row["self_s"]["idle"] == 2.0
    assert sum(row["self_s"].values()) == row["t_last"] - row["t_open"]


def test_public_call_from_another_thread_is_not_this_accounts():
    _clock, _dev, acct = fresh()
    tok = acct.begin()
    assert acct.begin() == 0           # nested on the owner's thread
    seen = {}

    def foreign():
        seen["tok"] = acct.begin()
        acct.end(seen["tok"])
        seen["current"] = A.current()

    th = threading.Thread(target=foreign)
    th.start()
    th.join(10)
    assert not th.is_alive()
    assert seen == {"tok": -1, "current": None}
    assert A.current() is acct
    acct.end(tok)
    assert A.current() is None and acct.row()["n"]["loop"] == 1
    # the null account takes every site and records nothing
    with obs.NULL_ACCOUNT.enter("x"):
        obs.NULL_ACCOUNT.switch("y")
        obs.NULL_ACCOUNT.tick()


# --------------------------------------------- CPU seconds, thread accounts
def test_cpu_seconds_are_charged_at_marks_on_hand_clocks():
    """``mark_cpu`` charges the CPU seconds since the last mark to the
    phase on top: exact for a phase marked at both ends, one lump for
    the phases between two marks; the first mark of a claim only
    starts the count (the stretch before was the caller's, maybe
    another thread's); ``move`` moves wall seconds only."""
    cpu = Clock(7.0)
    clock, _dev, acct = fresh(cpu_clock=cpu)
    assert acct.row()["cpu_s"] is None          # never marked
    clock.step(4.0)
    cpu.step(64.0)                     # another thread's clock, say
    tok = acct.begin()
    acct.mark_cpu()                    # starts the count: charges none
    clock.step(1.0)
    cpu.step(0.5)                      # loop: ran half of it
    acct.mark_cpu()
    with acct.enter("wait"):
        clock.step(2.0)                # blocked: no CPU
        acct.mark_cpu()
    with acct.enter("a"):
        clock.step(2.0)
        cpu.step(2.0)
        acct.move("a", "moved", 0.5, entries=3)
    with acct.enter("b"):
        clock.step(1.0)
        cpu.step(0.25)
    acct.mark_cpu()                    # a + b as one, to the top: loop
    acct.end(tok)
    row = acct.row()
    assert row["cpu_s"] == {"idle": 0.0, "loop": 2.75, "wait": 0.0,
                            "a": 0.0, "moved": 0.0, "b": 0.0}
    assert row["self_s"] == {"idle": 4.0, "loop": 1.0, "wait": 2.0,
                             "a": 1.5, "moved": 0.5, "b": 1.0}
    assert sum(row["self_s"].values()) == row["t_last"] - row["t_open"]
    assert row["role"] == "replay"
    assert row["thread"] == threading.current_thread().name
    # the next claim starts its own count
    cpu.step(100.0)
    tok = acct.begin()
    acct.mark_cpu()
    acct.end(tok)
    assert sum(acct.row()["cpu_s"].values()) == 2.75


def test_no_boundary_reads_the_cpu_clock():
    """The CPU clock is a system call (5.6 us on the chip's host): a
    boundary never reads it, a mark reads it once."""
    reads = []
    _clock, _dev, acct = fresh(
        cpu_clock=lambda: (reads.append(1), 0.0)[1])
    tok = acct.begin()
    with acct.enter("a"):
        acct.switch("b")
    acct.tick()
    acct.move("b", "c", 0.0)
    acct.end(tok)
    assert reads == [] and acct.row()["cpu_s"] is None
    acct.mark_cpu()
    assert reads == [1] and set(acct.row()["cpu_s"]) == set(
        acct.row()["self_s"])
    obs.NULL_ACCOUNT.mark_cpu()        # a no-op there, like the rest


def test_a_sleep_reads_no_cpu_and_a_spin_reads_its_own():
    """On the real clocks, marked at both ends of each phase:
    ``cpu_s <= self_s`` by phase, a phase that sleeps ran nothing, one
    that spins until the thread has burnt 50 ms reads those 50 ms."""
    acct = A.Account(role="test", register=False)
    acct.mark_cpu()
    with acct.enter("sleep"):
        time.sleep(0.05)
        acct.mark_cpu()
    with acct.enter("spin"):
        until = time.thread_time() + 0.05
        while time.thread_time() < until:
            pass
        acct.mark_cpu()
    row = acct.row()
    assert sum(row["self_s"].values()) == pytest.approx(
        row["t_last"] - row["t_open"], rel=1e-9)
    for phase, wall in row["self_s"].items():
        assert row["cpu_s"][phase] <= wall + 1e-4, phase
    assert row["self_s"]["sleep"] >= 0.05
    assert row["cpu_s"]["sleep"] < 0.01
    assert 0.05 <= row["cpu_s"]["spin"] < 0.06


def test_thread_account_is_the_threads_own():
    """``thread_account``: opened by the thread itself, found by
    ``current()`` there and nowhere else, nested ``begin`` is its
    own, and its phases sum to its age."""
    seen = {}

    def worker():
        acct = obs.thread_account("recover")
        seen["acct"] = acct
        seen["current"] = obs.current() is acct
        seen["begin"] = acct.begin()   # the owner's: nested, no claim
        acct.mark_cpu()
        with acct.enter("sender/native"):
            time.sleep(0.01)
            acct.mark_cpu()
        acct.end(seen["begin"])
        seen["still"] = obs.current() is acct

    t0 = time.monotonic()
    th = threading.Thread(target=worker, name="a-worker")
    th.start()
    th.join(10)
    assert not th.is_alive()
    acct = seen["acct"]
    assert seen["current"] and seen["still"] and seen["begin"] == 0
    assert obs.current() is None       # not this thread's
    assert acct.begin() == -1          # nor this thread's to claim
    row = acct.row()
    assert row["role"] == "recover" and row["thread"] == "a-worker"
    assert t0 <= row["t_open"] <= time.monotonic()
    assert row["n"] == {"idle": 1, "sender/native": 1}
    assert sum(row["self_s"].values()) == pytest.approx(
        row["t_last"] - row["t_open"], rel=1e-9)
    assert row["cpu_s"]["sender/native"] <= row["self_s"]["sender/native"]


def test_accounts_between_by_role():
    """Without ``role`` the registry answers what it answered before
    the other threads had accounts: the engines' alone."""
    t0 = time.monotonic()
    engine = A.Account()
    others = [A.Account(role=r) for r in ("recover", "feed", "prefetch")]
    t1 = time.monotonic()
    assert A.accounts_between(t0, t1) == [engine]
    assert A.accounts_between(t0, t1, role="replay") == [engine]
    assert A.accounts_between(t0, t1, role="feed") == [others[1]]
    assert A.accounts_between(t0, t1, role=None) == [engine] + others


# ------------------------------------------------------ device in flight
def test_starved_is_the_time_nothing_was_in_flight():
    """A 1 s empty, issue, B 2 s, done, C 1 s -> starved {A: 1, C: 1}."""
    clock, dev, acct = fresh()
    tok = acct.begin()
    acct.enter("A")
    clock.step(1.0)
    ticket = dev.issue(acct)
    acct.switch("B")
    clock.step(2.0)
    dev.done(ticket, acct)
    acct.switch("C")
    clock.step(1.0)
    acct.exit()
    acct.end(tok)
    row = acct.row()
    starved = {k: v for k, v in row["starved_s"].items() if v}
    assert starved == {"A": 1.0, "C": 1.0}
    assert row["self_s"]["B"] == 2.0
    assert dev.in_flight == 0 and not dev.busy


def test_issue_and_done_tick_inside_one_phase():
    """The in-flight count changes mid-phase: each side of the change
    is charged by what was in flight THEN (no boundary, no fault)."""
    clock, dev, acct = fresh()
    tok = acct.begin()
    acct.enter("machine")
    clock.step(1.0)                    # empty
    ticket = dev.issue(acct)
    clock.step(4.0)                    # in flight
    dev.done(ticket, acct)
    clock.step(2.0)                    # empty again
    acct.exit()
    acct.end(tok)
    row = acct.row()
    assert row["self_s"]["machine"] == 7.0
    assert row["starved_s"]["machine"] == 3.0


def test_a_later_ticket_retires_the_earlier_ones():
    clock, dev, acct = fresh()
    tok = acct.begin()
    first = dev.issue(acct)
    discarded = dev.issue(acct)        # a speculative window, dropped
    last = dev.issue(acct)
    assert (first, discarded, last) == (1, 2, 3) and dev.in_flight == 3
    dev.done(first, acct)
    assert dev.in_flight == 2 and dev.busy
    dev.done(last, acct)               # in order: 2 finished before 3
    assert dev.in_flight == 0 and not dev.busy
    dev.done(discarded, acct)          # late or never: no effect
    dev.done(None, acct)               # a dispatch that got no ticket
    assert dev.in_flight == 0 and dev.retired == 3
    clock.step(1.0)
    acct.end(tok)
    assert acct.row()["starved_s"]["loop"] == 1.0


def test_device_seam_without_an_engine_ticks_the_threads_account():
    """evm/device/adapter.py has no engine at hand: ``issue()`` with no
    account ticks the one whose public call runs on this thread."""
    clock, dev, acct = fresh()
    tok = acct.begin()
    acct.enter("machine")
    clock.step(1.0)
    ticket = dev.issue()               # finds acct through current()
    clock.step(2.0)
    dev.done(ticket)
    acct.exit()
    acct.end(tok)
    assert acct.row()["starved_s"]["machine"] == 1.0
    assert dev.issue() == 2            # no account anywhere: no fault


# ------------------------------------------------------------- registry
def test_accounts_between_bounds_and_cap():
    t0 = time.monotonic()
    first = A.Account()
    t1 = time.monotonic()
    second = A.Account()
    t2 = time.monotonic()
    assert A.accounts_between(t0, t1) == [first]
    assert A.accounts_between(t0, t2) == [first, second]
    assert A.accounts_between(first.t_open, first.t_open) == [first]
    assert A.accounts_between(t2 + 1.0, t2 + 2.0) == []
    hidden = A.Account(register=False)
    assert hidden not in A.accounts_between(t0, time.monotonic())
    for _ in range(A._ACCOUNTS.maxlen):
        A.Account()
    assert len(A._ACCOUNTS) == A._ACCOUNTS.maxlen == 256
    assert A.accounts_between(t0, t2) == []    # the oldest fell out


# ------------------------------------------------- three sinks, one site
class _CountingAnnotation:
    made = []

    def __init__(self, name):
        type(self).made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _CountingAnnotation)
    return _CountingAnnotation.made


def _drive_phases(acct):
    tok = acct.begin()
    with acct.enter("window/prepare"):
        acct.switch("window/dispatch")
    with acct.enter("validate"):
        acct.switch("commit/stage")
    with acct.enter("classify"):
        pass
    acct.end(tok)


def test_tracer_off_no_ring_no_annotation_no_contextvar(annotations,
                                                        monkeypatch):
    writes = []
    real_set = T.PARENT.set

    class Watched:
        def get(self):
            return T.PARENT.get()

        def set(self, v):
            writes.append(v)
            return real_set(v)

    _clock, dev, acct = fresh()
    monkeypatch.setattr(A._trace, "PARENT", Watched())
    assert obs.tracer() is None
    _drive_phases(acct)
    ticket = dev.issue(acct)
    dev.done(ticket, acct)
    assert annotations == [] and writes == [] and acct._frames == []
    assert obs.tracer() is None        # and nothing installed one
    assert acct.row()["n"]["window/dispatch"] == 1


def test_tracer_armed_phases_are_events_with_parents(annotations):
    tr = obs.install()
    _clock, dev, acct = fresh()
    tok = acct.begin()
    with acct.enter("machine"):
        with obs.span("machine/window_issue", blocks=2):
            ticket = dev.issue(acct)
        dev.done(ticket, acct)
    with acct.enter("validate"):
        acct.switch("commit/stage")
    acct.end(tok)
    evs = tr.export()["traceEvents"]
    by_name = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(by_name) == {"loop", "machine", "machine/window_issue",
                            "validate", "commit/stage"}
    loop, machine = by_name["loop"], by_name["machine"]
    assert loop["args"]["parent"] is None
    assert machine["args"]["parent"] == loop["args"]["id"]
    # a span nests under the phase that caused it, a phase under a span
    assert by_name["machine/window_issue"]["args"]["parent"] \
        == machine["args"]["id"]
    assert by_name["machine/window_issue"]["args"]["blocks"] == 2
    assert by_name["commit/stage"]["args"]["parent"] \
        == loop["args"]["id"]
    ids = [e["args"]["id"] for e in by_name.values()]
    assert len(set(ids)) == len(ids)
    # the device seams emit the instants they replaced
    instants = [e["name"] for e in evs if e["ph"] == "i"]
    assert instants == ["device/dispatch", "device/result_fetch"]
    # every phase reaches the profiler's host plane (none recurs per
    # block: 30,000 a pass would drown stop_trace)
    assert annotations == ["coreth/loop", "coreth/machine",
                           "coreth/validate", "coreth/commit/stage"]
    assert T.PARENT.get() is None and acct._frames == []


def test_a_phase_open_when_the_tracer_arms_closes_quietly(annotations):
    _clock, _dev, acct = fresh()
    tok = acct.begin()
    acct.enter("early")
    tr = obs.install()
    with acct.enter("late"):
        pass
    acct.exit()                        # "early" has no frame: no event
    acct.end(tok)
    names = [e["name"] for e in tr.export()["traceEvents"]
             if e["ph"] == "X"]
    assert names == ["late"] and acct._frames == []


def test_self_times_on_a_hand_made_export():
    def x(name, dur, sid=None, parent=None):
        args = {} if sid is None else {"id": sid, "parent": parent}
        return {"ph": "X", "name": name, "ts": 0, "dur": dur,
                "args": args}
    events = [
        x("replay", 10_000_000, 1),
        x("window", 4_000_000, 2, 1), x("window", 3_000_000, 3, 1),
        x("flush", 1_000_000, 4, 2),
        x("old-span", 2_000_000),                  # no id: whole
        {"ph": "i", "name": "tick", "ts": 5},      # not a span
        x("orphan", 500_000, 9, 77),               # parent evicted
    ]
    assert obs.self_times(events) == {
        "replay": 3.0, "window": 6.0, "flush": 1.0, "old-span": 2.0,
        "orphan": 0.5}


def test_self_times_never_count_a_second_twice():
    """What bench.py's shares now read: over a real nested export the
    self times sum to the root's duration."""
    clock = Clock()
    tr = obs.install(T.SpanTracer(clock=clock))
    with tr.span("root"):
        clock.step(1.0)
        with tr.span("child"):
            clock.step(2.0)
            with tr.span("leaf"):
                clock.step(4.0)
        clock.step(0.5)
    evs = tr.export()["traceEvents"]
    root = next(e for e in evs if e["name"] == "root")
    own = obs.self_times(evs)
    assert own == {"root": 1.5, "child": 2.0, "leaf": 4.0}
    assert sum(own.values()) == root["dur"] / 1e6


# ------------------------------------------------------------ the engine
TOY_BLOCKS = 7
SEED = 2**31 + 29


@pytest.fixture(scope="module")
def toy_chain():
    """The benchmark's p2p chain at toy size, built in this process."""
    import copy
    from benchlib import chains, names
    _cell, _entry, config, traffic = names.resolve_cell(
        names.load_spec(), "p2p-1k.catchup")
    config = copy.deepcopy(config)
    config["chain_blocks"] = TOY_BLOCKS
    config["chain"]["accounts"] = 16
    config["txs_per_block"] = 8
    genesis, wire = chains.build_wire(config, traffic, SEED)
    return genesis, wire


ENGINE_KW = dict(batch_pad=8, window=2, capacity=256, slot_capacity=64)

TRANSFER_PHASES = (
    "engine/build", "loop", "sender/pack", "sender/native",
    "sender/wait_host", "sender/apply", "classify", "window/prepare", "window/upload",
    "window/dispatch", "window/fetch_wait", "validate", "commit/stage",
    "commit/flush")


def _pass(genesis, wire):
    from benchlib import replay_pass
    from coreth_tpu.types import Block
    blocks = [Block.decode(w) for w in wire]
    engine = replay_pass.fresh_engine(genesis, ENGINE_KW)
    built = engine.account.row()
    t0 = time.monotonic()
    replay_pass.run_engine(engine, blocks)
    wall = time.monotonic() - t0
    assert engine.root == blocks[-1].header.root
    return engine, built, wall


def _busy(row):
    return sum(v for k, v in row["self_s"].items() if k != "idle")


def test_engine_accounts_for_its_whole_replay(toy_chain):
    _pass(*toy_chain)                  # compiles: not the pass measured
    engine, built, wall = _pass(*toy_chain)
    row = engine.account.row()
    assert engine.account in obs.accounts_between(
        row["t_open"], row["t_open"])
    assert row["cpu_s"] is None        # batch replay marks no CPU
    for phase in TRANSFER_PHASES:
        assert row["n"].get(phase, 0) > 0, phase
    assert row["n"]["engine/build"] == 1
    assert row["n"]["loop"] == 2       # replay_block + replay
    windows = row["n"]["window/fetch_wait"]
    assert row["n"]["window/dispatch"] == windows
    # no boundary recurs per block: one validate and one classify
    # phase a window, and the blocks' staging seconds (t_trie's own
    # clock pairs) moved to commit/stage once a window
    assert row["n"]["validate"] == windows
    assert row["n"]["commit/stage"] == TOY_BLOCKS \
        == engine.stats.blocks_device
    assert row["n"]["classify"] <= windows + 2
    assert 0 < row["self_s"]["commit/stage"] < engine.stats.t_trie
    assert row["self_s"]["commit/stage"] + row["self_s"]["commit/flush"] \
        >= engine.stats.t_trie
    assert "fallback" not in row["n"] and "machine" not in row["n"]
    # every instant belongs to one phase
    assert sum(row["self_s"].values()) == pytest.approx(
        row["t_last"] - row["t_open"], rel=1e-9)
    # the phases of the two calls are the wall around them
    assert _busy(row) - _busy(built) == pytest.approx(wall, rel=0.01)
    # and the account splits what ReplayStats sums, it does not move it
    # (absolute room: a thread switch between the two clock reads of
    # one site)
    st = engine.stats

    def parts(prefix):
        return sum(v for k, v in row["self_s"].items()
                   if k.startswith(prefix))
    assert parts("sender/") == pytest.approx(st.t_sender, rel=0.02,
                                             abs=1e-3)
    assert parts("window/") == pytest.approx(st.t_device, rel=0.02,
                                             abs=1e-3)
    assert st.t_classify > 0 and st.t_trie > 0 and st.t_fallback == 0


def test_sender_recovery_issues_no_device_work(toy_chain, monkeypatch):
    """The sender pipeline's segments: packing and applying are work
    (``sender/pack``, ``sender/apply``), the worker's result is waited
    for in ``sender/wait_host``; the device phases are never entered
    and sender recovery puts nothing in flight on the device."""
    from coreth_tpu.crypto import native
    from coreth_tpu.replay import engine as E
    if native.load() is None:
        pytest.skip("no native library: no batch engine")
    seen = []
    real_issue = E._SenderPipeline._issue
    real_complete = E._SenderPipeline._complete

    def tickets(real):
        def around(self, s):
            before = A.DEVICE.issued
            real(self, s)
            seen.append(A.DEVICE.issued - before)
        return around

    monkeypatch.setattr(E._SenderPipeline, "_issue", tickets(real_issue))
    monkeypatch.setattr(E._SenderPipeline, "_complete",
                        tickets(real_complete))
    engine, _built, _wall = _pass(*toy_chain)
    row = engine.account.row()
    assert engine.stats.sigs_host > 0 and engine.stats.sigs_device == 0
    assert engine.stats.recover_degraded == 0
    for phase in ("sender/pack", "sender/wait_host", "sender/apply"):
        assert row["n"].get(phase, 0) > 0, phase
    assert "sender/issue_device" not in row["n"]
    assert "sender/wait_device" not in row["n"]
    assert seen and not any(seen)
    # the batches themselves: the lead block's on this thread
    # (replay_block -> warm_senders), every segment's on the worker,
    # whose account it opened itself inside the pass
    assert row["n"]["sender/native"] == 1
    (worker,) = obs.accounts_between(row["t_open"], row["t_last"],
                                     role="recover")
    wrow = worker.row()
    segments = row["n"]["sender/wait_host"]
    assert wrow["n"] == {"idle": 1, "sender/native": segments}
    assert wrow["thread"] != row["thread"]
    assert row["t_open"] < wrow["t_open"] < row["t_last"]
    assert 0 < wrow["self_s"]["sender/native"] \
        <= wrow["t_last"] - wrow["t_open"]
    assert wrow["cpu_s"]["sender/native"] > 0


def test_armed_tracer_rows_the_workers_batches_under_its_thread(
        toy_chain, annotations):
    """One sink for every thread: with the tracer armed the worker's
    ``sender/native`` is an event on the worker's own thread row and
    an annotation in the profiler's host plane, as the engine's are."""
    from coreth_tpu.crypto import native
    if native.load() is None:
        pytest.skip("no native library: no batch engine")
    tr = obs.install()
    engine, _built, _wall = _pass(*toy_chain)
    doc = tr.export()["traceEvents"]
    rows = {e["tid"]: e["args"]["name"] for e in doc
            if e["ph"] == "M" and e["name"] == "thread_name"}
    natives = [e for e in doc if e["ph"] == "X"
               and e["name"] == "sender/native"]
    by_thread = {}
    for e in natives:
        by_thread.setdefault(rows[e["tid"]], []).append(e)
    here = threading.current_thread().name
    (worker,) = [name for name in by_thread if name != here]
    assert worker.startswith("coreth-recover")
    assert len(by_thread[worker]) \
        == engine.account.row()["n"]["sender/wait_host"]
    assert len(by_thread[here]) == 1   # the lead block's
    assert annotations.count("coreth/sender/native") == len(natives)


def test_streaming_report_carries_the_account():
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from tests.test_serve import build_transfer_chain, _fresh_engine
    genesis, blocks = build_transfer_chain(4, 4)
    eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(eng, ChainFeed(list(blocks)),
                             window_wait=0.005)
    rep = pipe.run()
    assert eng.root == blocks[-1].header.root
    acct = rep.account
    assert set(acct) == {"role", "thread", "t_open", "t_last", "self_s",
                         "cpu_s", "n", "starved_s"}
    assert acct["role"] == "replay"
    assert acct["thread"] == threading.current_thread().name
    assert acct["n"]["loop"] == 1      # the execute stage, one claim
    for phase in ("classify", "window/dispatch", "window/fetch_wait",
                  "validate", "commit/flush"):
        assert acct["n"].get(phase, 0) > 0, phase
    assert pipe._live_report()["account"]["n"] == acct["n"]
    assert A.current() is None         # the claim was given back


def _streamed(n_blocks=12, rate=None, **kw):
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from tests.test_serve import build_transfer_chain, _fresh_engine
    from coreth_tpu.types import Block
    genesis, blocks = build_transfer_chain(n_blocks, 4)
    blocks = [Block.decode(b.encode()) for b in blocks]  # no senders
    eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(eng, ChainFeed(list(blocks), rate=rate),
                             window_wait=0.005, **kw)
    return eng, blocks, pipe


def test_stream_wait_takes_the_wait_for_blocks_out_of_loop():
    """``stream/wait`` is the execute thread's top-up loop, entered once
    a window and never once an item: it holds every second spent in
    ``_next_item`` — which read as ``loop`` before — and ``loop`` keeps
    what is left."""
    eng, blocks, pipe = _streamed(rate=200.0)
    waited = [0.0, 0]
    real = pipe._next_item

    def timed(idle):
        t0 = time.monotonic()
        try:
            return real(idle)
        finally:
            waited[0] += time.monotonic() - t0
            waited[1] += 1

    pipe._next_item = timed
    t0 = time.monotonic()
    rep = pipe.run()
    wall = time.monotonic() - t0
    assert eng.root == blocks[-1].header.root
    row = rep.account
    wait, loop = row["self_s"]["stream/wait"], row["self_s"]["loop"]
    # the same interval read twice: round each call, and round the loop
    assert waited[0] <= wait <= waited[0] + 0.05
    assert wait > loop                 # a paced feed: mostly waiting
    assert wait + loop < wall
    # once a window at most, not once an item
    assert 0 < row["n"]["stream/wait"] < waited[1]
    assert row["n"]["stream/wait"] <= len(blocks) + 2
    # a wait burns no CPU
    assert row["cpu_s"]["stream/wait"] < 0.5 * wait


def test_serve_threads_keep_accounts_and_the_report_reads_them():
    """The feed and prefetch threads open accounts of their own inside
    the run; ``feed_blocked_s`` / ``prefetch_blocked_s`` / ``overlap_s``
    are those accounts' phases; the engine's account holds no second
    thread's time."""
    tr = obs.install()                 # armed: the ring shows the rows
    t0 = time.monotonic()
    eng, blocks, pipe = _streamed(n_blocks=24, depth=4, commit_delay=0.02)
    t_run = time.monotonic()
    rep = pipe.run()
    t1 = time.monotonic()
    assert eng.root == blocks[-1].header.root
    by_role = {a.role: a.row()
               for a in obs.accounts_between(t0, t1, role=None)}
    assert set(by_role) == {"replay", "feed", "prefetch"}
    assert obs.accounts_between(t0, t1) == [eng.account]
    feed, pre = by_role["feed"], by_role["prefetch"]
    assert feed["thread"] == "serve-feed"
    assert pre["thread"] == "serve-prefetch"
    for row in (feed, pre):
        assert sum(row["self_s"].values()) == pytest.approx(
            row["t_last"] - row["t_open"], rel=1e-9)
        assert t_run < row["t_open"] < row["t_last"] <= t1
    # each thread's CPU seconds, marked once a window's worth of blocks
    for row in (feed, pre, by_role["replay"]):
        assert 0 < sum(row["cpu_s"].values()) \
            <= sum(row["self_s"].values()) + 1e-3
    assert {k for k, v in feed["cpu_s"].items() if v} == {"loop"}
    assert {k for k, v in pre["cpu_s"].items() if v} \
        <= {"prefetch/touch_code"}
    # the feed: two intervals a block by clock pairs, moved over
    assert set(feed["n"]) == {"idle", "loop", "feed/source", "feed/put"}
    assert feed["n"]["feed/put"] == len(blocks)
    assert feed["n"]["feed/source"] == len(blocks) + 1  # + exhausted
    assert feed["n"]["loop"] == 1
    # the prefetch thread: phases a CHUNK, sender/* inside touch_code
    chunks = pre["n"]["prefetch/touch_code"]
    assert 0 < chunks <= len(blocks)
    assert pre["n"]["prefetch/put"] == chunks
    assert pre["n"]["sender/native"] == pre["n"]["sender/pack"] \
        == pre["n"]["sender/apply"] <= chunks
    assert "sender/native" not in rep.account["n"]
    # the report's numbers are the accounts'
    bp, pf = rep.backpressure, rep.prefetch
    assert bp["feed_blocked_s"] == round(feed["self_s"]["feed/put"], 3)
    assert bp["feed_blocked_s"] > 0    # a slow commit parks the feed
    assert bp["prefetch_blocked_s"] \
        == round(pre["self_s"]["prefetch/put"], 3)
    warm = sum(v for k, v in pre["self_s"].items()
               if k.startswith("sender/") or k == "prefetch/touch_code")
    assert pf["overlap_s"] == rep.stages_s["prefetch"] == round(warm, 3)
    # armed, the chunk phases are in the ring under the threads' own
    # rows; the per-block intervals of the feed are not events at all
    doc = tr.export()["traceEvents"]
    rows = {e["tid"]: e["args"]["name"] for e in doc
            if e["ph"] == "M" and e["name"] == "thread_name"}
    where = {}
    for e in doc:
        if e["ph"] == "X":
            where.setdefault(e["name"], set()).add(rows[e["tid"]])
    assert where["prefetch/touch_code"] == {"serve-prefetch"}
    assert where["sender/native"] == {"serve-prefetch"}
    assert where["stream/wait"] == {threading.current_thread().name}
    assert "feed/source" not in where and "feed/put" not in where


# ------------------------------------------------- scopes on the kernels
def _lower_transfer_window():
    from coreth_tpu.replay import engine as E
    i32 = np.int32
    # the entry the engine calls: (K, pad, t_pad, s_pad, L, SL) cut
    # from one staging buffer
    dims = (2, 8, 16, 8, 16, 8)
    return E._transfer_window_packed.lower(
        np.zeros((64, 16), i32), np.zeros((64,), i32),
        np.zeros((8, 16), i32), np.zeros((E.window_words(dims),), i32),
        dims=dims)


def _lower_recover_kernel():
    from coreth_tpu.ops import secp
    return secp.recover_kernel.lower(
        np.zeros((64, 33), np.uint8), np.zeros((64,), np.int32),
        np.zeros((64, 8), np.int32), np.zeros((64, 8), np.int32))


def _lower_occ_machine():
    from coreth_tpu.evm.device import machine as M
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    p = M.MachineParams(fork="durango", batch=4, code_cap=64,
                        data_cap=32, scache_cap=4,
                        features=frozenset(["storage"]))
    occ = M.OccParams(blocks=2, table_cap=64, rounds=5)
    runner = MachineWindowRunner("durango", lambda _c, _k: 0)
    return M.get_occ_machine(p, occ, ()).lower(
        *runner._warm_args(p, occ))


@pytest.mark.parametrize("lower,scope", [
    (_lower_transfer_window, "coreth/transfer_step"),
    (_lower_recover_kernel, "coreth/recover_ladder"),
    (_lower_occ_machine, "coreth/occ_round"),
], ids=["transfer_window", "recover_kernel", "occ_run"])
def test_jitted_steps_carry_their_scope_names(lower, scope):
    """A kernel is found in a device trace by its scope, not by
    ``jit_<function>(<hash>)``: the lowered text holds the name."""
    assert scope in lower().as_text(debug_info=True)

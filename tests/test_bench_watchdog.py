"""Bench emission is unconditional.

A bench run once exited rc 124 with NO JSON despite the in-process
watchdog thread: a wedged section holding the GIL starves every Python
thread, the timer included.  bench.py now (a) flushes incremental per-section
state and (b) runs a child-process watchdog that SIGKILLs a wedged
parent at the deadline and prints the recorded state as the stdout
JSON line itself.  These tests wedge bench.py deliberately — including
inside a C call that never releases the GIL — and require a parseable
result line anyway.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_wedged(mode, deadline="14"):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", BENCH_WEDGE=mode,
               BENCH_DEADLINE=deadline)
    # generous outer timeout: the wedge fires right after imports, so
    # the run costs ~deadline + interpreter/jax startup
    return subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=180)


def _last_json_line(stdout):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert lines, "no output at all"
    return json.loads(lines[-1])


def test_gil_wedged_section_still_yields_json_line():
    """The worst case, which once cost a run its line: the main thread
    stuck inside a C call that never releases the GIL.  The in-process
    timer thread cannot run; the CHILD watchdog must SIGKILL the
    parent and print the recorded state as a parseable stdout line."""
    r = _run_wedged("gil")
    assert r.returncode != 0  # parent was killed, not graceful
    obj = _last_json_line(r.stdout)
    assert obj["metric"] == "transfer_replay_throughput"
    assert obj["unit"] == "txs/s"
    assert obj.get("watchdog") == "child", obj


def test_gilfree_wedge_served_by_inprocess_watchdog():
    """A GIL-free wedge (main thread parked on an Event) is handled by
    the faster in-process timer: the line prints before the child
    deadline and the process exits itself (os._exit(0))."""
    r = _run_wedged("event")
    assert r.returncode == 0, r.stdout + r.stderr
    obj = _last_json_line(r.stdout)
    assert obj["metric"] == "transfer_replay_throughput"
    assert "watchdog" not in obj  # in-process path, not the child
    assert obj.get("elapsed_s") is not None

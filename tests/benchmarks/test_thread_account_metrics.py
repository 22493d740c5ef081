"""The readers of the accounts of every thread a pass waits on (tier-1).

The five readers on a hand-made ``run`` whose passes have planted
accounts of four roles (hand-moved wall AND CPU clocks, so every
expected number is exact); None — never 0 — where the program's
accounts have no roles, a pass has no account, or there is no pass;
and the older account readers reading from such a run exactly what
they read with the engines' accounts alone.
"""

import itertools
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from benchlib import account, names, thread_account  # noqa: E402
from coreth_tpu import obs  # noqa: E402
from coreth_tpu.obs import account as A  # noqa: E402

SPEC = names.load_spec()
LISTED = {m["name"]: m for m in SPEC["per_layer"]}
CELLS = [w["name"] for w in SPEC["workloads"]]
CATCHUP = [c for c in CELLS if c.endswith(".catchup")]

# a clock of its own stretch for every hand-made run (planted accounts
# stay in the program's registry for the life of the process), far
# below test_account_metrics.py's
_BASES = itertools.count(1)
WINDOW_S = 20.0
SIGS = 1000  # signatures a pass, by the pass rows' count


class Clock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t


class Planted:
    """One account on two hand clocks: ``spend`` puts ``wall`` seconds
    into a phase of which the thread ran ``cpu``, marked at both ends
    (so this planted thread's CPU seconds are exact by phase)."""

    def __init__(self, role, t_open):
        self.wall, self.cpu = Clock(t_open), Clock(0.0)
        self.acct = A.Account(role=role, clock=self.wall,
                              cpu_clock=self.cpu, device=A.InFlight())
        self.acct.mark_cpu()

    def spend(self, phase, wall, cpu):
        self.acct.mark_cpu()
        with self.acct.enter(phase):
            self.wall.t += wall
            self.cpu.t += cpu
            self.acct.mark_cpu()


def plant_pass(t_open: float) -> list:
    """One pass's four threads.  Wall / CPU seconds: the engine's
    thread loop 0.5 / 0.25, stream/wait 2.0 / 0, sender/pack 0.25 /
    0.25, sender/native 0.5 / 0.5, sender/wait_host 1.0 / 0, validate
    2.0 / 1.0, window/fetch_wait 0.5 / 0; the worker three segments of
    sender/native 0.75 / 0.5 with 0.25 idle between; the prefetch
    thread sender/native 1.0 / 0.25 and prefetch/wait 0.5 / 0; the feed
    loop 1.0 / 1.0 with 0.25 of it moved to feed/source."""
    eng = Planted("replay", t_open)
    tok = eng.acct.begin()
    eng.acct.mark_cpu()
    eng.wall.t += 0.5
    eng.cpu.t += 0.25
    eng.spend("stream/wait", 2.0, 0.0)
    eng.spend("sender/pack", 0.25, 0.25)
    eng.spend("sender/native", 0.5, 0.5)
    eng.spend("sender/wait_host", 1.0, 0.0)
    eng.spend("validate", 2.0, 1.0)
    eng.spend("window/fetch_wait", 0.5, 0.0)
    eng.acct.end(tok)
    worker = Planted("recover", t_open + 0.125)
    for _ in range(3):
        worker.wall.t += 0.25
        worker.spend("sender/native", 0.75, 0.5)
    pre = Planted("prefetch", t_open + 0.25)
    pre.spend("prefetch/wait", 0.5, 0.0)
    pre.spend("sender/native", 1.0, 0.25)
    feed = Planted("feed", t_open + 0.375)
    feed.spend("loop", 1.0, 1.0)
    feed.acct.move("loop", "feed/source", 0.25, entries=16)
    eng.acct.mark_cpu()
    return [p.acct for p in (eng, worker, pre, feed)]


def hand_made_run(with_accounts=True, cell=CELLS[0]):
    rows = []
    t0 = -1.0e6 - 1024.0 * next(_BASES)
    for i in range(2):
        t_start = t0 + 10.0 * i
        rows.append({"t_start": t_start, "t_end": t_start + 10.0,
                     "decode_s": 1.0, "engine_build_s": 0.75,
                     "t_sender": 2.5, "t_classify": 0.5,
                     "t_device": 2.0, "t_trie": 0.75,
                     "blocks_fallback": 0, "sigs_device": 0,
                     "sigs_host": SIGS})
    planted = [a for r in rows
               for a in plant_pass(r["t_start"] + r["decode_s"])] \
        if with_accounts else []
    by_name = {w["name"]: w for w in SPEC["workloads"]}
    run = {"spec": SPEC, "cell": by_name[cell], "passes": rows,
           "window_s": WINDOW_S, "setup_s": 1.0, "trace": None,
           "compile": {"compiles": 0}, "config": {}, "traffic": {}}
    return run, planted


# two passes of a 20 s window, each as ``plant_pass`` spends it
EXPECTED = {
    "recover_worker_busy_share_acct": 100 * 2 * 3 * 0.75 / 20,
    # the batch wherever it ran: replay 0.5, worker 2.25, prefetch 1.0
    "recover_us_per_sig": 1e6 * 2 * (0.5 + 2.25 + 1.0) / (2 * SIGS),
    "sender_native_share_acct": 100 * 2 * 0.5 / 20,
    "stream_wait_share_acct": 100 * 2 * 2.0 / 20,
    # wall less CPU over loop 0.25, pack 0, native 0, validate 1.0; the
    # waits (stream/wait, sender/wait_host, window/fetch_wait) left out
    "gil_wait_share_acct": 100 * 2 * 1.25 / 20,
}

WHERE = {
    "recover_worker_busy_share_acct": CATCHUP,
    "recover_us_per_sig": CELLS,
    "sender_native_share_acct": ["valuetx.bootstrap"],
    "stream_wait_share_acct": ["valuetx.tip"],
    "gil_wait_share_acct": ["valuetx.tip"],
}


def test_the_five_readers_are_listed():
    for name, cells in WHERE.items():
        m = LISTED[name]
        assert m["workloads"] == cells, name
        assert m["source"] == "program_span"
        assert m["unit"] == ("us" if name.endswith("_sig") else "%")
        # outside test_harness.py's sum-to-100 rule
        assert not name.endswith("_share")
        # ISSUE 40 has the two tip readers move block_latency_p90_ms;
        # test_tip_cell.py holds that list to six names by equality
        # and may not be edited by the PR that adds these (PERF.md §7):
        # they are listed as the execute thread's other readers are
        assert m["moves"] == "committed_txs_per_s"
    assert LISTED["stream_wait_share_acct"]["better"] == "higher"
    for name in ("stream_wait_share_acct", "gil_wait_share_acct"):
        assert LISTED[name]["layer"] == "serve pipeline"
    for name in ("recover_worker_busy_share_acct", "recover_us_per_sig",
                 "sender_native_share_acct"):
        assert LISTED[name]["layer"] == "sender recovery"


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_planted_accounts(name, capsys):
    run, _planted = hand_made_run(cell=WHERE[name][0])
    reader, _ = names.load_named("metrics", name)
    value = reader.read(run)
    assert value == pytest.approx(EXPECTED[name], abs=1e-9)
    assert not getattr(reader, "WINDOW_SHARE", False)
    err = capsys.readouterr().err.strip()
    if name in ("recover_worker_busy_share_acct", "gil_wait_share_acct"):
        table = json.loads(err.splitlines()[-1])["thread_accounts"]
        assert table["window_s"] == WINDOW_S
        roles = table["by_role"]
        assert set(roles) == {"replay", "recover", "prefetch", "feed"}
        assert all(r["threads"] == 2 for r in roles.values())
        # [wall, CPU, entries] by phase, the largest wall first
        assert roles["recover"]["phases"] == {
            "sender/native": [4.5, 3.0, 6], "idle": [1.5, 0.0, 2]}
        assert roles["prefetch"]["phases"]["sender/native"] \
            == [2.0, 0.5, 2]
        assert roles["feed"]["phases"]["feed/source"] == [0.5, 0.0, 32]
        assert roles["feed"]["phases"]["loop"] == [1.5, 2.0, 2]
        assert roles["replay"]["phases"]["validate"] == [4.0, 2.0, 2]
        walls = [c[0] for c in roles["replay"]["phases"].values()]
        assert walls == sorted(walls, reverse=True)
    else:
        assert err == ""


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_thread_accounts_reads_nothing(name, monkeypatch):
    """No pass, a pass with no account, a program that predates the
    account, and one whose ``accounts_between`` takes no ``role`` (the
    parent of the PR that brought the roles) all give None, never 0."""
    reader, _ = names.load_named("metrics", name)
    run, _planted = hand_made_run(with_accounts=False)
    assert reader.read(run) is None
    assert reader.read(dict(run, passes=[])) is None
    run, _planted = hand_made_run()
    run["passes"][1]["t_start"] += 9.5      # its accounts are before it
    assert reader.read(run) is None
    run, _planted = hand_made_run()
    rolled = obs.accounts_between
    monkeypatch.setattr(obs, "accounts_between",
                        lambda t_lo, t_hi: rolled(t_lo, t_hi))
    assert reader.read(run) is None
    monkeypatch.delattr(obs, "accounts_between")
    assert reader.read(run) is None


def test_a_thread_that_read_no_cpu_clock_has_no_cpu_column():
    """Batch replay marks no CPU seconds (``cpu_s`` None): the
    runnable-not-running share is then nothing, not the whole wall,
    and the table says None; the wall readers are unmoved."""
    run, planted = hand_made_run(cell="valuetx.tip")
    for a in planted:
        if a.role in ("replay", "prefetch"):
            a._marked = False
    gil, _ = names.load_named("metrics", "gil_wait_share_acct")
    assert gil.read(run) is None
    table = thread_account.by_role(thread_account.window_accounts(run))
    assert table["prefetch"]["phases"]["sender/native"] == [2.0, None, 2]
    assert table["prefetch"]["cpu_s"] is None
    assert table["recover"]["phases"]["sender/native"] == [4.5, 3.0, 6]
    assert (table["recover"]["wall_s"], table["recover"]["cpu_s"]) \
        == (6.0, 3.0)
    wait, _ = names.load_named("metrics", "stream_wait_share_acct")
    assert wait.read(run) == pytest.approx(
        EXPECTED["stream_wait_share_acct"])


def test_the_constructors_phase_is_left_out_of_the_gil_wait():
    """``engine/build`` runs before the thread's first mark: its CPU
    seconds are unread, so its wall is not counted as lost."""
    run, planted = hand_made_run(cell="valuetx.tip")
    for a in planted:
        if a.role == "replay":
            a._recs["engine/build"] = [0.5, 0.5, 1, 0.0]
    gil, _ = names.load_named("metrics", "gil_wait_share_acct")
    assert gil.read(run) == pytest.approx(EXPECTED["gil_wait_share_acct"])


def test_no_signatures_no_reading():
    run, _planted = hand_made_run()
    for r in run["passes"]:
        r["sigs_host"] = 0
    reader, _ = names.load_named("metrics", "recover_us_per_sig")
    assert reader.read(run) is None


def test_the_older_readers_read_the_engines_accounts_alone():
    """``benchlib.account`` — the ten ``_acct`` readers and the VM's —
    sums every account it is given: with a worker's, a feed's and a
    prefetcher's account open inside the same passes it is given the
    engines' and reads what it read before."""
    run, planted = hand_made_run()
    engines = [a for a in planted if a.role == "replay"]
    rows = account.window_accounts(run)
    assert [r["t_open"] for r in rows] == [a.t_open for a in engines]
    assert all(r["role"] == "replay" for r in rows)
    everyone = thread_account.window_accounts(run)
    assert len(everyone) == len(planted) == 4 * len(engines)
    alone = {phase: sum(a.row()["self_s"].get(phase, 0.0)
                        for a in engines)
             for phase in ("loop", "sender/pack", "sender/native",
                           "sender/wait_host", "validate", "stream/wait")}
    assert account.phase_seconds(rows)["sender/native"] \
        == alone["sender/native"] == 1.0
    for name, phases in (
            ("replay_loop_share_acct", ("loop",)),
            ("sender_pack_share_acct", ("sender/pack",)),
            ("sender_wait_host_share_acct", ("sender/wait_host",)),
            ("validate_share_acct", ("validate",))):
        reader, _ = names.load_named("metrics", name)
        assert reader.read(run) == pytest.approx(
            100 * sum(alone[p] for p in phases) / WINDOW_S), name
    # the two shifts ISSUE 40 names, as sums: what left ``loop`` and
    # ``sender/pack`` is read by the new readers, nothing is lost
    read = {n: names.load_named("metrics", n)[0].read(run)
            for n in ("replay_loop_share_acct", "stream_wait_share_acct",
                      "sender_pack_share_acct", "sender_native_share_acct")}
    assert read["replay_loop_share_acct"] + read["stream_wait_share_acct"] \
        == pytest.approx(100 * 2 * (0.5 + 2.0) / WINDOW_S)
    assert read["sender_pack_share_acct"] + read["sender_native_share_acct"] \
        == pytest.approx(100 * 2 * (0.25 + 0.5) / WINDOW_S)
    # the starved table and the window outside the engines: unmoved by
    # the other threads' seconds
    inside = sum(sum(v for k, v in a.row()["self_s"].items()
                     if k != "idle") for a in engines)
    assert account.starved(run)["by_phase"]["outside"] \
        == pytest.approx(WINDOW_S - inside)

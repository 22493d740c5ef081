"""The per-layer readers of the program's self-time account (tier-1).

Each reader on a hand-made ``run`` whose passes have planted accounts
(driven on a hand-moved clock, so every expected number is exact); None
— never 0 — where a pass has no account or the program predates the
account; and ``unaccounted_share`` reading exactly what it read before
these entries were added.
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

from benchlib import account, names  # noqa: E402
from coreth_tpu import obs  # noqa: E402
from coreth_tpu.obs import account as A  # noqa: E402

SPEC = names.load_spec()
NEW = [m["name"] for m in SPEC["per_layer"]
       if m["name"].endswith("_acct")]

# A window of two 10 s passes on a clock no real account shares
# (time.monotonic() is never negative); every hand-made run gets a
# stretch of its own, because planted accounts stay in the program's
# registry for the life of the process.
_BASES = itertools.count(1)
WINDOW_S = 20.0


class Clock:
    def __init__(self, t):
        self.t = t

    def __call__(self):
        return self.t


def plant(t_open: float) -> A.Account:
    """One pass's engine, as its account would record it.  Seconds:
    engine/build 0.5; loop 0.25; sender pack 0.5 + issue 0.25 + apply
    0.25, wait_device 1.0, wait_host 0.5; classify 0.5; window prepare
    1.0, upload 0.25 + dispatch 0.25, fetch_wait 0.5; validate 0.75;
    commit/stage 0.25 + flush 0.5; idle 0.5 between the calls.  The
    device has something in flight from the sender issue to the sender
    read, and from the window dispatch to the window read."""
    clock = Clock(t_open)
    dev = A.InFlight()
    acct = A.Account(clock=clock, device=dev)

    def spend(phase, s):
        acct.switch(phase)
        clock.t += s

    build = acct.begin("engine/build")
    clock.t += 0.5
    acct.end(build)
    clock.t += 0.5                      # idle: the runner, between calls
    tok = acct.begin()
    clock.t += 0.25                     # loop
    acct.enter("sender/pack")
    clock.t += 0.5
    spend("sender/issue_device", 0.25)
    ticket = dev.issue(acct)
    spend("sender/wait_host", 0.5)
    spend("classify", 0.5)
    spend("sender/wait_device", 1.0)
    dev.done(ticket, acct)
    spend("sender/apply", 0.25)
    spend("window/prepare", 1.0)
    spend("window/upload", 0.25)
    spend("window/dispatch", 0.25)
    ticket = dev.issue(acct)
    spend("window/fetch_wait", 0.5)
    dev.done(ticket, acct)
    spend("validate", 0.75)
    spend("commit/stage", 0.25)
    spend("commit/flush", 0.5)
    acct.exit()
    acct.end(tok)
    return acct


def hand_made_run(with_accounts=True):
    rows = []
    t0 = -1024.0 * next(_BASES)
    for i in range(2):
        t_start = t0 + 10.0 * i
        rows.append({"t_start": t_start, "t_end": t_start + 10.0,
                     "decode_s": 1.0, "engine_build_s": 0.75,
                     "t_sender": 2.5, "t_classify": 0.5,
                     "t_device": 2.0, "t_trie": 0.75,
                     "blocks_fallback": 0, "sigs_device": 1,
                     "sigs_host": 1})
    planted = [plant(r["t_start"] + r["decode_s"]) for r in rows] \
        if with_accounts else []
    run = {"spec": SPEC, "cell": SPEC["workloads"][0], "passes": rows,
           "window_s": WINDOW_S, "setup_s": 1.0, "trace": None,
           "compile": {"compiles": 0}, "config": {}, "traffic": {}}
    return run, planted


# percent of a 20 s window; two passes, each as ``plant`` spends it
EXPECTED = {
    "sender_pack_share_acct": 100 * 2 * (0.5 + 0.25 + 0.25) / 20,
    "sender_wait_device_share_acct": 100 * 2 * 1.0 / 20,
    "sender_wait_host_share_acct": 100 * 2 * 0.5 / 20,
    "window_prepare_share_acct": 100 * 2 * 1.0 / 20,
    "window_dispatch_share_acct": 100 * 2 * (0.25 + 0.25) / 20,
    "window_fetch_wait_share_acct": 100 * 2 * 0.5 / 20,
    "validate_share_acct": 100 * 2 * 0.75 / 20,
    "replay_loop_share_acct": 100 * 2 * 0.25 / 20,
    # 20 s less 2 x 1 s decode less 2 x 7.25 s in phases
    "outside_engine_share_acct": 100 * (20 - 2 - 14.5) / 20,
    # in each pass nothing is in flight outside sender issue->read
    # (0.5 + 0.5 + 1.0 = 2 s) and window dispatch->read (0.5 s):
    # 7.25 - 2.5 starved inside the phases, plus 20 - 14.5 outside them
    "device_starved_share_acct": 100 * (2 * 4.75 + 5.5) / 20,
}


def test_the_ten_readers_are_listed():
    assert sorted(NEW) == sorted(EXPECTED)
    for m in SPEC["per_layer"]:
        if m["name"] in EXPECTED:
            assert m["unit"] == "%" and m["better"] == "lower"
            assert m["source"] == "program_span"
            assert m["moves"] == "committed_txs_per_s"
            assert "workloads" not in m


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_planted_accounts(name, capsys):
    run, _planted = hand_made_run()
    reader, _ = names.load_named("metrics", name)
    value = reader.read(run)
    assert value == pytest.approx(EXPECTED[name], abs=1e-9)
    assert 0.0 <= value <= 100.0
    # a busy share of the window that unaccounted_share must not
    # subtract: it already subtracts the sum these split
    assert not getattr(reader, "WINDOW_SHARE", False)
    if name == "device_starved_share_acct":
        line = capsys.readouterr().err.strip().splitlines()[-1]
        table = json.loads(line)["device_starved"]
        assert table["share"] == value
        by_phase = table["by_phase"]
        assert by_phase["outside"] == pytest.approx(5.5)
        assert by_phase["window/prepare"] == pytest.approx(2.0)
        assert by_phase["sender/wait_device"] == 0.0
        assert by_phase["window/fetch_wait"] == 0.0
        assert "idle" not in by_phase
        assert list(by_phase.values()) == sorted(by_phase.values(),
                                                 reverse=True)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_without_accounts_reads_nothing(name, monkeypatch):
    """A pass with no account, a program that predates the account,
    and a run with no pass all give None, never 0."""
    reader, _ = names.load_named("metrics", name)
    run, _planted = hand_made_run(with_accounts=False)
    assert reader.read(run) is None
    run, _planted = hand_made_run()
    run["passes"][1]["t_start"] += 5.0      # its account is before it
    assert reader.read(run) is None
    assert reader.read(dict(run, passes=[])) is None
    run, _planted = hand_made_run()
    monkeypatch.delattr(obs, "accounts_between")
    assert reader.read(run) is None


def test_the_parts_add_up_to_what_replaystats_sums():
    """The split the acceptance criteria hold the chip runs to, on the
    planted numbers: sender parts = t_sender, window parts = t_device,
    and validate + loop + outside = unaccounted where the two clocks
    bracket the same code (here the runner's engine build is 0.25 s
    more than the constructor's own phase, and that is the gap)."""
    run, _planted = hand_made_run()

    def read(name):
        return names.load_named("metrics", name)[0].read(run)
    assert read("sender_pack_share_acct") \
        + read("sender_wait_device_share_acct") \
        + read("sender_wait_host_share_acct") \
        == pytest.approx(read("sender_share"))
    assert read("window_prepare_share_acct") \
        + read("window_dispatch_share_acct") \
        + read("window_fetch_wait_share_acct") \
        == pytest.approx(read("device_wait_share"))
    gap = 100 * 2 * (0.75 - 0.5) / WINDOW_S
    assert read("validate_share_acct") + read("replay_loop_share_acct") \
        + read("outside_engine_share_acct") \
        == pytest.approx(read("unaccounted_share") + gap)


def test_unaccounted_share_reads_what_it_read_before():
    run, _planted = hand_made_run()
    unaccounted, _ = names.load_named("metrics", "unaccounted_share")
    with_new = unaccounted.read(run)
    before = dict(SPEC, per_layer=[m for m in SPEC["per_layer"]
                                   if m["name"] not in EXPECTED])
    assert len(before["per_layer"]) == 11
    assert unaccounted.read(dict(run, spec=before)) == with_new
    # 100 less decode, engine build, sender, classify, device wait, trie
    assert with_new == pytest.approx(
        100 - 100 * 2 * (1.0 + 0.75 + 2.5 + 0.5 + 2.0 + 0.75) / 20)
    subtracted = sorted(
        m["name"] for m in SPEC["per_layer"]
        if getattr(names.load_named("metrics", m["name"])[0],
                   "WINDOW_SHARE", False))
    assert subtracted == ["classify_share", "decode_share",
                          "device_wait_share", "engine_build_share",
                          "sender_share", "trie_share"]


def test_accounts_of_one_window_only():
    """Accounts opened outside the window's passes (warm-up, the traced
    pass) are not read."""
    run, planted = hand_made_run()
    t0 = run["passes"][0]["t_start"]
    plant(t0 - 50.0)                        # a warm pass's engine
    plant(t0 + WINDOW_S + 5.0)              # the traced pass's engine
    rows = account.window_accounts(run)
    assert [r["t_open"] for r in rows] == [a.t_open for a in planted]

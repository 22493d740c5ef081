"""Reading the accounts of EVERY thread a pass's work ran on.

``benchlib.account`` reads the engines' own accounts (role ``replay``:
one thread's time, so its phases are shares of the window).  Since the
threads a pass waits on keep accounts too — the engine's recovery
worker (``recover``), the serve pipeline's feed and prefetch threads
(``feed``, ``prefetch``) — ``obs.accounts_between(..., role=None)``
returns them all, each row with its ``role``, its thread's name and,
beside the wall seconds by phase, the thread's CPU seconds by the phase
on top where it marked them (``Account.mark_cpu``: a system call, so a
few a window — exact for the worker's ``sender/native`` and the execute
thread's ``stream/wait``, one lump for what lies between two marks;
``coreth_tpu/obs/account.py``).  A pass's accounts are the ones opened
between its ``t_start`` and ``t_end``: every one of these threads opens
its account itself, as it starts, inside the pass.

A program whose accounts have no roles, or a pass with no account,
gives None, never 0: the metric is then left out of the line.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, Iterable, List, Optional

# phases in which a thread WAITS by design: wall less CPU there is the
# wait itself, not time stood runnable
BLOCKING = ("idle", "stream/wait", "window/fetch_wait",
            "sender/wait_host", "machine/fetch_wait")
# an engine's constructor runs before the thread's CPU clock is first
# marked: its CPU seconds are not read, not 0
UNREAD = ("engine/build",)
NATIVE = "sender/native"  # the native batch, on whichever thread runs it


def window_accounts(run: dict) -> Optional[List[dict]]:
    """``Account.row()`` of every account of every role opened inside
    a timed pass, in pass order."""
    rows = run["passes"]
    if not rows or run["window_s"] <= 0:
        return None
    from coreth_tpu import obs
    between = getattr(obs, "accounts_between", None)
    if between is None:
        return None
    out = []
    for r in rows:
        try:
            found = between(r["t_start"], r["t_end"], role=None)
        except TypeError:
            return None  # a program whose accounts have no roles
        if not found:
            return None
        out.extend(a.row() for a in found)
    return out


def seconds(accounts: List[dict], phases: Iterable[str],
            role: Optional[str] = None) -> float:
    """Wall seconds of ``phases`` summed over the accounts of ``role``
    (None: of every role)."""
    return sum(a["self_s"].get(p, 0.0) for a in accounts
               if role is None or a["role"] == role for p in phases)


def share(run: dict, phases: Iterable[str], role: str) -> Optional[float]:
    """Percent of the window's wall that was SELF time of ``phases``
    on the threads of ``role`` (more than one such thread can pass
    100)."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    return 100.0 * seconds(accounts, tuple(phases), role) / run["window_s"]


def runnable_not_running(run: dict) -> Optional[float]:
    """Percent of the window the replay threads stood runnable and did
    not run: wall less CPU over the phases that do not block (the
    interpreter lock held by another thread, or the machine).  None
    where a pass's replay thread marked no CPU seconds (only the serve
    pipeline's execute stage does)."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    replay = [a for a in accounts if a["role"] == "replay"]
    if not replay or any(a["cpu_s"] is None for a in replay):
        return None
    lost = sum(wall - a["cpu_s"].get(phase, 0.0)
               for a in replay for phase, wall in a["self_s"].items()
               if phase not in BLOCKING and phase not in UNREAD)
    return 100.0 * lost / run["window_s"]


def by_role(accounts: List[dict]) -> Dict[str, dict]:
    """``{role: {"threads": n, "wall_s", "cpu_s", "phases": {phase:
    [wall s, CPU s, entries]}}}`` summed over the accounts, phases by
    wall seconds; CPU None for a role whose threads marked none."""
    table: Dict[str, dict] = {}
    for a in accounts:
        entry = table.setdefault(a["role"], {
            "threads": 0, "wall_s": 0.0, "cpu_s": None, "phases": {}})
        entry["threads"] += 1
        entry["wall_s"] += sum(a["self_s"].values())
        cpu = a["cpu_s"]
        if cpu is not None:
            entry["cpu_s"] = (entry["cpu_s"] or 0.0) + sum(cpu.values())
        for phase, wall in a["self_s"].items():
            cell = entry["phases"].setdefault(phase, [0.0, None, 0])
            cell[0] += wall
            if cpu is not None:
                cell[1] = (cell[1] or 0.0) + cpu.get(phase, 0.0)
            cell[2] += a["n"].get(phase, 0)
    for entry in table.values():
        entry["phases"] = dict(sorted(entry["phases"].items(),
                                      key=lambda kv: -kv[1][0]))
    return table


def log_by_role(run: dict) -> None:
    """The window's wall and CPU seconds by role and phase, one line on
    standard error (as ``device_starved_share_acct`` prints its own)."""
    accounts = window_accounts(run)
    if accounts is not None:
        print(json.dumps({"thread_accounts": {
            "window_s": run["window_s"], "by_role": by_role(accounts)}}),
            file=sys.stderr, flush=True)

"""Reading the program's own self-time account for the window.

Every ``ReplayEngine`` opens an ``obs.Account`` first thing in its
constructor: a stack of phases on ``time.monotonic`` — this harness's
clock — whose seconds sum to the engine's age, with the seconds of each
phase in which the device had nothing in flight beside them
(``coreth_tpu/obs/account.py``).  The program keeps the accounts it has
opened; a pass's account is the one opened between the pass's
``t_start`` and ``t_end``, so the window's accounts are found with no
engine in hand and no edit to the pass row.

A program that predates the account (``obs.accounts_between`` missing)
or a pass with no account gives None, never 0: the metric is then left
out of the line.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional

IDLE = "idle"  # the account's root: engine alive, no call in progress


def window_accounts(run: dict) -> Optional[List[dict]]:
    """``Account.row()`` of each timed pass's engine, in pass order."""
    rows = run["passes"]
    if not rows or run["window_s"] <= 0:
        return None
    from coreth_tpu import obs
    between = getattr(obs, "accounts_between", None)
    if between is None:
        return None
    out = []
    for r in rows:
        found = between(r["t_start"], r["t_end"])
        if not found:
            return None
        out.extend(a.row() for a in found)
    return out


def phase_seconds(accounts: List[dict], key: str = "self_s"
                  ) -> Dict[str, float]:
    """Seconds by phase summed over the accounts, ``idle`` left out:
    between the calls of a pass the engine does nothing, and that time
    belongs to the runner."""
    total: Dict[str, float] = {}
    for a in accounts:
        for phase, s in a[key].items():
            if phase != IDLE:
                total[phase] = total.get(phase, 0.0) + s
    return total


def share(run: dict, phases: Iterable[str]) -> Optional[float]:
    """Percent of the window's wall that was SELF time of ``phases``."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    by_phase = phase_seconds(accounts)
    return 100.0 * sum(by_phase.get(p, 0.0) for p in phases) \
        / run["window_s"]


def outside_engine(run: dict) -> Optional[float]:
    """Percent of the window in no phase of any engine and not in the
    runner's decode: engine teardown between passes, the genesis
    database, the runner itself."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    inside = sum(phase_seconds(accounts).values())
    decode = sum(r["decode_s"] for r in run["passes"])
    return 100.0 * (run["window_s"] - decode - inside) / run["window_s"]


def starved(run: dict) -> Optional[dict]:
    """Where the device had nothing in flight: ``{"share": percent of
    the window, "by_phase": seconds by the phase the replay thread was
    in, with the window's wall outside every phase as "outside"}``.
    Completion is seen only when the host reads back, so this is a
    lower bound on the device's idle time."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    by_phase = phase_seconds(accounts, "starved_s")
    by_phase["outside"] = run["window_s"] \
        - sum(phase_seconds(accounts).values())
    return {"share": 100.0 * sum(by_phase.values()) / run["window_s"],
            "by_phase": dict(sorted(by_phase.items(),
                                    key=lambda kv: -kv[1]))}

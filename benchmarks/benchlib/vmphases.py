"""Reading the VM's phases of the engine's account (``vm/parse``,
``vm/verify``, ``vm/insert``, ``vm/accept``, ...: a pass through
``plugin/vm.py`` on the device state processor) a block of the chain.
``benchlib.account`` finds the accounts; a program or a pass without
the phases gives None, never 0."""

from typing import Dict, Iterable, Optional

from benchlib.account import phase_seconds, window_accounts


def by_phase(run: dict) -> Optional[Dict[str, float]]:
    """Self seconds by phase over the window's passes, or None where no
    pass went through the VM."""
    accounts = window_accounts(run)
    if accounts is None:
        return None
    seconds = phase_seconds(accounts)
    return seconds if any(p.startswith("vm/") for p in seconds) else None


def us_per_block(run: dict, phases: Iterable[str]) -> Optional[float]:
    """Self time of ``phases`` a chain block, in microseconds."""
    seconds = by_phase(run)
    blocks = sum(r["blocks"] for r in run["passes"])
    if seconds is None or not blocks:
        return None
    return 1e6 * sum(seconds.get(p, 0.0) for p in phases) / blocks

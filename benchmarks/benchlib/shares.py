"""Arithmetic the per-layer readers share.

A share is of the window's wall time, summed over its passes: busy time
on the replay thread as ``ReplayStats`` counts it (or the runner's own
clock around a call), not self time — the engine's spans have no
parents yet (ROADMAP D16).  What the shares leave over is its own
metric, ``unaccounted_share``.
"""

from typing import Optional


def share(run: dict, key: str) -> Optional[float]:
    """Percent of the window's wall spent under ``key``."""
    rows = run["passes"]
    if not rows or run["window_s"] <= 0:
        return None
    return 100.0 * sum(r[key] for r in rows) / run["window_s"]

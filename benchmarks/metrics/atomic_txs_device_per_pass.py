"""Atomic txs the engine's device path committed a pass
(``ReplayStats.atomic_txs_device``, the pass row's ``atomic``
counters): one a chain block, plus the sibling's and the last block's
second run.  Only a pass of the atomic driver has the counter."""


def read(run):
    rows = [r for r in run["passes"] if "atomic" in r]
    if not rows:
        return None
    return sum(r["atomic"]["txs_device"] for r in rows) / len(rows)

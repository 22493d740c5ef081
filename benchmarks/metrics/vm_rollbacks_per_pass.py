"""Times a pass brought the engine back to a fork point
(``ReplayStats.engine_rollbacks``, the pass row's ``vm`` counters): 1
where the traffic plans one sibling a pass.  Only a pass through
``plugin/vm.py`` has the counter."""


def read(run):
    rows = [r for r in run["passes"] if "vm" in r]
    if not rows:
        return None
    return sum(r["vm"]["engine_rollbacks"] for r in rows) / len(rows)

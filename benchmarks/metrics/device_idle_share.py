"""Share of the traced pass in which no operation ran on the device:
1 minus the union of the device's operation intervals over the pass,
from the profiler's trace (``benchlib/trace_reduce.py``)."""


def read(run):
    trace = run.get("trace")
    return 100.0 * trace["idle_share"] if trace else None

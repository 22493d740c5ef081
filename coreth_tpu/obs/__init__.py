"""Observability: end-to-end span tracing, Perfetto export, telemetry.

Level-0 leaf beside ``metrics``/``faults`` in layers.toml: every layer
from the serve pipeline down to the device dispatch seams threads its
timing evidence through it, so it imports nothing of the tree above
(metrics and faults are same-level peers).

- ``obs.trace`` — the span tracer: ``span()``/``instant()`` with ONE
  module-global None check when disabled (CORETH_TRACE=0, the default),
  per-block :class:`BlockTrace` contexts whose stage intervals become
  ``StreamReport.stage_breakdown``, a bounded ring, and Chrome
  trace-event / Perfetto JSON export (CORETH_TRACE_OUT).
- ``obs.account`` — the ALWAYS-ON self-time accounts, one a thread
  (:class:`Account`: a phase stack whose wall seconds sum to its age,
  with the thread's CPU seconds beside them; an engine opens its
  replay thread's, ``thread_account(role)`` a worker's own) and the
  in-flight count of device work (``device_issue`` / ``device_done``)
  that says which phase the host was in while the chip had nothing to
  do; the same sites feed the tracer's ring and the profiler's host
  plane when the tracer is armed.
- ``obs.server`` — the zero-dependency live telemetry endpoint
  (CORETH_TELEMETRY_PORT): /metrics, /trace, /report.
- ``obs.recorder`` — the divergence flight recorder
  (CORETH_FORENSICS=1): a per-block witness ring that freezes into
  content-addressed, offline-replayable bundles when an oracle trips,
  a block quarantines, or a backend hard-demotes
  (tools/replay_bundle.py is the matching bisection CLI).
"""

from coreth_tpu.obs.account import (
    NULL as NULL_ACCOUNT, Account, InFlight, accounts_between, current,
    device_done, device_issue, thread_account,
)
from coreth_tpu.obs.trace import (
    PT_EXPORT_FAIL, BlockTrace, EventRing, SpanTracer,
    StageAccumulator, arm_from_env, block_begin, enabled, install,
    instant, self_times, span, tracer, uninstall, write_out,
)
from coreth_tpu.obs import recorder  # noqa: F401 — re-export the forensics module (and its obs/bundle_fail declaration) under the obs namespace

__all__ = [
    "NULL_ACCOUNT", "PT_EXPORT_FAIL", "Account", "BlockTrace",
    "EventRing", "InFlight", "SpanTracer", "StageAccumulator",
    "accounts_between", "arm_from_env", "block_begin", "current",
    "device_done", "device_issue", "enabled", "install", "instant",
    "recorder", "self_times", "span", "thread_account", "tracer",
    "uninstall", "write_out",
]

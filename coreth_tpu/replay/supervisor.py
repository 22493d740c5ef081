"""Backend supervisor: retry, demote, probe, quarantine.

The replay stack already has a correctness ladder — fused device OCC
-> native host engine -> Python interpreter — but until now only
per-tx/per-block *semantic* escapes moved work down it.  The
supervisor adds the *fault* dimension:

- **transient faults retry** with bounded exponential backoff
  (``CORETH_SUPERVISOR_RETRIES`` / ``_BACKOFF``);
- **repeated failures demote** the affected scope — ``device`` (every
  jitted dispatch: transfer windows, fused OCC, the shard exchange)
  or ``native`` (the hostexec C++ engine) — for a cooldown
  (``_STRIKES`` strikes -> ``_COOLDOWN`` seconds, doubling per
  re-demotion up to 8x).  A demoted ``device`` routes blocks through
  the exact host path; a demoted ``native`` routes txs through the
  Python interpreter.  Roots stay bit-identical either way — the
  ladder only ever trades speed;
- **re-promotion probes**: once the cooldown lapses the next eligible
  dispatch simply tries the backend again; success promotes, failure
  re-demotes with a longer cooldown;
- **armed-oracle divergences** (CORETH_HOST_EXEC_CHECK) hard-demote
  ``native`` immediately — a backend that disagrees with the
  interpreter is wrong, not slow;
- **poison blocks** — blocks that fail validation on every backend —
  are *quarantined* by callers that opt in (the streaming pipeline):
  counted here, reported in StreamReport, never wedging the queue.

Counters mirror into the metrics registry under ``supervisor/*``.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from coreth_tpu import obs


class BackendFault(Exception):
    """A supervised call failed past its retry budget; the caller must
    route the work down the ladder (the supervisor has already counted
    the strike and applied any demotion)."""

    def __init__(self, scope: str, cause: BaseException):
        super().__init__(f"backend fault in scope {scope!r}: {cause!r}")
        self.scope = scope
        self.cause = cause


class BackendSupervisor:
    """Per-engine fault policy for the execution ladder.

    Scopes: ``device`` (jitted dispatch paths) and ``native`` (the
    hostexec C++ engine).  ``allows(scope)`` is the routing gate the
    classify/dispatch sites consult; ``run(scope, point, fn, *args)``
    wraps a supervised call with injection, retry, and strike
    accounting.  A ``clock`` injection point keeps the cooldown logic
    unit-testable without sleeping.
    """

    # "commit" has no alternative backend (a persistent flush failure
    # is fatal) but shares the retry/strike accounting
    SCOPES = ("device", "native", "commit")
    COOLDOWN_CAP = 8  # max cooldown growth factor across re-demotions

    def __init__(self, engine=None, registry=None, clock=time.monotonic,
                 sleep=time.sleep):
        self.engine = engine
        self._registry = registry
        self._clock = clock
        self._sleep = sleep
        self.max_retries = int(os.environ.get(
            "CORETH_SUPERVISOR_RETRIES", "2"))
        self.backoff = float(os.environ.get(
            "CORETH_SUPERVISOR_BACKOFF", "0.05"))
        self.strikes_to_demote = int(os.environ.get(
            "CORETH_SUPERVISOR_STRIKES", "3"))
        self.cooldown = float(os.environ.get(
            "CORETH_SUPERVISOR_COOLDOWN", "30"))
        # per-scope cooldown is None until a re-demotion doubles it,
        # so late tuning of self.cooldown (tests, benches) takes effect
        # "seq" counts strikes ever recorded for the scope — run()
        # snapshots it to tell a strike-free success from a
        # partial-progress return that contained its own fault
        self._state: Dict[str, dict] = {
            s: {"strikes": 0, "demoted": False, "until": 0.0,
                "cooldown": None, "seq": 0}
            for s in self.SCOPES
        }
        # counters (plain ints; publish() mirrors to the registry).
        # strike/ok/quarantine accounting holds _mu: today's callers
        # strike from the execute path, but the telemetry handler reads
        # snapshot() mid-run and the scale-out direction adds striking
        # workers — bare += here loses demotions exactly under load
        self._mu = threading.Lock()
        self.retries = 0
        self.demotions = 0
        self.promotions = 0
        self.strikes = 0
        self.quarantined = 0
        # recovery-latency attribution (bench faults section): wall
        # seconds from the first strike of a scope to its demotion —
        # how long the supervisor took to stop banging on a dead
        # backend and route around it
        self._first_strike_t: Dict[str, Optional[float]] = {
            s: None for s in self.SCOPES}
        self.demote_latency_s: Dict[str, float] = {}
        # the newest ladder transition (demote / probe_failed /
        # promote), timestamped on the injected clock — surfaced in
        # StreamReport.supervisor and mirrored into the obs event
        # stream so the Perfetto timeline shows WHEN routing flipped
        self.last_transition: Optional[dict] = None

    def _transition(self, kind: str, scope: str) -> None:
        self.last_transition = {"kind": kind, "scope": scope,
                                "at_s": round(self._clock(), 3)}
        obs.instant(f"supervisor/{kind}", scope=scope)

    # ------------------------------------------------------------ routing
    def allows(self, scope: str) -> bool:
        """May work route to ``scope`` right now?  True while healthy,
        False while demoted-and-cooling; True again once the cooldown
        lapses (the probe — the next supervised call decides)."""
        st = self._state[scope]
        if not st["demoted"]:
            return True
        return self._clock() >= st["until"]

    def demoted(self, scope: str) -> bool:
        return self._state[scope]["demoted"]

    # ----------------------------------------------------------- outcomes
    def note_ok(self, scope: str) -> None:
        """A supervised call in ``scope`` succeeded: reset strikes; a
        success after the cooldown lapsed is a successful probe and
        re-promotes the scope (cooldown resets too)."""
        with self._mu:
            st = self._state[scope]
            st["strikes"] = 0
            self._first_strike_t[scope] = None
            if st["demoted"] and self._clock() >= st["until"]:
                st["demoted"] = False
                st["cooldown"] = None
                self.promotions += 1
                self._transition("promote", scope)

    def strike(self, scope: str, exc: BaseException,
               hard: bool = False) -> None:
        """A supervised call failed past retries.  ``hard`` demotes
        immediately (oracle divergence — the backend is *wrong*)."""
        now = self._clock()
        with self._mu:
            st = self._state[scope]
            self.strikes += 1
            st["seq"] += 1
            if self._first_strike_t[scope] is None:
                self._first_strike_t[scope] = now
            if st["demoted"]:
                if now >= st["until"]:
                    # failed probe: re-demote, back off harder
                    st["cooldown"] = min(
                        (st["cooldown"] or self.cooldown) * 2,
                        self.cooldown * self.COOLDOWN_CAP)
                    st["until"] = now + st["cooldown"]
                    self.demotions += 1
                    self._transition("probe_failed", scope)
                return
            st["strikes"] += 1
            demote = hard or st["strikes"] >= self.strikes_to_demote
            if demote:
                st["demoted"] = True
                st["until"] = now + (st["cooldown"] or self.cooldown)
                self.demotions += 1
                self._transition("demote", scope)
                first = self._first_strike_t[scope]
                if first is not None:
                    self.demote_latency_s[scope] = round(now - first, 4)
        if hard:
            # a hard demotion means a backend was WRONG (an armed
            # oracle disagreed), not slow — bundle the evidence; the
            # seam that struck usually noted a richer trigger moments
            # earlier, and the pending triggers freeze together when
            # the block's host-path witness lands.  Outside _mu: the
            # recorder takes its own lock and may write bundles
            from coreth_tpu.obs import recorder as _forensics
            _forensics.note_trigger(
                _forensics.TR_DEMOTE,
                f"hard demote of scope {scope!r}: {exc!r}")

    def note_quarantined(self) -> None:
        with self._mu:
            self.quarantined += 1

    # --------------------------------------------------------- supervision
    def run(self, scope: str, point: Optional[str], fn, *args):
        """Run ``fn(*args)`` under supervision: fire the injection
        point first (no-op unarmed), retry transient faults with
        bounded exponential backoff, and convert a persistent failure
        into a strike + :class:`BackendFault`.

        ``fn`` must be safe to re-invoke after a failed attempt —
        every wrapped site either fails before mutating shared state
        or contains its own mid-run faults (machine_block.execute_run
        returns its consumed count instead of raising once progress
        has been staged).  That includes device buffers: the OCC slot
        table is DONATED into each window dispatch, so a dispatch that
        fails may have consumed it — the window runner marks its table
        stale on any failed dispatch (adapter._dispatch) and the retry
        rebuilds it from the host mirror.

        Consensus failures (:class:`~coreth_tpu.replay.engine
        .ReplayError`) are NEVER a backend fault: they propagate
        untouched — the ladder handles *broken backends*, the
        quarantine path handles *broken blocks*.
        """
        from coreth_tpu import faults
        from coreth_tpu.consensus.engine import ConsensusError
        from coreth_tpu.replay.engine import ReplayError
        delay = self.backoff
        seq0 = self._state[scope]["seq"]
        attempt = 0
        while True:
            try:
                if point is not None:
                    faults.fire(point)
                out = fn(*args)
            except (ReplayError, ConsensusError):
                # block-validity failures, not backend failures: the
                # quarantine path owns them, never the ladder
                raise
            except faults.FaultInjected as exc:
                if exc.transient and attempt < self.max_retries:
                    attempt += 1
                    with self._mu:
                        self.retries += 1
                    self._sleep(delay)
                    delay *= 2
                    continue
                self.strike(scope, exc)
                raise BackendFault(scope, exc) from exc
            except Exception as exc:  # noqa: BLE001 — a real backend failure IS the supervised case: strike + route down the ladder; correctness is re-proven on the fallback path
                if attempt < self.max_retries:
                    attempt += 1
                    with self._mu:
                        self.retries += 1
                    self._sleep(delay)
                    delay *= 2
                    continue
                self.strike(scope, exc)
                raise BackendFault(scope, exc) from exc
            else:
                # a wrapped call may CONTAIN a mid-run fault and still
                # return progress (machine_block.execute_run): it
                # strikes the scope itself, and that strike must not
                # be erased by crediting the partial return as a
                # success — only a strike-free run counts as ok
                if self._state[scope]["seq"] == seq0:
                    self.note_ok(scope)
                return out

    def retry_point(self, scope: str, point: str) -> None:
        """Fire an injection point with the transient-retry policy but
        no wrapped callable — for seams like the commit flush where
        the real work must not re-run (only the injected gate does)."""
        from coreth_tpu import faults
        delay = self.backoff
        attempt = 0
        while True:
            try:
                faults.fire(point)
                return
            except faults.FaultInjected as exc:
                if exc.transient and attempt < self.max_retries:
                    attempt += 1
                    with self._mu:
                        self.retries += 1
                    self._sleep(delay)
                    delay *= 2
                    continue
                self.strike(scope, exc)
                raise

    # ------------------------------------------------------------ reporting
    def snapshot(self) -> dict:
        with self._mu:
            return {
                "retries": self.retries,
                "strikes": self.strikes,
                "demotions": self.demotions,
                "promotions": self.promotions,
                "quarantined": self.quarantined,
                "demoted_scopes": sorted(
                    s for s in self.SCOPES
                    if self._state[s]["demoted"]),
                "demote_latency_s": dict(self.demote_latency_s),
                "last_transition": self.last_transition,
            }

    def publish(self, registry=None) -> None:
        """Mirror the counters into the metrics registry (scrapeable
        next to replay/* and serve/*)."""
        from coreth_tpu.metrics import Gauge, get_or_register
        reg = registry or self._registry
        for name in ("retries", "strikes", "demotions", "promotions",
                     "quarantined"):
            get_or_register(f"supervisor/{name}", Gauge,
                            reg).update(getattr(self, name))

"""Bounded-queue streaming pipeline: feed -> prefetch -> execute -> commit.

Stage model
-----------

- **feed** (thread): pulls blocks from the :class:`BlockFeed`, stamps
  the enqueue time, and blocks on the bounded feed queue when the
  pipeline is behind — backpressure propagates all the way to the
  source instead of buffering unboundedly.
- **prefetch** (thread): drains the feed queue in window-sized chunks,
  warms them (serve/prefetch.py — batched sender recovery + bytecode
  touches), and blocks on the bounded execute queue.
- **execute** (the ``run()`` caller's thread): the streaming analog of
  ``ReplayEngine.replay`` — classify arriving blocks into transfer
  windows, issue window N+1's device dispatch BEFORE validating window
  N (cross-window speculation survives streaming), route
  unclassifiable runs through ``_machine_run`` (fused OCC windows /
  host fallback), and rewind exactly like batch replay when a window
  fails validation.  Runs on the caller's thread because every engine
  structure it touches (tries, DeviceState mirrors, commit staging) is
  single-owner by design.
- **commit**: the engine's window-batched CommitPipeline, wrapped so
  every ``flush()`` is timed (and can be fault-injected slow in
  tests).  Commit work is interleaved on the execute thread AFTER the
  next window's dispatch is in flight — the host/device overlap the
  batch engine already proves — so a slow commit stage stretches the
  execute stage, the bounded queues fill, and the feed blocks: latency
  degrades measurably, queues stay bounded.

Each stage's thread keeps a self-time account (obs/account.py): the
execute stage the engine's (role ``replay``; its wait for blocks is
phase ``stream/wait``), the feed and prefetch threads their own (roles
``feed``, ``prefetch``): wall seconds by phase, and each thread's CPU
seconds marked once a window's worth of blocks.  No phase
recurs per block: the feed's two intervals a block are clock pairs,
moved over once a window's worth of blocks; the prefetch thread's
phases are per chunk.  The report's ``feed_blocked_s``,
``prefetch_blocked_s`` and ``overlap_s`` are read from them.

Every block's enqueue->committed latency lands in a
:class:`~coreth_tpu.metrics.Histogram` (p50/p99/max), and the report
carries sustained txs/s over the wall of the run — the SLO surface the
bench's streaming section publishes.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from coreth_tpu import faults, obs
from coreth_tpu.obs import recorder as forensics
from coreth_tpu.metrics import Counter, Gauge, Histogram, Meter, \
    get_or_register
from coreth_tpu.serve.feed import BlockFeed, FeedExhausted
from coreth_tpu.serve.prefetch import Prefetcher
from coreth_tpu.types import Block

# Injection points on the serve boundary (coreth_tpu/faults):
PT_FEED_STALL = faults.declare(
    "serve/feed_stall", "feed delivers nothing for a while (stall)")
PT_FEED_DROP = faults.declare(
    "serve/feed_drop", "feed silently loses a block (sequence gap)")
PT_MALFORMED = faults.declare(
    "serve/malformed_block",
    "a block arrives corrupted (header fields lie about the body)")
PT_CRASH = faults.declare(
    "serve/crash",
    "process dies (SIGKILL) after the Nth committed block")


def _corrupt_block(b: Block) -> Block:
    """The malformed-block injection: a wire-roundtripped copy whose
    receipt_hash lies — execution still succeeds, every backend's
    validation fails, which is exactly the poison-block shape the
    quarantine must absorb without stalling later blocks."""
    bad = Block.decode(b.encode())
    bad.header.receipt_hash = b"\xde\xad\xbe\xef" * 8
    return bad


@dataclass
class _Item:
    block: Block
    t_enqueue: float
    # per-block trace context (obs.BlockTrace; None when tracing off):
    # rides the block through every stage, so the committed report can
    # attribute its enqueue->committed latency stage by stage
    bt: object = None


@dataclass
class StreamReport:
    """One streaming run's SLO surface (bench JSON shape)."""
    blocks: int = 0
    txs: int = 0
    wall_s: float = 0.0
    sustained_txs_s: float = 0.0
    latency_ms: dict = field(default_factory=dict)   # p50/p99/max
    prefetch: dict = field(default_factory=dict)
    queues: dict = field(default_factory=dict)
    stages_s: dict = field(default_factory=dict)
    backpressure: dict = field(default_factory=dict)
    feed_stalls: int = 0
    feed_drops: int = 0
    shutdown: bool = False
    # fault-tolerance surface: blocks applied-but-unverified (poison
    # parked without wedging the queue), the supervisor's ladder
    # counters, checkpoint cadence, armed-plan firing counts, and the
    # reason the stream halted early (None = ran to exhaustion)
    quarantined: List[dict] = field(default_factory=list)
    supervisor: dict = field(default_factory=dict)
    checkpoint: dict = field(default_factory=dict)
    faults: dict = field(default_factory=dict)
    halted: Optional[str] = None
    # flat-state layer surface (state/flat): read hit/miss counters,
    # generation/rollback counts (empty when CORETH_FLAT=0)
    flat: dict = field(default_factory=dict)
    # per-stage SHARE of total enqueue->committed time across every
    # committed block (obs tracer; {} when CORETH_TRACE=0): queue_feed
    # / prefetch / queue_exec / execute / commit sum to ~1.0
    stage_breakdown: dict = field(default_factory=dict)
    # divergence-forensics surface (obs/recorder, CORETH_FORENSICS=1):
    # bundle write/failure counts, ring occupancy, and the written
    # bundle paths; quarantined entries above also gain a "bundle"
    # path.  {} when the recorder is off.
    forensics: dict = field(default_factory=dict)
    # the engine's self-time account (obs/account.py Account.row()):
    # seconds and entries per phase of the execute stage's thread, and
    # the seconds of each phase in which the device had nothing in
    # flight.  Always on; the stream runs under its ``loop`` phase.
    account: dict = field(default_factory=dict)
    # lane fill of the engine's transfer windows (ReplayStats
    # lanes_real / lanes_padded): transactions packed against lanes
    # uploaded and scanned; machine_real / machine_padded: the same
    # for its fused machine windows; window_uploads /
    # window_upload_bytes: the host->device transfers that carried the
    # transfer windows (one staging buffer a window on one device);
    # blocks_order_dependent: device blocks only the in-order solvency
    # rule could commit (a sender funded earlier in the same block);
    # sigs_left_to_signer: lanes of the native sender batch it did not
    # vouch for, so signer.sender's per-tx path decided them;
    # sigs_slow_path: lanes its sequential fallback recovered (ok = 2);
    # receipt_blocks_python: device blocks whose receipts took the
    # Python StackTrie path instead of native.receipt_check
    lanes: dict = field(default_factory=dict)

    def row(self) -> dict:
        return dict(self.__dict__)


class StreamingPipeline:
    """Drive one engine from one feed until exhaustion or shutdown.

    ``depth`` bounds each inter-stage queue in blocks (default 2x the
    engine window): total in-flight work is capped at ~2*depth +
    2*window blocks no matter how far ahead the feed could run.
    ``window_wait`` is how long the execute stage waits to top up a
    partial window before running it — the latency/throughput knob
    (holding blocks hostage for a full window would trade p50 for
    batch efficiency).  ``commit_delay`` injects a per-flush stall
    (fault-injection tests only).
    """

    def __init__(self, engine, feed: BlockFeed,
                 depth: Optional[int] = None,
                 window_wait: float = 0.01,
                 commit_delay: float = 0.0,
                 registry=None,
                 quarantine: bool = True,
                 quarantine_limit: int = 8,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_worker: Optional[str] = None):
        faults.arm_from_env()  # CORETH_FAULT_PLAN (idempotent)
        obs.arm_from_env()     # CORETH_TRACE=1 (idempotent)
        forensics.arm_from_env()  # CORETH_FORENSICS=1 (idempotent)
        self.engine = engine
        self.feed = feed
        self.depth = depth or 2 * engine.window
        self.window_wait = window_wait
        self.commit_delay = commit_delay
        # serving must not wedge: a poison block (fails every backend)
        # is applied tolerantly + parked in the report by default;
        # quarantine=False restores batch replay's strict raise
        self.quarantine = quarantine
        self.quarantine_limit = quarantine_limit
        self._quar_streak = 0
        # crash-consistent checkpoints (replay/checkpoint.py) every N
        # committed blocks; default from CORETH_CHECKPOINT, active
        # only when the engine's Database is disk-backed (rawdb
        # PersistentNodeDict exposes its kv)
        if checkpoint_every is None:
            checkpoint_every = int(os.environ.get("CORETH_CHECKPOINT",
                                                  "0"))
        self._ckpt = None
        ckpt_kv = getattr(engine.db.node_db, "kv", None)
        if checkpoint_every > 0 and ckpt_kv is not None:
            from coreth_tpu.replay.checkpoint import CheckpointManager
            # checkpoint_worker scopes the record key to a cluster
            # lane (serve/cluster): N lanes checkpoint without
            # clobbering, and a replacement worker resumes by lane id
            self._ckpt = CheckpointManager(engine, ckpt_kv,
                                           checkpoint_every,
                                           worker=checkpoint_worker)
        self._expect_number: Optional[int] = None
        self._q_feed: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._q_exec: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._feed_done = threading.Event()
        self._pre_done = threading.Event()
        self._shutdown_called = False
        self.prefetcher = Prefetcher(engine)
        self.stats = StreamReport()
        self._latency = Histogram(window=4096)
        self._tx_meter = Meter()
        self._registry = registry
        # progress-stat lock: feed/prefetch threads and the commit path
        # all mutate the inflight accounting, and the live telemetry
        # report reads it mid-run
        self._mu = threading.Lock()
        self._enqueued = 0
        self._committed_blocks = 0
        self._max_inflight = 0
        self._t_first_enqueue: Optional[float] = None
        self._t_last_commit: Optional[float] = None
        # the feed and prefetch threads' own accounts: each opened by
        # its thread first thing, read by _publish after the join
        self._feed_acct = None
        self._prefetch_acct = None
        self._t_commit = 0.0
        # commit time already attributed to committed blocks' traces
        # (the delta since the last _mark_committed amortizes over
        # that batch of blocks)
        self._t_commit_attr = 0.0
        self._commit_flushes = 0
        # live telemetry endpoint (obs/server.py): started by run()
        # when CORETH_TELEMETRY_PORT is set, stopped in its finally
        self._telemetry = None
        # THIS run's stage-attribution sink (lazily created when
        # tracing is on): per-pipeline, so concurrent or back-to-back
        # runs sharing the process-global tracer never blend
        self._stages = None
        self._prefetch_hits = 0
        self._errors: List[BaseException] = []
        # quarantined Block objects, parallel to stats.quarantined
        # (rollback_quarantined needs the block itself back)
        self._quarantined_blocks: List[Block] = []

    # ------------------------------------------------------- queue helpers
    def _put(self, q: "queue.Queue", item) -> float:
        """Stop-aware bounded put; returns seconds spent blocked.
        Returns -1 if the pipeline stopped before the item fit (the
        item is dropped — mid-stream shutdown sheds un-entered work)."""
        t0 = time.monotonic()
        while not self._stop.is_set():
            try:
                q.put(item, timeout=0.05)
                return time.monotonic() - t0
            except queue.Full:
                continue
        return -1.0

    # ------------------------------------------------------------ stages
    def _feed_loop(self) -> None:
        # what no phase names is ``loop``; the two intervals a block —
        # inside the source, parked on the queue — are clock pairs,
        # moved out of it once a window's worth of blocks
        acct = obs.thread_account("feed")
        self._feed_acct = acct  # corethlint: shared written once, by this thread as it starts; run() reads it after the join
        acct.enter("loop")
        acct.mark_cpu()
        window = self.engine.window
        source_s = put_s = 0.0
        pulls = puts = 0

        def settle():
            nonlocal source_s, put_s, pulls, puts
            acct.mark_cpu()   # the thread's CPU seconds, to ``loop``
            acct.move("loop", "feed/source", source_s, entries=pulls)
            acct.move("loop", "feed/put", put_s, entries=puts)
            source_s = put_s = 0.0
            pulls = puts = 0

        try:
            while not self._stop.is_set():
                t_src = time.monotonic()
                try:
                    b = self.feed.next_block(timeout=0.05)
                except FeedExhausted:
                    break
                finally:
                    source_s += time.monotonic() - t_src
                    pulls += 1
                if b is None:
                    self.stats.feed_stalls += 1
                    continue
                # injected feed faults: a stall delays the block, a
                # drop loses it (the execute stage detects the gap),
                # a malformed block arrives corrupted (quarantine)
                if faults.fire(PT_FEED_STALL) is not None:
                    self.stats.feed_stalls += 1
                if faults.check(PT_FEED_DROP) is not None:
                    self.stats.feed_drops += 1
                    get_or_register("serve/feed_drops", Counter,
                                    self._registry).inc()
                    continue
                if faults.check(PT_MALFORMED) is not None:
                    b = _corrupt_block(b)
                it = _Item(block=b, t_enqueue=time.monotonic())
                # trace context rides the block from here to commit,
                # folding into THIS run's stage sink (one-None-check
                # no-op when tracing is off)
                if obs.enabled():
                    with self._mu:
                        if self._stages is None:
                            self._stages = obs.StageAccumulator()
                    it.bt = obs.block_begin(b.number, it.t_enqueue,
                                            sink=self._stages)
                with self._mu:
                    if self._t_first_enqueue is None:
                        self._t_first_enqueue = it.t_enqueue
                # the bounded put IS the backpressure: when the
                # pipeline is behind, the feed parks here and the
                # source (paced chain / mempool builder) stops draining
                blocked = self._put(self._q_feed, it)
                if blocked < 0:
                    break
                put_s += blocked
                puts += 1
                if puts >= window:
                    settle()
                with self._mu:
                    self._enqueued += 1
                    inflight = self._enqueued - self._committed_blocks
                    if inflight > self._max_inflight:
                        self._max_inflight = inflight
        except BaseException as exc:  # noqa: BLE001 — surfaced by run()
            self._errors.append(exc)
            self._stop.set()
        finally:
            settle()
            acct.exit()
            self._feed_done.set()

    def _prefetch_loop(self) -> None:
        # one chain of phases a CHUNK: prefetch/wait -> prefetch/
        # touch_code (warm_senders' sender/pack -> sender/native ->
        # sender/apply inside it) -> prefetch/put -> prefetch/wait
        acct = obs.thread_account("prefetch")
        self._prefetch_acct = acct  # corethlint: shared written once, by this thread as it starts; run() reads it after the join
        acct.enter("prefetch/wait")
        acct.mark_cpu()
        window = self.engine.window
        unmarked = 0   # blocks since the thread's CPU clock was read
        try:
            while True:
                chunk: List[_Item] = []
                try:
                    chunk.append(self._q_feed.get(timeout=0.05))
                except queue.Empty:
                    if self._feed_done.is_set() and self._q_feed.empty():
                        break
                    if self._stop.is_set():
                        break
                    continue
                while len(chunk) < window:
                    try:
                        chunk.append(self._q_feed.get_nowait())
                    except queue.Empty:
                        break
                acct.switch("prefetch/touch_code")
                unmarked += len(chunk)
                if unmarked >= window:
                    # the thread's CPU seconds since the last mark, as
                    # one, to this phase: a system call, so once a
                    # window's worth of blocks and not once a chunk
                    acct.mark_cpu()
                    unmarked = 0
                t_pf = time.monotonic()
                self.prefetcher.warm([c.block for c in chunk])
                if obs.enabled():
                    # chunk warm cost amortizes per block; t_pf marks
                    # the end of each block's feed-queue wait
                    share = (time.monotonic() - t_pf) / len(chunk)
                    for c in chunk:
                        if c.bt is not None:
                            c.bt.prefetched(t_pf, share)
                acct.switch("prefetch/put")
                for c in chunk:
                    if self._put(self._q_exec, c) < 0:
                        return
                acct.switch("prefetch/wait")
        except BaseException as exc:  # noqa: BLE001 — surfaced by run()
            self._errors.append(exc)
            self._stop.set()
        finally:
            acct.exit()
            self._pre_done.set()

    # ----------------------------------------------------------- commit
    def _wrap_commit(self):
        """Time (and optionally fault-inject) every commit flush."""
        pipe = self.engine.commit_pipe
        orig = pipe.flush

        def timed_flush():
            t0 = time.monotonic()
            if self.commit_delay:
                time.sleep(self.commit_delay)
            out = orig()
            self._t_commit += time.monotonic() - t0
            self._commit_flushes += 1
            return out

        pipe.flush = timed_flush
        return lambda: setattr(pipe, "flush", orig)

    def _mark_committed(self, items: List[_Item]) -> None:
        now = time.monotonic()
        if items and obs.enabled():
            # the commit-flush time since the last committed batch
            # belongs to exactly these blocks' windows; amortize it
            # per block so each trace's stage sum stays exact
            delta = self._t_commit - self._t_commit_attr
            self._t_commit_attr = self._t_commit
            share = delta / len(items)
            for it in items:
                if it.bt is not None:
                    it.bt.finish(now, commit_s=share)
        for it in items:
            self._latency.update(now - it.t_enqueue)
            self._tx_meter.mark(len(it.block.transactions))
            self.stats.txs += len(it.block.transactions)
            # the SIGKILL seam: an armed plan kills the process after
            # the Nth committed block — mid-stream, past a checkpoint
            # boundary — to prove the resume path (crash-consistency
            # tests; a no-op lookup otherwise)
            faults.fire(PT_CRASH)
        self.stats.blocks += len(items)
        with self._mu:
            self._committed_blocks += len(items)
        if items:
            self._t_last_commit = now
            # any clean commit breaks a quarantine streak — the limit
            # counts CONSECUTIVE quarantined blocks, so _try_quarantine
            # re-increments right after its own call here
            self._quar_streak = 0
            if self._ckpt is not None:
                self._ckpt.on_committed(len(items))

    # ------------------------------------------------- fault handling
    def _halt(self, reason: str) -> None:
        """Stop the stream cleanly with the reason in the report: the
        committed prefix stays durable (and checkpointed), run()
        returns its report instead of wedging or crashing."""
        if self.stats.halted is None:
            self.stats.halted = reason
        self._stop.set()

    def _try_quarantine(self, it: _Item, exc: BaseException) -> bool:
        """A block failed validation on every backend: apply it
        tolerantly (engine.quarantine_block) and park it in the
        report.  False (and a halt) when the block cannot even be
        applied, or when too many consecutive blocks quarantine — the
        chain itself has diverged and blind progress would be noise."""
        if not self.quarantine:
            raise exc
        if self._quar_streak + 1 > self.quarantine_limit:
            self._halt(f"quarantine limit ({self.quarantine_limit}) "
                       f"reached at block {it.block.number}")
            return False
        try:
            reasons = self.engine.quarantine_block(it.block)
        except Exception as sub:  # noqa: BLE001 — the block cannot even be applied (invalid txs): halt with the reason; resume needs operator intervention
            self._halt(f"unservable block {it.block.number}: {sub!r}")
            return False
        streak = self._quar_streak
        self.stats.quarantined.append({
            "number": it.block.number,
            "hash": it.block.hash().hex(),
            "reasons": [str(exc)] + reasons,
        })
        self._quarantined_blocks.append(it.block)
        get_or_register("serve/quarantined", Counter,
                        self._registry).inc()
        self._mark_committed([it])  # resets the streak; restore + bump
        self._quar_streak = streak + 1
        return True

    # ---------------------------------------------------------- execute
    def _next_item(self, idle: bool) -> Optional[_Item]:
        """One item from the execute queue, or None at end-of-stream /
        when a partial window should run instead of waiting longer."""
        deadline = time.monotonic() + (0.25 if idle else self.window_wait)
        while True:
            if self._pre_done.is_set() and self._q_exec.empty():
                return None
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            try:
                it = self._q_exec.get(timeout=min(0.05, remaining))
            except queue.Empty:
                continue
            # continuity gate: a lost block (dropped upstream, a
            # wedged peer) would otherwise surface blocks later as a
            # baffling state-root mismatch — halt HERE with the gap
            # named, the committed prefix durable (checkpoint), and
            # the report saying exactly what to refetch
            num = it.block.number
            if self._expect_number is not None \
                    and num != self._expect_number:
                self._halt(f"sequence gap: got block {num}, "
                           f"expected {self._expect_number}")
                get_or_register("serve/sequence_gaps", Counter,
                                self._registry).inc()
                return None
            self._expect_number = num + 1
            # first sight of the block on the execute stage: senders
            # the prefetch stage already recovered count as hits
            self._prefetch_hits += sum(
                1 for tx in it.block.transactions
                if tx.cached_sender() is not None)
            if it.bt is not None:
                it.bt.exec_start()
            return it

    def _eos(self) -> bool:
        return self._pre_done.is_set() and self._q_exec.empty()

    def _drive(self) -> None:
        """The execute stage — see the module docstring's stage model.
        Mirrors ReplayEngine.replay()'s issue-ahead/retire-behind loop,
        driven by arriving items instead of a fixed block list.

        Fault handling on top of the batch loop: a device BackendFault
        leaves the classified items in the buffer (the supervisor has
        struck/demoted; they re-route down the ladder next iteration),
        and a ReplayError carrying its block — a poison block that
        failed every backend — goes through the quarantine instead of
        killing the stream."""
        from coreth_tpu.replay.engine import ReplayError
        from coreth_tpu.replay.supervisor import BackendFault
        e = self.engine
        buf: List[_Item] = []
        pending = None  # (win, its items) — issued, not yet validated
        while True:
            # top up the working buffer; wait only when idle, and only
            # window_wait when a partial window could run instead.
            # ``stream/wait`` once a WINDOW, round the whole top-up: the
            # queue's blocking get, with the loop's bookkeeping an item
            # (continuity gate, prefetch-hit count) riding inside it
            # (the thread's CPU seconds are marked at its two ends: the
            # work since the last wait goes to ``loop`` as one)
            e.account.mark_cpu()
            with e.account.enter("stream/wait"):
                while len(buf) < e.window:
                    it = self._next_item(
                        idle=not buf and pending is None)
                    if it is None:
                        break
                    buf.append(it)
                e.account.mark_cpu()
            if not buf and pending is None:
                if self._eos():
                    break
                continue
            # classify a transfer run off the head of the buffer
            run = []
            k = 0
            t0 = time.monotonic()
            with e.account.enter("classify"):
                while k < len(buf) and len(run) < e.window:
                    batch = e._classify(buf[k].block)
                    if batch is None:
                        break
                    run.append((buf[k].block, batch))
                    k += 1
            e.stats.t_classify += time.monotonic() - t0
            win = None
            if run:
                try:
                    win = e._issue_window(run)
                except BackendFault:
                    # struck (and maybe demoted): the items stay in
                    # the buffer and re-route through the host ladder
                    win = None
            # retire the previous window while the chip runs this one
            if pending is not None:
                p_win, p_items = pending
                pending = None
                try:
                    resume = e._complete_window(
                        p_win, [it.block for it in p_items], 0)
                except ReplayError as exc:
                    blk = getattr(exc, "block", None)
                    if blk is None or not self.quarantine:
                        raise
                    # the engine rewound to the prefix before the
                    # poison block and already retried it on the
                    # exact host path; quarantine it and hand the
                    # window tail (stale speculative base) back
                    j = next((i for i, it in enumerate(p_items)
                              if it.block is blk), None)
                    if j is None:
                        raise
                    self._mark_committed(p_items[:j])
                    if win is not None:
                        e._discard_window(win)
                    if not self._try_quarantine(p_items[j], exc):
                        return
                    buf = p_items[j + 1:] + buf
                    continue
                if resume is not None:
                    # prefix [0, resume) is committed (device blocks +
                    # the host-fallback block); the tail re-enters the
                    # buffer for fresh classification, and the window
                    # speculatively issued above ran on a stale base
                    self._mark_committed(p_items[:resume])
                    if win is not None:
                        e._discard_window(win)
                    buf = p_items[resume:] + buf
                    continue
                self._mark_committed(p_items)
            if win is not None:
                pending = (win, buf[:k])
                buf = buf[k:]
                continue
            if buf:
                # head is not transfer-classifiable and nothing is in
                # flight: machine-OCC run / exact host path, exactly
                # like batch replay's hit_fallback branch
                blocks = [it.block for it in buf]
                try:
                    n = e._machine_run(blocks, 0)
                except ReplayError as exc:
                    blk = getattr(exc, "block", None)
                    if blk is None or not self.quarantine:
                        raise
                    # blocks before the poison one were committed
                    # (the fallback flushes staged work first)
                    j = next((i for i, it in enumerate(buf)
                              if it.block is blk), None)
                    if j is None:
                        raise
                    self._mark_committed(buf[:j])
                    if not self._try_quarantine(buf[j], exc):
                        return
                    buf = buf[j + 1:]
                    continue
                self._mark_committed(buf[:n])
                buf = buf[n:]

    # -------------------------------------------------------------- run
    def run(self) -> StreamReport:
        """Drive the pipeline until the feed exhausts (or shutdown()),
        then drain in-flight work, flush the commit stage, and return
        the SLO report.  The engine ends on the same root batch replay
        would produce for the blocks that were committed."""
        t_start = time.monotonic()
        # live inspection while the stream runs: /metrics (Prometheus),
        # /trace (Perfetto JSON), /report (this run's live report) —
        # opt-in via CORETH_TELEMETRY_PORT (obs/server.py).  The stop
        # lives in the OUTERMOST finally, immediately below the start:
        # no failure after this point may leak the listener thread.
        from coreth_tpu.obs.server import maybe_start_from_env
        self._telemetry = maybe_start_from_env(
            registry=self._registry, report=self._live_report)
        try:
            restore = self._wrap_commit()
            feed_t = threading.Thread(target=self._feed_loop,
                                      name="serve-feed", daemon=True)
            pre_t = threading.Thread(target=self._prefetch_loop,
                                     name="serve-prefetch", daemon=True)
            # the execute stage is the engine's replay thread: claim
            # its account BEFORE the prefetch thread can call
            # warm_senders, whose time is not this thread's
            acct = self.engine.account
            claim = acct.begin()
            feed_t.start()
            pre_t.start()
            try:
                try:
                    self._drive()
                finally:
                    self._stop.set()
                    feed_t.join(timeout=10)
                    pre_t.join(timeout=10)
                    # anything still staged belongs to completed blocks
                    self.engine.commit_pipe.flush()
                    restore()
                    acct.mark_cpu()   # the drain since the last wait
                    acct.end(claim)
                if self._errors:
                    raise self._errors[0]
                if self._ckpt is not None and self.stats.blocks:
                    # final checkpoint: the whole committed stream is
                    # durable, a restart resumes at the exact tail.  In
                    # background mode write() stamps the tip and DRAINS
                    # the flat exporter — the one synchronous wait, at
                    # shutdown, not per interval.
                    self._ckpt.write()
            finally:
                if self._ckpt is not None:
                    # ALWAYS stop the exporter thread — an error path
                    # that skipped it would leak one polling thread per
                    # failed run
                    self._ckpt.close()
        finally:
            if self._telemetry is not None:
                # same argument for the telemetry listener thread
                self._telemetry.stop()
                self._telemetry = None
            # CORETH_TRACE_OUT: flush the ring to a Perfetto-loadable
            # file (failures counted, never raised — obs/export_fail)
            obs.write_out()
            # forensics: a trigger still waiting for a witness at
            # shutdown (a crash-path oracle trip) freezes as a
            # context-only bundle instead of evaporating
            forensics.flush_pending()
        wall = time.monotonic() - t_start
        self._publish(wall)
        return self.stats

    def _live_report(self) -> dict:  # corethlint: thread telemetry-report — called by the TelemetryServer handler thread while the stream runs
        """The /report payload while the stream runs: the report row
        with the CURRENT latency histogram and stage attribution
        spliced in (the final _publish numbers are richer; this is the
        mid-run view)."""
        row = self.stats.row()
        snap = self._latency.snapshot()
        row["latency_ms"] = {
            "p50": round(1000 * snap["p50"], 3),
            "p99": round(1000 * snap["p99"], 3),
            "max": round(1000 * snap["max"], 3),
        }
        if self._stages is not None:
            row["stage_breakdown"] = self._stages.breakdown()
        # as of the execute stage's last phase boundary
        row["account"] = self.engine.account.row()
        row["lanes"] = self._lanes()
        rec = forensics.recorder()
        if rec is not None:
            # quarantine forensics, live: counters + bundle paths for
            # already-drained bundles (entries parked mid-run show
            # their replay handle without waiting for the final report)
            row["forensics"] = rec.snapshot()
            for entry in row["quarantined"]:
                paths = rec.bundles_for(entry["number"])
                if paths:
                    entry["bundle"] = paths[-1]
        row["committed_blocks"] = self._committed_blocks
        row["enqueued_blocks"] = self._enqueued
        return row

    def rollback_quarantined(self) -> dict:
        """Pop the NEWEST quarantined block (its tolerantly-applied
        state transition reverts through engine.rollback_block: the one
        rollback primitive, which consensus's Reject of a processing
        block takes too) so a corrected block can be streamed in its
        place.  Call after
        run() returned (the engine is single-owner again).  Returns
        the popped quarantine report entry."""
        if not self._quarantined_blocks:
            raise ValueError("no quarantined block to roll back")
        blk = self._quarantined_blocks[-1]
        self.engine.rollback_block(blk)
        self._quarantined_blocks.pop()
        entry = self.stats.quarantined.pop()
        self.stats.blocks -= 1
        self.stats.txs -= len(blk.transactions)
        with self._mu:
            self._committed_blocks -= 1
        # the replacement block re-enters at the popped number
        self._expect_number = blk.number
        return entry

    def shutdown(self) -> None:
        """Mid-stream stop: the feed stops pulling, in-flight queues
        drain what fits, the pending window validates, staged commits
        flush.  run() returns its report as usual."""
        self._shutdown_called = True
        self._stop.set()

    # ------------------------------------------------------------ report
    def _lanes(self) -> dict:
        st = self.engine.stats
        return {"real": st.lanes_real, "padded": st.lanes_padded,
                "machine_real": st.machine_lanes_real,
                "machine_padded": st.machine_lanes_padded,
                "machine_frames": st.machine_frames,
                "machine_lane_steps": st.machine_lane_steps,
                "machine_fused_lane_steps": st.machine_fused_lane_steps,
                "machine_xla_lane_steps": st.machine_xla_lane_steps,
                "machine_fused_exits": st.machine_fused_exits,
                "machine_frame_host_txs": dict(
                    st.machine_frame_host_txs),
                "window_uploads": st.window_uploads,
                "window_upload_bytes": st.window_upload_bytes,
                "blocks_order_dependent": st.blocks_order_dependent,
                "sigs_left_to_signer": st.sigs_left_to_signer,
                "sigs_slow_path": st.sigs_slow_path,
                "receipt_blocks_python": st.receipt_blocks_python}

    @staticmethod
    def _thread_seconds(acct) -> dict:
        """Wall seconds by phase of a stage thread's account ({}: the
        thread never started)."""
        return {} if acct is None else acct.row()["self_s"]

    def _publish(self, wall: float) -> None:
        s = self.stats
        s.wall_s = round(wall, 3)
        span = None
        if self._t_first_enqueue is not None \
                and self._t_last_commit is not None:
            span = self._t_last_commit - self._t_first_enqueue
        s.sustained_txs_s = round(s.txs / span, 1) if span else 0.0
        snap = self._latency.snapshot()
        s.latency_ms = {
            "p50": round(1000 * snap["p50"], 3),
            "p99": round(1000 * snap["p99"], 3),
            "max": round(1000 * snap["max"], 3),
        }
        # the other two threads' seconds, from their own accounts
        feed = self._thread_seconds(self._feed_acct)
        pre = self._thread_seconds(self._prefetch_acct)
        warm_s = sum(v for k, v in pre.items()
                     if k.startswith("sender/")) \
            + pre.get("prefetch/touch_code", 0.0)
        s.prefetch = {
            "hits": self._prefetch_hits,
            "sigs": self.prefetcher.sigs,
            "code_touches": self.prefetcher.code_touches,
            "overlap_s": round(warm_s, 3),
            "reads_prefetched": self.engine.stats.reads_prefetched,
        }
        s.queues = {
            "depth": self.depth,
            "max_inflight": self._max_inflight,
        }
        s.stages_s = {
            "prefetch": round(warm_s, 3),
            "commit": round(self._t_commit, 3),
        }
        s.backpressure = {
            "feed_blocked_s": round(feed.get("feed/put", 0.0), 3),
            "prefetch_blocked_s": round(pre.get("prefetch/put", 0.0), 3),
            "commit_flushes": self._commit_flushes,
        }
        s.shutdown = self._shutdown_called
        # fault-tolerance surface: ladder counters, checkpoint
        # cadence, and what the armed plan (if any) actually fired
        sup = getattr(self.engine, "supervisor", None)
        if sup is not None:
            s.supervisor = sup.snapshot()
            sup.publish(self._registry)
        if self._ckpt is not None:
            s.checkpoint = self._ckpt.snapshot()
        flat = getattr(self.engine, "flat", None)
        if flat is not None:
            s.flat = flat.snapshot()
        s.account = self.engine.account.row()
        s.lanes = self._lanes()
        if self._stages is not None:
            # per-stage share of enqueue->committed time (sums to ~1.0
            # across queue_feed/prefetch/queue_exec/execute/commit) —
            # THIS run's sink, not the process-global tracer's
            s.stage_breakdown = self._stages.breakdown()
        rec = forensics.recorder()
        if rec is not None:
            # wait for queued bundle writes, then surface them: the
            # report carries the forensics counters and every
            # quarantined entry gains its bundle path (the offline
            # replay handle for exactly that block)
            rec.drain()
            s.forensics = rec.snapshot()
            rec.publish(self._registry)
            for entry in s.quarantined:
                paths = rec.bundles_for(entry["number"])
                if paths:
                    entry["bundle"] = paths[-1]
        s.faults = faults.fired()
        # SLO surface in the metrics registry (scrapeable next to the
        # engine's replay/* gauges)
        reg = self._registry
        get_or_register("serve/block_latency", Histogram,
                        reg).replace_from(self._latency)
        get_or_register("serve/sustained_txs_s", Gauge,
                        reg).update(s.sustained_txs_s)
        get_or_register("serve/blocks", Gauge, reg).update(s.blocks)

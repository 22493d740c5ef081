"""Cluster worker: one StreamingPipeline lane per assignment.

``python -m coreth_tpu.serve.cluster.worker --connect HOST:PORT
--worker ID`` dials the coordinator, says hello, and then serves
assignments until drained: each ``assign`` names a lane (its
contiguous block range), the lane's seeded store, and the shared
chain file.  The worker resumes an engine from the lane's scoped
``ReplayCheckpoint/<lane>`` record — the SAME path a replacement
worker takes after a crash, so recovery is not a special case — runs
the existing streaming pipeline over the remaining blocks, and
reports the boundary root plus its full ``StreamReport`` row and
metrics snapshot for the coordinator to federate.

While the pipeline runs, a heartbeat thread emits liveness +
progress, and promotes every newly durable checkpoint record into a
``checkpoint_advance`` message — the coordinator's recovery horizon.

Fault points (coreth_tpu/faults):

- ``cluster/heartbeat_loss``: the heartbeat tick consults ``check()``
  and DROPS the send when armed — the network-partition shape; the
  worker stays alive and productive while the coordinator's timeout
  policy decides its fate.
- ``cluster/boundary_mismatch``: corrupts the REPORTED boundary root
  (state on disk stays correct) — the lying-worker shape the
  aggregator must catch by verification, not trust.
"""

from __future__ import annotations

import argparse
import os
import socket
import threading
import time  # noqa: DET003 — control-plane cadence/wall-clock only, never consensus data
from typing import Optional

from coreth_tpu import faults, obs
from coreth_tpu import rlp
from coreth_tpu.obs import recorder as _forensics
from coreth_tpu.serve.cluster import protocol
from coreth_tpu.serve.cluster.bootstrap import open_store

PT_HEARTBEAT_LOSS = faults.declare(
    "cluster/heartbeat_loss",
    "worker heartbeats dropped while the worker stays alive "
    "(network-partition shape; serve/cluster/worker.py tick)")
PT_BOUNDARY_MISMATCH = faults.declare(
    "cluster/boundary_mismatch",
    "worker reports a corrupted boundary root while its store stays "
    "correct (serve/cluster/worker.py boundary report)")

# chain-config vocabulary for assignment messages (a config object
# cannot travel as JSON); extend as workloads need them
def _config(name: str):
    from coreth_tpu import params
    table = {
        "test": params.TEST_CHAIN_CONFIG,
        "ap5": params.TEST_APRICOT_PHASE5_CONFIG,
    }
    if name not in table:
        raise protocol.ProtocolError(f"unknown chain config {name!r}")
    return table[name]


def _jsonable(obj):
    """Bytes-free copy for the control protocol (roots/hashes -> hex);
    drops values JSON cannot carry."""
    if isinstance(obj, (bytes, bytearray)):
        return obj.hex()
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return str(obj)


class HeartbeatSender:
    """Periodic heartbeat + checkpoint-advance emitter.

    Injectable ``clock``/``send`` keep the drop fault and the
    coordinator's timeout detection unit-testable without sockets or
    sleeps (tests/test_cluster.py).  ``progress`` returns the live
    (committed_blocks, txs) pair; ``record`` the newest durable
    checkpoint number (None while none landed).
    """

    def __init__(self, send, worker: str, lane: str, period: float,
                 progress=None, record=None,
                 clock=time.monotonic):
        self.send = send
        self.worker = worker
        self.lane = lane
        self.period = period
        self.progress = progress or (lambda: (0, 0))
        self.record = record or (lambda: None)
        self.clock = clock
        self.sent = 0
        self.dropped = 0
        self.last_record: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def tick(self) -> bool:
        """One heartbeat cycle; False when the armed loss fault ate
        the send (the worker is alive — the wire is not)."""
        advanced = self.record()
        if faults.check(PT_HEARTBEAT_LOSS) is not None:
            self.dropped += 1  # corethlint: shared tick() has one caller at a time — the loop thread in production, the test body in units; never both
            return False
        committed, txs = self.progress()
        self.send({"verb": "heartbeat", "worker": self.worker,
                   "lane": self.lane, "committed": committed,
                   "txs": txs})
        if advanced is not None and advanced != self.last_record:
            self.last_record = advanced  # corethlint: shared single tick() caller (see dropped above)
            self.send({"verb": "checkpoint_advance",
                       "worker": self.worker, "lane": self.lane,
                       "number": advanced})
        self.sent += 1  # corethlint: shared single tick() caller (see dropped above)
        return True

    def _loop(self) -> None:
        while not self._stop.wait(self.period):
            try:
                self.tick()
            except OSError:
                return  # coordinator gone; the main loop will notice

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="cluster-heartbeat", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


class ClusterWorker:
    """The worker-side protocol loop over one coordinator socket."""

    def __init__(self, sock: socket.socket, worker_id: str):
        self.sock = sock
        self.worker_id = worker_id
        self.buf = bytearray()
        # the heartbeat thread and the main loop both write the one
        # socket; frames must not interleave
        self._send_mu = threading.Lock()
        self.heartbeat_s = float(os.environ.get(
            "CORETH_CLUSTER_HEARTBEAT_S", "0.5"))

    def send(self, msg: dict) -> None:
        with self._send_mu:
            protocol.send_msg(self.sock, msg)

    # ------------------------------------------------------------- serve
    def run(self) -> None:
        self.send({"verb": "hello", "worker": self.worker_id,
                   "pid": os.getpid()})
        while True:
            msg = protocol.recv_msg(self.sock, self.buf)
            if msg is None:
                return
            verb = msg["verb"]
            if verb == "assign":
                try:
                    self._serve_range(msg)
                except Exception as exc:  # noqa: BLE001 — the coordinator owns the failure policy; a dying worker must say why before the socket drops
                    self.send({"verb": "error",
                               "worker": self.worker_id,
                               "lane": msg.get("lane"),
                               "reason": f"{type(exc).__name__}: {exc}"})
                    raise
            elif verb == "drain":
                if msg.get("bundle"):
                    self._send_bundles(msg)
                return
            else:
                raise protocol.ProtocolError(
                    f"coordinator sent worker-only verb {verb!r}")

    def _serve_range(self, msg: dict) -> None:
        from coreth_tpu.replay.checkpoint import resume_engine
        from coreth_tpu.serve import ChainFeed, StreamingPipeline
        from coreth_tpu.types import Block
        lane, start, end = msg["lane"], msg["start"], msg["end"]
        kv, db = open_store(msg["db_dir"])
        try:
            engine_kw = msg.get("engine") or {}
            eng, ckpt = resume_engine(_config(msg.get("config",
                                                      "test")),
                                      db, kv, worker=lane, **engine_kw)
            if eng is None:
                raise RuntimeError(
                    f"lane {lane} store has no seed record")
            wire = rlp.decode(open(msg["chain"], "rb").read())
            # wire[j] is block number j+1; the lane owns (start, end]
            # and the record closes everything through ckpt.number
            rest = [Block.decode(w) for w in wire[ckpt.number:end]]
            rate = msg.get("feed_rate") or None
            pipe = StreamingPipeline(
                eng, ChainFeed(rest, rate=rate), window_wait=0.005,
                checkpoint_every=msg.get("checkpoint_every") or int(
                    os.environ.get("CORETH_CLUSTER_CHECKPOINT", "4")),
                checkpoint_worker=lane)
            hb = HeartbeatSender(
                self.send, self.worker_id, lane, self.heartbeat_s,
                progress=lambda: (pipe._committed_blocks,
                                  pipe.stats.txs),
                record=lambda: (pipe._ckpt.last_number
                                if pipe._ckpt is not None else None))
            hb.start()
            try:
                # flow id = the lane's first block: the assign arrow
                # from the coordinator continues into execution here
                with obs.span("cluster/execute", flow=start + 1,
                              lane=lane, start=start, end=end):
                    rep = pipe.run()
            finally:
                hb.stop()
            root = eng.root
            spec = faults.check(PT_BOUNDARY_MISMATCH)
            if spec is not None:
                # lie about the boundary (state on disk stays right):
                # the aggregator must catch this by verification
                root = bytes(b ^ 0xFF for b in root)
            self.send({"verb": "boundary_root",
                       "worker": self.worker_id, "lane": lane,
                       "root": root.hex(),
                       "resumed_from": ckpt.number,
                       "blocks": rep.blocks,
                       "report": _jsonable(rep.row()),
                       "metrics": _jsonable(
                           pipe._registry.snapshot()
                           if pipe._registry is not None else {})})
        finally:
            kv.close()

    def _send_bundles(self, msg: dict) -> None:
        """The root-mismatch escrow: freeze this worker's forensic
        evidence and hand the bundle paths over before exiting."""
        rec = _forensics.recorder()
        paths = []
        if rec is not None:
            _forensics.note_trigger(
                _forensics.TR_BOUNDARY,
                msg.get("reason", "coordinator demanded bundles"))
            rec.flush_pending()
            rec.drain()
            paths = [b["path"] for b in rec.snapshot()["bundles"]]
        self.send({"verb": "bundle", "worker": self.worker_id,
                   "lane": msg.get("lane"), "paths": paths})


def run_worker(host: str, port: int, worker_id: str) -> None:
    sock = socket.create_connection((host, port))
    try:
        ClusterWorker(sock, worker_id).run()
    finally:
        try:
            sock.close()
        except OSError:
            pass


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--connect", required=True,
                    help="coordinator HOST:PORT")
    ap.add_argument("--worker", required=True, help="worker id")
    args = ap.parse_args(argv)
    from coreth_tpu import compile_cache
    compile_cache.configure()
    host, port = args.connect.rsplit(":", 1)
    run_worker(host, int(port), args.worker)


if __name__ == "__main__":
    main()

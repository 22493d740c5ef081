"""Prefetch stage: resolve window N+1's inputs while window N executes.

Two prefetch channels, both measured (the acceptance counter for the
streaming bench is "prefetch-hit or overlap counter > 0"):

- **Sender recovery** (host, GIL-releasing): arriving blocks are
  batched through the engine's packed ECDSA recovery
  (``ReplayEngine.warm_senders`` — native C++ batch or the device
  ladder) on the prefetch thread, so by the time the execute stage
  classifies a block its senders are already cached.  ``sigs`` counts
  signatures recovered here; the pipeline's ``prefetch_hits`` counts
  the txs whose sender the execute stage found pre-cached.  The
  device/mesh-sharded ladder is no longer serve-only: batch replay's
  ``_SenderPipeline`` honors the same ``CORETH_SHARD_RECOVER`` opt-in
  and overlaps a window's recovery with the previous window's
  execution (replay/engine.py).

- **Bytecode** : call-shaped txs touch ``db.contract_code`` for their
  callee's code hash so the machine classifier's first read hits the
  rawdb dict instead of a cold path.  Account/slot resolution itself
  stays on the execute thread — it reads and extends the engine's trie
  and DeviceState mirrors, which the commit stage mutates; the third
  prefetch channel (the *fetch-tensor* download of an issued window)
  therefore lives in the engine: ``_issue_window`` starts the
  device->host copy of the window's fetch tensor asynchronously at
  issue time (``ReplayStats.reads_prefetched``), converting the old
  blocking per-window download into a windowed read that overlaps the
  next window's host work.
"""

from __future__ import annotations

import os
import threading
import time
from typing import List

from coreth_tpu import obs
from coreth_tpu.types import Block


class Prefetcher:
    """Stage worker: warms a chunk of blocks for the execute stage."""

    def __init__(self, engine):
        self.e = engine
        # counters land from the prefetch stage thread while the
        # pipeline report reads them — writes hold _mu
        self._mu = threading.Lock()
        self.sigs = 0
        self.shard_sigs = 0   # recovered via the mesh-sharded ladder
        self.code_touches = 0
        self.busy_s = 0.0

    def warm(self, blocks: List[Block]) -> None:
        t0 = time.monotonic()
        with obs.span("serve/prefetch_warm", blocks=len(blocks)):
            todo = sum(1 for b in blocks for tx in b.transactions
                       if tx.cached_sender() is None)
            if todo:
                if not self._shard_recover(blocks):
                    self.e.warm_senders(blocks)
                with self._mu:
                    self.sigs += todo
            self._touch_code(blocks)
        dt = time.monotonic() - t0
        with self._mu:
            self.busy_s += dt

    def _shard_recover(self, blocks: List[Block]) -> bool:
        """CORETH_SHARD_RECOVER=1 + a dp mesh: recover this chunk's
        senders on the device-sharded ECDSA ladder (parallel/mesh.py
        sharded_recover — the signature batch fans out across shards)
        instead of the native host batch.  Falls back (returns False)
        whenever the mesh path cannot serve the batch, so recovery
        semantics never change — only the engine doing the work.
        Parity with the native path is pinned by tests/test_shard_replay."""
        if not bool(int(os.environ.get("CORETH_SHARD_RECOVER", "0"))):
            return False
        e = self.e
        # _recover_kernel owns the eligibility rule (mesh present,
        # pad-floor divisibility): None means no sharded ladder
        kernel = e._recover_kernel() if hasattr(e, "_recover_kernel") \
            else None
        if kernel is None:
            return False
        t0 = time.monotonic()
        try:
            todo, hashes, rs, ss, recids = e._pack_sigs(blocks)
            if not todo:
                return True
            from coreth_tpu.crypto.secp_device import (
                complete_recover, issue_recover)
            ctxs = issue_recover(hashes, rs, ss, recids, kernel=kernel)
            out, ok = complete_recover(ctxs)
            if out is None:
                return False
            e._apply_recovered(todo, out, ok)
            with self._mu:
                self.shard_sigs += len(todo)
            return True
        except Exception:  # noqa: BLE001 — advisory: host path recovers
            e.stats.recover_degraded += 1
            return False
        finally:
            # keep the engine's phase attribution honest: this IS
            # sender-recovery time, same as warm_senders accounts it
            e.stats.t_sender += time.monotonic() - t0

    def _touch_code(self, blocks: List[Block]) -> None:
        """Pull callee bytecode for call-shaped txs into the rawdb read
        path.  Reads only: the engine's account index/trie belong to
        the execute thread, so resolution goes through the already-
        known DeviceState rows and skips anything not yet indexed."""
        e = self.e
        state = e.state
        for b in blocks:
            for tx in b.transactions:
                if tx.to is None or not tx.data:
                    continue
                idx = state.index.get(tx.to)
                if idx is None or not state.has_code[idx]:
                    continue
                try:
                    e.db.contract_code(state.code_hashes[idx])
                    with self._mu:
                        self.code_touches += 1
                except Exception:  # noqa: BLE001 — prefetch is advisory
                    pass

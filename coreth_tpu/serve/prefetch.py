"""Prefetch stage: resolve window N+1's inputs while window N executes.

Two prefetch channels, both measured (the acceptance counter for the
streaming bench is "prefetch-hit or overlap counter > 0"):

- **Sender recovery** (host, GIL-releasing): arriving blocks are
  batched through the engine's packed ECDSA recovery
  (``ReplayEngine.warm_senders`` — the native C++ batch, the one batch
  engine) on the prefetch thread, so by the time the execute stage
  classifies a block its senders are already cached.  ``sigs`` counts
  signatures recovered here; the pipeline's ``prefetch_hits`` counts
  the txs whose sender the execute stage found pre-cached.

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

import threading
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
        self.code_touches = 0

    def warm(self, blocks: List[Block]) -> None:
        """On the pipeline's prefetch thread this is phase
        ``prefetch/touch_code`` of that thread's account, with
        ``warm_senders``' ``sender/*`` phases inside it."""
        with obs.span("serve/prefetch_warm", blocks=len(blocks)):
            todo = sum(1 for b in blocks for tx in b.transactions
                       if tx.cached_sender() is None)
            if todo:
                self.e.warm_senders(blocks)
                with self._mu:
                    self.sigs += todo
            self._touch_code(blocks)

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

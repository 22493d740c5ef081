"""The device engine behind BlockChain's insert / accept / reject.

A Coreth node is driven by AvalancheGo through ``snowman.Block``:
Verify, then — maybe much later, maybe never — Accept or Reject, one
block at a time, and a block that is verified is not yet accepted: its
sibling may be accepted in its place.  ``BlockChain`` (chain/) speaks
that contract on the host ``Processor``; this is the same contract on a
``ReplayEngine``, selected by the VM's config key ``state-processor``
(plugin/config.py; the host processor stays the default).

One rule.  The engine follows ONE branch: the last accepted block and,
on top of it, the processing blocks it has executed, each child of the
one before.  A block that extends that tip is executed by
``ReplayEngine.replay_block(hold=True)`` — a window of one, held to its
header, its nodes committed to the Database the chain shares, and
revertible.  A block that does not (a sibling, a side branch) takes
the chain's host path on a StateDB at its parent's root.  Accept of the
oldest processing block retires its undo record; Accept of a block the
engine does not hold brings the engine back to the fork point (undo,
newest first) and runs the accepted block there, so the engine's tip
never falls behind the last accepted block; Reject of a block on the
engine's branch undoes down to its parent.  SetPreference does not move
the engine.  Processing versions are ``state/flat`` generations, pinned
until decided: no third state layer.

chain/ may not import replay/ (tools/lint/layers.toml), so ``eth``
hands ``BlockChain`` this class as a factory and the chain calls what
it is given: ``extends_tip``, ``execute``, ``accept``, ``reject``,
``reset``, ``note_host_verified``, ``account``.

Atomic txs (a VM with shared memory).  The engine's window of one makes
the calls the host path makes through ``on_extra_state_change``: its
classify asks the chain's consensus callbacks for the block's atomic
bookkeeping (``on_extra_state_rows``: semantic verify, conflicts, the
backend's record of the block) and applies the state transfer as lanes
of the same window.  Accept and Reject of the atomic effect stay the
VM's (shared memory, the atomic trie); a rollback takes the atomic rows
back with the block's other rows, since they are device rows and flat
generations like any other.  An accepted block run again here after a
rollback finds its record in the backend and is not recorded twice.
"""

from __future__ import annotations

from typing import List

from coreth_tpu.chain.blockchain import BadBlockError
from coreth_tpu.replay.engine import ReplayEngine, ReplayError
from coreth_tpu.types import Block, Receipt


def derive_fields(block: Block, receipts: List[Receipt]) -> List[Receipt]:
    """The non-consensus fields of a block's receipts and logs, as the
    host processor's ``apply_transaction`` sets them (the reference's
    ``Receipts.DeriveFields``).  The engine's device paths keep the
    consensus fields alone; a receipt the host fallback made already
    has a transaction hash and is left as it is."""
    block_hash = block.hash()
    number = block.number
    base_fee = block.base_fee
    log_index = 0
    for i, (tx, r) in enumerate(zip(block.transactions, receipts)):
        if r.tx_hash == b"\x00" * 32:
            r.tx_hash = tx.hash()
            r.block_number = number
            # tx_to_message's rule: min(feeCap, baseFee + tip)
            r.effective_gas_price = tx.gas_price if base_fee is None \
                else min(tx.gas_fee_cap, base_fee + tx.gas_tip_cap)
            for j, log in enumerate(r.logs):
                log.tx_hash = r.tx_hash
                log.tx_index = i
                log.block_hash = block_hash
                log.block_number = number
                log.index = log_index + j
        log_index += len(r.logs)
    return receipts


class DeviceProcessor:
    """See the module docstring.  ``engine_kw`` are ReplayEngine's
    constructor arguments, passed programmatically (the VM's
    ``engine_kw=``), never config keys."""

    def __init__(self, chain, **engine_kw):
        self._config = chain.config
        self._db = chain.db
        self._consensus = chain.engine
        self._engine_kw = engine_kw
        self.engine: ReplayEngine = None
        self.reset(chain.last_accepted)

    # ------------------------------------------------------------ engine
    def reset(self, block: Block, stats=None) -> None:
        """A fresh engine on ``block``'s state, whose nodes are in the
        Database: at start-up, after a state-sync pivot, and where the
        engine refused a block consensus accepted."""
        self.engine = ReplayEngine(
            self._config, self._db, block.root,
            parent_header=block.header, engine=self._consensus,
            **self._engine_kw)
        if self.engine.flat is None:
            raise BadBlockError(
                "the device state processor needs the flat layer "
                "(CORETH_FLAT=1): a processing block is one of its "
                "generations")
        self.engine.keep_receipts = True
        if stats is not None:
            self.engine.stats = stats  # the counters outlive the engine
        self._base = block.hash()      # the last accepted block
        self._branch: List[Block] = []  # processing, each on the last

    @property
    def account(self):
        return self.engine.account

    @property
    def stats(self):
        return self.engine.stats

    def tip(self) -> bytes:
        return self._branch[-1].hash() if self._branch else self._base

    # ---------------------------------------------------- Verify / insert
    def extends_tip(self, block: Block) -> bool:
        return block.parent_hash == self.tip()

    def execute(self, block: Block) -> List[Receipt]:
        """Verify's execution of a block on the engine's tip; the
        engine's refusal is the chain's ``BadBlockError`` and leaves the
        engine at the parent."""
        try:
            self.engine.replay_block(block, hold=True)
        except ReplayError as exc:
            raise BadBlockError(str(exc)) from exc
        self._branch.append(block)
        self.engine.stats.blocks_verified_device += 1
        return derive_fields(block, self.engine.last_receipts)

    def note_host_verified(self) -> None:
        self.engine.stats.blocks_verified_host += 1

    # ----------------------------------------------------- Accept / Reject
    def _undo_to(self, depth: int) -> None:
        """Undo the processing blocks past ``depth``, newest first."""
        if len(self._branch) <= depth:
            return
        with self.engine.account.enter("vm/rollback"):
            while len(self._branch) > depth:
                self.engine.rollback_block(self._branch.pop())
        self.engine.stats.engine_rollbacks += 1

    def accept(self, block: Block) -> None:
        """``block`` is a child of the last accepted block."""
        eng = self.engine
        if self._branch and self._branch[0].hash() == block.hash():
            eng.retire_block(self._branch.pop(0).hash())
        else:
            # consensus chose a block beside the engine's branch: back
            # to the fork point, and the accepted block runs there (it
            # was verified on the host path; the engine now holds it to
            # the same header)
            self._undo_to(0)
            try:
                eng.replay_block(block)
                eng.stats.blocks_reapplied += 1
            except ReplayError:
                # the host path took what the engine refuses: serve the
                # accepted state from a fresh engine, and say so
                eng.stats.accepted_off_engine += 1
                self.reset(block, stats=eng.stats)
        self._base = block.hash()
        self.engine.stats.blocks_accepted += 1

    def reject(self, block_hash: bytes) -> None:
        for depth, b in enumerate(self._branch):
            if b.hash() == block_hash:
                self._undo_to(depth)
                break
        self.engine.stats.blocks_rejected += 1

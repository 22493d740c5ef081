"""BlockChain: consensus-less chain store + processing orchestrator.

Twin of reference core/blockchain.go, restructured around the snowman
lifecycle (SURVEY.md section 1): blocks are inserted individually —
possibly as competing siblings — via :meth:`insert_block`, and only
become canonical on :meth:`accept`.  The per-phase timers replicate the
metric split at blockchain.go:1343-1357 (execution / validation /
state-root hashing / write) so TPU-vs-host comparisons decompose the
same way.
"""

from __future__ import annotations

import queue as _queue
import threading as _threading
import time as _time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from coreth_tpu import obs
from coreth_tpu.chain.genesis import Genesis
from coreth_tpu.consensus.engine import ConsensusError, DummyEngine
from coreth_tpu.params import ChainConfig
from coreth_tpu.processor.state_processor import Processor
from coreth_tpu.state import Database, StateDB
from coreth_tpu.mpt import StackTrie
from coreth_tpu.types import Block, Receipt, create_bloom, derive_sha
from coreth_tpu.types.block import calc_ext_data_hash


@dataclass
class PhaseTimers:
    """blockchain.go:1343-1357 insert-phase decomposition (seconds)."""
    sender_recover: float = 0.0
    execution: float = 0.0
    validation: float = 0.0
    state_root: float = 0.0
    write: float = 0.0
    total: float = 0.0
    blocks: int = 0

    def row(self) -> dict:
        return {k: getattr(self, k) for k in
                ("sender_recover", "execution", "validation", "state_root",
                 "write", "total", "blocks")}


class BadBlockError(Exception):
    pass


@dataclass
class _Entry:
    block: Block
    receipts: List[Receipt] = field(default_factory=list)
    status: str = "processed"  # processed | accepted | rejected


class BlockChain:
    def __init__(self, genesis: Genesis, db: Optional[Database] = None,
                 engine: Optional[DummyEngine] = None,
                 chain_kv=None, commit_interval: int = 4096,
                 archive: bool = False, snapshots: bool = True,
                 prefetch: bool = False, freezer_dir=None,
                 freeze_threshold: int = 90_000, state_processor=None):
        """state_processor: None — every block runs on the host
        ``Processor`` — or a factory ``f(chain)`` of the backend that
        executes the blocks extending its tip instead (eth hands over
        replay/device_processor.DeviceProcessor, where the contract is
        written down; chain/ sits below replay/ and imports none of
        it).  A block beside that tip still takes the host path here,
        on the trie alone: the flat-state snapshot tree is the HOST
        backend's processing layer and is not built.

        chain_kv: optional rawdb.KVStore making the chain durable —
        accepted blocks/receipts/canonical index persist immediately,
        trie nodes every `commit_interval` accepts (state_manager.go
        policy); reopening on the same store resumes at the last
        accepted block, re-executing any tail whose trie state was not
        yet flushed (blockchain.go:1750 reprocessState)."""
        self.chain_kv = chain_kv
        self.commit_interval = commit_interval
        self.trie_writer = None
        # built last (below), on the last accepted block: a reopened
        # store's tail re-executes on the host path before it exists
        self.state_processor = None
        if chain_kv is not None:
            if db is not None:
                raise ValueError(
                    "pass either db or chain_kv, not both: the durable "
                    "chain owns its Database via PersistentNodeDict")
            from coreth_tpu.rawdb import (
                PersistentCodeDict, PersistentNodeDict, TrieWriter)
            nodes = PersistentNodeDict(chain_kv)
            db = Database(node_db=nodes,
                          code_db=PersistentCodeDict(chain_kv))
            self.trie_writer = TrieWriter(chain_kv, nodes,
                                          commit_interval, archive)
        self.db = db if db is not None else Database()
        self.config: ChainConfig = genesis.config
        self.engine = engine or DummyEngine()
        self.engine.set_config(self.config)
        self.genesis_block = genesis.to_block(self.db)
        self.processor = Processor(self.config, engine=self.engine)
        g = self.genesis_block
        self._blocks: Dict[bytes, _Entry] = {
            g.hash(): _Entry(g, status="accepted")}
        self._canonical: Dict[int, bytes] = {0: g.hash()}
        self.last_accepted: Block = g
        self._head: Block = g
        # acceptor pipeline (blockchain.go:566-648): accept() returns
        # after the cheap canonical bookkeeping; durable writes + trie
        # flush run on this queue's worker thread, drained by
        # drain_acceptor_queue()/close().  acceptor_tip is the last
        # block whose accept-side effects have fully landed
        # (LastAcceptedBlock vs LastConsensusAcceptedBlock).
        self.acceptor_tip: Block = g  # corethlint: shared single-reference publish by the acceptor thread; readers synchronize via _acceptor_queue.join() in drain_acceptor_queue()
        self._acceptor_queue: _queue.Queue = _queue.Queue()
        self._acceptor_thread: Optional[_threading.Thread] = None
        self._acceptor_error: Optional[BaseException] = None  # corethlint: shared single-reference publish by the acceptor thread; raised on the caller side only after the queue join
        self._head_subs: List[Callable[[Block], None]] = []
        self._accepted_subs: List[Callable[[Block, list], None]] = []
        self.timers = PhaseTimers()
        # flat-state snapshot tree (core/state/snapshot): one diff
        # layer per processed block over a disk layer at the accepted
        # base; StateDB reads go through it, bypassing trie traversal
        self.snaps = None
        snapshots = snapshots and state_processor is None
        self._want_snapshots = snapshots
        # one persistent path-warming worker per chain (KV-backed only;
        # measured OFF by default on the 1-core eval host, where the
        # memory-indexed node store leaves no latency to hide and the
        # GIL makes the warm thread pure contention — BASELINE.md)
        self._prefetcher = None
        if prefetch and chain_kv is not None:
            from coreth_tpu.state.trie_prefetcher import TriePrefetcher
            self._prefetcher = TriePrefetcher(self.db.node_db)
        # ancient store (core/rawdb/freezer.go role): accepted blocks
        # freeze_threshold behind the head migrate from the KV log to
        # immutable flat files on the acceptor thread
        self.freezer = None
        self.freeze_threshold = freeze_threshold
        if freezer_dir is not None and chain_kv is not None:
            from coreth_tpu.rawdb.freezer import Freezer
            self.freezer = Freezer(freezer_dir)
        if chain_kv is not None:
            # _load_last_state seeds the snapshot at the on-disk base
            # (genesis only for a fresh store), so it is not generated
            # twice on reopen
            self._load_last_state()
        elif snapshots:
            from coreth_tpu.state.snapshot import generate_from_trie
            self.snaps = generate_from_trie(self.db, g.root, g.hash())
        if state_processor is not None:
            self.state_processor = state_processor(self)

    # ---------------------------------------------------------- durability
    def _load_last_state(self) -> None:
        """loadLastState + reprocessState (blockchain.go:685, :1750):
        resume at the persisted last-accepted block, re-executing any
        accepted tail whose trie state never reached disk."""
        from coreth_tpu.rawdb import schema
        from coreth_tpu.state.snapshot import generate_from_trie
        g = self.genesis_block
        if schema.read_last_accepted(self.chain_kv) is None:
            # fresh database: persist genesis + its state
            schema.write_block(self.chain_kv, g)
            schema.write_canonical_hash(self.chain_kv, 0, g.hash())
            schema.write_last_accepted(self.chain_kv, g.hash())
            self.trie_writer.force_flush(0, g.root)
            if self._want_snapshots:
                self.snaps = generate_from_trie(self.db, g.root,
                                                g.hash())
            return
        last_hash = schema.read_last_accepted(self.chain_kv)
        last = schema.read_block_by_hash(self.chain_kv, last_hash)
        if last is None:
            raise BadBlockError("missing last accepted block body")
        flushed_root, flushed_height = \
            schema.read_last_flushed_root(self.chain_kv)
        flushed_height = flushed_height or 0
        if self._want_snapshots:
            # rebuild the flat state at the on-disk base (snapshot
            # Rebuild, snapshot.go:745) on a BACKGROUND thread
            # (generate.go): the reopened node serves immediately,
            # reads above the marker fall through to the trie; tail
            # re-execution below adds diff layers on top concurrently
            from coreth_tpu.state.snapshot import Tree
            base_root = flushed_root if flushed_root is not None \
                else g.root
            base_hash = schema.read_canonical_hash(
                self.chain_kv, flushed_height) or g.hash()
            self.snaps = Tree(base_root, base_hash)
            self.snaps.rebuild(self.db, base_root, base_hash)
        # walk the canonical chain from the last flushed state forward,
        # re-executing into memory (insert_block reads parent state
        # through the disk-backed node dict)
        for height in range(flushed_height, last.number + 1):
            h = schema.read_canonical_hash(self.chain_kv, height)
            block = schema.read_block(self.chain_kv, height, h)
            if block is None:
                raise BadBlockError(f"missing canonical block {height}")
            self._canonical[height] = h
            if height == 0 or h == g.hash():
                continue
            if height <= flushed_height:
                # state already on disk: resident without re-execution
                self._blocks[h] = _Entry(block, status="accepted")
            else:
                self.insert_block(block)
                self._blocks[h].status = "accepted"
            self.last_accepted = block
            self._head = block
            self.acceptor_tip = block
        # canonical index below the flushed height stays on disk only;
        # get_block_by_number falls back to the store

    def publish_metrics(self, registry=None, prefix: str = "chain"
                        ) -> None:
        """Feed the per-phase insert timers into a metrics registry
        (the blockchain.go:1343-1357 timer split as gauges)."""
        from coreth_tpu.metrics import Gauge, get_or_register
        for name, value in self.timers.row().items():
            g = get_or_register(f"{prefix}/insert/{name}", Gauge,
                                registry)
            g.update(value)

    def close(self) -> None:
        """Drain the acceptor, flush every pending trie node + the
        store (clean shutdown; blockchain.go Stop).  A sticky acceptor
        error is re-raised AFTER threads are stopped and the store is
        closed, so shutdown never leaks handles or workers."""
        if self._acceptor_thread is not None:
            self._acceptor_queue.join()
        self._stop_acceptor()
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        err = self._acceptor_error
        try:
            if err is None and self.trie_writer is not None:
                self.trie_writer.force_flush(self.last_accepted.number,
                                             self.last_accepted.root)
        finally:
            if self.freezer is not None:
                self.freezer.close()
            if self.chain_kv is not None:
                self.chain_kv.close()
        if err is not None:
            raise err

    # ------------------------------------------------------------- accessors
    def current_block(self) -> Block:
        return self._head

    def subscribe_chain_head(self, cb: Callable[[Block], None]) -> None:
        """chainHeadFeed analog: cb(block) on every head change (the
        txpool's reset driver, txpool.go:379)."""
        self._head_subs.append(cb)

    def subscribe_chain_accepted(self, cb) -> None:
        """chainAcceptedFeed analog: cb(block, receipts) once a block's
        accept-side effects have landed (fired on the acceptor thread,
        blockchain.go:597)."""
        self._accepted_subs.append(cb)

    def get_block(self, block_hash: bytes) -> Optional[Block]:
        entry = self._blocks.get(block_hash)
        if entry is not None:
            return entry.block
        if self.chain_kv is not None:
            from coreth_tpu.rawdb import schema
            blk = schema.read_block_by_hash(self.chain_kv, block_hash)
            if blk is not None:
                return blk
            if self.freezer is not None:
                # frozen: the hash->number index survives migration
                num = schema.read_block_number(self.chain_kv,
                                               block_hash)
                if num is not None:
                    raw = self.freezer.body(num)
                    if raw is not None:
                        return Block.decode(raw)
        return None

    def get_block_by_number(self, number: int) -> Optional[Block]:
        h = self._canonical.get(number)
        if h is not None and h in self._blocks:
            return self._blocks[h].block
        if self.chain_kv is not None:
            from coreth_tpu.rawdb import schema
            h = h or schema.read_canonical_hash(self.chain_kv, number)
            if h is not None:
                blk = schema.read_block(self.chain_kv, number, h)
                if blk is not None:
                    return blk
        if self.freezer is not None:
            raw = self.freezer.body(number)
            if raw is not None:
                return Block.decode(raw)
        return None

    def get_receipts(self, block_hash: bytes) -> Optional[List[Receipt]]:
        entry = self._blocks.get(block_hash)
        if entry is not None and entry.receipts:
            return entry.receipts
        if self.chain_kv is not None:
            from coreth_tpu import rlp
            from coreth_tpu.rawdb import schema
            from coreth_tpu.types.receipt import decode_consensus_receipt
            num = schema.read_block_number(self.chain_kv, block_hash)
            if num is not None:
                raw = schema.read_raw_receipts(self.chain_kv, num,
                                               block_hash)
                if raw is None and self.freezer is not None:
                    payload = self.freezer.receipts(num)
                    # empty payload marks receipts-unknown, not []
                    raw = list(rlp.decode(payload)) \
                        if payload else None
                if raw is not None:
                    return [decode_consensus_receipt(r) for r in raw]
        return entry.receipts if entry else None

    def has_state(self, root: bytes) -> bool:
        from coreth_tpu.mpt import EMPTY_ROOT
        return (root == EMPTY_ROOT or root in self.db.trie_cache
                or root in self.db.node_db)

    def state_at(self, root: bytes) -> StateDB:
        return StateDB(root, self.db)

    def _ancestry_hash_fn(self, parent: Block):
        """BLOCKHASH resolver walking header ancestry from [parent]
        (geth GetHashFn) — correct even for inserted-but-unaccepted
        chains and competing siblings, where the accepted-canonical map
        would lie."""
        def get_hash(number: int) -> bytes:
            cur = parent
            while cur.number > number:
                entry = self._blocks.get(cur.parent_hash)
                if entry is None:
                    return b"\x00" * 32
                cur = entry.block
            return cur.hash() if cur.number == number else b"\x00" * 32
        return get_hash

    # ------------------------------------------------------------ validation
    def _validate_body(self, block: Block) -> None:
        """ValidateBody (block_validator.go): structural roots."""
        header = block.header
        tx_root = derive_sha(block.transactions, StackTrie())
        if tx_root != header.tx_hash:
            raise BadBlockError(
                f"tx root mismatch: {tx_root.hex()} != "
                f"{header.tx_hash.hex()}")
        if calc_ext_data_hash(block.ext_data()) != header.ext_data_hash:
            raise BadBlockError("extdata hash mismatch")
        if block.uncles:
            raise BadBlockError("uncles are not allowed")

    def _validate_state(self, block: Block, statedb: StateDB,
                        receipts: List[Receipt], used_gas: int) -> bytes:
        """ValidateState (block_validator.go): post-execution roots."""
        header = block.header
        if header.gas_used != used_gas:
            raise BadBlockError(
                f"gas used mismatch: header {header.gas_used}, "
                f"actual {used_gas}")
        bloom = create_bloom(receipts)
        if bloom != header.bloom:
            raise BadBlockError("bloom mismatch")
        receipt_root = derive_sha(receipts, StackTrie())
        if receipt_root != header.receipt_hash:
            raise BadBlockError(
                f"receipt root mismatch: {receipt_root.hex()} != "
                f"{header.receipt_hash.hex()}")
        t0 = _time.monotonic()
        root = statedb.intermediate_root(self.config.is_eip158(header.number))
        self.timers.state_root += _time.monotonic() - t0
        if root != header.root:
            raise BadBlockError(
                f"state root mismatch: {root.hex()} != {header.root.hex()}")
        return root

    # --------------------------------------------------------------- insert
    def insert_block(self, block: Block) -> None:
        """InsertBlockManual (blockchain.go:1241-1357): verify + execute +
        keep resident; canonicality is decided later by accept().  With
        a ``state_processor`` a block that extends its tip is executed
        there and every other block here, on the host path."""
        t_start = _time.monotonic()
        if block.hash() in self._blocks:
            return
        parent_entry = self._blocks.get(block.parent_hash)
        if parent_entry is None:
            raise BadBlockError("unknown ancestor")
        parent = parent_entry.block
        backend = self.state_processor
        # the chain's bookkeeping round the backend's call, as a phase
        # of the backend's account (its own phases take their time out)
        acct = backend.account if backend is not None \
            else obs.NULL_ACCOUNT
        acct.enter("vm/insert")
        try:
            self.engine.verify_header(self.config, block.header,
                                      parent.header)
            self._validate_body(block)
            if backend is not None and backend.extends_tip(block):
                t0 = _time.monotonic()
                receipts = backend.execute(block)
                self.timers.execution += _time.monotonic() - t0
            else:
                receipts = self._execute_on_host(block, parent)
                if backend is not None:
                    backend.note_host_verified()
            for i, r in enumerate(receipts):
                r.block_hash = block.hash()
                r.transaction_index = i
            self._blocks[block.hash()] = _Entry(block, receipts)
            # writeBlockAndSetHead (blockchain.go:1134): a block extending
            # the current head optimistically becomes the new canonical
            # tip; a competing sibling stays a side block until consensus
            # prefers or accepts it (newTip check, :1127)
            if block.parent_hash == self._head.hash():
                self._write_head_block(block)
        finally:
            acct.exit()
        self.timers.total += _time.monotonic() - t_start
        self.timers.blocks += 1

    def _execute_on_host(self, block: Block, parent: Block
                         ) -> List[Receipt]:
        """The host ``Processor`` on a StateDB at the parent's root,
        held to the header, committed; returns the receipts."""
        t0 = _time.monotonic()
        # warm the sender cache (senderCacher.Recover analog; the TPU
        # path batches this through the native/ecrecover kernel)
        from coreth_tpu.types import LatestSigner
        signer = LatestSigner(self.config.chain_id)
        for tx in block.transactions:
            signer.sender(tx)
        self.timers.sender_recover += _time.monotonic() - t0
        # read through the parent block's flat-state layer when one is
        # live (statedb.go:147 New with snaps); the trie stays
        # authoritative for hashing.  A missing layer (parent flattened
        # away under a sibling) degrades to trie reads.
        snap_layer = (self.snaps.snapshot(block.parent_hash)
                      if self.snaps is not None else None)
        statedb = StateDB(parent.root, self.db, snap=snap_layer)
        if self._prefetcher is not None:
            # StartPrefetcher (blockchain.go:1319): warm KV-resident
            # trie paths concurrently with execution so the hashing
            # phase hits the in-memory node cache.  Pointless without
            # a KV store — then every node is already in memory.
            statedb.prefetcher = self._prefetcher
        t0 = _time.monotonic()
        receipts, logs, used_gas = self.processor.process(
            block, parent.header, statedb,
            get_hash=self._ancestry_hash_fn(parent))
        self.timers.execution += _time.monotonic() - t0
        if statedb.prefetcher is not None:
            # drain before hashing (StopPrefetcher role); the hash
            # phase below reads the now-warm node cache
            statedb.prefetcher = None
            self._prefetcher.drain()
        t0 = _time.monotonic()
        self._validate_state(block, statedb, receipts, used_gas)
        self.timers.validation += _time.monotonic() - t0
        t0 = _time.monotonic()
        statedb.commit(delete_empty_objects=True)
        if snap_layer is not None:
            # new diff layer for this block (snaps.Update at
            # writeBlockWithState, blockchain.go:1384)
            from coreth_tpu.state.snapshot import (SnapshotError,
                                                   diff_from_statedb)
            accounts, storage, destructs = diff_from_statedb(statedb)
            try:
                self.snaps.update(block.hash(), block.parent_hash,
                                  block.root, accounts, storage,
                                  destructs)
            except SnapshotError:
                # parent layer flattened past by the acceptor while
                # this block executed: reads just degrade to the trie
                pass
        self.timers.write += _time.monotonic() - t0
        return receipts

    def insert_chain(self, blocks: List[Block]) -> int:
        for i, b in enumerate(blocks):
            self.insert_block(b)
            self.accept(b.hash())
        return len(blocks)

    # ----------------------------------------------------------- head/reorg
    def _write_head_block(self, block: Block) -> None:
        """writeHeadBlock + chainHeadFeed: extend the canonical index,
        move head, notify subscribers (every head transition routes
        through here — optimistic insert tip, preference, reorg)."""
        self._canonical[block.number] = block.hash()
        self._head = block
        for cb in self._head_subs:
            cb(block)

    def _reorg(self, old_head: Block, new_head: Block) -> None:
        """reorg (blockchain.go:1429): rewind the canonical index to
        the branch of [new_head].  Refuses to orphan accepted blocks —
        the common ancestor must be at or above last_accepted."""
        new_chain: List[Block] = []
        old_block, new_block = old_head, new_head
        while new_block.number > old_block.number:
            new_chain.append(new_block)
            new_block = self._require_block(new_block.parent_hash)
        while old_block.number > new_block.number:
            old_block = self._require_block(old_block.parent_hash)
        while old_block.hash() != new_block.hash():
            new_chain.append(new_block)
            old_block = self._require_block(old_block.parent_hash)
            new_block = self._require_block(new_block.parent_hash)
        if new_block.number < self.last_accepted.number:
            raise BadBlockError(
                f"cannot orphan finalized block at height "
                f"{self.last_accepted.number} to common block at height "
                f"{new_block.number}")
        # canonical entries for the new branch (reverse order), then
        # delete stale assignments above the new head (old branch
        # longer than new)
        for b in reversed(new_chain):
            self._canonical[b.number] = b.hash()
        n = new_head.number + 1
        while self._canonical.pop(n, None) is not None:
            n += 1
        # _head itself moves in the caller's _write_head_block

    def _require_block(self, block_hash: bytes) -> Block:
        b = self.get_block(block_hash)
        if b is None:
            raise BadBlockError("missing block during reorg walk")
        return b

    def set_preference(self, block_hash: bytes) -> None:
        """SetPreference (blockchain.go:980): move the head to an
        already-inserted block, reorging the canonical index across
        branches when necessary, and notify head subscribers."""
        entry = self._blocks.get(block_hash)
        if entry is None:
            raise BadBlockError("preferring unknown block")
        block = entry.block
        if self._head.hash() == block_hash:
            return
        if block.parent_hash != self._head.hash():
            self._reorg(self._head, block)
        self._write_head_block(block)

    # -------------------------------------------------------- accept/reject
    def accept(self, block_hash: bytes) -> None:
        """Accept (blockchain.go:1041): pin finality + enqueue the
        durable side effects on the acceptor."""
        entry = self._blocks.get(block_hash)
        if entry is None:
            raise BadBlockError("accepting unknown block")
        # surface a pending acceptor failure BEFORE mutating finality
        # state, so a failed accept leaves the chain untouched
        if self._acceptor_error is not None:
            raise self._acceptor_error
        block = entry.block
        if block.parent_hash != self.last_accepted.hash():
            raise BadBlockError(
                "accepted block is not a child of the last accepted block")
        if self.state_processor is not None:
            # on its branch: the undo record goes; beside it: back to
            # the fork point and this block runs there
            self.state_processor.accept(block)
        # accepting a non-canonical sibling reorgs preference to it
        # (blockchain.go:1059)
        if self._canonical.get(block.number) != block_hash:
            self.set_preference(block_hash)
        entry.status = "accepted"
        self.last_accepted = block
        # flatten synchronously: the disk layer is merged in place, and
        # insert_block (same thread) reads through it — running this on
        # the acceptor thread would let a concurrent sibling insert see
        # a half-merged base (the reference swaps in a fresh disk layer
        # instead, snapshot.go diffToDisk; in-place + same-thread is
        # our equivalent since the merge is dict-cheap)
        if self.snaps is not None \
                and self.snaps.snapshot(block_hash) is not None \
                and self.snaps.disk_block != block_hash:
            self.snaps.flatten(block_hash)
        self._add_acceptor_queue(entry)

    def reject(self, block_hash: bytes) -> None:
        """Reject (blockchain.go:1074): drop the block's data."""
        entry = self._blocks.get(block_hash)
        if entry is not None:
            entry.status = "rejected"
            entry.receipts = []
        if self.snaps is not None:
            self.snaps.discard(block_hash)
        if self.state_processor is not None:
            # on its branch: undone, with whatever was verified on it
            self.state_processor.reject(block_hash)

    # -------------------------------------------------------- acceptor queue
    def _add_acceptor_queue(self, entry: _Entry) -> None:
        if self._acceptor_thread is None:
            self._acceptor_thread = _threading.Thread(
                target=self._acceptor_loop, name="chain-acceptor",
                daemon=True)
            self._acceptor_thread.start()
        self._acceptor_queue.put(entry)

    def _acceptor_loop(self) -> None:
        """startAcceptor (blockchain.go:566): durable accepted-block
        effects off the consensus thread."""
        while True:
            entry = self._acceptor_queue.get()
            if entry is None:
                self._acceptor_queue.task_done()
                return
            try:
                # a prior failure is fatal (the reference log.Crits):
                # drain later entries without side effects so the
                # durable last-accepted pointer never outruns a
                # partially-written predecessor
                if self._acceptor_error is None:
                    self._accept_side_effects(entry)
                    self.acceptor_tip = entry.block
            except BaseException as exc:  # noqa: BLE001 — surfaced on drain/close; acceptor must record even SystemExit
                self._acceptor_error = exc
            finally:
                self._acceptor_queue.task_done()

    def _accept_side_effects(self, entry: _Entry) -> None:
        block = entry.block
        if self.chain_kv is not None:
            from coreth_tpu.rawdb import schema
            schema.write_block(self.chain_kv, block)
            schema.write_canonical_hash(self.chain_kv, block.number,
                                        block.hash())
            if entry.receipts is not None:
                schema.write_receipts(self.chain_kv, block,
                                      entry.receipts)
            schema.write_last_accepted(self.chain_kv, block.hash())
            self.trie_writer.accept_trie(block.number, block.root)
            if self.freezer is not None:
                self._freeze_tail(block.number)
            self.chain_kv.flush()
        for cb in self._accepted_subs:
            cb(block, entry.receipts)

    def _freeze_tail(self, head_number: int) -> None:
        """Migrate canonical blocks older than freeze_threshold into
        the ancient store and drop their mutable copies
        (freezer.go freeze loop)."""
        from coreth_tpu.rawdb import schema
        target = head_number - self.freeze_threshold
        froze = False
        while self.freezer.ancients() < target:
            n = self.freezer.ancients() + 1
            h = schema.read_canonical_hash(self.chain_kv, n)
            if h is None:
                break
            body = schema.raw_body_payload(self.chain_kv, n, h)
            receipts = schema.raw_receipts_payload(self.chain_kv, n, h)
            if body is None:
                break
            # empty payload = receipts unknown (a state-synced block
            # stored without them) — NOT an empty receipt list
            self.freezer.append(n, body, receipts or b"")
            schema.delete_block_payloads(self.chain_kv, n, h)
            # evict the resident entry too: frozen history is cold
            self._blocks.pop(h, None)
            froze = True
        if froze:
            self.freezer.flush()

    # ------------------------------------------------------------ sync pivot
    def reset_to_synced(self, tip: Block, ancestors: List[Block] = ()
                        ) -> None:
        """finishSync pivot (syncervm_client.go:330): adopt a
        state-synced block as the accepted tip WITHOUT executing it —
        its state trie was downloaded verified into self.db.  The
        ancestors (newest-first) become canonical accepted history.
        The flat-state snapshot regenerates at the synced root."""
        if not self.has_state(tip.root):
            raise BadBlockError(
                "cannot pivot: synced state root not resident")
        for b in list(ancestors) + [tip]:
            self._blocks[b.hash()] = _Entry(b, status="accepted")
            self._canonical[b.number] = b.hash()
        self._head = tip
        self.last_accepted = tip
        self.acceptor_tip = tip
        if self.chain_kv is not None:
            from coreth_tpu.rawdb import schema
            for b in list(ancestors) + [tip]:
                schema.write_block(self.chain_kv, b)
                schema.write_canonical_hash(self.chain_kv, b.number,
                                            b.hash())
            schema.write_last_accepted(self.chain_kv, tip.hash())
            self.trie_writer.force_flush(tip.number, tip.root)
        if self._want_snapshots:
            from coreth_tpu.state.snapshot import generate_from_trie
            self.snaps = generate_from_trie(self.db, tip.root,
                                            tip.hash())
        if self.state_processor is not None:
            self.state_processor.reset(tip)
        for cb in self._head_subs:
            cb(tip)

    def drain_acceptor_queue(self) -> None:
        """DrainAcceptorQueue (blockchain.go:634): block until every
        queued accept has fully landed; re-raise any acceptor error."""
        if self._acceptor_thread is not None:
            self._acceptor_queue.join()
        if self._acceptor_error is not None:
            # sticky: a failed accept is fatal for this chain instance
            # (the reference log.Crits); every later drain/accept
            # re-raises rather than resuming on inconsistent state
            raise self._acceptor_error

    def _stop_acceptor(self) -> None:
        if self._acceptor_thread is not None:
            self._acceptor_queue.put(None)
            self._acceptor_thread.join()
            self._acceptor_thread = None

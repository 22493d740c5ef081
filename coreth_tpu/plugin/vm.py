"""The snowman ChainVM facade.

Twin of reference plugin/evm/vm.go: Initialize (:368) wires the chain,
tx pool and miner from genesis bytes; buildBlock (:1262) assembles a
block from the mempool; parseBlock (:1317) / getBlock (:1347) /
SetPreference (:1359) complete the consensus-facing surface.  Blocks
returned from here are PluginBlock adapters whose Verify/Accept/Reject
drive the underlying BlockChain.

The engine-notification channel (`to_engine`) carries PendingTxs
messages the way plugin/evm/block_builder.go:91 signals AvalancheGo to
call BuildBlock.
"""

from __future__ import annotations

import time as _time
from collections import deque
from typing import Deque, Dict, List, Optional, Union

from coreth_tpu import obs
from coreth_tpu.chain import BlockChain
from coreth_tpu.miner import Miner
from coreth_tpu.plugin.block import PluginBlock, Status
from coreth_tpu.plugin.config import parse_config
from coreth_tpu.plugin.genesis_json import parse_genesis_json
from coreth_tpu.txpool import TxPool
from coreth_tpu.types import Block, Transaction

PENDING_TXS = "PendingTxs"  # the message on the toEngine channel


class VMError(Exception):
    pass


class VM:
    """Consensus-driven EVM execution engine (vm.go:242)."""

    def __init__(self, clock=_time.time, shared_memory=None,
                 chain_ctx=None, atomic_store=None, engine_kw=None):
        """engine_kw: ReplayEngine's constructor arguments for the
        device state processor (config key ``state-processor``), handed
        over programmatically like ``clock``; None: its defaults.

        shared_memory/chain_ctx: supplying an atomic.SharedMemory
        (and optionally a ChainContext) wires the full atomic subsystem
        — backend, mempool, ExtData packing at build, accept-time
        shared-memory application (vm.go:986 / :979 / block.go:177).
        atomic_store: durable dict/KVStore for the atomic tx
        repository + the shared-memory apply cursor (the versiondb
        role); pass the same store across restarts for recovery."""
        self.clock = clock
        self.engine_kw = engine_kw
        self.atomic_store = atomic_store if atomic_store is not None \
            else {}
        self.atomic_repository = None
        self.initialized = False
        self.eth = None
        self.chain: Optional[BlockChain] = None
        self.txpool: Optional[TxPool] = None
        self.miner: Optional[Miner] = None
        self._blocks: Dict[bytes, PluginBlock] = {}
        self.to_engine: Deque[str] = deque()
        self.preferred_id: Optional[bytes] = None
        self.shared_memory = shared_memory
        self.chain_ctx = chain_ctx
        self.atomic_backend = None
        self.atomic_mempool = None
        self._building_atomic = []
        from coreth_tpu.plugin.block_verification import (
            SyntacticBlockValidator,
        )
        self.block_validator = SyntacticBlockValidator()
        # set False while consensus bootstraps (SetState analog);
        # UTXO-presence verification is skipped before normal op
        self.bootstrapped = True
        # warp subsystem (vm.go warp backend + handlers): wired by
        # enable_warp() before initialize
        self.warp_backend = None
        self.warp_config = None

    # ------------------------------------------------------------ lifecycle
    def initialize(self, genesis_bytes: Union[bytes, str, dict],
                   config_bytes: bytes = b"") -> None:
        """VM.Initialize (vm.go:368): decode genesis + the per-chain
        JSON config (vm.go:379, plugin/config.py twin) and build the
        chain stack from them."""
        if self.initialized:
            raise VMError("already initialized")
        genesis = parse_genesis_json(genesis_bytes)
        self.config = parse_config(config_bytes)
        if self.config.state_processor == "device" \
                and self.shared_memory is not None:
            # atomic ExtData runs through consensus callbacks the
            # engine's device paths do not make (ROADMAP R5): refuse,
            # never compute on another path silently
            raise VMError(
                "state-processor \"device\" does not run the atomic "
                "subsystem yet (shared_memory given): use \"host\"")
        engine = None
        if self.shared_memory is not None:
            from coreth_tpu.atomic import (
                AtomicBackend, ChainContext, make_callbacks,
            )
            from coreth_tpu.atomic.mempool import AtomicMempool
            from coreth_tpu.consensus.engine import DummyEngine
            ctx = self.chain_ctx or ChainContext()
            self.chain_ctx = ctx
            from coreth_tpu.atomic.backend import TRIE_META_KEY
            from coreth_tpu.atomic.trie import AtomicTrie
            from coreth_tpu.atomic.repository import (
                AtomicTxRepository, PrefixedStore,
            )
            from coreth_tpu.mpt import EMPTY_ROOT
            # the atomic trie's nodes live in the durable store (its
            # committed root persisted alongside), so the apply cursor
            # always has the trie it refers to after a restart
            meta = self.atomic_store.get(TRIE_META_KEY)
            trie = AtomicTrie(
                node_db=PrefixedStore(self.atomic_store, b"an"),
                root=meta[:32] if meta else EMPTY_ROOT,
                commit_interval=self.config.commit_interval)
            if meta:
                trie.last_committed_root = meta[:32]
                trie.last_committed_height = int.from_bytes(meta[32:],
                                                            "big")
                trie.committed_roots[trie.last_committed_height] = \
                    meta[:32]
            self.atomic_backend = AtomicBackend(
                ctx, self.shared_memory, trie=trie,
                metadata=self.atomic_store)
            self.atomic_repository = AtomicTxRepository(
                self.atomic_store)
            if self.atomic_backend.pending_apply():
                # crashed mid-ApplyToSharedMemory: resume from the
                # durable cursor before serving anything (vm.go init
                # path -> atomic_backend.go:252)
                self.atomic_backend.apply_to_shared_memory()
            self.atomic_mempool = AtomicMempool(ctx)
            cb = make_callbacks(self.atomic_backend, genesis.config,
                                pending_atomic_txs=self._pending_atomic)
            engine = DummyEngine(cb=cb)  # config lands in BlockChain
        # the engine stack comes from ONE constructor (vm.go:694
        # initializeChain -> eth.New): chain + txpool with head-event
        # reset + miner + the assembled RPC surface
        from coreth_tpu.eth import EthConfig, Ethereum
        from coreth_tpu.eth.ethconfig import TxPoolDefaults
        try:
            self.eth = Ethereum(
                genesis,
                EthConfig(
                    network_id=genesis.config.chain_id,
                    commit_interval=self.config.commit_interval,
                    state_processor=self.config.state_processor,
                    tx_pool=TxPoolDefaults(
                        price_limit=self.config.tx_pool_price_limit,
                        account_slots=self.config.tx_pool_account_slots,
                        global_slots=self.config.tx_pool_global_slots,
                        account_queue=self.config.tx_pool_account_queue,
                        global_queue=self.config.tx_pool_global_queue)),
                engine=engine, clock=self.clock, engine_kw=self.engine_kw)
        except ValueError as exc:
            # eth owns the choice of state processor and refuses a
            # value it does not know
            raise VMError(str(exc)) from exc
        self.chain = self.eth.chain
        self.txpool = self.eth.txpool
        self.miner = self.eth.miner
        if self.warp_backend is not None:
            # only accepted blocks may receive block-hash signatures
            def _accepted(h: bytes) -> bool:
                entry = self.chain._blocks.get(h)
                return entry is not None and entry.status == "accepted"
            self.warp_backend.accepted_block_fn = _accepted
        g = self.chain.genesis_block
        gb = PluginBlock(self, g)
        gb.status = Status.ACCEPTED
        self._blocks[gb.id] = gb
        self.preferred_id = gb.id
        from coreth_tpu.plugin.builder import BlockBuilder
        self.builder = BlockBuilder(
            self, clock=self.clock,
            min_interval=self.config.min_block_build_interval_ms / 1000)
        from coreth_tpu.plugin.syncervm import StateSyncServer
        self.state_sync_server = StateSyncServer(self)
        self.initialized = True

    def app_request_handler(self):
        """The request handler this VM joins the app network with
        (network_handler.go): sync handlers over the chain database +
        the warp signature handler."""
        from coreth_tpu.plugin.network_handler import NetworkHandler
        from coreth_tpu.sync.handlers import SyncHandler
        # resolved per request: a state sync swaps the backend's trie
        # (and its node store), and served leaves must follow it
        atomic_db = ((lambda: self.atomic_backend.trie.node_db)
                     if self.atomic_backend is not None else None)
        return NetworkHandler(
            sync_handler=SyncHandler(self.chain.db, self.chain,
                                     atomic_node_db=atomic_db),
            warp_backend=self.warp_backend).handle

    def state_sync_client(self, transport):
        """Build the syncervm client against a peer transport
        (syncervm_client.go)."""
        from coreth_tpu.plugin.syncervm import StateSyncClient
        return StateSyncClient(self, transport)

    def shutdown(self) -> None:
        """vm.go Shutdown -> eth Stop: transports down, acceptor
        drained, chain flushed + closed."""
        if self.initialized and self.eth is not None:
            self.eth.stop()
        self.initialized = False

    def health(self) -> dict:
        out = {"healthy": self.initialized}
        if self.initialized:
            out["lastAcceptedHeight"] = self.chain.last_accepted.number
            out["configWarnings"] = list(self.config.warnings)
        return out

    # -------------------------------------------------------------- blocks
    def _require_init(self) -> None:
        if not self.initialized:
            raise VMError("vm not initialized")

    def _register(self, blk: PluginBlock) -> None:
        self._blocks[blk.id] = blk

    # ------------------------------------------------------------- warp
    def enable_warp(self, network_id: int, source_chain_id: bytes,
                    secret_key: int, validator_set_fn=None,
                    quorum_num: int = 67, quorum_den: int = 100) -> None:
        """Wire the warp subsystem (vm.go warpBackend init + module
        registration): the backend stores/signs this chain's outgoing
        messages; the registered stateful precompile serves
        sendWarpMessage/getVerifiedWarpMessage; validator_set_fn is
        the P-Chain view used to verify inbound predicates.  Call
        before initialize(); the module registry is global, so tests
        must disable_warp() when done."""
        from coreth_tpu.precompile.modules import register_module
        from coreth_tpu.warp.contract import (
            WarpConfig, make_warp_module,
        )
        from coreth_tpu.warp.backend import WarpBackend
        self.warp_config = WarpConfig(
            network_id, source_chain_id,
            validator_set_fn=validator_set_fn,
            quorum_num=quorum_num, quorum_den=quorum_den)
        self.warp_backend = WarpBackend(network_id, source_chain_id,
                                        secret_key)
        register_module(make_warp_module(self.warp_config))

    def disable_warp(self) -> None:
        from coreth_tpu.precompile.modules import unregister_module
        from coreth_tpu.warp.contract import WARP_ADDRESS
        unregister_module(WARP_ADDRESS)
        self.warp_backend = None
        self.warp_config = None

    def _harvest_warp_messages(self, blk: PluginBlock) -> None:
        """Accepted-block hook (block.go:234 handlePrecompileAccept):
        every SendWarpMessage log in the accepted block lands in the
        warp backend, which can then sign it for aggregators."""
        from coreth_tpu.warp.contract import (
            SEND_WARP_MESSAGE_TOPIC, WARP_ADDRESS,
        )
        from coreth_tpu.warp.messages import UnsignedMessage
        receipts = self.chain.get_receipts(blk.id) or []
        for receipt in receipts:
            for log in receipt.logs:
                if log.address == WARP_ADDRESS and log.topics \
                        and log.topics[0] == SEND_WARP_MESSAGE_TOPIC:
                    self.warp_backend.add_message(
                        UnsignedMessage.decode(log.data))

    def _on_accept(self, blk: PluginBlock) -> None:
        if self.warp_backend is not None:
            self._harvest_warp_messages(blk)
        if self.atomic_backend is not None:
            from coreth_tpu.atomic import decode_ext_data
            self.atomic_backend.accept(blk.id, height=blk.height)
            txs = decode_ext_data(blk.block.ext_data())
            if txs:
                # index by tx id + height (atomic_tx_repository.go)
                self.atomic_repository.write(blk.height, txs)
                self.atomic_mempool.remove_accepted(
                    [t.id() for t in txs])
                # local txs spending the same UTXOs can never be valid
                # again — drop them rather than letting the next build
                # pull a guaranteed-to-fail spender
                consumed = [i for t in txs
                            for i in t.unsigned.input_utxos()]
                self.atomic_mempool.remove_conflicts(consumed)

    def _on_reject(self, blk: PluginBlock) -> None:
        if self.atomic_backend is not None:
            from coreth_tpu.atomic import decode_ext_data
            self.atomic_backend.reject(blk.id)
            restored = False
            for t in decode_ext_data(blk.block.ext_data()):
                self.atomic_mempool.cancel_current_tx(t.id())
                restored = True
            if restored:
                # the cancelled txs need a rebuild signal or they could
                # sit in the pool forever (liveness)
                self.builder.signal_txs_ready()

    def _pending_atomic(self):
        """Atomic txs for the next built block (vm.go:979
        onFinalizeAndAssemble pulls from the mempool).  Issued ids are
        tracked so a failed build can discard them instead of leaving
        them stranded in the issued set."""
        if self.atomic_mempool is None:
            return []
        tx = self.atomic_mempool.next_tx()
        if tx is None:
            return []
        self._building_atomic.append(tx.id())
        return [tx]

    def build_block(self) -> PluginBlock:
        """buildBlock (vm.go:1262): assemble from pending txs and verify
        immediately (the built block enters processing state)."""
        self._require_init()
        pending, _ = self.txpool.stats()
        atomic_pending = (self.atomic_mempool.pending_len()
                          if self.atomic_mempool is not None else 0)
        if pending == 0 and atomic_pending == 0:
            raise VMError("no pending transactions")
        self._building_atomic = []
        try:
            block = self.miner.generate_block()
            blk = PluginBlock(self, block)
            blk.verify()
        except Exception:  # noqa: BLE001 — any build failure must unwind issued atomic txs
            # a failed build must not strand issued atomic txs: discard
            # them (onFinalizeAndAssemble-error semantics — the tx was
            # pulled and found unbuildable)
            if self.atomic_mempool is not None:
                for tx_id in self._building_atomic:
                    self.atomic_mempool.discard_current_tx(tx_id)
            raise
        self.builder.handle_generate_block()
        return blk

    def parse_block(self, data: bytes) -> PluginBlock:
        """parseBlock (vm.go:1317): decode wire bytes; returns the
        cached adapter when the block is already known."""
        self._require_init()
        acct = self.account()
        tok = acct.begin("vm/parse")
        try:
            block = Block.decode(data)
            existing = self._blocks.get(block.hash())
            if existing is not None:
                return existing
            blk = PluginBlock(self, block)
            self._blocks[blk.id] = blk
            return blk
        finally:
            acct.end(tok)

    def account(self):
        """The self-time account the consensus calls are phases of
        (``vm/parse``, ``vm/verify``, ``vm/insert``, ``vm/accept``,
        ``vm/rollback``): the device state processor's engine's, so
        they sum with its own; no account under the host processor."""
        backend = self.chain.state_processor
        return obs.NULL_ACCOUNT if backend is None else backend.account

    def get_block(self, block_id: bytes) -> PluginBlock:
        """getBlock (vm.go:1347)."""
        self._require_init()
        blk = self._blocks.get(block_id)
        if blk is None:
            raise VMError(f"block {block_id.hex()} not found")
        return blk

    def set_preference(self, block_id: bytes) -> None:
        """SetPreference (vm.go:1359): the chain head used for building."""
        self._require_init()
        self.chain.set_preference(block_id)
        self.preferred_id = block_id

    def last_accepted(self) -> PluginBlock:
        self._require_init()
        return self._blocks[self.chain.last_accepted.hash()]

    # ------------------------------------------------------------- mempool
    def issue_tx(self, tx: Transaction) -> None:
        """Feed a transaction into the pool and, on success, signal the
        consensus engine to build (block_builder.go:129
        signalTxsReady)."""
        self._require_init()
        errs = self.txpool.add_remotes([tx])
        if errs and errs[0] is not None:
            raise errs[0]
        self.builder.signal_txs_ready()

    def issue_atomic_tx(self, tx) -> None:
        """Feed an atomic tx: semantic-verify against the current tip
        fee, pool it, signal the engine (vm.go issueTx for avax.*)."""
        self._require_init()
        if self.atomic_backend is None:
            raise VMError("atomic subsystem not configured")
        rules = self.chain.config.rules(
            self.chain.current_block().number + 1,
            int(self.clock()))
        self.atomic_backend.semantic_verify(
            tx, self.chain.current_block().base_fee, rules)
        self.atomic_mempool.add_tx(tx)
        self.builder.signal_txs_ready()

    def mempool_stats(self):
        self._require_init()
        return self.txpool.stats()

    def atomic_mempool_stats(self):
        self._require_init()
        pool = self.atomic_mempool
        if pool is None:
            return {"pending": 0, "total": 0}
        return {"pending": pool.pending_len(), "total": len(pool)}

    # ------------------------------------------------------- avax queries
    def get_atomic_tx(self, tx_id: bytes):
        """(tx, accepted height | None) or None (service.go
        GetAtomicTx): accepted txs resolve through the repository,
        mempool txs with no height."""
        self._require_init()
        if self.atomic_repository is not None:
            hit = self.atomic_repository.get_by_tx_id(tx_id)
            if hit is not None:
                return hit
        if self.atomic_mempool is not None:
            tx = self.atomic_mempool.get(tx_id)
            if tx is not None:
                return tx, None
        return None

    def get_atomic_tx_status(self, tx_id: bytes) -> str:
        """Accepted | Processing | Unknown (service.go
        GetAtomicTxStatus)."""
        self._require_init()
        if self.atomic_repository is not None \
                and self.atomic_repository.get_by_tx_id(tx_id):
            return "Accepted"
        if self.atomic_mempool is not None \
                and self.atomic_mempool.has(tx_id):
            return "Processing"
        return "Unknown"

    def get_utxos(self, addresses, source_chain: bytes,
                  limit: int = 100):
        """UTXOs in this chain's inbound shared memory owned by the
        given short-id addresses (service.go:506 GetUTXOs)."""
        self._require_init()
        if self.shared_memory is None:
            return []
        return self.shared_memory.indexed(source_chain, list(addresses),
                                          limit=limit)

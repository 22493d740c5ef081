"""The Ethereum facade: one object assembling the full node stack.

Twin of reference eth/backend.go (:117 New, :266 APIs): construct the
chain database + BlockChain (pruning/archive per config, snapshots,
freezer), TxPool, Miner, the JSON-RPC surface (eth_*/debug_*/
txpool_*/personal_* + filters + gas oracle + bloombits), optional
keystore, and the HTTP/WS transports — so an embedder (or the plugin
VM) gets the whole engine from one constructor, and Stop() tears it
down cleanly.
"""

from __future__ import annotations

from typing import Optional

from coreth_tpu.chain import BlockChain, Genesis
from coreth_tpu.eth.ethconfig import DEFAULTS, EthConfig
from coreth_tpu.miner import Miner
from coreth_tpu.txpool import TxPool
from coreth_tpu.txpool.pool import TxPoolConfig


class Ethereum:
    def __init__(self, genesis: Genesis,
                 config: Optional[EthConfig] = None,
                 chain_kv=None, clock=None, engine=None,
                 engine_kw=None):
        """eth.New (backend.go:117).  engine: an optional consensus
        engine with callbacks (the plugin VM passes its atomic-wired
        DummyEngine, the way vm.go hands callbacks into eth.New).
        engine_kw: ReplayEngine's constructor arguments where
        ``config.state_processor`` is "device" (programmatic, like
        ``clock``: no config key)."""
        import time as _time
        self.config = config or DEFAULTS
        cfg = self.config
        state_processor = None
        if cfg.state_processor == "device":
            import functools
            from coreth_tpu.replay.device_processor import DeviceProcessor
            state_processor = functools.partial(DeviceProcessor,
                                                **(engine_kw or {}))
        elif cfg.state_processor != "host":
            raise ValueError(
                f"state_processor {cfg.state_processor!r}: "
                "\"host\" or \"device\"")
        self.chain = BlockChain(
            genesis, chain_kv=chain_kv, engine=engine,
            commit_interval=cfg.commit_interval,
            archive=not cfg.pruning,
            snapshots=cfg.snapshot_cache > 0,
            freezer_dir=cfg.freezer_dir,
            freeze_threshold=cfg.freeze_threshold,
            state_processor=state_processor)
        self.txpool = TxPool(genesis.config, self.chain, TxPoolConfig(
            price_limit=cfg.tx_pool.price_limit,
            account_slots=cfg.tx_pool.account_slots,
            global_slots=cfg.tx_pool.global_slots,
            account_queue=cfg.tx_pool.account_queue,
            global_queue=cfg.tx_pool.global_queue))
        self.chain.subscribe_chain_head(lambda _b: self.txpool.reset())
        self.miner = Miner(genesis.config, self.chain, self.txpool,
                           engine=self.chain.engine,
                           clock=clock or _time.time)
        self.keystore = None
        if cfg.keystore_dir is not None:
            from coreth_tpu.accounts import KeyStore
            self.keystore = KeyStore(cfg.keystore_dir)
        self._assemble_apis()
        self._ws = None
        self._http_port: Optional[int] = None

    # ----------------------------------------------------------------- APIs
    def _assemble_apis(self) -> None:
        """APIs() (backend.go:266): the registered method surface."""
        from coreth_tpu.rpc import Backend, RPCServer, register_eth_api
        from coreth_tpu.rpc.debugapi import register_debug_runtime_api
        from coreth_tpu.rpc.tracers import register_debug_api
        self.api_backend = Backend(
            self.chain, self.txpool,
            bloom_section_size=self.config.bloom_section_size,
            rpc_gas_cap=self.config.rpc_gas_cap,
            network_id=self.config.network_id,
            allow_unfinalized_queries=(
                self.config.allow_unfinalized_queries),
            gpo_blocks=self.config.gpo.blocks,
            gpo_percentile=self.config.gpo.percentile)
        self.rpc_server = RPCServer()
        self.filters = register_eth_api(self.rpc_server,
                                        self.api_backend)
        register_debug_api(self.rpc_server, self.api_backend)
        # retained: the single CPU-profiler instance every surface
        # (debug_* over HTTP/WS, admin.* over the plugin socket)
        # shares, so mutual exclusion actually excludes
        self.cpu_profiler = register_debug_runtime_api(self.rpc_server)
        if self.keystore is not None:
            from coreth_tpu.rpc.personal import register_personal_api
            register_personal_api(self.rpc_server, self.keystore)

    # ------------------------------------------------------------ transports
    def serve_http(self, host: str = "127.0.0.1", port: int = 0) -> int:
        self._http_port = self.rpc_server.serve_http(host, port)
        return self._http_port

    def serve_ws(self, host: str = "127.0.0.1", port: int = 0) -> int:
        from coreth_tpu.rpc.websocket import WSServer
        if self._ws is not None:
            self._ws.close()  # rebinding: no leaked listener/thread
        self._ws = WSServer(self.rpc_server, self.api_backend)
        return self._ws.serve(host, port)

    def attach(self):
        """An in-process EthClient against the served HTTP endpoint
        (node.Attach role)."""
        if self._http_port is None:
            raise RuntimeError("serve_http first")
        from coreth_tpu.rpc.ethclient import EthClient
        return EthClient(f"http://127.0.0.1:{self._http_port}")

    # -------------------------------------------------------------- lifecycle
    def stop(self) -> None:
        """Stop (backend.go Stop): transports down, chain drained +
        flushed + closed."""
        if self._ws is not None:
            self._ws.close()
            self._ws = None
        self.rpc_server.close()
        self._http_port = None
        self.chain.close()

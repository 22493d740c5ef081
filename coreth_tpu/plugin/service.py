"""Local-socket RPC boundary for the VM — the rpcchainvm twin.

Twin of reference plugin/main.go:33 (rpcchainvm.Serve): the consensus
engine lives in another process and drives the VM over a wire protocol.
Here the transport is a unix domain socket carrying newline-delimited
JSON frames ({"id", "method", "params"} -> {"id", "result"} |
{"id", "error"}); byte-valued fields travel hex-encoded.  The method
surface mirrors the snowman ChainVM + Block interfaces:

  initialize, buildBlock, parseBlock, getBlock, setPreference,
  lastAccepted, issueTx, issueAtomicTx, blockVerify, blockAccept,
  blockReject, blockStatus, mempoolStats, atomicMempoolStats, health,
  shutdown

VMServer hosts a VM instance; VMClient is the in-Python consensus-side
stub (the role AvalancheGo's rpcchainvm client plays).
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import threading
from typing import Optional

from coreth_tpu.plugin.vm import VM, VMError
from coreth_tpu.types import Transaction


def _blk_info(blk) -> dict:
    return {
        "id": blk.id.hex(),
        "parentId": blk.parent_id.hex(),
        "height": blk.height,
        "timestamp": blk.timestamp,
        "status": blk.status.value,
        "bytes": blk.bytes().hex(),
    }


class VMServer:
    """Serves one VM over a unix socket (rpcchainvm.Serve role)."""

    def __init__(self, vm: Optional[VM] = None):
        self.vm = vm or VM()
        self._server: Optional[socketserver.ThreadingUnixStreamServer] = None
        self._thread: Optional[threading.Thread] = None
        # one VM, many connections: the real rpcchainvm relies on the
        # VM's internal locks; this VM has none, so serialize here
        self._lock = threading.Lock()
        self._cpu_profiler = None
        # cross-process app network state (appRequest/appGossip seam)
        self._app_handler = None
        self._peers: list = []
        self._gossiper = None

    def _inbound_gossiper(self):
        if self._gossiper is None:
            from coreth_tpu.plugin.gossiper import Gossiper
            self._gossiper = Gossiper(
                None, self.vm.txpool,
                atomic_mempool=getattr(self.vm, "atomic_mempool", None))
        return self._gossiper

    # ------------------------------------------------------------ dispatch
    def handle(self, method: str, params: dict):
        vm = self.vm
        if method == "initialize":
            vm.initialize(params["genesisBytes"],
                          bytes.fromhex(params.get("configBytes", "")))
            return _blk_info(vm.last_accepted())
        if method == "buildBlock":
            return _blk_info(vm.build_block())
        if method == "parseBlock":
            return _blk_info(vm.parse_block(bytes.fromhex(params["bytes"])))
        if method == "getBlock":
            return _blk_info(vm.get_block(bytes.fromhex(params["id"])))
        if method == "setPreference":
            vm.set_preference(bytes.fromhex(params["id"]))
            return {}
        if method == "lastAccepted":
            return _blk_info(vm.last_accepted())
        if method == "issueTx":
            vm.issue_tx(Transaction.decode(bytes.fromhex(params["tx"])))
            return {}
        if method == "issueAtomicTx":
            from coreth_tpu.atomic import Tx as AtomicTx
            vm.issue_atomic_tx(
                AtomicTx.decode(bytes.fromhex(params["tx"])))
            return {}
        if method == "atomicMempoolStats":
            return vm.atomic_mempool_stats()
        if method == "avax.getAtomicTx":
            hit = vm.get_atomic_tx(bytes.fromhex(params["txID"]))
            if hit is None:
                return {"status": "Unknown"}
            tx, height = hit
            return {"tx": tx.encode().hex(),
                    "blockHeight": height,
                    "status": "Accepted" if height is not None
                    else "Processing"}
        if method == "avax.getAtomicTxStatus":
            return {"status": vm.get_atomic_tx_status(
                bytes.fromhex(params["txID"]))}
        if method == "avax.getUTXOs":
            utxos = vm.get_utxos(
                [bytes.fromhex(a) for a in params["addresses"]],
                bytes.fromhex(params["sourceChain"]),
                limit=int(params.get("limit", 100)))
            return {"numFetched": len(utxos),
                    "utxos": [u.hex() for u in utxos]}
        if method == "blockVerify":
            blk = vm.get_block(bytes.fromhex(params["id"]))
            blk.verify()
            return _blk_info(blk)
        if method == "blockAccept":
            blk = vm.get_block(bytes.fromhex(params["id"]))
            blk.accept()
            return _blk_info(blk)
        if method == "blockReject":
            blk = vm.get_block(bytes.fromhex(params["id"]))
            blk.reject()
            return _blk_info(blk)
        if method == "blockStatus":
            return {"status":
                    vm.get_block(bytes.fromhex(params["id"])).status.value}
        if method == "mempoolStats":
            pending, queued = vm.mempool_stats()
            return {"pending": pending, "queued": queued}
        if method == "pollEngineMessage":
            return {"message":
                    vm.to_engine.popleft() if vm.to_engine else None}
        if method == "health":
            return vm.health()
        # ---- cross-process app network (peer/socket_transport.py):
        # the AppRequest/AppGossip seam served over THIS process's
        # socket, so sync/warp/gossip flow between VM processes
        if method == "appRequest":
            if self._app_handler is None:
                self._app_handler = vm.app_request_handler()
            resp = self._app_handler(bytes.fromhex(params["payload"]))
            return {"response": resp.hex()}
        if method == "appGossip":
            self._inbound_gossiper().handle_gossip(
                bytes.fromhex(params["payload"]))
            return {}
        if method == "connectPeer":
            from coreth_tpu.peer.socket_transport import SocketPeer
            self._peers.append(SocketPeer(params["path"]))
            return {"peers": len(self._peers)}
        if method == "getLastStateSummary":
            summary = vm.state_sync_server.get_last_state_summary()
            return {"summary": summary.encode().hex()}
        if method == "stateSyncFromPeer":
            # sync this VM from the last connected peer: fetch the
            # peer's latest summary over its socket, then run the full
            # syncervm client against the cross-process transport
            peer = self._peers[-1]
            raw = bytes.fromhex(peer._client.call(
                "getLastStateSummary")["summary"])
            client = vm.state_sync_client(peer.send_request)
            client.accept_summary(client.parse_state_summary(raw))
            return {"height": vm.chain.last_accepted.number,
                    "stats": client.stats}
        if method == "getBlockByHeight":
            blk = vm.chain.get_block_by_number(int(params["height"]))
            return {"bytes": blk.encode().hex()}
        if method == "gossipTx":
            from coreth_tpu.peer.socket_transport import MultiPeer
            from coreth_tpu.plugin.gossiper import Gossiper
            from coreth_tpu.types import Transaction as _Tx
            g = Gossiper(MultiPeer(self._peers), vm.txpool)
            n = g.gossip_txs(
                [_Tx.decode(bytes.fromhex(params["tx"]))])
            return {"gossiped": n}
        # admin.* (plugin/evm/admin.go surface): profiling control,
        # log level, live VM config
        if method == "admin.startCPUProfiler":
            self._admin_profiler().start(params.get(
                "file", "/tmp/coreth_tpu_cpu.prof"))
            return {}
        if method == "admin.stopCPUProfiler":
            return {"file": self._admin_profiler().stop()}
        if method == "admin.memoryProfile":
            from coreth_tpu.rpc.debugapi import memory_stats
            return memory_stats()
        if method == "admin.setLogLevel":
            import logging
            level = params.get("level", "info").upper()
            if level not in ("DEBUG", "INFO", "WARNING", "ERROR",
                             "CRITICAL"):
                raise VMError(f"unknown log level {level!r}")
            logging.getLogger("coreth_tpu").setLevel(level)
            return {}
        if method == "admin.getVMConfig":
            vm._require_init()
            cfg = vm.config
            return {k: getattr(cfg, k) for k in vars(cfg)
                    if not k.startswith("_")
                    and isinstance(getattr(cfg, k),
                                   (int, float, str, bool, type(None)))}
        if method == "shutdown":
            vm.shutdown()
            return {}
        raise VMError(f"unknown method {method!r}")

    def _admin_profiler(self):
        # one profiler per process: share the instance the Ethereum
        # facade registered for debug_* so the already-in-progress
        # guard spans every surface
        eth = getattr(self.vm, "eth", None)
        if eth is not None:
            return eth.cpu_profiler
        if self._cpu_profiler is None:
            from coreth_tpu.rpc.debugapi import CPUProfiler
            self._cpu_profiler = CPUProfiler()
        return self._cpu_profiler

    # ----------------------------------------------------------- transport
    def serve(self, path: str) -> None:
        """Bind the socket and serve in a daemon thread."""
        if os.path.exists(path):
            os.unlink(path)
        handle = self.handle

        lock = self._lock

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):  # noqa: A003
                for line in self.rfile:
                    req = None
                    try:
                        req = json.loads(line)
                        with lock:
                            result = handle(req["method"],
                                            req.get("params", {}))
                        resp = {"id": req.get("id"), "result": result}
                    except Exception as e:  # noqa: BLE001 — wire error
                        rid = req.get("id") if isinstance(req, dict) \
                            else None
                        resp = {"id": rid,
                                "error": f"{type(e).__name__}: {e}"}
                    self.wfile.write(
                        (json.dumps(resp) + "\n").encode())
                    self.wfile.flush()

        class Server(socketserver.ThreadingUnixStreamServer):
            # handler threads block in rfile reads while clients hold
            # their sockets open; non-daemon threads would deadlock
            # server_close() and interpreter exit
            daemon_threads = True

        self._server = Server(path, Handler)
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


def serve(vm: VM, path: str) -> VMServer:
    """Serve `vm` at the unix-socket `path` (plugin/main.go:33 role)."""
    server = VMServer(vm)
    server.serve(path)
    return server


class VMClient:
    """Consensus-side stub speaking the wire protocol."""

    def __init__(self, path: str):
        self.sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self.sock.connect(path)
        self._file = self.sock.makefile("rwb")
        self._next_id = 0

    def call(self, method: str, **params):
        self._next_id += 1
        frame = {"id": self._next_id, "method": method, "params": params}
        self._file.write((json.dumps(frame) + "\n").encode())
        self._file.flush()
        resp = json.loads(self._file.readline())
        if "error" in resp:
            raise VMError(resp["error"])
        return resp["result"]

    # convenience wrappers mirroring the ChainVM surface
    def initialize(self, genesis_json: str, config_bytes: bytes = b""):
        return self.call("initialize", genesisBytes=genesis_json,
                         configBytes=config_bytes.hex())

    def build_block(self):
        return self.call("buildBlock")

    def parse_block(self, data: bytes):
        return self.call("parseBlock", bytes=data.hex())

    def get_block(self, block_id: bytes):
        return self.call("getBlock", id=block_id.hex())

    def set_preference(self, block_id: bytes):
        return self.call("setPreference", id=block_id.hex())

    def last_accepted(self):
        return self.call("lastAccepted")

    def issue_tx(self, tx_bytes: bytes):
        return self.call("issueTx", tx=tx_bytes.hex())

    def block_verify(self, block_id: bytes):
        return self.call("blockVerify", id=block_id.hex())

    def block_accept(self, block_id: bytes):
        return self.call("blockAccept", id=block_id.hex())

    def block_reject(self, block_id: bytes):
        return self.call("blockReject", id=block_id.hex())

    def issue_atomic_tx(self, tx_bytes: bytes):
        return self.call("issueAtomicTx", tx=tx_bytes.hex())

    def atomic_mempool_stats(self):
        return self.call("atomicMempoolStats")

    def get_atomic_tx(self, tx_id: bytes):
        return self.call("avax.getAtomicTx", txID=tx_id.hex())

    def get_atomic_tx_status(self, tx_id: bytes):
        return self.call("avax.getAtomicTxStatus",
                         txID=tx_id.hex())["status"]

    def get_utxos(self, addresses, source_chain: bytes, limit=100):
        return self.call("avax.getUTXOs",
                         addresses=[a.hex() for a in addresses],
                         sourceChain=source_chain.hex(), limit=limit)

    def poll_engine_message(self):
        return self.call("pollEngineMessage")["message"]

    def close(self) -> None:
        self._file.close()
        self.sock.close()

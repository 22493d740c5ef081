"""Plugin/VM boundary: snowman VM facade + Block adapter + RPC service.

Mirrors the reference's full-VM-without-a-cluster strategy
(plugin/evm/vm_test.go GenesisVM :241): boot a complete VM from genesis
JSON, feed txs, and simulate consensus by calling
buildBlock/parseBlock/Verify/Accept/Reject directly — and through the
local-socket service (the rpcchainvm boundary twin).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.plugin import (
    PluginBlock, Status, VM, VMClient, parse_genesis_json, serve,
)
from coreth_tpu.plugin.vm import VMError
from coreth_tpu.types import DynamicFeeTx, sign_tx

GWEI = 10**9
KEY = 0xBADD00D5
ADDR = priv_to_address(KEY)
KEY2 = 0xFACE
ADDR2 = priv_to_address(KEY2)
CHAIN_ID = 43111


def genesis_json() -> str:
    """Genesis with every Avalanche phase active from epoch 0 (the
    TEST_CHAIN_CONFIG shape, serialized the way AvalancheGo hands the
    VM its genesis bytes)."""
    config = {
        "chainId": CHAIN_ID,
        "homesteadBlock": 0, "eip150Block": 0, "eip155Block": 0,
        "eip158Block": 0, "byzantiumBlock": 0,
        "constantinopleBlock": 0, "petersburgBlock": 0,
        "istanbulBlock": 0, "muirGlacierBlock": 0,
        "apricotPhase1BlockTimestamp": 0,
        "apricotPhase2BlockTimestamp": 0,
        "apricotPhase3BlockTimestamp": 0,
        "apricotPhase4BlockTimestamp": 0,
        "apricotPhase5BlockTimestamp": 0,
        "apricotPhasePre6BlockTimestamp": 0,
        "apricotPhase6BlockTimestamp": 0,
        "apricotPhasePost6BlockTimestamp": 0,
        "banffBlockTimestamp": 0,
        "cortinaBlockTimestamp": 0,
        "durangoBlockTimestamp": 0,
    }
    return json.dumps({
        "config": config,
        "alloc": {ADDR.hex(): {"balance": hex(10**24)},
                  ADDR2.hex(): {"balance": hex(10**24)}},
        "gasLimit": hex(8_000_000),
        "timestamp": "0x0",
    })


def make_tx(nonce: int, key=KEY, value=1000):
    return sign_tx(DynamicFeeTx(
        chain_id_=CHAIN_ID, nonce=nonce, gas_tip_cap_=GWEI,
        gas_fee_cap_=300 * GWEI, gas=21_000, to=b"\x42" * 20,
        value=value), key, CHAIN_ID)


def genesis_vm(clock=None) -> VM:
    vm = VM(**({"clock": clock} if clock else {}))
    vm.initialize(genesis_json())
    return vm


def test_vm_initialize_and_last_accepted():
    vm = genesis_vm()
    last = vm.last_accepted()
    assert last.height == 0
    assert last.status == Status.ACCEPTED
    assert vm.get_block(last.id) is last
    with pytest.raises(VMError):
        vm.initialize(genesis_json())  # double init refused


def test_vm_build_verify_accept_cycle():
    t = [1_000]

    def clock():
        t[0] += 10
        return t[0]

    vm = genesis_vm(clock)
    with pytest.raises(VMError):
        vm.build_block()  # empty mempool
    vm.issue_tx(make_tx(0))
    assert vm.to_engine and vm.to_engine[0] == "PendingTxs"
    blk = vm.build_block()
    assert blk.status == Status.PROCESSING
    assert blk.height == 1
    vm.set_preference(blk.id)
    blk.accept()
    assert blk.status == Status.ACCEPTED
    assert vm.last_accepted().id == blk.id
    # included tx left the mempool
    assert vm.mempool_stats() == (0, 0)


def test_vm_parse_block_roundtrip_and_second_vm():
    """A block built by one VM parses, verifies and accepts on another
    VM booted from the same genesis (the two-node simulation shape,
    vm_test.go / syncervm_test.go)."""
    t = [1_000]

    def clock():
        t[0] += 10
        return t[0]

    vm1 = genesis_vm(clock)
    vm2 = genesis_vm(clock)
    vm1.issue_tx(make_tx(0))
    built = vm1.build_block()
    wire = built.bytes()

    parsed = vm2.parse_block(wire)
    assert parsed.id == built.id
    assert parsed.status == Status.UNKNOWN
    parsed.verify()
    assert parsed.status == Status.PROCESSING
    parsed.accept()
    assert vm2.last_accepted().id == built.id
    # parse of a known block returns the cached adapter
    assert vm2.parse_block(wire) is parsed


def test_vm_reject_sibling():
    """Two competing siblings: accepting one rejects the other
    (consensus decides; the chain keeps both as processing until then)."""
    t = [1_000]

    def clock():
        t[0] += 10
        return t[0]

    vm = genesis_vm(clock)
    vm.issue_tx(make_tx(0))
    a = vm.build_block()
    # competing sibling: consensus moves preference back to the parent
    # (the inserted block optimistically became head,
    # writeBlockAndSetHead) so the next build forks at the same height
    vm.set_preference(vm.last_accepted().id)
    vm.issue_tx(make_tx(0, key=KEY2))
    b = vm.build_block()
    assert a.id != b.id
    assert a.height == b.height == 1
    a.accept()
    b.reject()
    assert a.status == Status.ACCEPTED
    assert b.status == Status.REJECTED
    assert vm.last_accepted().id == a.id


@pytest.mark.parametrize("backend", ["host", "device"])
def test_vm_service_over_socket(tmp_path, backend):
    """Drive the full cycle through the rpcchainvm-twin local-socket
    service: initialize -> issueTx -> buildBlock -> parse on a second
    served VM -> verify -> accept; on the host processor and with the
    per-chain config selecting the device engine."""
    sock1 = str(tmp_path / "vm1.sock")
    server = serve(VM(engine_kw=dict(capacity=256, window=2)), sock1)
    try:
        client = VMClient(sock1)
        genesis_info = client.initialize(
            genesis_json(),
            json.dumps({"state-processor": backend}).encode())
        assert genesis_info["height"] == 0
        tx = make_tx(0)
        client.issue_tx(tx.encode())
        assert client.poll_engine_message() == "PendingTxs"
        built = client.build_block()
        assert built["status"] == "processing"
        assert built["height"] == 1
        client.set_preference(bytes.fromhex(built["id"]))
        accepted = client.block_accept(bytes.fromhex(built["id"]))
        assert accepted["status"] == "accepted"
        last = client.last_accepted()
        assert last["id"] == built["id"]
        stats = getattr(server.vm.chain.state_processor, "stats", None)
        assert (stats is not None) == (backend == "device")
        if stats is not None:
            assert (stats.blocks_verified_device, stats.blocks_accepted,
                    stats.blocks_fallback) == (1, 1, 0)
        # errors cross the wire as failures, not hangs
        with pytest.raises(VMError):
            client.build_block()  # empty mempool again
        client.close()
    finally:
        server.close()


def test_parse_genesis_json_storage_and_code():
    g = parse_genesis_json(json.dumps({
        "config": {"chainId": 7},
        "alloc": {
            "11" * 20: {"balance": "0x64", "nonce": "0x1",
                        "code": "0x6001",
                        "storage": {"0x01": "0x02"}},
        },
        "gasLimit": "0x1000",
    }))
    assert g.config.chain_id == 7
    acct = g.alloc[b"\x11" * 20]
    assert acct.balance == 100 and acct.nonce == 1
    assert acct.code == b"\x60\x01"
    assert acct.storage[(1).to_bytes(32, "big")] == (2).to_bytes(32, "big")
    assert g.config.apricot_phase1_time is None  # fork keys absent


def test_vm_atomic_import_end_to_end():
    """The VM assembles the atomic subsystem from a shared-memory hub:
    issue an ImportTx, build a block carrying it as ExtData, accept,
    and the UTXO is consumed + the EVM balance credited."""
    from coreth_tpu.atomic import (
        ChainContext, EVMOutput, Memory, TransferableInput,
        TransferableOutput, Tx, UnsignedImportTx, UTXO, X2C_RATE,
        short_id,
    )
    from coreth_tpu.atomic.shared_memory import Element, Requests
    from coreth_tpu.crypto.secp256k1 import _g_mul, _to_affine

    ctx = ChainContext()
    memory = Memory()
    out = TransferableOutput(asset_id=ctx.avax_asset_id,
                             amount=5_000_000_000,
                             addrs=[short_id(_to_affine(_g_mul(KEY)))])
    utxo = UTXO(b"\x91" * 32, 0, out)
    memory.new_shared_memory(ctx.x_chain_id).apply(
        {ctx.chain_id: Requests(put_requests=[
            Element(utxo.input_id(), utxo.encode(), out.addrs)])})

    t = [1_000]

    def clock():
        t[0] += 10
        return t[0]

    vm = VM(clock=clock, shared_memory=memory.new_shared_memory(
        ctx.chain_id), chain_ctx=ctx)
    vm.initialize(genesis_json())
    atx = Tx(UnsignedImportTx(
        network_id=ctx.network_id, blockchain_id=ctx.chain_id,
        source_chain=ctx.x_chain_id,
        imported_inputs=[TransferableInput(
            tx_id=utxo.tx_id, output_index=0, asset_id=out.asset_id,
            amount=out.amount, sig_indices=[0])],
        outs=[EVMOutput(ADDR, 4_990_000_000, ctx.avax_asset_id)]))
    atx.sign([[KEY]])
    vm.issue_tx(make_tx(0))       # an EVM tx rides along
    vm.issue_atomic_tx(atx)
    blk = vm.build_block()
    assert blk.block.ext_data() != b""
    pre = vm.chain.state_at(
        vm.chain.genesis_block.root).get_balance(ADDR)
    blk.accept()
    state = vm.chain.state_at(blk.block.root)
    # import credit minus the EVM tx's value+fees still nets way up
    assert state.get_balance(ADDR) > pre + 4_900_000_000 * X2C_RATE - 10**18
    # UTXO consumed from shared memory
    import pytest as _p
    with _p.raises(Exception):
        memory.new_shared_memory(ctx.chain_id).get(
            ctx.x_chain_id, [utxo.input_id()])
    # mempool drained
    assert vm.atomic_mempool.pending_len() == 0
    assert len(vm.atomic_mempool) == 0


def test_service_atomic_methods(tmp_path):
    from coreth_tpu.atomic import (
        ChainContext, EVMOutput, Memory, TransferableInput,
        TransferableOutput, Tx, UnsignedImportTx, UTXO, short_id,
    )
    from coreth_tpu.atomic.shared_memory import Element, Requests
    from coreth_tpu.crypto.secp256k1 import _g_mul, _to_affine

    ctx = ChainContext()
    memory = Memory()
    out = TransferableOutput(asset_id=ctx.avax_asset_id,
                             amount=5_000_000_000,
                             addrs=[short_id(_to_affine(_g_mul(KEY)))])
    utxo = UTXO(b"\x92" * 32, 0, out)
    memory.new_shared_memory(ctx.x_chain_id).apply(
        {ctx.chain_id: Requests(put_requests=[
            Element(utxo.input_id(), utxo.encode(), out.addrs)])})
    vm = VM(shared_memory=memory.new_shared_memory(ctx.chain_id),
            chain_ctx=ctx)
    sock = str(tmp_path / "vm.sock")
    server = serve(vm, sock)
    try:
        client = VMClient(sock)
        client.initialize(genesis_json())
        atx = Tx(UnsignedImportTx(
            network_id=ctx.network_id, blockchain_id=ctx.chain_id,
            source_chain=ctx.x_chain_id,
            imported_inputs=[TransferableInput(
                tx_id=utxo.tx_id, output_index=0,
                asset_id=out.asset_id, amount=out.amount,
                sig_indices=[0])],
            outs=[EVMOutput(ADDR, 4_990_000_000, ctx.avax_asset_id)]))
        atx.sign([[KEY]])
        client.issue_atomic_tx(atx.encode())
        assert client.atomic_mempool_stats() == \
            {"pending": 1, "total": 1}
        built = client.build_block()
        client.block_accept(bytes.fromhex(built["id"]))
        assert client.atomic_mempool_stats() == \
            {"pending": 0, "total": 0}
        client.close()
    finally:
        server.close()


def test_engine_publishes_metrics():
    from coreth_tpu.metrics import Registry
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={ADDR: GenesisAccount(balance=10**20)})
    db = Database()
    gb = genesis.to_block(db)
    engine = ReplayEngine(CFG, db, gb.root, parent_header=gb.header,
                          capacity=256)
    reg = Registry()
    engine.publish_metrics(reg)
    snap = reg.snapshot()
    assert "replay/t_device" in snap and "replay/blocks_device" in snap
    assert "replay/lanes_real" in snap and "replay/lanes_padded" in snap
    assert "replay/window_uploads" in snap \
        and "replay/window_upload_bytes" in snap
    assert "replay/sigs_host" in snap and "replay/recover_degraded" in snap
    assert not [k for k in snap if "segs_" in k or "t_recover_" in k]


def test_avax_service_queries(tmp_path):
    """avax.getUTXOs / getAtomicTx / getAtomicTxStatus over the
    socket boundary (reference service.go:506 surface)."""
    from coreth_tpu.atomic import (
        ChainContext, EVMOutput, Memory, TransferableInput,
        TransferableOutput, Tx, UnsignedImportTx, UTXO, short_id,
    )
    from coreth_tpu.atomic.shared_memory import Element, Requests
    from coreth_tpu.crypto.secp256k1 import _g_mul, _to_affine

    ctx = ChainContext()
    memory = Memory()
    owner = short_id(_to_affine(_g_mul(KEY)))
    out = TransferableOutput(asset_id=ctx.avax_asset_id,
                             amount=5_000_000_000, addrs=[owner])
    utxo = UTXO(b"\x93" * 32, 0, out)
    memory.new_shared_memory(ctx.x_chain_id).apply(
        {ctx.chain_id: Requests(put_requests=[
            Element(utxo.input_id(), utxo.encode(), out.addrs)])})
    vm = VM(shared_memory=memory.new_shared_memory(ctx.chain_id),
            chain_ctx=ctx)
    sock = str(tmp_path / "vm.sock")
    server = serve(vm, sock)
    try:
        client = VMClient(sock)
        client.initialize(genesis_json())
        # the seeded UTXO is discoverable by owner address
        got = client.get_utxos([owner], ctx.x_chain_id)
        assert got["numFetched"] == 1
        assert got["utxos"][0] == utxo.encode().hex()

        atx = Tx(UnsignedImportTx(
            network_id=ctx.network_id, blockchain_id=ctx.chain_id,
            source_chain=ctx.x_chain_id,
            imported_inputs=[TransferableInput(
                tx_id=utxo.tx_id, output_index=0,
                asset_id=out.asset_id, amount=out.amount,
                sig_indices=[0])],
            outs=[EVMOutput(ADDR, 4_990_000_000, ctx.avax_asset_id)]))
        atx.sign([[KEY]])
        assert client.get_atomic_tx_status(atx.id()) == "Unknown"
        client.issue_atomic_tx(atx.encode())
        assert client.get_atomic_tx_status(atx.id()) == "Processing"
        built = client.build_block()
        client.block_accept(bytes.fromhex(built["id"]))
        assert client.get_atomic_tx_status(atx.id()) == "Accepted"
        info = client.get_atomic_tx(atx.id())
        assert info["status"] == "Accepted"
        assert info["blockHeight"] == 1
        assert info["tx"] == atx.encode().hex()
        # consumed UTXO disappears from getUTXOs
        assert client.get_utxos([owner],
                                ctx.x_chain_id)["numFetched"] == 0
        client.close()
    finally:
        server.close()


def test_shared_memory_apply_cursor_crash_resume():
    """VM 'restart' mid-ApplyToSharedMemory resumes from the durable
    cursor without double-applying (atomic_backend.go:252/:373)."""
    from coreth_tpu.atomic import ChainContext, Memory
    from coreth_tpu.atomic.backend import APPLY_CURSOR_KEY, AtomicBackend
    from coreth_tpu.atomic.shared_memory import Element, Requests
    from coreth_tpu.atomic.trie import AtomicTrie, encode_ops, height_key

    ctx = ChainContext()
    memory = Memory()
    sm = memory.new_shared_memory(ctx.chain_id)
    store = {}  # the durable versiondb role, shared across "restarts"

    # an atomic trie with removes at heights 1..4; seed those UTXOs
    trie = AtomicTrie()
    for h in range(1, 5):
        key = bytes([h]) * 32
        memory.new_shared_memory(ctx.x_chain_id).apply(
            {ctx.chain_id: Requests(put_requests=[
                Element(key, b"v%d" % h, [b"t" * 20])])})
        trie.trie.update(height_key(h), encode_ops(
            {ctx.x_chain_id: Requests(remove_requests=[key])}))

    backend = AtomicBackend(ctx, sm, trie=trie, metadata=store)
    backend.mark_apply_to_shared_memory(4)
    # simulate the crash: apply only heights 1..2 manually, advancing
    # the cursor the way apply_to_shared_memory does, then "die"
    from coreth_tpu.atomic.trie import decode_ops
    for h in (1, 2):
        sm.apply_tolerant(decode_ops(trie.get(h)))
        store[APPLY_CURSOR_KEY] = (h + 1).to_bytes(8, "big") \
            + (4).to_bytes(8, "big")
    del backend

    # restart: a fresh backend over the same durable store resumes
    backend2 = AtomicBackend(ctx, sm, trie=trie, metadata=store)
    assert backend2.pending_apply()
    applied = backend2.apply_to_shared_memory()
    assert applied == 2  # only heights 3..4
    assert not backend2.pending_apply()
    for h in range(1, 5):
        with pytest.raises(KeyError):
            sm.get(ctx.x_chain_id, [bytes([h]) * 32])
    # idempotent: nothing pending, nothing re-applied
    assert backend2.apply_to_shared_memory() == 0


def test_vm_restart_resumes_pending_apply():
    """Full-VM shape of the crash-resume: a VM with a durable
    atomic_store commits its atomic trie, 'crashes' with an apply
    cursor pending, and a REBUILT VM over the same store + shared
    memory resumes the application at initialize — the trie itself
    reconstructs from the durable node store."""
    import json as _json
    from coreth_tpu.atomic import (
        ChainContext, EVMOutput, Memory, TransferableInput,
        TransferableOutput, Tx, UnsignedImportTx, UTXO, short_id,
    )
    from coreth_tpu.atomic.backend import APPLY_CURSOR_KEY
    from coreth_tpu.atomic.shared_memory import Element, Requests
    from coreth_tpu.crypto.secp256k1 import _g_mul, _to_affine

    ctx = ChainContext()
    memory = Memory()
    store = {}
    config = _json.dumps({"commit-interval": 2}).encode()
    owner = short_id(_to_affine(_g_mul(KEY)))

    def seed(tag):
        out = TransferableOutput(asset_id=ctx.avax_asset_id,
                                 amount=5_000_000_000, addrs=[owner])
        utxo = UTXO(bytes([tag]) * 32, 0, out)
        memory.new_shared_memory(ctx.x_chain_id).apply(
            {ctx.chain_id: Requests(put_requests=[
                Element(utxo.input_id(), utxo.encode(), out.addrs)])})
        return utxo, out

    t = [1_000]

    def clock():
        t[0] += 10
        return t[0]

    vm = VM(clock=clock,
            shared_memory=memory.new_shared_memory(ctx.chain_id),
            chain_ctx=ctx, atomic_store=store)
    vm.initialize(genesis_json(), config)
    for i, tag in enumerate((0xA1, 0xA2)):
        utxo, out = seed(tag)
        atx = Tx(UnsignedImportTx(
            network_id=ctx.network_id, blockchain_id=ctx.chain_id,
            source_chain=ctx.x_chain_id,
            imported_inputs=[TransferableInput(
                tx_id=utxo.tx_id, output_index=0,
                asset_id=out.asset_id, amount=out.amount,
                sig_indices=[0])],
            outs=[EVMOutput(ADDR, 4_990_000_000, ctx.avax_asset_id)]))
        atx.sign([[KEY]])
        vm.issue_atomic_tx(atx)
        vm.build_block().accept()
    # both heights committed (interval=2) and the trie meta persisted
    assert any(k == b"atomicTrieRoot" for k in store)

    # 'crash': re-seed the consumed UTXOs in shared memory (the state
    # a replayed application must re-consume) and leave a pending
    # cursor covering heights 1..2 in the durable store
    for tag in (0xA1, 0xA2):
        seed(tag)
    store[APPLY_CURSOR_KEY] = (0).to_bytes(8, "big") \
        + (2).to_bytes(8, "big")
    del vm

    vm2 = VM(clock=clock,
             shared_memory=memory.new_shared_memory(ctx.chain_id),
             chain_ctx=ctx, atomic_store=store)
    vm2.initialize(genesis_json(), config)
    # resume happened at initialize: cursor cleared, UTXOs re-consumed
    assert not vm2.atomic_backend.pending_apply()
    for tag in (0xA1, 0xA2):
        out = TransferableOutput(asset_id=ctx.avax_asset_id,
                                 amount=5_000_000_000, addrs=[owner])
        with pytest.raises(KeyError):
            memory.new_shared_memory(ctx.chain_id).get(
                ctx.x_chain_id,
                [UTXO(bytes([tag]) * 32, 0, out).input_id()])
    # and the reconstructed trie matches the committed meta
    assert vm2.atomic_backend.trie.last_committed_height == 2


def test_admin_api_over_socket(tmp_path):
    """admin.* surface (plugin/evm/admin.go role): profiling control,
    log level, live config readback."""
    sock = str(tmp_path / "vm.sock")
    server = serve(VM(), sock)
    try:
        client = VMClient(sock)
        client.initialize(genesis_json())
        prof = str(tmp_path / "cpu.prof")
        client.call("admin.startCPUProfiler", file=prof)
        client.call("lastAccepted")  # some work to record
        out = client.call("admin.stopCPUProfiler")
        assert out["file"] == prof and os.path.getsize(prof) > 0
        mem = client.call("admin.memoryProfile")
        assert mem["maxRssKiB"] > 0
        import logging
        logger = logging.getLogger("coreth_tpu")
        prev_level = logger.level
        try:
            client.call("admin.setLogLevel", level="debug")
            assert logger.level == logging.DEBUG
            with pytest.raises(VMError):
                client.call("admin.setLogLevel", level="loud")
        finally:
            logger.setLevel(prev_level)
        cfg = client.call("admin.getVMConfig")
        assert cfg["commit_interval"] == 4096
        client.close()
    finally:
        server.close()

"""Atomic txs on the device path (tier-1).

An ImportTx or ExportTx in a block's ExtData is a state transfer applied
after the block's transactions (plugin/evm vm.go onExtraStateChange,
import_tx.go / export_tx.go EVMStateTransfer).  ``ReplayEngine`` runs it
as lanes of the transfer window after the block's transactions: a
credit of ``amount x 10^9`` wei an import output, a debit under the
account's nonce an export input (``_atomic_lanes``).  Here:

- the engine against the host path on seeded chains of atomic blocks:
  roots, receipts, every account's nonce and balance, and every atomic
  block on the device (``atomic_blocks_host`` 0);
- an export the account cannot fund, one under the wrong nonce, one
  whose nonce the block's own transaction moved, and one funded by an
  import earlier in the block: the engine commits and refuses exactly
  where the host path does;
- the pre-Banff ``workloads/mixed.py`` fixture: a block whose import
  credits another asset than AVAX keeps the host path, counted;
- the VM with shared memory on ``"state-processor": "device"`` against
  the host backend and the plain reference (``plainatomic``): Verify,
  Accept, the conflicting sibling, Reject, the rollback, shared memory;
- the credentials' recovery on the native batch against the pure-Python
  ``recover_pubkey``;
- the Banff asset rule of ``semantic_verify``, each of its four refusals.

No host-clock assertion anywhere.
"""

import json
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from benchlib import chains, names, plainatomic  # noqa: E402
from benchlib.genesis_bytes import genesis_to_json  # noqa: E402
from coreth_tpu.atomic import (  # noqa: E402
    AtomicBackend, EVMInput, EVMOutput, Memory, TransferableInput,
    TransferableOutput, Tx, UnsignedExportTx, UnsignedImportTx,
    decode_ext_data, encode_ext_data,
)
from coreth_tpu.atomic.tx import AtomicTxError, recover_pubkeys  # noqa: E402
from coreth_tpu.chain import (  # noqa: E402
    BlockChain, Genesis, GenesisAccount, generate_chain,
)
from coreth_tpu.consensus.engine import (  # noqa: E402
    ConsensusCallbacks, DummyEngine,
)
from coreth_tpu.crypto import secp256k1 as secp  # noqa: E402
from coreth_tpu.crypto.secp256k1 import priv_to_address  # noqa: E402
from coreth_tpu.params import (  # noqa: E402
    TEST_APRICOT_PHASE5_CONFIG, TEST_CHAIN_CONFIG,
)
from coreth_tpu.plugin import VM, Status  # noqa: E402
from coreth_tpu.replay import ReplayEngine  # noqa: E402
from coreth_tpu.state import Database, StateDB  # noqa: E402
from coreth_tpu.types import Block, LegacyTx, sign_tx  # noqa: E402
from coreth_tpu.workloads import atomic as W  # noqa: E402

ENGINE_KW = dict(window=2, capacity=256, slot_capacity=64)
KEYS = [0xA7D0 + i for i in range(4)]   # A, B funded; C the X-Chain key;
ADDRS = [priv_to_address(k) for k in KEYS]  # D starts empty
FUNDS = 10**22
SINK = b"\x5b" * 20
AVAX = 10**9  # nAVAX


# ========================================================== chain writer
def build(config, plan, pack_only=()):
    """A chain from ``plan``: per block a list of ops, each
    ``("tx", key, to, value)`` (a legacy transfer at the base fee),
    ``("import", utxo, to)`` or ``("export", key, nano, nonce_shift)``.
    Transfers come first, then the atomic txs in order, as the block
    applies them; an export carries its key's nonce after them (plus
    ``nonce_shift``).  Blocks listed in ``pack_only`` are written
    without applying their atomic txs: their ExtData is packed, their
    state and root are the transfers' alone (a block a node must
    refuse).  Returns (genesis, blocks, utxos)."""
    utxos = [W.avax_utxo(bytes([0x30 + j]) * 32, 2 * AVAX + j, KEYS[2])
             for j in range(len(plan))]
    genesis = Genesis(config=config, gas_limit=8_000_000, alloc={
        ADDRS[0]: GenesisAccount(balance=FUNDS),
        ADDRS[1]: GenesisAccount(balance=FUNDS)})
    db = Database()
    g = genesis.to_block(db)
    memory = Memory()
    W.seed(memory, utxos)
    pending = []
    engine = W.engine(memory, config, pending)
    packing = DummyEngine(cb=ConsensusCallbacks(
        on_finalize_and_assemble=lambda header, statedb, txs: _pack(
            config, header, pending)))
    nonces = [0] * len(KEYS)
    blocks, parent = [], g
    for i, ops in enumerate(plan):
        def gen(_i, bg):
            pending.clear()
            for op in ops:
                if op[0] == "tx":
                    _, k, to, value = op
                    bg.add_tx(sign_tx(LegacyTx(
                        nonce=nonces[k], gas_price=bg.base_fee, gas=21_000,
                        to=to, value=value), KEYS[k], config.chain_id))
                    nonces[k] += 1
                elif op[0] == "import":
                    pending.append(W.import_tx(utxos[op[1]], KEYS[2],
                                               op[2], bg.base_fee))
                else:
                    _, k, nano, shift = op
                    pending.append(W.export_tx(
                        KEYS[k], ADDRS[k], nonces[k] + shift, nano,
                        W.short_address(KEYS[2]), bg.base_fee))
                    if i not in pack_only:
                        nonces[k] += 1

        (block,), _ = generate_chain(
            config, parent, db, 1, gen, gap=10,
            engine=packing if i in pack_only else engine)
        blocks.append(block)
        parent = block
    return genesis, blocks, utxos


def _pack(config, header, pending):
    """onFinalizeAndAssemble without the state transfer."""
    rules = config.rules(header.number, header.time)
    gas = sum(tx.block_fee_contribution(True, W.CTX.avax_asset_id,
                                        header.base_fee)[1]
              for tx in pending)
    return encode_ext_data(pending), 0, gas if rules.is_apricot_phase4 \
        else None


def _memory(utxos):
    memory = Memory()
    W.seed(memory, utxos)
    return memory


def host_chain(genesis, utxos):
    return BlockChain(genesis, engine=W.engine(_memory(utxos),
                                               genesis.config, []))


def device_engine(genesis, utxos, engine=None):
    db = Database()
    g = genesis.to_block(db)
    eng = ReplayEngine(
        genesis.config, db, g.root, parent_header=g.header,
        engine=engine or W.engine(_memory(utxos), genesis.config, []),
        **ENGINE_KW)
    eng.keep_receipts = True
    return eng


def fresh(blocks):
    return [Block.decode(b.encode()) for b in blocks]


def atomic_txs(block):
    return decode_ext_data(block.ext_data())


# =========================================== the engine against the host
def _to(j):
    return bytes([0xE0, j]) * 10


PLANS = {
    # upstream's shapes, one atomic tx a block beside a transfer
    "imports": [[("tx", 0, SINK, 1), ("import", j, _to(j))]
                for j in range(5)],
    "exports": [[("tx", 0, SINK, 1), ("export", 1, AVAX, 0)]
                for _ in range(5)],
    "alternating": [[("tx", 0, SINK, 1), ("import", j, _to(j % 2))]
                    if j % 2 else [("tx", 0, SINK, 1),
                                   ("export", 1, AVAX + j, 0)]
                    for j in range(6)],
    # the exporter's own transaction moves its nonce first
    "export_after_own_tx": [[("tx", 1, SINK, 7), ("export", 1, AVAX, 0)],
                            [("tx", 1, SINK, 8), ("tx", 1, SINK, 9),
                             ("export", 1, AVAX, 0)]],
    # an empty account is funded by an import and exports it, in one
    # block: the debit is solvent only after the credit before it
    "export_funded_in_block": [[("import", 0, ADDRS[3]),
                                ("export", 3, AVAX, 0)],
                               [("tx", 0, ADDRS[3], 5),
                                ("import", 1, ADDRS[3]),
                                ("export", 3, AVAX // 2, 0),
                                ("export", 3, AVAX // 4, 0)]],
    # an import alone, a block without ExtData, an export and an import
    "atomic_then_plain": [[("import", 0, SINK)], [("tx", 0, SINK, 3)],
                          [("export", 1, AVAX, 0), ("import", 1, SINK)]],
}


def _accounts(root, db_of):
    sdb = db_of(root)
    addrs = ADDRS + [SINK, b"\x01" + b"\x00" * 19] \
        + [_to(j) for j in range(6)]
    return {a: (sdb.get_nonce(a), sdb.get_balance(a)) for a in addrs}


@pytest.mark.parametrize("config", [TEST_CHAIN_CONFIG,
                                    TEST_APRICOT_PHASE5_CONFIG],
                         ids=["banff", "ap5"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_engine_matches_the_host_path_on_atomic_blocks(plan, config):
    """Block by block (``replay_block``, a window of one, as the VM runs
    it) and as one windowed ``replay``: the root of every block, its
    receipts, and every account the chain touches equal the host
    processor's; every atomic block ran on the device path."""
    genesis, blocks, utxos = build(config, PLANS[plan])
    n_atomic = sum(1 for b in blocks if b.ext_data())
    host = host_chain(genesis, utxos)
    host.insert_chain(fresh(blocks))
    assert host.last_accepted.root == blocks[-1].root
    eng = device_engine(genesis, utxos)
    for b, h in zip(fresh(blocks), blocks):
        assert eng.replay_block(b) == h.root
        assert [r.encode_consensus() for r in eng.last_receipts] == \
            [r.encode_consensus() for r in host.get_receipts(h.hash())]
    st = eng.stats
    assert (st.blocks_device, st.blocks_fallback, st.atomic_blocks_device,
            st.atomic_txs_device, st.atomic_blocks_host) == (
        len(blocks), 0, n_atomic,
        sum(len(atomic_txs(b)) for b in blocks), 0)
    eng.commit()
    assert _accounts(eng.root, lambda r: StateDB(r, eng.db)) \
        == _accounts(blocks[-1].root, host.state_at)
    windowed = device_engine(genesis, utxos)
    assert windowed.replay(fresh(blocks)) == blocks[-1].root
    assert (windowed.stats.atomic_blocks_device,
            windowed.stats.atomic_blocks_host,
            windowed.stats.blocks_fallback) == (n_atomic, 0, 0)


@pytest.mark.parametrize("plan,on_host", [("alternating", 0),
                                          ("export_funded_in_block", 1)])
def test_mesh_window_takes_the_atomic_lanes(plan, on_host):
    """On a two-device mesh the window keeps the pre-block solvency rule
    (credits of the same block ignored): atomic lanes commit on the
    device where that rule holds, and an export funded by an import
    earlier in its block takes the host path, counted (the first block
    of ``export_funded_in_block``; its second is funded before the
    block) — the same root either way."""
    import jax
    from coreth_tpu.parallel import make_mesh
    genesis, blocks, utxos = build(TEST_CHAIN_CONFIG, PLANS[plan])
    db = Database()
    g = genesis.to_block(db)
    eng = ReplayEngine(genesis.config, db, g.root, parent_header=g.header,
                       engine=W.engine(_memory(utxos), genesis.config, []),
                       mesh=make_mesh(jax.devices("cpu")[:2]), **ENGINE_KW)
    assert eng.replay(fresh(blocks)) == blocks[-1].root
    assert eng.stats.atomic_blocks_host == on_host
    assert eng.stats.atomic_blocks_device == len(blocks) - on_host


# ================================================= refusals, exactly
REFUSALS = {
    # the exporter holds FUNDS wei; it exports more
    "export_unfunded": ([("tx", 0, SINK, 1),
                         ("export", 1, FUNDS // AVAX + 1, 0)],
                        "insufficient funds"),
    "export_nonce_ahead": ([("tx", 0, SINK, 1), ("export", 1, AVAX, 1)],
                           "invalid nonce"),
    # its own transaction moved the nonce; the export ignores it
    "export_nonce_before_own_tx": ([("tx", 1, SINK, 1),
                                    ("export", 1, AVAX, -1)],
                                   "invalid nonce"),
    # an empty account exports before the import that would fund it
    "export_before_its_funding": ([("export", 3, AVAX, 0),
                                   ("import", 1, ADDRS[3])],
                                  "insufficient funds"),
}


@pytest.mark.parametrize("case", sorted(REFUSALS))
def test_engine_refuses_exactly_where_the_host_path_does(case):
    """The bad block's atomic tx passes semantic verification; its state
    transfer cannot be applied.  The device lane reads ``ok = False``,
    the block goes to the host path, and both raise the host path's
    error.  The engine stays on the parent: the next valid block
    commits on the device."""
    ops, message = REFUSALS[case]
    first = [("tx", 0, SINK, 1), ("import", 0, _to(0))]
    genesis, (good, bad), utxos = build(TEST_CHAIN_CONFIG, [first, ops],
                                        pack_only=(1,))
    # the valid block consensus takes in the bad one's place
    _g, (same, valid), _u = build(
        TEST_CHAIN_CONFIG, [first, [("tx", 0, SINK, 2),
                                    ("export", 1, AVAX, 0)]])
    assert same.hash() == good.hash()
    host = host_chain(genesis, utxos)
    host.insert_chain(fresh([good]))
    with pytest.raises(AtomicTxError, match=message):
        host.insert_block(fresh([bad])[0])
    eng = device_engine(genesis, utxos)
    eng.replay_block(fresh([good])[0])
    device_before = eng.stats.blocks_device
    with pytest.raises(AtomicTxError, match=message):
        eng.replay_block(fresh([bad])[0], hold=True)
    assert eng.root == good.root and eng.stats.blocks_device == device_before
    assert eng.replay_block(fresh([valid])[0], hold=True) == valid.root
    assert (eng.stats.atomic_blocks_device, eng.stats.atomic_blocks_host) \
        == (2, 0)


def test_engine_without_atomic_semantics_takes_the_host_path():
    """Callbacks with no atomic semantics give the engine nothing to
    apply an ExtData block with: it takes the host path, which refuses
    it there as a host chain does (no atomic gas where the header has
    some)."""
    genesis, blocks, utxos = build(TEST_CHAIN_CONFIG,
                                   PLANS["imports"][:1])
    eng = device_engine(genesis, utxos, engine=DummyEngine())
    bare = BlockChain(genesis)
    with pytest.raises(Exception, match="extDataGasUsed") as host_err:
        bare.insert_block(fresh(blocks)[0])
    with pytest.raises(type(host_err.value), match="extDataGasUsed"):
        eng.replay_block(fresh(blocks)[0])
    assert eng.stats.blocks_device == 0


def test_mixed_fixture_keeps_other_assets_on_the_host_path():
    """``workloads/mixed.py`` under Apricot Phase 5: its imports credit
    a second asset beside AVAX (multicoin state, before Banff), so their
    blocks take the host path, counted in ``atomic_blocks_host``; the
    chain's root is the host processor's."""
    from coreth_tpu.workloads import mixed as MX
    keys = [0xB0B + i for i in range(6)]
    genesis, blocks = MX.build_mixed_chain(TEST_APRICOT_PHASE5_CONFIG, 10,
                                           2, keys)
    n_atomic = sum(1 for b in blocks if b.ext_data())
    assert n_atomic == 2
    eng, _ = MX.replay_engine(genesis, 10, keys[0], **ENGINE_KW)
    assert eng.replay(fresh(blocks)) == blocks[-1].root
    assert (eng.stats.atomic_blocks_host, eng.stats.atomic_blocks_device) \
        == (n_atomic, 0)
    host = MX.host_chain(genesis, 10, keys[0])
    host.insert_chain(fresh(blocks))
    assert host.last_accepted.root == eng.root


# ====================================================== the VM, both ways
SPEC = names.load_spec()
CELL = "atomic.bootstrap"
SEED = 2**31 + 4343


def toy_atomic_chain(n_blocks: int):
    _c, _e, config, traffic = names.resolve_cell(SPEC, CELL)
    config = json.loads(json.dumps(config))
    config["chain_blocks"] = n_blocks
    genesis, wire = chains.build_wire(config, traffic, SEED)
    return config, genesis, wire


def boot(genesis, backend, memory, now):
    vm = VM(clock=lambda: now[0], engine_kw=dict(ENGINE_KW),
            shared_memory=memory.new_shared_memory(W.CTX.chain_id))
    vm.initialize(genesis_to_json(genesis),
                  json.dumps({"state-processor": backend}))
    return vm


def _spaces(memory):
    return {k: dict(v) for k, v in memory._spaces.items()}


@pytest.mark.parametrize("n_blocks", [6, 7])
def test_vm_with_shared_memory_on_the_device_backend(n_blocks):
    """The benchmark's atomic chain (an import at even heights, an
    export at odd ones) through parse -> Verify -> Accept on a VM with
    shared memory, host and device; at the last height the conflicting
    sibling — verified first, on the engine — then the last block
    (host path), accepted (the engine rolls back and runs it again),
    the sibling rejected.  Statuses, roots, the accepted accounts and
    what shared memory holds agree across the backends and with
    ``plainatomic``; nothing of the sibling is left; the last block's
    atomic txs were recorded once."""
    config, genesis, wire = toy_atomic_chain(n_blocks)
    blocks = [Block.decode(w) for w in wire]
    sibling = genesis.sibling(blocks[:-1]).encode()
    now = [0]
    memories = {b: genesis.seed_shared_memory() for b in ("host", "device")}
    vms = {b: boot(genesis, b, memories[b], now) for b in memories}
    inserts = []
    backend = vms["device"].atomic_backend
    real = backend.insert_txs
    backend.insert_txs = lambda h, *a, **k: (inserts.append(h),
                                             real(h, *a, **k))[1]
    for w in wire[:-1]:
        for vm in vms.values():
            blk = vm.parse_block(w)
            now[0] = blk.timestamp
            blk.verify()
            blk.accept()
    for vm in vms.values():
        sib, last = vm.parse_block(sibling), vm.parse_block(wire[-1])
        now[0] = max(sib.timestamp, last.timestamp)
        sib.verify()
        last.verify()
        last.accept()
        sib.reject()
        assert (sib.status, last.status) == (Status.REJECTED,
                                             Status.ACCEPTED)
    assert inserts.count(blocks[-1].hash()) == 1
    assert backend._pending == {}
    book = plainatomic.ledger(config, SEED)
    reference = plainatomic.inbox(config, SEED)
    for name, vm in vms.items():
        assert vm.chain.last_accepted.root == blocks[-1].root \
            == book.state_root(), name
        sdb = vm.chain.state_at(blocks[-1].root)
        assert {a: (sdb.get_nonce(a), sdb.get_balance(a))
                for a in book.accounts()} == book.accounts(), name
        got = _spaces(memories[name])
        assert got.get((W.CTX.x_chain_id, W.CTX.chain_id), {}) \
            == reference["x_to_c"]
        assert got.get((W.CTX.chain_id, W.CTX.x_chain_id), {}) \
            == reference["c_to_x"]
    assert _spaces(memories["host"]) == _spaces(memories["device"])
    st = vms["device"].chain.state_processor.stats
    assert (st.blocks_verified_device, st.blocks_verified_host,
            st.engine_rollbacks, st.blocks_reapplied,
            st.accepted_off_engine, st.blocks_fallback,
            st.atomic_blocks_host) == (n_blocks, 1, 1, 1, 0, 0, 0)
    assert st.atomic_blocks_device == n_blocks + 1   # sibling, re-run
    drv, _ = names.load_named("drivers", "snowman_bootstrap")
    sp = vms["device"].chain.state_processor
    assert drv.engine_off_accepted(sp.engine, vms["device"].chain) == 0
    for vm in vms.values():
        vm.shutdown()


def test_reject_rolls_an_atomic_block_back():
    """A processing block's import and export leave the engine when it
    is rejected: the device rows, the flat layer and the root are the
    accepted block's again, the import's UTXO is still in shared memory
    and spent by the block consensus accepts in its place."""
    _config, genesis, wire = toy_atomic_chain(2)
    blocks = [Block.decode(w) for w in wire]
    sibling = genesis.sibling(blocks[:-1])
    memory = genesis.seed_shared_memory()
    now = [blocks[-1].time]
    vm = boot(genesis, "device", memory, now)
    first = vm.parse_block(wire[0])
    first.verify()
    first.accept()
    sib = vm.parse_block(sibling.encode())
    sib.verify()                                  # on the engine
    sp = vm.chain.state_processor
    drv, _ = names.load_named("drivers", "snowman_bootstrap")
    assert sp.engine.root == sibling.root
    assert drv.engine_off_accepted(sp.engine, vm.chain) > 0
    sib.reject()
    assert sp.engine.root == blocks[0].root
    assert drv.engine_off_accepted(sp.engine, vm.chain) == 0
    (spent,) = atomic_txs(blocks[-1])[0].unsigned.input_utxos()
    assert spent in memory._spaces[(W.CTX.x_chain_id, W.CTX.chain_id)]
    last = vm.parse_block(wire[-1])
    last.verify()
    last.accept()
    assert vm.chain.last_accepted.root == blocks[-1].root
    assert sp.stats.atomic_blocks_host == 0
    assert drv.engine_off_accepted(sp.engine, vm.chain) == 0
    vm.shutdown()


def test_host_backend_vm_agrees_on_a_refused_export():
    """An export the account cannot fund is refused by Verify on both
    backends, and neither VM keeps anything of it."""
    plan = [[("tx", 0, SINK, 1), ("import", 0, _to(0))],
            [("tx", 0, SINK, 1), ("export", 1, FUNDS // AVAX + 1, 0)]]
    genesis, blocks, utxos = build(TEST_CHAIN_CONFIG, plan, pack_only=(1,))
    now = [blocks[-1].time]
    for backend in ("host", "device"):
        vm = boot(genesis, backend, _memory(utxos), now)
        good = vm.parse_block(blocks[0].encode())
        good.verify()
        good.accept()
        bad = vm.parse_block(blocks[1].encode())
        with pytest.raises(AtomicTxError, match="insufficient funds"):
            bad.verify()
        assert bad.status == Status.UNKNOWN
        assert vm.chain.last_accepted.root == blocks[0].root
        vm.shutdown()


# ============================================ credentials on the native batch
def _sig(r, s, v):
    return r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v])


def _python(digest, sig):
    try:
        return secp.recover_pubkey(digest, int.from_bytes(sig[:32], "big"),
                                   int.from_bytes(sig[32:64], "big"),
                                   sig[64])
    except ValueError:
        return None


def _native_or_none(digest, sigs):
    """recover_pubkeys lane by lane: the key, or None where it raises."""
    out = []
    for sig in sigs:
        try:
            out.append(recover_pubkeys(digest, [sig])[0])
        except ValueError:
            out.append(None)
    return out


@pytest.mark.parametrize("seed", range(4))
def test_native_credential_recovery_matches_the_reference(seed):
    """Seeded keys and digests, a batch of several signatures in one
    call (a tx with several credentials), and edge signatures: r or s 0
    or the group order, recovery ids past 1, high s, an r with no point
    on the curve.  The native batch answers what ``recover_pubkey``
    answers — the key, or the same refusal."""
    rng = random.Random(seed)
    digest = rng.randbytes(32)
    keys = [rng.randrange(1, secp.N) for _ in range(5)]
    sigs = [_sig(*secp.sign(digest, k)) for k in keys]
    assert recover_pubkeys(digest, sigs) == [secp.pubkey(k) for k in keys]
    r, s, v = secp.sign(digest, keys[0])
    edges = [_sig(0, s, v), _sig(r, 0, v), _sig(secp.N, s, v),
             _sig(r, secp.N, v), _sig(r, s, v ^ 1), _sig(r, s, v | 2),
             _sig(r, s, 4), _sig(r, secp.N - s, v ^ 1),
             _sig(r, secp.N - s, v)]
    # an x with no point: x^3 + 7 is not a square mod p
    x = 5
    while pow(x**3 + 7, (secp.P - 1) // 2, secp.P) == 1:
        x += 1
    edges.append(_sig(x, s, 0))
    want = [_python(digest, sig) for sig in edges]
    assert _native_or_none(digest, edges) == want
    assert want[0] is None and want[-1] is None
    assert want[7] == secp.pubkey(keys[0])   # high s recovers all the same


# ============================================================ Banff's rule
OTHER = b"\x77" * 32


def _import(asset_in, asset_out):
    utxo = W.avax_utxo(b"\x44" * 32, 2 * AVAX, KEYS[2])
    utxo.out.asset_id = asset_in
    tx = Tx(UnsignedImportTx(
        network_id=W.CTX.network_id, blockchain_id=W.CTX.chain_id,
        source_chain=W.CTX.x_chain_id,
        imported_inputs=[TransferableInput(
            tx_id=utxo.tx_id, output_index=0, asset_id=asset_in,
            amount=2 * AVAX, sig_indices=[0])],
        outs=[EVMOutput(ADDRS[0], AVAX, asset_out)]))
    tx.sign([[KEYS[2]]])
    return tx, utxo


def _export(asset_in, asset_out):
    tx = Tx(UnsignedExportTx(
        network_id=W.CTX.network_id, blockchain_id=W.CTX.chain_id,
        destination_chain=W.CTX.x_chain_id,
        ins=[EVMInput(ADDRS[1], 2 * AVAX, asset_in, 0)],
        exported_outputs=[TransferableOutput(
            asset_id=asset_out, amount=AVAX,
            addrs=[W.short_address(KEYS[2])])]))
    tx.sign([[KEYS[1]]])
    return tx, None


BANFF = {
    "import_input": (lambda a: _import(OTHER, a), "import input"),
    "import_output": (lambda a: _import(a, OTHER), "import output"),
    "export_input": (lambda a: _export(OTHER, a), "export input"),
    "export_output": (lambda a: _export(a, OTHER), "export output"),
}


@pytest.mark.parametrize("case", sorted(BANFF))
def test_banff_refuses_an_atomic_tx_of_another_asset(case):
    """import_tx.go / export_tx.go: from Banff on an input or output of
    another asset than AVAX is refused.  Before Banff the same tx passes
    that rule (it is the multicoin path)."""
    make, what = BANFF[case]
    tx, utxo = make(W.CTX.avax_asset_id)
    memory = Memory()
    if utxo is not None:
        W.seed(memory, [utxo])
    backend = AtomicBackend(W.CTX, memory.new_shared_memory(W.CTX.chain_id))
    with pytest.raises(AtomicTxError,
                       match=f"{what} cannot contain non-AVAX in Banff"):
        backend.semantic_verify(tx, None, TEST_CHAIN_CONFIG.rules(1, 1000))
    backend.semantic_verify(tx, None,
                            TEST_APRICOT_PHASE5_CONFIG.rules(1, 1000))

"""The device engine behind Snowman's Verify / Accept / Reject (tier-1).

``plugin/vm.py`` with the per-chain config key ``state-processor`` set
to "device" runs every block that extends the engine's tip on
``ReplayEngine`` (``replay/device_processor.py``) and every other block
on the host path.  Whatever consensus then decides, three parties have
to agree: the VM on the host processor (the parent's behaviour), the VM
on the device engine, and ``benchmarks/benchlib/plainsnow.py`` — the
contract written down with nothing of the program in it, its books
added up by ``plainref``:

- the benchmark's four chain shapes at toy size, block by block through
  parse → verify → accept;
- upstream's fork scenarios by name (plugin/evm/vm_test.go) and the
  ones the undo log is for, each followed by one more valid block (the
  engine is not wedged);
- seeded random scripts of the calls the contract allows;
- what the chain hands out after an accept: ``state_at``, receipts, the
  tx pool's reset, one ``eth_getBalance`` through ``rpc/``.

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

from benchlib import chains, names, plainref, plainsnow  # noqa: E402
from benchlib.genesis_bytes import genesis_to_json  # noqa: E402
from coreth_tpu.chain import (  # noqa: E402
    Genesis, GenesisAccount, generate_chain,
)
from coreth_tpu.chain.blockchain import BadBlockError  # noqa: E402
from coreth_tpu.crypto.secp256k1 import priv_to_address  # noqa: E402
from coreth_tpu.params import TEST_CHAIN_CONFIG  # noqa: E402
from coreth_tpu.plugin import VM, Status  # noqa: E402
from coreth_tpu.plugin.block_verification import (  # noqa: E402
    BlockVerificationError,
)
from coreth_tpu.plugin.genesis_json import (  # noqa: E402
    parse_genesis_json,
)
from coreth_tpu.plugin.vm import VMError  # noqa: E402
from coreth_tpu.state import Database  # noqa: E402
from coreth_tpu.types import Block, LegacyTx, sign_tx  # noqa: E402

BACKENDS = ("host", "device")
ENGINE_KW = dict(window=2, capacity=256, slot_capacity=64)
KEYS = [0xA11CE + i for i in range(3)]
ADDRS = [priv_to_address(k) for k in KEYS]
FUNDS = 10**24
GAP = 10
SINK = b"\x5a" * 20
SPEC = names.load_spec()


def boot(genesis_json: str, backend: str, clock) -> VM:
    vm = VM(clock=clock, engine_kw=dict(ENGINE_KW))
    vm.initialize(genesis_json, json.dumps({"state-processor": backend}))
    return vm


def accounts_at(vm: VM, root: bytes, addrs) -> dict:
    sdb = vm.chain.state_at(root)
    got = {a: (sdb.get_nonce(a), sdb.get_balance(a)) for a in addrs}
    return {a: v for a, v in got.items() if v != (0, 0)}


# ===================================================== the four chain shapes
CHAIN_CELLS = ["valuetx.catchup", "ring1k.catchup", "p2p-1k.catchup",
               "p2p-token-1k.catchup"]


def toy_chain(workload: str, seed: int = 2**31 + 38):
    _cell, _entry, config, traffic = names.resolve_cell(SPEC, workload)
    config = json.loads(json.dumps(config))
    config["chain_blocks"] = 5
    if "accounts" in config["chain"]:
        config["chain"]["accounts"] = 16
        config["txs_per_block"] = 8
    genesis, wire = chains.build_wire(config, traffic, seed)
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    return genesis, wire, builder, builder.ledger(config, traffic, seed)


@pytest.mark.parametrize("workload", CHAIN_CELLS)
def test_chain_shapes_block_by_block_on_both_backends(workload):
    """value_tx, tx_ring, p2p_transfer, p2p_token: parse → verify →
    accept, one block at a time.  Roots, receipts, blooms, gas,
    statuses and the last accepted block agree across the backends,
    with the headers and with the contract; the accepted state is the
    plain reference's book."""
    genesis, wire, builder, book = toy_chain(workload)
    gj = genesis_to_json(genesis)
    assert parse_genesis_json(gj).to_block().hash() \
        == genesis.to_block().hash()
    now = [0]
    vms = {b: boot(gj, b, lambda: now[0]) for b in BACKENDS}
    snow = plainsnow.Snow(vms["host"].last_accepted().id, None)
    seen = {b: [] for b in BACKENDS}
    for w in wire:
        for backend, vm in vms.items():
            blk = vm.parse_block(w)
            now[0] = blk.timestamp
            assert blk.status == Status.UNKNOWN
            blk.verify()
            assert blk.status == Status.PROCESSING
            # verified is not accepted
            assert vm.last_accepted().id == blk.parent_id
            blk.accept()
            head = vm.chain.last_accepted
            receipts = vm.chain.get_receipts(blk.id)
            seen[backend].append({
                "id": blk.id, "status": blk.status.value,
                "root": head.root,
                "receipts": [(r.encode_consensus(), r.gas_used, r.tx_hash,
                              r.block_hash, r.block_number,
                              r.transaction_index, r.effective_gas_price,
                              r.contract_address,
                              [(lg.tx_hash, lg.tx_index, lg.index,
                                lg.block_hash, lg.block_number)
                               for lg in r.logs]) for r in receipts],
                "bloom": head.header.bloom,
                "gas": sum(r.gas_used for r in receipts)})
            assert seen[backend][-1]["gas"] == head.header.gas_used
        blk = vms["host"].get_block(seen["host"][-1]["id"])
        snow.verify(blk.id, blk.parent_id, blk.height)
        snow.accept(blk.id)
        assert seen["device"][-1] == seen["host"][-1]
        assert seen["host"][-1]["status"] == snow.status(blk.id)
    last = Block.decode(wire[-1])
    for backend, vm in vms.items():
        assert vm.last_accepted().id == snow.last_accepted == last.hash()
        assert vm.chain.last_accepted.root == last.header.root
    stats = vms["device"].chain.state_processor.stats
    assert (stats.blocks_verified_device, stats.blocks_verified_host,
            stats.blocks_accepted, stats.engine_rollbacks,
            stats.accepted_off_engine, stats.blocks_fallback) \
        == (len(wire), 0, len(wire), 0, 0, 0)
    # the accepted state, read through the chain, is the reference's
    # book; the builder's own read-back does the comparing
    sp = vms["device"].chain.state_processor
    back = builder.read_back(sp.engine, book)
    assert back["wrong"] == [] and back["compared"] >= 3
    if back["root"] is not None:
        assert back["root"] == last.header.root
    for vm in vms.values():
        vm.shutdown()


@pytest.mark.parametrize("workload", CHAIN_CELLS)
def test_two_deep_fork_on_each_chain_shape(workload):
    """The same chain twice, the second with one transaction of block 4
    moving one unit more (the benchmark's altered chain): X and Y agree
    up to block 3 and fork there.  The engine executes X4 and X5 (the
    transfer window, the in-order check, the step machine and its
    storage trie, by shape), consensus accepts Y4 and Y5, which took
    the host path: account rows, slot mirrors and tries come back out,
    and the accepted state is Y's on both backends."""
    _cell, _entry, config, traffic = names.resolve_cell(SPEC, workload)
    config = json.loads(json.dumps(config))
    config["chain_blocks"] = 5
    if "accounts" in config["chain"]:
        config["chain"]["accounts"] = 16
        config["txs_per_block"] = 8
    seed = 2**31 + 39
    genesis, x = chains.build_wire(config, traffic, seed)
    _g, y = chains.build_wire(config, traffic, seed, alter=(3, 0))
    assert x[:3] == y[:3] and x[3] != y[3]
    gj = genesis_to_json(genesis)
    now = [10**6]
    vms = {b: boot(gj, b, lambda: now[0]) for b in BACKENDS}
    snow = plainsnow.Snow(vms["host"].last_accepted().id, None)

    def call(what, wire):
        for vm in vms.values():
            blk = vm.parse_block(wire)
            getattr(blk, what)()
        if what == "verify":
            snow.verify(blk.id, blk.parent_id, blk.height)
        else:
            getattr(snow, what)(blk.id)
        for vm in vms.values():
            assert vm.last_accepted().id == snow.last_accepted
            assert {h: b.status.value for h, b in vm._blocks.items()} \
                == snow.statuses()

    for w in x[:3]:
        call("verify", w)
        call("accept", w)
    for w in (x[3], x[4], y[3], y[4]):
        call("verify", w)
    sp = vms["device"].chain.state_processor
    assert sp.engine.root == Block.decode(x[4]).header.root
    call("accept", y[3])
    assert sp.engine.root == Block.decode(y[3]).header.root
    call("reject", x[3])
    call("reject", x[4])
    call("accept", y[4])
    last = Block.decode(y[4])
    st = sp.stats
    assert (st.blocks_verified_device, st.blocks_verified_host,
            st.engine_rollbacks, st.blocks_rolled_back,
            st.blocks_reapplied, st.accepted_off_engine,
            st.blocks_fallback) == (5, 2, 1, 2, 2, 0, 0)
    for vm in vms.values():
        assert vm.chain.last_accepted.root == last.header.root
    assert sp.engine.root == last.header.root
    # every account and slot the host VM's accepted state holds, read
    # back from the engine's own tables: nothing of X is left in them
    host = vms["host"].chain.state_at(last.header.root)
    eng = sp.engine
    for addr, idx in eng.state.index.items():
        bal, nonce = eng.state.read_accounts([idx])[0]
        assert (nonce, bal) == (host.get_nonce(addr),
                                host.get_balance(addr)), addr.hex()
    for s_idx in range(1, len(eng.state.slot_keys)):
        contract, key = eng.state.slot_keys[s_idx]
        assert eng.state.slot_host[s_idx] == int.from_bytes(
            host.get_state(contract, key), "big")
    for vm in vms.values():
        vm.shutdown()


# ============================================================ fork scripts
class Forks:
    """Two VMs (host, device), the contract, and a maker of blocks.

    Blocks are written by the program's host processor
    (``generate_chain``) on a database of the maker's own, one 1-transfer
    legacy block at a time on any parent, and handed to each VM as wire
    bytes.  ``plainsnow`` is told the same block as (id, parent, height,
    transfer) and adds the transfer up with ``plainref``."""

    def __init__(self):
        self.genesis = Genesis(
            config=TEST_CHAIN_CONFIG, gas_limit=8_000_000,
            alloc={a: GenesisAccount(balance=FUNDS) for a in ADDRS})
        self.db = Database()
        self.g = self.genesis.to_block(self.db)
        gj = genesis_to_json(self.genesis)
        self.now = [10**6]
        self.vms = {b: boot(gj, b, lambda: self.now[0]) for b in BACKENDS}
        self.snow = plainsnow.Snow(
            self.g.hash(), plainref.Book({a: FUNDS for a in ADDRS}))
        self.blocks = {self.g.hash(): self.g}
        self.moves = {}
        self.serial = 0
        self.fees = plainref.base_fees(64, GAP)

    # ----------------------------------------------------------- the maker
    def make(self, parent: Block, sender: int = 0, tamper=None) -> Block:
        """One more block on ``parent``: key ``sender`` moves a value no
        other block moves (so no two blocks are the same) to SINK."""
        self.serial += 1
        value = self.serial
        book = self.snow.book.get(parent.hash())
        nonce = book.nonce[ADDRS[sender]] if book is not None \
            else self._nonce_by_walk(parent, sender)
        cid = self.genesis.config.chain_id

        def gen(_i, bg):
            bg.add_tx(sign_tx(LegacyTx(
                nonce=nonce, gas_price=bg.base_fee, gas=21_000, to=SINK,
                value=value), KEYS[sender], cid))

        (block,), _ = generate_chain(self.genesis.config, parent, self.db,
                                     1, gen, gap=GAP)
        if tamper is not None:
            block = tamper(block)
        self.blocks[block.hash()] = block
        self.moves[block.hash()] = (ADDRS[sender], SINK, value, 21_000,
                                    self.fees[block.number - 1])
        return block

    def _nonce_by_walk(self, parent: Block, sender: int) -> int:
        n, cur = 0, parent
        while cur.hash() != self.g.hash():
            n += self.moves[cur.hash()][0] == ADDRS[sender]
            cur = self.blocks[cur.parent_hash]
        return n

    # ---------------------------------------------------------- the calls
    def each(self, block: Block):
        wire = block.encode()
        return [vm.parse_block(wire) for vm in self.vms.values()]

    def verify(self, block: Block) -> None:
        for blk in self.each(block):
            blk.verify()
        move = self.moves[block.hash()]
        self.snow.verify(block.hash(), block.parent_hash, block.number,
                         lambda book: book.transfer(*move))
        self.check()

    def refused(self, block: Block, errors) -> None:
        """Every VM refuses the block; nobody's state moves."""
        for blk in self.each(block):
            with pytest.raises(errors):
                blk.verify()
            assert blk.status == Status.UNKNOWN
        self.moves.pop(block.hash(), None)
        self.check()

    def accept(self, block: Block) -> None:
        for blk in self.each(block):
            blk.accept()
        self.snow.accept(block.hash())
        self.check()

    def reject(self, block: Block) -> None:
        for blk in self.each(block):
            blk.reject()
        self.snow.reject(block.hash())
        self.check()

    def prefer(self, block: Block) -> None:
        for vm in self.vms.values():
            vm.set_preference(block.hash())
        self.check()

    # ---------------------------------------------------------- agreement
    def check(self) -> None:
        snow = self.snow
        want_last = self.blocks[snow.last_accepted]
        book = snow.accepted_book()
        assert book.state_root() == want_last.root
        for backend, vm in self.vms.items():
            for h, block in self.blocks.items():
                if h in vm._blocks:
                    assert vm._blocks[h].status.value == snow.status(h), \
                        (backend, block.number)
                else:
                    assert snow.status(h) == plainsnow.UNKNOWN
            assert vm.last_accepted().id == snow.last_accepted, backend
            assert vm.chain.last_accepted.root == want_last.root
            assert accounts_at(vm, want_last.root, ADDRS + [
                SINK, plainref.COINBASE]) == book.accounts(), backend
        # the engine: on a verified block all of whose ancestors are
        # accepted or processing, never behind the last accepted block
        sp = self.vms["device"].chain.state_processor
        assert sp._base == snow.last_accepted
        assert snow.viable(sp.tip())
        assert sp.engine.root == self.blocks[sp.tip()].root
        assert [h.block_hash for h in sp.engine._held] \
            == [b.hash() for b in sp._branch]
        assert sp.stats.accepted_off_engine == 0

    def finish(self) -> None:
        """One more valid block verifies and is accepted on both: the
        engine is not wedged, whatever came before."""
        last = self.blocks[self.snow.last_accepted]
        nxt = self.make(last, sender=1)
        self.verify(nxt)
        self.accept(nxt)
        sp = self.vms["device"].chain.state_processor
        assert sp.tip() == nxt.hash() and sp.engine.root == nxt.root
        for vm in self.vms.values():
            vm.shutdown()


def _accepted_a(f: Forks) -> Block:
    a = f.make(f.g)
    f.verify(a)
    f.accept(a)
    return a


def non_canonical_accept(f: Forks):
    """TestNonCanonicalAccept: B is built and preferred on A, C arrives
    beside it, consensus accepts C."""
    a = _accepted_a(f)
    b = f.make(a)
    f.verify(b)
    f.prefer(b)
    c = f.make(a, sender=2)
    f.verify(c)
    f.accept(c)
    f.reject(b)
    stats = f.vms["device"].chain.state_processor.stats
    assert (stats.engine_rollbacks, stats.blocks_reapplied,
            stats.blocks_verified_host) == (1, 1, 1)


def reorg_protection(f: Forks):
    """TestReorgProtection: once B is accepted, its sibling C cannot
    be."""
    a = _accepted_a(f)
    b = f.make(a)
    c = f.make(a, sender=2)
    f.verify(b)
    f.verify(c)
    f.accept(b)
    for blk in f.each(c):
        with pytest.raises(BadBlockError):
            blk.accept()
        assert blk.status == Status.PROCESSING
    f.check()
    f.reject(c)
    stats = f.vms["device"].chain.state_processor.stats
    assert (stats.engine_rollbacks, stats.blocks_reapplied) == (0, 0)


def sticky_preference(f: Forks):
    """TestStickyPreference: verifying C and D beside the preferred B
    does not move the head; preferring D does; C and D are accepted."""
    a = _accepted_a(f)
    b = f.make(a)
    f.verify(b)
    f.prefer(b)
    c = f.make(a, sender=2)
    d = f.make(c, sender=2)
    f.verify(c)
    f.verify(d)
    for vm in f.vms.values():
        assert vm.chain.current_block().hash() == b.hash()
    f.prefer(d)
    for vm in f.vms.values():
        assert vm.chain.current_block().hash() == d.hash()
        assert vm.chain.get_block_by_number(c.number).hash() == c.hash()
    f.accept(c)
    f.accept(d)
    f.reject(b)


def accept_reorg(f: Forks):
    """TestAcceptReorg: B preferred; C and D verified beside it; the
    accept of C reorgs, D follows, B is rejected."""
    a = _accepted_a(f)
    b = f.make(a)
    c = f.make(a, sender=2)
    d = f.make(c, sender=2)
    f.verify(b)
    f.verify(c)
    f.verify(d)
    f.accept(c)
    for vm in f.vms.values():
        assert vm.chain.get_block_by_number(c.number).hash() == c.hash()
    f.reject(b)
    f.accept(d)


def uncle_block(f: Forks):
    """TestUncleBlock: a block that names an uncle is refused."""
    a = _accepted_a(f)
    b = f.make(a)
    f.verify(b)

    def with_uncle(block):
        from coreth_tpu.crypto import keccak256
        from coreth_tpu import rlp
        uncles = [b.header]
        header = block.header.copy()
        header.uncle_hash = keccak256(rlp.encode(
            [u.rlp_items() for u in uncles]))
        return Block(header, block.transactions, uncles, block.version,
                     block.extdata)

    f.refused(f.make(a, sender=2, tamper=with_uncle),
              (BlockVerificationError, BadBlockError))
    f.accept(b)


def rejected_parent_processing_child(f: Forks):
    """A is rejected while its child B is still processing."""
    a = f.make(f.g)
    b = f.make(a)
    s = f.make(f.g, sender=2)
    f.verify(a)
    f.verify(b)
    f.verify(s)
    f.accept(s)
    f.reject(a)
    f.reject(b)
    stats = f.vms["device"].chain.state_processor.stats
    assert (stats.engine_rollbacks, stats.blocks_rolled_back) == (1, 2)


def two_deep_other_branch_accepted(f: Forks):
    """The engine holds A1 and A2; consensus accepts B1 and B2."""
    a1 = f.make(f.g)
    a2 = f.make(a1)
    b1 = f.make(f.g, sender=2)
    b2 = f.make(b1, sender=2)
    f.verify(a1)
    f.verify(a2)
    f.verify(b1)
    f.verify(b2)
    f.accept(b1)
    f.reject(a1)
    f.reject(a2)
    f.accept(b2)
    stats = f.vms["device"].chain.state_processor.stats
    assert (stats.blocks_verified_device, stats.blocks_verified_host,
            stats.engine_rollbacks, stats.blocks_rolled_back,
            stats.blocks_reapplied) == (2, 2, 1, 2, 2)


def reverify_decided(f: Forks):
    """Verify of an accepted and of a rejected block changes nothing."""
    a = _accepted_a(f)
    f.verify(a)
    s = f.make(a)
    t = f.make(a, sender=2)
    f.verify(s)
    f.verify(t)
    f.accept(t)
    f.reject(s)
    f.verify(s)
    f.verify(t)


def invalid_then_valid(f: Forks):
    """A block with a wrong state root on the engine's tip is refused
    and leaves the engine at the parent; the valid one then verifies."""
    a = _accepted_a(f)

    def wrong_root(block):
        header = block.header.copy()
        header.root = bytes(32 - len(b"bad")) + b"bad"
        return Block(header, block.transactions, block.uncles,
                     block.version, block.extdata)

    bad = f.make(a, tamper=wrong_root)
    f.refused(bad, BadBlockError)
    sp = f.vms["device"].chain.state_processor
    assert sp.engine.root == a.root and sp.tip() == a.hash()
    good = f.make(a)
    f.verify(good)
    # and the same one level up, on a processing tip
    f.refused(f.make(good, tamper=wrong_root), BadBlockError)
    f.accept(good)
    assert sp.stats.blocks_device == sp.stats.blocks_verified_device


SCENARIOS = [non_canonical_accept, reorg_protection, sticky_preference,
             accept_reorg, uncle_block, rejected_parent_processing_child,
             two_deep_other_branch_accepted, reverify_decided,
             invalid_then_valid]


@pytest.mark.parametrize("scenario", SCENARIOS,
                         ids=[s.__name__ for s in SCENARIOS])
def test_fork_scenarios(scenario):
    f = Forks()
    scenario(f)
    f.finish()


# ========================================================== random scripts
MAX_DEPTH = 4
N_SCRIPTS = 56
STEPS = 14


@pytest.mark.parametrize("seed", range(N_SCRIPTS))
def test_random_scripts_of_legal_calls(seed):
    """Any order of calls the contract allows: new blocks on any branch
    consensus can still accept (at most MAX_DEPTH undecided
    generations), accepts, rejects, set_preference, verifies again.
    After every call the three agree."""
    rng = random.Random(seed)
    f = Forks()
    snow = f.snow
    for _ in range(STEPS):
        parents = [h for h in f.blocks
                   if snow.status(h) != plainsnow.UNKNOWN
                   and snow.viable(h) and snow.depth(h) < MAX_DEPTH]
        roll = rng.random()
        decided = [c for c in snow.legal() if c[0] != "verify"]
        if roll < 0.5 or not decided:
            f.verify(f.make(f.blocks[rng.choice(sorted(parents))],
                            sender=rng.randrange(3)))
        elif roll < 0.9:
            # consensus mostly accepts; a reject is rarer, and lands
            # on doomed and on still-viable blocks alike
            accepts = [c for c in decided if c[0] == "accept"]
            call, h = rng.choice(sorted(
                accepts if accepts and rng.random() < 0.7 else decided))
            getattr(f, call)(f.blocks[h])
        elif roll < 0.95:
            known = sorted(h for c, h in snow.legal() if c == "verify")
            if known:
                f.verify(f.blocks[rng.choice(known)])
        else:
            viable = sorted(h for h in f.blocks
                            if snow.status(h) == plainsnow.PROCESSING
                            and snow.viable(h))
            if viable:
                f.prefer(f.blocks[rng.choice(viable)])
    # decide what is left, children after parents, then go on
    for h in sorted((h for h, s in snow.statuses().items()
                     if s == plainsnow.PROCESSING),
                    key=lambda h: f.blocks[h].number):
        f.reject(f.blocks[h])
    f.finish()


# ================================================= reads after an accept
def test_reads_after_accept_on_the_device_backend():
    """What the chain hands out once the engine has executed a block:
    a state a StateDB can open, receipts, a tx pool that follows the
    head, and ``eth_getBalance`` through ``rpc/``."""
    f = Forks()
    vm = f.vms["device"]
    a = f.make(f.g)
    b = f.make(a)
    f.verify(a)
    f.verify(b)      # processing: its root is handed out too
    f.accept(a)
    for block in (a, b):
        sdb = vm.chain.state_at(block.root)
        assert sdb.get_nonce(ADDRS[0]) == block.number
        assert sdb.get_balance(SINK) == sum(
            f.moves[h][2] for h in (a.hash(), b.hash())[:block.number])
        (r,) = vm.chain.get_receipts(block.hash())
        tx = block.transactions[0]
        assert (r.tx_hash, r.block_hash, r.gas_used, r.status) \
            == (tx.hash(), block.hash(), 21_000, 1)
    # the pool was reset on b's head event: a transaction at the
    # head's nonce is pending, one below it is refused
    cid = f.genesis.config.chain_id
    fresh = sign_tx(LegacyTx(nonce=2, gas_price=10**12, gas=21_000,
                             to=SINK, value=5), KEYS[0], cid)
    stale = sign_tx(LegacyTx(nonce=1, gas_price=10**12, gas=21_000,
                             to=SINK, value=5), KEYS[0], cid)
    vm.issue_tx(fresh)
    assert vm.mempool_stats()[0] == 1
    with pytest.raises(Exception, match="nonce"):
        vm.issue_tx(stale)
    # latest = the accepted block's state, through the RPC surface
    got = vm.eth.rpc_server.handle_request({
        "jsonrpc": "2.0", "id": 1, "method": "eth_getBalance",
        "params": ["0x" + SINK.hex(), "latest"]})
    assert int(got["result"], 16) in (
        f.moves[a.hash()][2],
        f.moves[a.hash()][2] + f.moves[b.hash()][2])
    f.accept(b)
    f.finish()


def test_vm_phases_and_counters_are_published():
    """The consensus calls are phases of the engine's account, and the
    consensus counters ride ``ReplayStats.row()`` and
    ``publish_metrics``."""
    from coreth_tpu.metrics.registry import Registry
    f = Forks()
    non_canonical_accept(f)
    sp = f.vms["device"].chain.state_processor
    acct = sp.account.row()
    for phase in ("vm/parse", "vm/verify", "vm/insert", "vm/accept",
                  "vm/reject", "vm/rollback"):
        assert acct["n"][phase] >= 1 and acct["self_s"][phase] > 0, phase
    assert abs(sum(acct["self_s"].values())
               - (acct["t_last"] - acct["t_open"])) < 1e-6
    row = sp.stats.row()
    want = {"blocks_verified_device": 2, "blocks_verified_host": 1,
            "blocks_accepted": 2, "blocks_rejected": 1,
            "engine_rollbacks": 1, "blocks_reapplied": 1,
            "accepted_off_engine": 0}
    assert {k: row[k] for k in want} == want
    reg = Registry()
    sp.engine.publish_metrics(reg)
    snap = reg.snapshot()
    for name, value in want.items():
        assert snap[f"replay/{name}"]["value"] == value, name
    f.finish()


# ============================================================ the selector
def test_host_is_the_default_and_device_refuses_shared_memory():
    """Host is the default; "device" takes shared memory (the atomic
    subsystem runs on the engine: tests/test_atomic_device.py) and runs
    blocks; an unknown value is refused."""
    f = Forks()
    gj = genesis_to_json(f.genesis)
    vm = VM()
    vm.initialize(gj)
    assert vm.config.state_processor == "host"
    assert vm.chain.state_processor is None and vm.chain.snaps is not None
    vm.shutdown()
    from coreth_tpu.atomic import Memory
    vm = VM(clock=lambda: f.now[0], engine_kw=dict(ENGINE_KW),
            shared_memory=Memory().new_shared_memory(b"\x11" * 32))
    vm.initialize(gj, json.dumps({"state-processor": "device"}))
    assert vm.atomic_backend is not None
    sp = vm.chain.state_processor
    assert sp.engine.engine.cb.on_extra_state_rows is not None
    a = f.make(f.g)
    blk = vm.parse_block(a.encode())
    blk.verify()
    blk.accept()
    assert vm.chain.last_accepted.root == a.root
    assert sp.stats.blocks_verified_device == 1
    vm.shutdown()
    for other in f.vms.values():
        other.shutdown()
    vm = VM()
    with pytest.raises(VMError, match="state_processor 'gpu'"):
        vm.initialize(gj, json.dumps({"state-processor": "gpu"}))


def test_device_backend_over_a_durable_store_reopens(tmp_path):
    """``BlockChain(chain_kv=..., state_processor=...)``: the engine
    commits into the store's node dict, and a reopened chain starts its
    engine on the last accepted block."""
    import functools
    from coreth_tpu.chain import BlockChain
    from coreth_tpu.rawdb import FileDB
    from coreth_tpu.replay.device_processor import DeviceProcessor
    f = Forks()
    blocks, parent = [], f.g
    for _ in range(6):
        parent = f.make(parent)
        blocks.append(parent)
    factory = functools.partial(DeviceProcessor, **ENGINE_KW)
    path = str(tmp_path / "chain.log")
    chain = BlockChain(f.genesis, chain_kv=FileDB(path), commit_interval=4,
                       state_processor=factory)
    chain.insert_chain(blocks[:5])
    assert chain.state_processor.stats.blocks_verified_device == 5
    chain.close()
    chain = BlockChain(f.genesis, chain_kv=FileDB(path), commit_interval=4,
                       state_processor=factory)
    assert chain.last_accepted.hash() == blocks[4].hash()
    assert chain.state_processor.engine.root == blocks[4].root
    chain.insert_chain(blocks[5:])
    assert chain.last_accepted.root == blocks[5].root
    assert chain.state_processor.stats.blocks_verified_device == 1
    chain.close()
    for vm in f.vms.values():
        vm.shutdown()


def test_processing_blocks_are_revertible_past_the_flat_keep():
    """More undecided generations than the flat store's KEEP: pinned
    generations are not pruned, the whole branch comes back out and the
    engine lands on the fork point's root."""
    from coreth_tpu.state.flat import FlatStore
    f = Forks()
    sp = f.vms["device"].chain.state_processor
    depth = FlatStore.KEEP + 3
    branch, parent = [], f.g
    for _ in range(depth):
        parent = f.make(parent)
        f.verify(parent)
        branch.append(parent)
    assert len(sp.engine.flat.gens) >= depth
    other = f.make(f.g, sender=2)
    f.verify(other)
    f.accept(other)
    assert sp.stats.blocks_rolled_back == depth
    for block in branch:
        f.reject(block)
    f.finish()

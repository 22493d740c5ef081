"""Closed loop, one client, the consensus engine's: a node bootstrapping
a chain through ``plugin/vm.py``, block by block.

AvalancheGo's Snowman bootstrapper fetches the chain and executes it
through the VM, ``blk.Verify`` then ``blk.Accept``, block by block in
height order (``snow/engine/snowman/bootstrap/block_job.go``).  A pass
here (``make_pass``, so the harness warms, times, traces and reads back
this call and no other) is:

- a fresh ``VM`` initialised from the genesis BYTES with the per-chain
  config ``{"state-processor": "device"}`` (``engine_build_s``);
- for each block of the chain, in height order: ``vm.parse_block(wire
  bytes)``, ``blk.verify()``, ``blk.accept()`` — each call made when
  the one before has returned;
- at the LAST height the fork (the traffic mix's ``siblings_per_pass``
  1): the sibling (``chains/value_tx_fork.py``; written once, in
  set-up, on the host path) is verified first, so the engine executes
  it; then the
  chain's last block is verified (host path), accepted (the engine
  undoes the sibling and runs the accepted block) and the sibling
  rejected;
- ``vm.shutdown()``.

The calls go through ``replay_pass.run_engine(client, blocks)`` with a
client whose ``replay_block`` / ``replay`` make them, so the controls
and faults of ``benchlib/faults.py`` are planted under this pass as
under the others (``benchmarks/control.py`` reaches it unedited).  The
blocks handed over are the wire bytes themselves (``_Wire``: bytes that
decode only when a fault asks for a header or a transaction).  The
cell's own fault is ``FAULT = "sibling_accepted"``: consensus's client
accepts the sibling and rejects the chain's block.

The row is in ``replay_pass.one_pass``'s keys.  ``blocks`` and
``txs_committed`` count what consensus ACCEPTED; ``decode_s`` is 0
(parse is a phase of the VM's own, ``vm/parse``); under ``vm`` the
engine's consensus counters, and two the window fills in once it has
closed: ``status_off_reference`` — the script of calls the client made,
replayed through ``benchlib/plainsnow.py``, against every block's final
status and the last accepted id — and ``rollbacks_off_plan``.
``_engine`` reads the VM's LAST ACCEPTED state, not the engine's tip.
"""

import functools
import json
import time

from benchlib import names, replay_pass

FAULT = None  # "sibling_accepted": the cell's own fault (tests, control)


class _Wire(bytes):
    """A block as its wire bytes; decoded only for whoever asks for a
    field of it (a planted fault)."""

    def __getattr__(self, name):
        from coreth_tpu.types import Block
        return getattr(Block.decode(bytes(self)), name)


class _Consensus:
    """The consensus engine's side: the bootstrapper's calls on one VM,
    behind the two methods ``replay_pass.run_engine`` drives."""

    def __init__(self, vm, now, sibling_wire, fork_parent):
        self.vm = vm
        self.now = now
        self.sibling_wire = sibling_wire
        self.fork_parent = fork_parent
        self.root = bytes(vm.chain.last_accepted.root)
        self.txs = 0
        self.script = []  # every call made, for plainsnow

    def replay_block(self, block) -> None:
        self.replay([block])

    def replay(self, blocks) -> None:
        vm, script = self.vm, self.script
        for b in blocks:
            blk = vm.parse_block(b if type(b) is _Wire else b.encode())
            self.now[0] = blk.timestamp
            if blk.parent_id == self.fork_parent:
                blk = self._fork(blk)
            else:
                blk.verify()
                script.append(("verify", blk.id, blk.parent_id, blk.height))
                blk.accept()
                script.append(("accept", blk.id))
            self.txs += len(blk.block.transactions)
            self.root = bytes(vm.chain.last_accepted.root)

    def _fork(self, blk):
        """Two blocks on one parent, both verified, one accepted, the
        other rejected; the sibling first, so the engine runs it."""
        sib = self.vm.parse_block(self.sibling_wire)
        keep, drop = (sib, blk) if FAULT == "sibling_accepted" \
            else (blk, sib)
        for b in (sib, blk):
            b.verify()
            self.script.append(("verify", b.id, b.parent_id, b.height))
        keep.accept()
        self.script.append(("accept", keep.id))
        drop.reject()
        self.script.append(("reject", drop.id))
        return keep


class _Accepted:
    """The state consensus accepted, as ``chains.read_accounts`` reads
    an engine: ``commit()``, ``root``, ``db``."""

    def __init__(self, chain):
        self.db = chain.db
        self.root = bytes(chain.last_accepted.root)

    def commit(self) -> bytes:
        return self.root


def engine_off_accepted(engine, chain) -> int:
    """How far what the ENGINE holds lies from what consensus accepted:
    1 if its root is not the last accepted block's, and one for every
    account it keeps whose nonce or balance on its device rows, or in
    its flat layer, is not the one under the accepted root."""
    from coreth_tpu.state import StateDB
    from coreth_tpu.state.flat.store import DELETED
    accepted = StateDB(bytes(chain.last_accepted.root), chain.db)
    st = engine.state
    off = int(bytes(engine.root) != bytes(chain.last_accepted.root))
    rows = st.read_accounts(list(range(len(st.addrs))))
    for addr, (balance, nonce) in zip(st.addrs, rows):
        want = (accepted.get_balance(addr), accepted.get_nonce(addr))
        flat = engine.flat.account(addr)  # None: it does not know
        if flat is not None:
            flat = (0, 0) if flat is DELETED else tuple(flat[:2])
        off += (balance, nonce) != want or flat not in (None, want)
    return off


def _boot(genesis_json, engine_kw, now):
    from coreth_tpu.plugin import VM
    vm = VM(clock=lambda: now[0], engine_kw=dict(engine_kw))
    vm.initialize(genesis_json,
                  json.dumps({"state-processor": "device"}).encode())
    return vm


def make_pass(ctx: dict, traffic: dict):
    from coreth_tpu.plugin import config as vm_config
    if not hasattr(vm_config.Config(), "state_processor"):
        raise SystemExit("this program's VM has no state-processor key: "
                         "the cell cannot run on it")
    from benchlib.genesis_bytes import genesis_to_json
    from coreth_tpu.types import Block
    genesis, wire = ctx["genesis"], ctx["wire"]
    genesis_json = genesis_to_json(genesis).encode()
    blocks = [Block.decode(w) for w in wire]
    return functools.partial(
        one_pass, genesis_json, [_Wire(w) for w in wire],
        genesis.sibling(blocks[:-1]).encode(),
        bytes(blocks[-1].parent_hash),
        sum(len(b.transactions) for b in blocks),
        ctx["engine_kw"], traffic["siblings_per_pass"])


def one_pass(genesis_json, wire, sibling, fork_parent, chain_txs,
             engine_kw, siblings, annotate: bool = False) -> dict:
    from coreth_tpu.evm.device import adapter
    now = [0]
    t0 = time.monotonic()
    with replay_pass._span("vm_build", annotate):
        vm = _boot(genesis_json, engine_kw, now)
    t1 = time.monotonic()
    client = _Consensus(vm, now, sibling, fork_parent)
    d0 = adapter.DISPATCH_COUNT
    error = None
    try:
        with replay_pass._span("bootstrap", annotate):
            replay_pass.run_engine(client, wire)
    except Exception as exc:  # noqa: BLE001 — a pass that raises is a failed pass; the comparison after the window reports it
        error = f"{type(exc).__name__}: {exc}"[:500]
    finally:
        vm.shutdown()
    t2 = time.monotonic()
    engine = vm.chain.state_processor.engine
    st = engine.stats
    row = {
        "t_start": t0, "t_end": t2,
        "decode_s": 0.0, "engine_build_s": t1 - t0, "replay_s": t2 - t1,
        "blocks": len(wire), "txs": chain_txs,
        "root": client.root, "error": error,
        "dispatches": adapter.DISPATCH_COUNT - d0,
    }
    row.update(replay_pass.engine_row(engine))
    # what consensus accepted, not what the engine ran (the sibling,
    # the accepted block a second time)
    row["txs_committed"] = client.txs
    row["blocks_off_device"] = len(wire) - (
        st.blocks_device - st.blocks_rolled_back)
    row["vm"] = {
        "blocks_verified_device": st.blocks_verified_device,
        "blocks_verified_host": st.blocks_verified_host,
        "blocks_accepted": st.blocks_accepted,
        "blocks_rejected": st.blocks_rejected,
        "engine_rollbacks": st.engine_rollbacks,
        "blocks_reapplied": st.blocks_reapplied,
        "accepted_off_engine": st.accepted_off_engine,
        "rollbacks_off_plan": abs(st.engine_rollbacks - siblings),
        "engine_off_accepted": engine_off_accepted(engine, vm.chain),
    }
    row["_script"] = client.script
    row["_final"] = ({h: b.status.value for h, b in vm._blocks.items()},
                     vm.chain.last_accepted.hash())
    row["_engine"] = _Accepted(vm.chain)
    return row


def status_off_reference(row: dict) -> int:
    """The pass's calls replayed through the contract's plain
    reference: blocks whose final status in the VM is not the
    reference's, one more if the last accepted id differs, and every
    call the contract did not allow."""
    from benchlib import plainsnow
    statuses, last = row["_final"]
    genesis = next(h for h, s in statuses.items()
                   if s == plainsnow.ACCEPTED
                   and all(h != c[1] for c in row["_script"]))
    snow = plainsnow.Snow(genesis, None)
    off = 0
    for call in row["_script"]:
        try:
            getattr(snow, call[0])(*call[1:])
        except plainsnow.ContractError:
            off += 1
    want = snow.statuses()
    off += sum(statuses.get(h, plainsnow.UNKNOWN) != s
               for h, s in want.items())
    off += sum(h not in want and s != plainsnow.UNKNOWN
               for h, s in statuses.items())
    return off + (last != snow.last_accepted)


def drive(ctx: dict, seconds: float, traffic: dict) -> dict:
    loop, _ = names.load_named("drivers", "closed_loop_passes")
    window = loop.drive(ctx, seconds, traffic)
    # the window has closed: the reference's verdict costs it nothing
    for row in window["rows"]:
        row["vm"]["status_off_reference"] = status_off_reference(row)
    return window

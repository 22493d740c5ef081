"""Closed loop, one client, the consensus engine's: a node bootstrapping
a chain whose blocks carry atomic txs, through ``plugin/vm.py`` with
shared memory, block by block.

``snowman_bootstrap``'s pass (its client, wire blocks, read-back of the
accepted state and the contract's reference are used as they are,
through ``load_named``) with the atomic subsystem wired in:

- each pass builds a fresh ``VM`` handed a freshly seeded shared-memory
  hub (the builder's ``Genesis.seed_shared_memory()``: the chain's
  import UTXOs) and the per-chain config ``{"state-processor":
  "device"}``, then makes 2,500 x parse, Verify and Accept;
- the fork at the last height: the sibling (``Genesis.sibling``) spends
  the last block's atomic input; it is verified first, on the engine,
  the last block is verified on the host path and accepted (the engine
  rolls back), the sibling is rejected;
- ``txs_committed`` counts the EVM transfers and the atomic txs
  consensus accepted, each once (the atomic repository's entries), the
  two kept apart as ``evm_txs_committed`` / ``atomic_txs_committed``.

The row is ``snowman_bootstrap``'s with ``atomic``: the engine's
counters (``blocks_device``, ``txs_device``, ``blocks_host``) and, once
the window has closed, ``utxos_off_reference`` — the UTXOs each pass
left in its hub against ``plainatomic.inbox`` (the builder's
``Genesis.inbox_reference()``): every one missing, extra or of other
bytes, in both directions.
"""

import functools
import json
import time

from benchlib import names, replay_pass

_base, _ = names.load_named("drivers", "snowman_bootstrap")


def _boot(genesis_json, engine_kw, now, memory):
    from coreth_tpu.plugin import VM
    from coreth_tpu.workloads.atomic import CTX
    vm = VM(clock=lambda: now[0], engine_kw=dict(engine_kw),
            shared_memory=memory.new_shared_memory(CTX.chain_id))
    vm.initialize(genesis_json,
                  json.dumps({"state-processor": "device"}).encode())
    return vm


def make_pass(ctx: dict, traffic: dict):
    from benchlib.genesis_bytes import genesis_to_json
    from coreth_tpu.types import Block
    genesis, wire = ctx["genesis"], ctx["wire"]
    genesis_json = genesis_to_json(genesis).encode()
    # a program whose VM refuses shared memory on the device backend
    # cannot run the cell: say so at once
    try:
        _boot(genesis_json, ctx["engine_kw"], [0],
              genesis.seed_shared_memory()).shutdown()
    except Exception as exc:  # noqa: BLE001 — any refusal ends the run here
        raise SystemExit(f"this program's VM cannot run the cell: {exc}")
    blocks = [Block.decode(w) for w in wire]
    return functools.partial(
        one_pass, genesis_json, genesis.seed_shared_memory,
        [_base._Wire(w) for w in wire],
        genesis.sibling(blocks[:-1]).encode(),
        bytes(blocks[-1].parent_hash),
        sum(len(b.transactions) for b in blocks),
        ctx["engine_kw"], traffic["siblings_per_pass"])


def one_pass(genesis_json, seed_memory, wire, sibling, fork_parent,
             chain_txs, engine_kw, siblings, annotate: bool = False) -> dict:
    from coreth_tpu.evm.device import adapter
    now = [0]
    memory = seed_memory()
    t0 = time.monotonic()
    with replay_pass._span("vm_build", annotate):
        vm = _boot(genesis_json, engine_kw, now, memory)
    t1 = time.monotonic()
    client = _base._Consensus(vm, now, sibling, fork_parent)
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
    atomic_accepted = sum(1 for k in vm.atomic_store
                          if k.startswith(b"atx"))
    row["evm_txs_committed"] = client.txs
    row["atomic_txs_committed"] = atomic_accepted
    row["txs_committed"] = client.txs + atomic_accepted
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
        "engine_off_accepted": _base.engine_off_accepted(engine, vm.chain),
    }
    row["atomic"] = {
        "blocks_device": st.atomic_blocks_device,
        "txs_device": st.atomic_txs_device,
        "blocks_host": st.atomic_blocks_host,
    }
    row["_script"] = client.script
    row["_final"] = ({h: b.status.value for h, b in vm._blocks.items()},
                     vm.chain.last_accepted.hash())
    row["_memory"] = memory
    row["_engine"] = _base._Accepted(vm.chain)
    return row


def utxos_off_reference(memory, reference: dict) -> int:
    """UTXOs of the pass's hub not as the reference has them: in the
    C-Chain's inbound view from the X-Chain (what imports left) and in
    the X-Chain's from the C-Chain (what exports put), every key
    missing, extra or holding other bytes."""
    from coreth_tpu.workloads.atomic import CTX
    off = 0
    for (src, dst), want in (
            ((CTX.x_chain_id, CTX.chain_id), reference["x_to_c"]),
            ((CTX.chain_id, CTX.x_chain_id), reference["c_to_x"])):
        got = memory._spaces.get((src, dst), {})
        off += sum(got.get(k) != v for k, v in want.items())
        off += sum(k not in want for k in got)
    return off


def drive(ctx: dict, seconds: float, traffic: dict) -> dict:
    loop, _ = names.load_named("drivers", "closed_loop_passes")
    window = loop.drive(ctx, seconds, traffic)
    # the window has closed: the references' verdicts cost it nothing
    reference = ctx["genesis"].inbox_reference()
    for row in window["rows"]:
        row["vm"]["status_off_reference"] = _base.status_off_reference(row)
        row["atomic"]["utxos_off_reference"] = utxos_off_reference(
            row.pop("_memory"), reference)
    return window

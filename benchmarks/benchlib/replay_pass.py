"""One catch-up pass, as the timed window drives it.

A pass is what a node catching up over a backlog does with one chain:
decode every block from its wire bytes (no cached senders), build a
fresh engine on the genesis state, put the lead block through
``ReplayEngine.replay_block`` and the rest through
``ReplayEngine.replay``.  Copied from ``chip_smoke.replay_once`` /
``_engine_row`` / ``replay_failures``; the clock readings around decode
and engine construction are the runner's own, because ``ReplayStats``
has no field for them (ROADMAP D16).
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Optional


def fresh_engine(genesis, engine_kw: dict):
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    db = Database()
    gblock = genesis.to_block(db)
    return ReplayEngine(genesis.config, db, gblock.root,
                        parent_header=gblock.header, **engine_kw)


def run_engine(engine, blocks) -> None:
    """The entry the window drives.  Tests plant faults here."""
    engine.replay_block(blocks[0])
    engine.replay(blocks[1:])


def _span(name: str, on: bool):
    """The runner's own span in the profiler's trace (traced pass
    only), so an idle gap of the device can be named by what the host
    was doing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench/" + name)


def one_pass(genesis, wire: List[bytes], engine_kw: dict,
             annotate: bool = False) -> dict:
    """Decode, build, replay; returns the pass's clock readings, the
    root the engine committed, and the engine's own counters.  A pass
    that raises is recorded, not re-raised: its blocks count as failed
    and the window goes on."""
    from coreth_tpu.evm.device import adapter
    from coreth_tpu.types import Block
    t0 = time.monotonic()
    with _span("decode", annotate):
        blocks = [Block.decode(w) for w in wire]
    t1 = time.monotonic()
    with _span("engine_build", annotate):
        engine = fresh_engine(genesis, engine_kw)
    t2 = time.monotonic()
    d0 = adapter.DISPATCH_COUNT
    error: Optional[str] = None
    try:
        with _span("replay", annotate):
            run_engine(engine, blocks)
    except Exception as exc:  # noqa: BLE001 — a pass that raises is a failed pass; the comparison after the window reports it
        error = f"{type(exc).__name__}: {exc}"[:500]
    t3 = time.monotonic()
    row = {
        "t_start": t0, "t_end": t3,
        "decode_s": t1 - t0, "engine_build_s": t2 - t1,
        "replay_s": t3 - t2,
        "blocks": len(blocks),
        "txs": sum(len(b.transactions) for b in blocks),
        "root": bytes(engine.root),
        "error": error,
        "dispatches": adapter.DISPATCH_COUNT - d0,
    }
    row.update(engine_row(engine))
    row["blocks_off_device"] = len(blocks) - row["blocks_device"]
    if "machine" in row:
        row["machine"]["blocks_off_machine"] = (
            len(blocks) - row["machine"]["machine_blocks"])
    row["_engine"] = engine
    return row


def engine_row(engine) -> dict:
    st = engine.stats
    sup = engine.supervisor.snapshot()
    row = {
        "blocks_device": st.blocks_device,
        "blocks_fallback": st.blocks_fallback,
        "txs_committed": st.txs,
        "sigs_device": st.sigs_device, "sigs_host": st.sigs_host,
        "t_sender_device": st.t_sender_device,
        "t_sender_host": st.t_sender_host,
        "recover_degraded": st.recover_degraded,
        "t_classify": st.t_classify, "t_sender": st.t_sender,
        "t_device": st.t_device, "t_trie": st.t_trie,
        "t_fallback": st.t_fallback,
        "supervisor": {k: sup[k] for k in ("retries", "strikes",
                                           "demotions")},
    }
    mx = getattr(engine, "_machine", None)
    if mx is not None:
        mc = mx.machine_counters()
        row["machine"] = {
            "machine_blocks": mx.blocks, "host_txs": mx.host_txs,
            "occ_rounds": mx.rounds, "occ_windows": mx.windows,
            "window_attempts": mx.window_attempts,
            "serial_blocks": mx.serial_blocks,
            "dirty_blocks": mx.dirty_blocks,
            "kernel_retraces": mc["kernel_retraces"],
            "warm_failures": mc["warm_failures"],
            "specialize_escapes": mc["specialize_escapes"],
            "lanes_specialized": mc["lanes_specialized"],
            "programs_traced": mc["programs_traced"],
            "discovery_dispatches": mc["discovery_dispatches"],
        }
    return row


def _lookup(row: dict, path: str):
    for part in path.split("."):
        row = row[part]
    return row


def path_violations(rows: List[dict], expect: dict) -> dict:
    """How far the passes left the path the cell stands for, by the
    engine's own counters (``chip_smoke.replay_failures``, as data).

    The configuration's ``expect.zero`` lists counters of a pass row by
    dotted path (``blocks_fallback``, ``blocks_off_device``,
    ``machine.host_txs`` ...) whose sum over the passes has to be 0.
    Returns name -> sum; each goes among the numbers ``correct``
    compares, limit 0: a run that is right but took another path is
    not a run of this cell."""
    return {path: sum(_lookup(r, path) for r in rows)
            for path in expect.get("zero", [])}

"""Closed loop, one client, backlog — ``closed_loop_passes`` with the
call-frame counters of each pass's engine in its row.

The pass is ``replay_pass.one_pass`` as the harness hands it over in
``ctx["pass_fn"]``; this driver wraps it to add ``frames`` to every
row (``replay_pass.engine_row`` names a fixed set of ``machine`` keys):
``frames`` (``ReplayStats.machine_frames``: call frames the committed
lanes entered on the device), ``lane_steps``
(``ReplayStats.machine_lane_steps``: device steps those lanes ran) and
``host_txs`` (``ReplayStats.machine_frame_host_txs``: transactions a
frame handed to the host, by reason).  ``metrics/call_frames_per_tx``
and ``metrics/machine_steps_per_tx`` read them.

A program whose device step machine has no CALL cannot run the cell:
every block would leave the device for the host interpreter, which
takes minutes a pass.  ``make_pass`` refuses at set-up then, with the
reason, before any pass runs.
"""

from benchlib import names, replay_pass


def _with_frames(row: dict) -> dict:
    st = row["_engine"].stats
    if hasattr(st, "machine_frames"):
        row["frames"] = {
            "frames": st.machine_frames,
            "lane_steps": st.machine_lane_steps,
            "host_txs": dict(st.machine_frame_host_txs)}
    return row


def make_pass(ctx: dict, traffic: dict):
    from coreth_tpu.evm.device import tables
    from coreth_tpu.types import Block
    genesis, wire = ctx["genesis"], ctx["wire"]
    block = Block.decode(wire[0])
    fork = tables.fork_key(genesis.config.rules(block.number, block.time))
    if fork is None or 0xF1 not in tables.device_opcodes(fork):
        raise SystemExit(
            "this program's device step machine has no CALL: every swap "
            "would run on the host interpreter, so it cannot run the cell")

    def pass_fn(annotate: bool = False) -> dict:
        return _with_frames(replay_pass.one_pass(
            genesis, wire, ctx["engine_kw"], annotate=annotate))

    return pass_fn


def drive(ctx: dict, seconds: float, traffic: dict) -> dict:
    loop, _ = names.load_named("drivers", "closed_loop_passes")
    return loop.drive(ctx, seconds, traffic)

"""Device steps the committed lanes ran, a committed transaction
(``ReplayStats.machine_lane_steps`` over ``txs_committed``, summed over
the timed passes; the rows of ``closed_loop_frame_passes`` carry it):
one step is one opcode of one lane in the step machine, call frames
included."""


def read(run):
    rows = [r for r in run["passes"] if "frames" in r]
    txs = sum(r["txs_committed"] for r in rows)
    if not txs:
        return None
    return sum(r["frames"]["lane_steps"] for r in rows) / txs

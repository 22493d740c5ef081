"""Call frames the device step machine entered, a committed
transaction (``ReplayStats.machine_frames`` over ``txs_committed``,
summed over the timed passes; the rows of ``closed_loop_frame_passes``
carry it).  A one-hop Uniswap V2 swap makes six: getReserves,
transferFrom, swap, transfer and two balanceOf."""


def read(run):
    rows = [r for r in run["passes"] if "frames" in r]
    txs = sum(r["txs_committed"] for r in rows)
    if not txs:
        return None
    return sum(r["frames"]["frames"] for r in rows) / txs

"""Share of the window the replay thread was BLOCKED reading a transfer
window's results back (``window/fetch_wait``: ``np.asarray(fetches)``).
Self time from the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("window/fetch_wait",))

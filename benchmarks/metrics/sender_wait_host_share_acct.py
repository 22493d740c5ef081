"""Share of the window the replay thread was BLOCKED on the native
recovery pool (``sender/wait_host``: ``Future.result()``).  Self time
from the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("sender/wait_host",))

"""Share of the window the replay thread was BLOCKED reading a device
recovery batch back (``sender/wait_device``: the read alone, the host
finish is work).  Self time from the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("sender/wait_device",))

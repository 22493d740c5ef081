"""Share of the window spent packing and padding transfer windows on the
host (``window/prepare``: ``_prepare_window``).  Self time from the
engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("window/prepare",))

"""Share of the window the replay thread spent issuing device windows
and waiting for their results (``ReplayStats.t_device``: the transfer
window or the fused OCC machine)."""

from benchlib.shares import share

# a busy share of the window's wall: unaccounted_share subtracts it
WINDOW_SHARE = True


def read(run):
    return share(run, "t_device")

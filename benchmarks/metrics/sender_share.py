"""Share of the window the replay thread spent in sender recovery
(``ReplayStats.t_sender``: issue and completion of device and host
batches)."""

from benchlib.shares import share

# a busy share of the window's wall: unaccounted_share subtracts it
WINDOW_SHARE = True


def read(run):
    return share(run, "t_sender")

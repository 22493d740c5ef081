"""Share of the window the runner spent decoding blocks from wire bytes
(its own clock around ``Block.decode``)."""

from benchlib.shares import share

# a busy share of the window's wall: unaccounted_share subtracts it
WINDOW_SHARE = True


def read(run):
    return share(run, "decode_s")

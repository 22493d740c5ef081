"""Share of the window the runner spent building fresh engines on the
genesis state (its own clock around ``fresh_engine``)."""

from benchlib.shares import share

# a busy share of the window's wall: unaccounted_share subtracts it
WINDOW_SHARE = True


def read(run):
    return share(run, "engine_build_s")

"""Share of the window the replay thread spent staging and folding
tries (``ReplayStats.t_trie``)."""

from benchlib.shares import share

# a busy share of the window's wall: unaccounted_share subtracts it
WINDOW_SHARE = True


def read(run):
    return share(run, "t_trie")

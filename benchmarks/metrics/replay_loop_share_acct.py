"""Share of the window inside a public call of the engine and in no phase
below it (``loop``): the replay loop's own bookkeeping.  Self time from
the engine's account."""

from benchlib.account import share


def read(run):
    return share(run, ("loop",))

"""Share of the window spent holding device blocks to their headers
before staging (``validate``: gas, receipt root, bloom, block fee),
which ``ReplayStats`` has no field for.  Self time from the engine's
account."""

from benchlib.account import share


def read(run):
    return share(run, ("validate",))

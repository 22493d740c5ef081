"""Share of the window in no phase of any engine and not in the runner's
decode: engine teardown between passes, the genesis database, the
runner itself (100 minus ``decode_share``'s seconds minus every phase
of every account but ``idle``)."""

from benchlib.account import outside_engine


def read(run):
    return outside_engine(run)

"""Microseconds a chain block of the atomic txs' bookkeeping on the
engine's path: account phase ``vm/atomic_verify`` (the consensus
callbacks' ``on_extra_state_rows``: ExtData decode, semantic verify with
the credentials' recovery, input conflicts, the backend's record of the
block).  None where no pass has the phase: a program without it, or a
chain without atomic txs."""

from benchlib.vmphases import by_phase, us_per_block

PHASE = "vm/atomic_verify"


def read(run):
    seconds = by_phase(run)
    if seconds is None or PHASE not in seconds:
        return None
    return us_per_block(run, (PHASE,))

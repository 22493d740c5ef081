"""Microseconds a chain block of the VM's atomic Accept: account phase
``vm/atomic_accept`` (the backend's accept — atomic trie and shared
memory — the repository's index and the mempool).  None where no pass
has the phase."""

from benchlib.vmphases import by_phase, us_per_block

PHASE = "vm/atomic_accept"


def read(run):
    seconds = by_phase(run)
    if seconds is None or PHASE not in seconds:
        return None
    return us_per_block(run, (PHASE,))

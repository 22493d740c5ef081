"""Microseconds a chain block of turning the atomic txs' state transfer
into transfer-window lanes: account phase ``classify/atomic`` (the
credit and debit rows, their accounts' device rows and nonce offsets).
None where no pass has the phase."""

from benchlib.vmphases import by_phase, us_per_block

PHASE = "classify/atomic"


def read(run):
    seconds = by_phase(run)
    if seconds is None or PHASE not in seconds:
        return None
    return us_per_block(run, (PHASE,))

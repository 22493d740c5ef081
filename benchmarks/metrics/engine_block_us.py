"""Microseconds a block inside the engine on the consensus path: every
phase of the VM's engine's account but ``idle``, ``engine/build`` and
the VM's own (``vm/*``) — sender recovery, classify, the window of one
(prepare, dispatch, the read), validate, the commit a block — over the
chain's blocks.  What ``valuetx.catchup`` pays once a window of 16 is
paid here once a block."""

from benchlib.vmphases import by_phase, us_per_block


def read(run):
    seconds = by_phase(run)
    if seconds is None:
        return None
    return us_per_block(run, [p for p in seconds if p != "engine/build"
                              and not p.startswith("vm/")])

"""Microseconds of ``PluginBlock.accept`` a block: account phase
``vm/accept`` (``BlockChain.accept``: the engine's undo record retired,
the canonical index, the acceptor queue; the VM's accept hooks).  Self
time: a rollback inside an accept is ``vm/rollback`` and the engine's
phases."""

from benchlib.vmphases import us_per_block


def read(run):
    return us_per_block(run, ("vm/accept",))

"""Microseconds of ``VM.parse_block`` a block of the chain: account phase
``vm/parse`` (``Block.decode`` from wire bytes, the known-block lookup,
the ``PluginBlock``) over the blocks consensus was offered.  Self time
from the VM's engine's account; only a pass through ``plugin/vm.py``
has the phase."""

from benchlib.vmphases import us_per_block


def read(run):
    return us_per_block(run, ("vm/parse",))

"""Microseconds a block of ``PluginBlock.verify`` that are the VM's and
the chain's own: account phases ``vm/verify`` (the ladder before the
insert: syntactic check, predicates, UTXOs) and ``vm/insert``
(``BlockChain.insert_block`` round the engine's call: header and body
checks, the receipts' derived fields, the entry, the head event and the
tx pool's reset on it).  Self times: the engine's own phases inside the
insert are taken out (``engine_block_us`` has them)."""

from benchlib.vmphases import us_per_block


def read(run):
    return us_per_block(run, ("vm/verify", "vm/insert"))

"""Ways to break the timed path, for the control and the fault tests.

Each is planted under a whole run of the harness (``planted``), so the
rest of the run (chain, warm-up, window, trace, comparison, result
line) is the real one.  A benchmark run never loads this file;
``benchmarks/control.py`` and ``tests/benchmarks`` do.

Two CONTROLS, each breaking a guarantee the configurations state:

- ``skip_tx`` ("nothing is skipped"): the engine is given one block
  short of its last transaction, as an engine that drops a transaction
  would see it, and has to commit at the header's roots all the same.
  The engine's own header check halts the pass.
- ``silent_alter`` ("the committed state is the one the transactions
  add up to"): one transaction moves one wei more than the plan says,
  in the chain itself, so the program's host processor and the engine
  agree on every header and the passes commit without a complaint at
  the engine's own root.  Only the plain reference can fail it: the
  ledger, and the state root folded from it.

The others are the faults a cell can have.
"""

from __future__ import annotations

import contextlib


def _replay(engine, blocks):
    engine.replay_block(blocks[0])
    engine.replay(blocks[1:])


def skip_tx(engine, blocks):
    """One transaction of the middle block is never executed."""
    from coreth_tpu.types import Block
    mid = len(blocks) // 2
    b = blocks[mid]
    cut = Block(b.header, b.transactions[:-1], b.uncles, b.version,
                b.extdata)
    _replay(engine, blocks[:mid] + [cut] + blocks[mid + 1:])


def state_unchanged(engine, blocks):
    """A step that returns its state unchanged: nothing is replayed."""


def half_left_out(engine, blocks):
    """Half of the batch left out: only the first half is replayed."""
    _replay(engine, blocks[:max(1, len(blocks) // 2)])


def answer_altered(engine, blocks):
    """The answer altered where it is produced: one bit of the root."""
    _replay(engine, blocks)
    root = bytearray(engine.root)
    root[-1] ^= 1
    engine.root = bytes(root)


def _altered_chain_for(real_chain_for):
    """``chains.chain_for``, but the chain is built anew with the
    middle block's first transaction moving one wei more."""
    from benchlib import chains

    def chain_for(config, traffic, seed, child=None):
        genesis, _wire, how = real_chain_for(config, traffic, seed, child)
        _g, wire = chains.build_wire(
            config, traffic, seed, alter=(config["chain_blocks"] // 2, 0))
        return genesis, wire, dict(how, chain="altered")

    return chain_for


ENGINE_FAULTS = {f.__name__: f for f in (skip_tx, state_unchanged,
                                         half_left_out, answer_altered)}
FAULTS = sorted(ENGINE_FAULTS) + ["silent_alter"]


@contextlib.contextmanager
def planted(name):
    """The named fault under the timed path for the length of the
    block; ``None`` plants nothing."""
    from benchlib import chains, replay_pass
    clean_engine, clean_chain = replay_pass.run_engine, chains.chain_for
    try:
        if name == "silent_alter":
            chains.chain_for = _altered_chain_for(clean_chain)
        elif name is not None:
            replay_pass.run_engine = ENGINE_FAULTS[name]
        yield
    finally:
        replay_pass.run_engine = clean_engine
        chains.chain_for = clean_chain

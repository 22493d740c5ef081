"""``value_tx``'s chain as it is — upstream ``core/bench_test.go``
``genValueTx(0)``: the same four functions, imported, not copied — and
ONE block more that lies beside it, for a cell that needs a fork: the
shape of upstream's ``plugin/evm/vm_test.go`` ``TestNonCanonicalAccept``
/ ``TestAcceptReorg`` (two blocks on one parent, both verified, one
accepted, the other rejected).

The sibling is a second block on the last block's parent in which the same
key sends ``value + 1`` wei (2) to the zero address, the same gas at the
block's base fee: were it accepted, or were anything of it left in the
accepted state, the sender's and the zero address's balances and the
state root would say so.  It is signed with the chain's one key, which
only the builder knows (the seed picks it) and a driver's context does
not carry (``harness.py`` hands a driver the genesis and the wire bytes,
neither the seed nor the configuration: PERF.md, Open questions).  So
the builder hands it over the one way it can: the ``Genesis`` it returns
carries ``sibling(chain)``, which replays the decoded chain but its last
block on the program's host path (``BlockChain.insert_chain``: the host
processor, no VM, no engine) and writes the sibling on the state that
leaves, as every block of the chain was written (``generate_chain``).
This module goes when the context carries ``config`` and ``seed``.
"""

import functools

from benchlib.names import load_named

_value_tx, _ = load_named("chains", "value_tx")

gen = _value_tx.gen
ledger = _value_tx.ledger
read_back = _value_tx.read_back


def genesis(config, traffic, seed):
    spec, state = _value_tx.genesis(config, traffic, seed)
    spec.sibling = functools.partial(_sibling, config, spec, state)
    return spec, state


def _sibling(config, spec, state, chain):
    from coreth_tpu.chain import BlockChain, generate_chain
    from coreth_tpu.types import LegacyTx, sign_tx
    c = config["chain"]
    host = BlockChain(spec, snapshots=False)
    host.insert_chain(chain)
    parent = chain[-1] if chain else host.genesis_block

    def block(_i, bg):
        # one transaction a block from the one key: its nonce is the
        # parent's height
        bg.add_tx(sign_tx(LegacyTx(
            nonce=parent.number, gas_price=bg.base_fee, gas=c["tx_gas"],
            to=_value_tx.ZERO_ADDRESS, value=c["value"] + 1),
            state["key"], spec.config.chain_id))

    (blk,), _ = generate_chain(spec.config, parent, host.db, 1, block,
                               gap=c["block_gap_s"])
    return blk

"""Peer-to-peer payments as Block-STM's evaluation defines them
(arXiv 2203.06871, section "Experimental evaluation"): a block of
``txs_per_block`` payments (the paper: 10^3; here what a C-Chain block
holds, see the configuration's ``reduced``) over ``accounts`` funded
accounts, each payment between two DIFFERENT accounts drawn uniformly
at random.
Rendered for the EVM as plain value transfers (21,000 gas).  An account
can send more than once in a block (consecutive nonces) and spend what
it was paid earlier in the chain, never more than it holds: every
account is funded far beyond what the chain moves.

The PAIR sequence is fixed by the configuration's ``pair_seed``: every
``--seed`` draws the same indices, block for block, and so does the
same work.  The seed moves who the indices are (the keys) and the
amounts.
"""

import random

from benchlib import plainref
from benchlib.chains import first_key, read_accounts


def _plan(config, seed):
    """Block ``i``'s payments as (sender index, recipient index, wei);
    blocks have to be asked for in order, each once."""
    c = config["chain"]
    pairs = random.Random(c["pair_seed"])
    amounts = random.Random(seed)

    def block(_i):
        rows = []
        for _ in range(config["txs_per_block"]):
            src, dst = pairs.sample(range(c["accounts"]), 2)
            rows.append((src, dst, amounts.randrange(1, c["max_value"])))
        return rows

    return block


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    c = config["chain"]
    keys = [first_key(config, seed) + i for i in range(c["accounts"])]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {a: GenesisAccount(balance=c["funded"]) for a in addrs}
    return Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc), {"keys": keys, "addrs": addrs}


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    c, cid = config["chain"], genesis.config.chain_id
    keys, addrs = state["keys"], state["addrs"]
    nonces = [0] * len(keys)
    plan = _plan(config, seed)

    def block(i, bg):
        for j, (src, dst, value) in enumerate(plan(i)):
            if alter == (i, j):
                value += 1
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=cid, nonce=nonces[src],
                gas_tip_cap_=c["tip_cap"], gas_fee_cap_=c["fee_cap"],
                gas=c["tx_gas"], to=addrs[dst], value=value, data=b""),
                keys[src], cid))
            nonces[src] += 1

    return block


def ledger(config, traffic, seed):
    c = config["chain"]
    addrs = plainref.addresses(first_key(config, seed), c["accounts"])
    book = plainref.Book({a: c["funded"] for a in addrs})
    plan = _plan(config, seed)
    fees = plainref.base_fees(config["chain_blocks"], c["block_gap_s"])
    for i, base_fee in enumerate(fees):
        price = min(c["fee_cap"], base_fee + c["tip_cap"])
        for src, dst, value in plan(i):
            book.transfer(addrs[src], addrs[dst], value, c["tx_gas"], price)
    return book


read_back = read_accounts

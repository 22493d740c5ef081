"""Upstream ``core/bench_test.go`` ``genValueTx(0)``, as
``BenchmarkInsertChain_valueTx_memdb`` runs it: ONE funded key; every
block holds ONE legacy transaction from it that moves 1 wei to the zero
address with no data, 21,000 gas, at a gas price equal to the block's
base fee; blocks lie 10 s apart (``GenerateChain``'s gap).  The chain is
as long as the configuration says (upstream: the benchmark's ``b.N``).

The seed moves the one key.
"""

from benchlib import plainref
from benchlib.chains import first_key, read_accounts

ZERO_ADDRESS = b"\x00" * 20


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    c = config["chain"]
    key = first_key(config, seed)
    alloc = {priv_to_address(key): GenesisAccount(balance=c["root_funds"])}
    return Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc), {"key": key}


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.types import LegacyTx, sign_tx
    c, cid = config["chain"], genesis.config.chain_id

    def block(i, bg):
        value = c["value"] + (1 if alter == (i, 0) else 0)
        bg.add_tx(sign_tx(LegacyTx(
            nonce=i, gas_price=bg.base_fee, gas=c["tx_gas"],
            to=ZERO_ADDRESS, value=value), state["key"], cid))

    return block


def ledger(config, traffic, seed):
    c = config["chain"]
    sender = plainref.addresses(first_key(config, seed), 1)[0]
    book = plainref.Book({sender: c["root_funds"]})
    for fee in plainref.base_fees(config["chain_blocks"], c["block_gap_s"]):
        book.transfer(sender, ZERO_ADDRESS, c["value"], c["tx_gas"], fee)
    return book


read_back = read_accounts

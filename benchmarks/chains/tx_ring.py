"""Upstream ``core/bench_test.go`` ``genTxRing(n)``, as
``BenchmarkInsertChain_ring1000_memdb`` runs it: ONE funded key; every
block is FILLED with 21,000-gas legacy transfers at a gas price equal
to the block's base fee, by upstream's own loop over the PARENT block's
gas limit (``fill``); each moves the sender's WHOLE balance less the fee
from ring account ``from`` to ``(from + 1) % n``, then ``from = to``;
``from`` and the funds carry on across blocks.  So every transaction of
a block but the first is sent by an account whose only money arrived
one transaction earlier in the same block, and each leaves its sender
at exactly 0.  Cited from memory (no network here); blocks lie 10 s
apart (``GenerateChain``'s gap).

The seed moves the keys (upstream: ``benchRootKey`` and n - 1 random
ones).

The control's ``alter=(i, j)``: one wei MORE is insolvent in a ring, so
from that transaction on every value is one wei LESS — one wei stays
behind on one account.  The engine and the host processor agree on that
chain; the plain reference alone fails it.
"""

from benchlib import plainref
from benchlib.chains import first_key, read_accounts

# every header's gas limit from Cortina on, whatever the genesis says
CORTINA_GAS_LIMIT = 15_000_000


def fill(parent_gas_limit: int, tx_gas: int, at_most: int) -> int:
    """How many transfers upstream's loop puts into a block —
    ``gas := parent.GasLimit(); for { gas -= TxGas; if gas < TxGas
    {break}; ... }`` — cut at the configuration's ``txs_per_block``
    (at full size the two agree; a toy chain stops early)."""
    n, gas = 0, parent_gas_limit
    while n < at_most:
        gas -= tx_gas
        if gas < tx_gas:
            break
        n += 1
    return n


class _Walk:
    """``genTxRing``'s closure: where the money is and how much is
    left, carried from transaction to transaction and block to block."""

    def __init__(self, config):
        c = config["chain"]
        self.n, self.tx_gas = c["accounts"], c["tx_gas"]
        self.at_most = config["txs_per_block"]
        self.src, self.funds = 0, c["root_funds"]
        self.nonces = [0] * self.n

    def block(self, parent_gas_limit: int, base_fee: int):
        """The block's transfers as (from, to, nonce, value)."""
        rows = []
        for _ in range(fill(parent_gas_limit, self.tx_gas, self.at_most)):
            dst = (self.src + 1) % self.n
            self.funds -= self.tx_gas * base_fee
            rows.append((self.src, dst, self.nonces[self.src], self.funds))
            self.nonces[self.src] += 1
            self.src = dst
        return rows


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    c = config["chain"]
    keys = [first_key(config, seed) + i for i in range(c["accounts"])]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {addrs[0]: GenesisAccount(balance=c["root_funds"])}
    return Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc), {"keys": keys, "addrs": addrs}


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.types import LegacyTx, sign_tx
    cid = genesis.config.chain_id
    keys, addrs = state["keys"], state["addrs"]
    walk = _Walk(config)
    short = 0  # the altered chain: wei every value is short by

    def block(i, bg):
        nonlocal short
        rows = walk.block(bg.parent.header.gas_limit, bg.base_fee)
        for j, (src, dst, nonce, value) in enumerate(rows):
            if alter == (i, j):
                short = 1
            bg.add_tx(sign_tx(LegacyTx(
                nonce=nonce, gas_price=bg.base_fee, gas=walk.tx_gas,
                to=addrs[dst], value=value - short), keys[src], cid))

    return block


def ledger(config, traffic, seed):
    """``genTxRing``'s own arithmetic through ``Book.transfer``, which
    raises the moment a sender is short: a rendering off by one wei
    fails the plan itself."""
    c = config["chain"]
    addrs = plainref.addresses(first_key(config, seed), c["accounts"])
    book = plainref.Book({addrs[0]: c["root_funds"]})
    walk = _Walk(config)
    fees = plainref.base_fees(config["chain_blocks"], c["block_gap_s"])
    for i, base_fee in enumerate(fees):
        limit = c["gas_limit"] if i == 0 else CORTINA_GAS_LIMIT
        for src, dst, _nonce, value in walk.block(limit, base_fee):
            book.transfer(addrs[src], addrs[dst], value, walk.tx_gas,
                          base_fee)
    return book


read_back = read_accounts

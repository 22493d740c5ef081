"""Block-STM's peer-to-peer payment as the VM transaction it is
(arXiv 2203.06871, section "Experimental evaluation"): a block of
``txs_per_block`` payments over ``accounts`` accounts, each between two
DIFFERENT accounts drawn uniformly at random.  The paper's payment is
a Move transaction that runs module code over two balance entries in
storage; rendered for the EVM it is an ERC-20
``transfer(address,uint256)`` on one token contract: bytecode that
hashes two mapping keys, reads and writes two storage slots and logs.
``p2p_transfer.py`` is the same plan moved as native value.

The token (``TOKEN_RUNTIME``) is a minimal ERC-20 with OpenZeppelin's
``_transfer`` semantics, hand-assembled (no compiler in this tree):

    selector = calldata[0:4]; transfer -> T, balanceOf -> B, else revert
    T: to = calldata[4:36];   if to == 0: revert
       amt = calldata[36:68]; fromKey = keccak(pad32(caller) ++ pad32(0))
       bal = sload(fromKey);  if bal < amt: revert
       sstore(fromKey, bal - amt)
       toKey = keccak(pad32(to) ++ pad32(0))
       sstore(toKey, sload(toKey) + amt)
       log3(Transfer, caller, to; amt); return 1
    B: return sload(keccak(pad32(calldata[4:36]) ++ pad32(0)))

Balances are the mapping at slot 0 under Solidity's key rule.  Every
account holds ``token_funded`` units at genesis and a payment moves
under ``max_value``, so no slot reaches zero and no call reverts.

The PAIR sequence is fixed by the configuration's ``pair_seed``; the
``--seed`` moves who the indices are (the keys) and the amounts.
``ledger`` adds the chain up with ``benchlib.plainevm`` alone.
"""

from benchlib import plainevm, plainref
from benchlib.chains import first_key, read_accounts
from benchlib.names import load_named

TOKEN_RUNTIME = bytes.fromhex(
    "60003560e01c8063a9059cbb1461002257806370a0823114610090575b600060"
    "00fd5b600435801561001c576024353360005260006020526040600020805482"
    "811061001c5782900390558160005260406000208054820190558060005281"
    "337fddf252ad1be2c89b69c2b068fc378daa952ba7f163c4a11628f55a4df523"
    "b3ef60206000a3600160005260206000f35b6004356000526000602052604060"
    "00205460005260206000f3")


# the same generator as blockstm-p2p-1k's: (sender index, recipient
# index, amount) a payment, the pairs from ``pair_seed`` alone
_plan = load_named("chains", "p2p_transfer")[0]._plan


def _token(config) -> bytes:
    return bytes.fromhex(config["chain"]["token"])


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    c = config["chain"]
    keys = [first_key(config, seed) + i for i in range(c["accounts"])]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {a: GenesisAccount(balance=c["funded"]) for a in addrs}
    held = c["token_funded"].to_bytes(32, "big")
    alloc[_token(config)] = GenesisAccount(
        code=TOKEN_RUNTIME, nonce=1,
        storage={plainevm.mapping_slot(a): held for a in addrs})
    return Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc), {"keys": keys, "addrs": addrs}


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    c, cid = config["chain"], genesis.config.chain_id
    keys, addrs, token = state["keys"], state["addrs"], _token(config)
    nonces = [0] * len(keys)
    plan = _plan(config, seed)

    def block(i, bg):
        for j, (src, dst, amount) in enumerate(plan(i)):
            if alter == (i, j):
                amount += 1
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=cid, nonce=nonces[src],
                gas_tip_cap_=c["tip_cap"], gas_fee_cap_=c["fee_cap"],
                gas=c["tx_gas"], to=token, value=0,
                data=plainevm.transfer_data(addrs[dst], amount)),
                keys[src], cid))
            nonces[src] += 1

    return block


def ledger(config, traffic, seed):
    c = config["chain"]
    addrs = plainref.addresses(first_key(config, seed), c["accounts"])
    book = plainevm.TokenBook(
        {a: c["funded"] for a in addrs}, _token(config), TOKEN_RUNTIME,
        c["token_funded"])
    plan = _plan(config, seed)
    fees = plainref.base_fees(config["chain_blocks"], c["block_gap_s"])
    for i, base_fee in enumerate(fees):
        price = min(c["fee_cap"], base_fee + c["tip_cap"])
        for src, dst, amount in plan(i):
            out = book.call(addrs[src],
                            plainevm.transfer_data(addrs[dst], amount),
                            c["tx_gas"], price)
            if out.status != plainevm.STOP_OK:
                raise ValueError(f"the plan has a call that {out.status}")
    return book


def read_back(engine, book) -> dict:
    """Every account's nonce and native balance (the token's own among
    them), every holder's token slot through the program's read path,
    and the book's state root."""
    from coreth_tpu.state import StateDB
    out = read_accounts(engine, book)
    sdb = StateDB(engine.root, engine.db)
    for holder in book.holders:
        key = plainevm.mapping_slot(holder)
        got = int.from_bytes(sdb.get_state(book.token, key), "big")
        want = book.slots.get(key, 0)
        if got != want:
            out["wrong"].append({"holder": holder.hex(), "got": got,
                                 "want": want})
    out["compared"] += len(book.holders)
    return out

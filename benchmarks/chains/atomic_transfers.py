"""``value_tx``'s chain (upstream ``genValueTx(0)``: one 1-wei legacy
transfer a block from one key) with one atomic tx in every block's
ExtData, as a C-Chain carries AVAX between the X-Chain and itself
(plugin/evm import_tx.go / export_tx.go):

- even heights: an ImportTx spends the next of the X-Chain's AVAX UTXOs
  (one signature, by the X-Chain key that owns them) into one EVMOutput
  to the next of ``recipients`` addresses, round-robin;
- odd heights: an ExportTx debits ``export_nanoavax`` from a second
  funded key under its nonce (its exports so far) into one UTXO owned by
  the X-Chain key;
- each burns exactly the dynamic fee at the block's base fee.

The host processor writes the chain (``benchlib.chains.generate``): the
builder hands the consensus engine that ``generate_chain`` finalizes
with (``bg.engine``) the atomic callbacks at block 0, over a shared
memory seeded with the chain's import UTXOs, so every header carries
its ExtData hash, ``ExtDataGasUsed`` and the block fee check.

The driver's context holds the genesis and the wire bytes, not the seed
or the configuration (PERF.md, Open questions), so the ``Genesis`` this
returns carries what the driver needs: ``seed_shared_memory()`` (a
fresh hub holding the import UTXOs, one a pass), ``sibling(chain)``
(``value_tx_fork``'s writer: the second block at the last height, here
spending the last block's atomic input) and ``inbox_reference()``
(``plainatomic.inbox``: the UTXOs a pass must leave in shared memory).
"""

import functools

from benchlib import plainatomic
from benchlib.chains import read_accounts

ZERO_ADDRESS = b"\x00" * 20


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    from coreth_tpu.workloads import atomic as W
    c = config["chain"]
    for key, want in (("c_chain_id", W.CTX.chain_id),
                      ("x_chain_id", W.CTX.x_chain_id),
                      ("avax_asset_id", W.CTX.avax_asset_id)):
        if bytes.fromhex(c[key]) != want:
            raise ValueError(f"configuration's {key} is not the VM's")
    keys = plainatomic.keys(config, seed)
    state = dict(keys, exporter_addr=priv_to_address(keys["exporter"]),
                 recipients=plainatomic.recipients(config, seed),
                 utxos=[W.avax_utxo(t, c["import_nanoavax"],
                                    keys["importer"])
                        for t in plainatomic.utxo_tx_ids(config, seed)])
    state["importer_short"] = W.short_address(keys["importer"])
    alloc = {priv_to_address(keys["sender"]):
             GenesisAccount(balance=c["root_funds"]),
             state["exporter_addr"]: GenesisAccount(balance=c["root_funds"])}
    spec = Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc)
    spec.seed_shared_memory = functools.partial(_memory, state)
    spec.sibling = functools.partial(_sibling, config, spec, state)
    spec.inbox_reference = functools.partial(plainatomic.inbox, config,
                                             seed)
    return spec, state


def _memory(state):
    from coreth_tpu.atomic import Memory
    from coreth_tpu.workloads import atomic as W
    memory = Memory()
    W.seed(memory, state["utxos"])
    return memory


def _atomic_tx(config, state, height, base_fee, other=False):
    """Block ``height``'s atomic tx; ``other``: the sibling's, which
    spends the same input for another recipient or amount."""
    from coreth_tpu.workloads import atomic as W
    c = config["chain"]
    if height % 2 == 0:
        j = height // 2 - 1
        to = state["recipients"][(j + other) % c["recipients"]]
        return W.import_tx(state["utxos"][j], state["importer"], to,
                           base_fee)
    return W.export_tx(state["exporter"], state["exporter_addr"],
                       (height - 1) // 2, c["export_nanoavax"] + other,
                       state["importer_short"], base_fee)


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.atomic import AtomicBackend, make_callbacks
    from coreth_tpu.types import LegacyTx, sign_tx
    from coreth_tpu.workloads import atomic as W
    c, cid = config["chain"], genesis.config.chain_id
    pending = []

    def block(i, bg):
        if i == 0:
            backend = AtomicBackend(W.CTX, _memory(state).new_shared_memory(
                W.CTX.chain_id))
            bg.engine.cb = make_callbacks(
                backend, genesis.config, pending_atomic_txs=lambda: pending)
        value = c["value"] + (1 if alter == (i, 0) else 0)
        bg.add_tx(sign_tx(LegacyTx(
            nonce=i, gas_price=bg.base_fee, gas=c["tx_gas"],
            to=ZERO_ADDRESS, value=value), state["sender"], cid))
        pending[:] = [_atomic_tx(config, state, i + 1, bg.base_fee)]

    return block


def _sibling(config, spec, state, chain):
    """A second block on the chain's last parent: 2 wei moved, and an
    atomic tx that spends the last block's input."""
    from coreth_tpu.chain import BlockChain, generate_chain
    from coreth_tpu.types import LegacyTx, sign_tx
    from coreth_tpu.workloads import atomic as W
    c = config["chain"]
    pending = []
    engine = W.engine(_memory(state), spec.config, pending)
    host = BlockChain(spec, snapshots=False, engine=engine)
    host.insert_chain(chain)
    parent = chain[-1] if chain else host.genesis_block

    def block(_i, bg):
        bg.add_tx(sign_tx(LegacyTx(
            nonce=parent.number, gas_price=bg.base_fee, gas=c["tx_gas"],
            to=ZERO_ADDRESS, value=c["value"] + 1),
            state["sender"], spec.config.chain_id))
        pending[:] = [_atomic_tx(config, state, parent.number + 1,
                                 bg.base_fee, other=True)]

    (blk,), _ = generate_chain(spec.config, parent, host.db, 1, block,
                               gap=c["block_gap_s"], engine=engine)
    return blk


def ledger(config, traffic, seed):
    return plainatomic.ledger(config, seed)


read_back = read_accounts

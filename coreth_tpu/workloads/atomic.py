"""C-Chain atomic transfers as upstream's tests write them: AVAX imported
from the X-Chain (an ImportTx spending a UTXO of shared memory) and
exported back (an ExportTx debiting an EVM account under its nonce),
one signature each, burning exactly the dynamic fee at the block's base
fee (plugin/evm import_tx.go / export_tx.go, tx.go:252
CalculateDynamicFee).

A chain carries them in its blocks' ExtData when ``generate_chain`` is
handed ``engine(...)``: the consensus callbacks pack and apply whatever
``pending`` holds while each block is built.
"""

from __future__ import annotations

from typing import List

from coreth_tpu.atomic import (
    AtomicBackend, ChainContext, EVMInput, EVMOutput, Memory,
    TransferableInput, TransferableOutput, Tx, UnsignedExportTx,
    UnsignedImportTx, UTXO, calculate_dynamic_fee, make_callbacks,
    short_id,
)
from coreth_tpu.atomic.shared_memory import Element, Requests
from coreth_tpu.consensus.engine import DummyEngine
from coreth_tpu.crypto.secp256k1 import pubkey

CTX = ChainContext()
_BLANK_SIG = b"\x00" * 65  # a signature's size, for the fee's byte count


def short_address(priv: int) -> bytes:
    """The X-Chain address (short id) of a key."""
    return short_id(pubkey(priv))


def avax_utxo(tx_id: bytes, amount: int, owner_priv: int,
              output_index: int = 0) -> UTXO:
    """An AVAX UTXO of the X-Chain owned by one key."""
    return UTXO(tx_id, output_index, TransferableOutput(
        asset_id=CTX.avax_asset_id, amount=amount,
        addrs=[short_address(owner_priv)]))


def seed(memory: Memory, utxos: List[UTXO]) -> None:
    """Put UTXOs in the C-Chain's inbound view from the X-Chain."""
    memory.new_shared_memory(CTX.x_chain_id).apply({CTX.chain_id: Requests(
        put_requests=[Element(u.input_id(), u.encode(), u.out.addrs)
                      for u in utxos])})


def _signed(unsigned, set_amount, burn_from: int, base_fee: int,
            priv: int) -> Tx:
    """Sign ``unsigned`` after ``set_amount(burn_from - fee)``: the fee is
    the dynamic fee of the tx's gas (its bytes, one signature, the AP5
    fixed cost), whose byte count does not depend on the amounts."""
    tx = Tx(unsigned, creds=[[_BLANK_SIG]])
    gas = unsigned.gas_used(True, len(tx.encode()))
    set_amount(burn_from - calculate_dynamic_fee(gas, base_fee))
    tx.sign([[priv]])
    return tx


def import_tx(utxo: UTXO, owner_priv: int, to: bytes,
              base_fee: int) -> Tx:
    """Spend ``utxo`` into one EVMOutput to ``to``, burning the fee."""
    out = EVMOutput(address=to, asset_id=CTX.avax_asset_id)
    unsigned = UnsignedImportTx(
        network_id=CTX.network_id, blockchain_id=CTX.chain_id,
        source_chain=CTX.x_chain_id,
        imported_inputs=[TransferableInput(
            tx_id=utxo.tx_id, output_index=utxo.output_index,
            asset_id=utxo.out.asset_id, amount=utxo.out.amount,
            sig_indices=[0])],
        outs=[out])
    return _signed(unsigned, lambda a: setattr(out, "amount", a),
                   utxo.out.amount, base_fee, owner_priv)


def export_tx(priv: int, address: bytes, nonce: int, amount: int,
              to_short: bytes, base_fee: int) -> Tx:
    """Debit ``amount`` nAVAX from ``address`` (the key's) under
    ``nonce``; one output of what is left after the fee, owned by
    ``to_short`` on the X-Chain."""
    out = TransferableOutput(asset_id=CTX.avax_asset_id, addrs=[to_short])
    unsigned = UnsignedExportTx(
        network_id=CTX.network_id, blockchain_id=CTX.chain_id,
        destination_chain=CTX.x_chain_id,
        ins=[EVMInput(address=address, amount=amount,
                      asset_id=CTX.avax_asset_id, nonce=nonce)],
        exported_outputs=[out])
    return _signed(unsigned, lambda a: setattr(out, "amount", a), amount,
                   base_fee, priv)


def engine(memory: Memory, config, pending: list) -> DummyEngine:
    """A consensus engine whose callbacks pack ``pending`` into the
    block being built and verify it against ``memory``."""
    backend = AtomicBackend(CTX, memory.new_shared_memory(CTX.chain_id))
    return DummyEngine(cb=make_callbacks(
        backend, config, pending_atomic_txs=lambda: list(pending)))

"""snowman.Block adapter around types.Block.

Twin of reference plugin/evm/block.go: Verify = validate + insert into
the chain without committing (the chain keeps it as a processing
sibling); Accept / Reject are the consensus decisions
(block.go:177/:269/:325).  Block IDs are the 32-byte block hashes.
"""

from __future__ import annotations

import enum
from typing import Optional

from coreth_tpu.types import Block


class Status(enum.Enum):
    UNKNOWN = "unknown"
    PROCESSING = "processing"
    ACCEPTED = "accepted"
    REJECTED = "rejected"


class PluginBlock:
    """One consensus-facing block (plugin/evm/block.go:149)."""

    def __init__(self, vm, block: Block):
        self.vm = vm
        self.block = block
        self.status = Status.UNKNOWN

    # ------------------------------------------------------------ identity
    @property
    def id(self) -> bytes:
        return self.block.hash()

    @property
    def parent_id(self) -> bytes:
        return self.block.header.parent_hash

    @property
    def height(self) -> int:
        return self.block.number

    @property
    def timestamp(self) -> int:
        return self.block.time

    def bytes(self) -> bytes:
        return self.block.encode()

    # ----------------------------------------------------------- consensus
    def verify(self) -> None:
        """The verification ladder (block.go:325 Verify -> :366
        verify): syntactic validation, block-level predicate
        verification against the header's results bytes, atomic-UTXO
        presence in shared memory, then execution + insertion as a
        processing block (InsertBlockManual with writes).
        Re-verifying a decided block is a legal snowman call and must
        not resurrect it to processing (block.go status check)."""
        if self.status in (Status.ACCEPTED, Status.REJECTED):
            return
        vm = self.vm
        block = self.block
        # phase vm/verify is the ladder before the insert; the chain's
        # insert (vm/insert) and the engine's phases nest inside it
        acct = vm.account()
        tok = acct.begin("vm/verify")
        try:
            rules = vm.chain.config.rules(block.number, block.time)
            atomic_txs = []
            if vm.atomic_backend is not None:
                from coreth_tpu.atomic import decode_ext_data
                atomic_txs = decode_ext_data(block.ext_data())
            if block.hash() != vm.chain.genesis_block.hash():
                vm.block_validator.syntactic_verify(
                    block, rules, atomic_txs, now=int(vm.clock()))
            self._verify_predicates(rules)
            self._verify_utxos_present(atomic_txs)
            vm.chain.insert_block(block)
        finally:
            acct.end(tok)
        self.status = Status.PROCESSING
        vm._register(self)

    def _verify_predicates(self, rules) -> None:
        """verifyPredicates (block.go:413): recompute every tx's
        predicate bitsets and require the header's carried results to
        match bit-for-bit."""
        from coreth_tpu.plugin.block_verification import (
            BlockVerificationError,
        )
        from coreth_tpu.predicate import (
            PredicateResults, check_tx_predicates,
            results_bytes_from_extra,
        )
        if not rules.is_durango:
            if rules.predicaters:
                raise BlockVerificationError(
                    "cannot enable predicates before Durango")
            return
        results = PredicateResults()
        for i, tx in enumerate(self.block.transactions):
            for addr, bits in check_tx_predicates(rules, tx).items():
                results.set_result(i, addr, bits)
        raw = results_bytes_from_extra(self.block.header.extra)
        if raw is None:
            raise BlockVerificationError(
                "missing predicate results in header extra")
        if raw != results.encode():
            raise BlockVerificationError(
                f"invalid header predicate results (remote {raw.hex()} "
                f"local {results.encode().hex()})")

    def _verify_utxos_present(self, atomic_txs) -> None:
        """verifyUTXOsPresent (block.go:449): every UTXO an import tx
        consumes must exist in shared memory when this node is past
        bootstrap."""
        vm = self.vm
        if not atomic_txs or vm.atomic_backend is None \
                or not vm.bootstrapped:
            return
        from coreth_tpu.atomic.backend import tx_requests
        from coreth_tpu.plugin.block_verification import (
            BlockVerificationError,
        )
        for atx in atomic_txs:
            for chain_id, reqs in tx_requests(atx).items():
                try:
                    vm.atomic_backend.shared_memory.get(
                        chain_id, reqs.remove_requests)
                except KeyError as exc:
                    raise BlockVerificationError(
                        f"missing UTXO for atomic tx: {exc}") from exc

    def accept(self) -> None:
        """Consensus accepted this block (block.go:177)."""
        acct = self.vm.account()
        tok = acct.begin("vm/accept")
        try:
            self.vm.chain.accept(self.id)
            self.status = Status.ACCEPTED
            self.vm._on_accept(self)
        finally:
            acct.end(tok)

    def reject(self) -> None:
        """Consensus rejected this block (block.go:269)."""
        acct = self.vm.account()
        tok = acct.begin("vm/reject")
        try:
            self.vm.chain.reject(self.id)
            self.status = Status.REJECTED
            self.vm._on_reject(self)
        finally:
            acct.end(tok)

    def __repr__(self) -> str:  # debugging aid
        return (f"PluginBlock(height={self.height}, "
                f"id={self.id.hex()[:12]}, status={self.status.value})")

"""The dummy consensus engine.

Twin of reference consensus/dummy/consensus.go: header gas-field
verification (:105), block-fee verification (:289), Finalize (:358) and
FinalizeAndAssemble (:414) with the atomic-tx callbacks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional

from coreth_tpu.consensus.dynamic_fees import (
    calc_base_fee, calc_block_gas_cost,
)
from coreth_tpu.mpt import StackTrie
from coreth_tpu.params import ChainConfig
from coreth_tpu.params import protocol as P
from coreth_tpu.types import Block, Header, derive_sha, create_bloom
from coreth_tpu.types.block import calc_ext_data_hash

UINT64_MAX = (1 << 64) - 1


class ConsensusError(Exception):
    pass


@dataclass
class Mode:
    """Test fakers (consensus.go:34 Mode)."""
    skip_header_verify: bool = False
    skip_block_fee: bool = False
    skip_coinbase: bool = False


@dataclass
class ConsensusCallbacks:
    """consensus.go:40: atomic-tx hooks wired in by the plugin VM."""
    # (block, statedb) -> (fee contribution, ext_data_gas_used)
    on_extra_state_change: Optional[Callable] = None
    # (header, statedb, txs) -> (extra_data, contribution, ext_gas_used)
    on_finalize_and_assemble: Optional[Callable] = None
    # (block) -> effect | None: on_extra_state_change for a caller that
    # applies the state transfer itself (the device engine) — the
    # bookkeeping done, the transfer handed back (``effect.rows()``, with
    # ``effect.contribution`` / ``effect.gas_used``); None where it
    # cannot be rows
    on_extra_state_rows: Optional[Callable] = None


class DummyEngine:
    def __init__(self, cb: Optional[ConsensusCallbacks] = None,
                 mode: Optional[Mode] = None, clock=None):
        self.cb = cb or ConsensusCallbacks()
        self.mode = mode or Mode()

    # -------------------------------------------------------------- headers
    def verify_header(self, config: ChainConfig, header: Header,
                      parent: Header) -> None:
        if self.mode.skip_header_verify:
            return
        self._verify_header_gas_fields(config, header, parent)
        # timestamp monotonicity + difficulty/number/extra checks
        # (consensus.go verifyHeader)
        if header.time < parent.time:
            raise ConsensusError("timestamp older than parent")
        if header.number != parent.number + 1:
            raise ConsensusError("invalid block number")
        if header.difficulty != 1:
            raise ConsensusError("invalid difficulty")
        if config.is_apricot_phase3(header.time):
            expected_extra = P.DYNAMIC_FEE_EXTRA_DATA_SIZE
            if config.is_durango(header.time):
                if len(header.extra) < expected_extra:
                    raise ConsensusError("invalid extra length for Durango")
            elif len(header.extra) != expected_extra:
                raise ConsensusError(
                    f"invalid extra length {len(header.extra)}")
        elif len(header.extra) > P.MAXIMUM_EXTRA_DATA_SIZE:
            raise ConsensusError("extra data too long")

    def _verify_header_gas_fields(self, config: ChainConfig, header: Header,
                                  parent: Header) -> None:
        """verifyHeaderGasFields (consensus.go:105)."""
        if header.gas_limit > P.MAX_GAS_LIMIT:
            raise ConsensusError("gas limit above maximum")
        if header.gas_used > header.gas_limit:
            raise ConsensusError(
                f"gasUsed {header.gas_used} > gasLimit {header.gas_limit}")
        if config.is_cortina(header.time):
            if header.gas_limit != P.CORTINA_GAS_LIMIT:
                raise ConsensusError("gas limit must be Cortina constant")
        elif config.is_apricot_phase1(header.time):
            if header.gas_limit != P.APRICOT_PHASE1_GAS_LIMIT:
                raise ConsensusError("gas limit must be AP1 constant")
        else:
            diff = abs(parent.gas_limit - header.gas_limit)
            limit = parent.gas_limit // P.GAS_LIMIT_BOUND_DIVISOR
            if diff >= limit or header.gas_limit < P.MIN_GAS_LIMIT:
                raise ConsensusError("invalid gas limit delta")
        if not config.is_apricot_phase3(header.time):
            if header.base_fee is not None:
                raise ConsensusError("baseFee before AP3")
        else:
            window, expected_base_fee = calc_base_fee(config, parent,
                                                      header.time)
            if (len(header.extra) < len(window)
                    or header.extra[:len(window)] != window):
                raise ConsensusError("invalid fee window bytes")
            if header.base_fee is None:
                raise ConsensusError("baseFee missing")
            if header.base_fee != expected_base_fee:
                raise ConsensusError(
                    f"base fee {header.base_fee} != {expected_base_fee}")
        if not config.is_apricot_phase4(header.time):
            if header.block_gas_cost is not None:
                raise ConsensusError("blockGasCost before AP4")
            if header.ext_data_gas_used is not None:
                raise ConsensusError("extDataGasUsed before AP4")
            return
        expected_cost = self._block_gas_cost(config, parent, header.time)
        if header.block_gas_cost is None:
            raise ConsensusError("blockGasCost missing")
        if header.block_gas_cost > UINT64_MAX:
            raise ConsensusError("blockGasCost too large")
        if header.block_gas_cost != expected_cost:
            raise ConsensusError(
                f"blockGasCost {header.block_gas_cost} != {expected_cost}")
        if header.ext_data_gas_used is None:
            raise ConsensusError("extDataGasUsed missing")
        if header.ext_data_gas_used > UINT64_MAX:
            raise ConsensusError("extDataGasUsed too large")

    @staticmethod
    def _block_gas_cost(config: ChainConfig, parent: Header,
                        timestamp: int) -> int:
        step = (P.AP5_BLOCK_GAS_COST_STEP
                if config.is_apricot_phase5(timestamp)
                else P.AP4_BLOCK_GAS_COST_STEP)
        return calc_block_gas_cost(
            P.AP4_TARGET_BLOCK_RATE, P.AP4_MIN_BLOCK_GAS_COST,
            P.AP4_MAX_BLOCK_GAS_COST, step, parent.block_gas_cost,
            parent.time, timestamp)

    # ------------------------------------------------------------ block fee
    def verify_block_fee(self, base_fee: Optional[int],
                         required_block_gas_cost: Optional[int],
                         txs, receipts,
                         extra_contribution: Optional[int]) -> None:
        """verifyBlockFee (consensus.go:289): the tips from each
        transaction and its receipt."""
        def tips(base_fee: int) -> int:
            total = 0
            for tx, receipt in zip(txs, receipts):
                premium = tx.effective_gas_tip(base_fee)
                if premium < 0:
                    raise ConsensusError("negative effective tip")
                total += premium * receipt.gas_used
            return total
        self._verify_tips(base_fee, required_block_gas_cost, tips,
                          extra_contribution)

    def verify_block_fee_columns(self, base_fee: Optional[int],
                                 required_block_gas_cost: Optional[int],
                                 paid: int, gas_used: int,
                                 extra_contribution: Optional[int]) -> None:
        """verify_block_fee from a block's totals: ``paid`` is the sum
        of used_i x min(base_fee + tip_i, fee_cap_i) and ``gas_used``
        the sum of used_i over its transactions, none of whose fee caps
        is below the base fee (the replay classifier's fee column holds
        these terms and refuses a lower cap).  Each term less base_fee x
        used_i is that transaction's premium x used_i, so the tips are
        paid - base_fee x gas_used."""
        def tips(base_fee: int) -> int:
            total = paid - base_fee * gas_used
            if total < 0:
                raise ConsensusError("negative effective tip")
            return total
        self._verify_tips(base_fee, required_block_gas_cost, tips,
                          extra_contribution)

    def _verify_tips(self, base_fee: Optional[int],
                     required_block_gas_cost: Optional[int],
                     tips: Callable[[int], int],
                     extra_contribution: Optional[int]) -> None:
        """The checks of verifyBlockFee round the tip total, in its
        order: ``tips(base_fee)`` gives the sum of premium x gas used,
        asked for once the base fee is known to be valid."""
        if self.mode.skip_block_fee:
            return
        if base_fee is None or base_fee <= 0:
            raise ConsensusError(f"invalid base fee {base_fee}")
        if (required_block_gas_cost is None
                or required_block_gas_cost > UINT64_MAX):
            raise ConsensusError("invalid block gas cost")
        total_block_fee = 0
        if extra_contribution is not None:
            if extra_contribution < 0:
                raise ConsensusError("negative extra contribution")
            total_block_fee += extra_contribution
        total_block_fee += tips(base_fee)
        block_gas = total_block_fee // base_fee
        if block_gas < required_block_gas_cost:
            raise ConsensusError(
                f"insufficient gas ({block_gas}) to cover block cost "
                f"({required_block_gas_cost}) at base fee ({base_fee})")

    # -------------------------------------------------------------- finalize
    def finalize(self, block: Block, parent: Header, statedb,
                 receipts, config: Optional[ChainConfig] = None) -> None:
        """Finalize (consensus.go:358)."""
        config = config or self._config
        contribution = ext_data_gas_used = None
        if self.cb.on_extra_state_change is not None:
            contribution, ext_data_gas_used = self.cb.on_extra_state_change(
                block, statedb)
        if config.is_apricot_phase4(block.time):
            if ext_data_gas_used is None:
                ext_data_gas_used = 0
            if (block.header.ext_data_gas_used is None
                    or block.header.ext_data_gas_used != ext_data_gas_used):
                raise ConsensusError(
                    f"invalid extDataGasUsed: have "
                    f"{block.header.ext_data_gas_used}, "
                    f"want {ext_data_gas_used}")
            expected_cost = self._block_gas_cost(config, parent, block.time)
            if (block.header.block_gas_cost is None
                    or block.header.block_gas_cost != expected_cost):
                raise ConsensusError("invalid blockGasCost")
            self.verify_block_fee(block.base_fee,
                                  block.header.block_gas_cost,
                                  block.transactions, receipts, contribution)

    _config: Optional[ChainConfig] = None

    def set_config(self, config: ChainConfig) -> None:
        """Bind the chain config used by finalize (the reference reaches it
        through the chain reader argument)."""
        self._config = config

    def finalize_and_assemble(self, config: ChainConfig, header: Header,
                              parent: Header, statedb, txs, uncles,
                              receipts) -> Block:
        """FinalizeAndAssemble (consensus.go:414)."""
        extra_data = b""
        contribution = ext_data_gas_used = None
        if self.cb.on_finalize_and_assemble is not None:
            extra_data, contribution, ext_data_gas_used = \
                self.cb.on_finalize_and_assemble(header, statedb, txs)
        if config.is_apricot_phase4(header.time):
            header.ext_data_gas_used = ext_data_gas_used or 0
            header.block_gas_cost = self._block_gas_cost(config, parent,
                                                         header.time)
            self.verify_block_fee(header.base_fee, header.block_gas_cost,
                                  txs, receipts, contribution)
        header.root = statedb.intermediate_root(
            config.is_eip158(header.number))
        header.tx_hash = derive_sha(txs, StackTrie())
        header.receipt_hash = derive_sha(receipts, StackTrie())
        header.bloom = create_bloom(receipts)
        if config.is_apricot_phase1(header.time):
            header.ext_data_hash = calc_ext_data_hash(extra_data)
        return Block(header, list(txs), list(uncles), version=0,
                     extdata=extra_data if extra_data else None)

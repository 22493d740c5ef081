"""Batched block-replay engine.

Re-design of the reference's sequential hot path (state_processor.go:95
tx loop) for TPU:

1. **Classify** (host): a block is device-replayable when every tx is
   either a pure value transfer (`to` set, empty calldata, 21k gas,
   callee has no code and no multicoin flag) or an ERC-20 ``transfer()``
   call on a known-bytecode token (workloads/erc20) — exact per-tx gas
   derived from a host-side scalar simulation of the mapping-slot
   sequence.  A block's atomic txs (ExtData) join it as credit / debit
   lanes after its transactions.  Anything else routes through the
   bit-exact host Processor (execute-validate fallback, cf. SURVEY.md
   section 2.8).
2. **Execute** (device): one jitted step per block — per-sender debits,
   per-recipient credits, and per-storage-slot token debits/credits as
   segment reductions over 16x16-bit limb arrays (ops/u256), with
   nonce-sequence and solvency validation included.  The solvency
   checks ignore same-block credits, so success implies the sequential
   result (credits only help); any doubt falls back.
3. **Hash** (host-native): account + touched storage tries fold and
   rehash in C++ (mpt/native_trie over native/baseline.cc) when the
   native runtime is built — bit-identical roots checked against the
   header; pure-python tries (with the measured mpt/rehash device
   policy) remain the fallback and interop format.

State is shared with the host path through the same state Database, so
both engines can interleave over one chain.
"""

from __future__ import annotations

import functools
import itertools
import math
import os
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from coreth_tpu import faults, obs
from coreth_tpu.atomic.tx import AtomicTxError
from coreth_tpu.obs import recorder as forensics
from coreth_tpu.consensus.engine import DummyEngine
from coreth_tpu.ops import u256
from coreth_tpu.params import ChainConfig
from coreth_tpu.params import protocol as P
from coreth_tpu.processor.state_processor import Processor
from coreth_tpu.state import Database, StateDB
from coreth_tpu.state.flat import DELETED as FLAT_DELETED
from coreth_tpu.workloads.erc20 import (
    TOKEN_CODE_HASH, TRANSFER_TOPIC, balance_slot,
    measure_transfer_exec_gas, parse_transfer_calldata,
)
from coreth_tpu.mpt.native_trie import derive_hasher
from coreth_tpu.types import (
    Block, LatestSigner, Log, Receipt, StateAccount, Transaction,
    create_bloom, derive_sha,
)
from coreth_tpu.types.account import EMPTY_CODE_HASH, EMPTY_ROOT_HASH


class ReplayError(Exception):
    pass


class _Held(NamedTuple):
    """The undo record of one processing block (replay_block(hold=
    True)): where the engine stood before it, and how many flat
    generations it sealed."""
    block_hash: bytes
    prev_root: bytes
    prev_header: object
    generations: int


class StateRootMismatch(ReplayError):
    """A commit window folded to another state root than its last
    header's (CommitPipeline.flush).  The engine's tries, device rows
    and mirrors hold the fold; ``accounts`` and ``writes`` are the keys
    it staged, so whoever can recover (a processing block) repairs
    exactly those."""

    def __init__(self, msg: str, accounts, writes):
        super().__init__(msg)
        self.accounts = accounts
        self.writes = writes


def _block_error(msg: str, block) -> ReplayError:
    """ReplayError carrying the failing block, so a streaming caller
    can quarantine exactly that block instead of losing the run."""
    err = ReplayError(msg)
    err.block = block
    return err


def _receipt_rows(receipts) -> list:
    """Per-tx receipt observations for a forensics witness: enough for
    tools/replay_bundle.py to bisect a recorded-vs-replayed divergence
    to one tx (status, gas, log shape) without storing full logs."""
    from coreth_tpu.crypto import keccak256
    rows = []
    for r in receipts:
        lh = keccak256(b"".join(
            bytes(lg.address) + b"".join(bytes(t) for t in lg.topics)
            + bytes(lg.data) for lg in r.logs)).hex() if r.logs else None
        rows.append({"status": r.status, "gas_used": r.gas_used,
                     "cumulative": r.cumulative_gas_used,
                     "logs": len(r.logs), "logs_hash": lh})
    return rows


# Injection points on the replay engine's failure seams (armed only by
# a FaultPlan — coreth_tpu/faults; a no-op dict miss in production):
PT_DISPATCH = faults.declare(
    "device/dispatch", "raise at window dispatch (transfer + fused OCC)")
PT_RECOVER = faults.declare(
    "recover/fault", "batched sender recovery failure")


# Blocking on uploads at issue time syncs the whole stream, so eager
# flush stays off by default (not re-measured on a locally attached
# chip).
_EAGER_FLUSH = bool(int(
    __import__("os").environ.get("CORETH_EAGER_FLUSH", "0")))


def _encoded(tx) -> bytes:
    """The wire bytes of a transaction built in process.  One that does
    not encode contributes none: the native walk answers ``ok = 0`` for
    it and signer.sender decides it, its segment untouched."""
    try:
        return tx.encode()
    except Exception:  # noqa: BLE001 — per-tx python path later
        return b""


@dataclass
class ReplayStats:
    blocks_device: int = 0
    blocks_fallback: int = 0
    txs: int = 0
    t_classify: float = 0.0
    t_sender: float = 0.0
    t_device: float = 0.0
    t_trie: float = 0.0
    t_fallback: float = 0.0
    # windows whose fetch-tensor download was started asynchronously at
    # issue time (the windowed device-read prefetch; serve/prefetch.py)
    reads_prefetched: int = 0
    # lane fill of the transfer windows issued: transactions packed
    # (real) against K x pad lanes uploaded and scanned (padded)
    lanes_real: int = 0
    lanes_padded: int = 0
    # host->device transfers issued for transfer windows and the bytes
    # they carried: ONE staging buffer a window on a single device
    # (window_uploads == windows issued, re-applies included), five
    # arrays a window on a mesh (_issue_window_mesh)
    window_uploads: int = 0
    window_upload_bytes: int = 0
    # blocks the device committed that the pre-block solvency rule
    # would have refused: some sender spent what an earlier transaction
    # of the same block paid it (_transfer_step's ok & ~pre_ok)
    blocks_order_dependent: int = 0
    # the same for the fused machine windows dispatched (every
    # attempt): call lanes packed against blocks x lanes uploaded and
    # scanned (the window runner counts; machine_block._chunk_loop)
    machine_lanes_real: int = 0
    machine_lanes_padded: int = 0
    # blocks with atomic txs in their ExtData (plugin/evm import / export
    # txs): committed on the device path, their state transfer lanes
    # after the block's transactions, and the atomic txs they held; and
    # those the engine ran on the host path instead (an EVM input or
    # output of another asset than AVAX, callbacks with no atomic
    # semantics, or a device refusal)
    atomic_blocks_device: int = 0
    atomic_txs_device: int = 0
    atomic_blocks_host: int = 0
    # blocks applied tolerantly after failing validation on every
    # backend (supervisor quarantine — streaming callers only)
    blocks_quarantined: int = 0
    # blocks popped again via rollback_block (the reorg primitive over
    # the flat layer's generational diffs): quarantined ones, and
    # processing blocks consensus decided against
    blocks_rolled_back: int = 0
    # the engine behind Snowman's Verify / Accept / Reject
    # (replay/device_processor.py counts; 0 everywhere else): blocks
    # verified on the engine's tip / on the host path beside it (a
    # sibling, a side branch); blocks consensus accepted / rejected;
    # times a decision brought the engine back to a fork point, and
    # accepted blocks it then ran again there; accepted blocks whose
    # state the engine could NOT execute (it was rebuilt on the
    # host's: has to stay 0)
    blocks_verified_device: int = 0
    blocks_verified_host: int = 0
    blocks_accepted: int = 0
    blocks_rejected: int = 0
    engine_rollbacks: int = 0
    blocks_reapplied: int = 0
    accepted_off_engine: int = 0
    # batched sender recovery: signatures whose native batch COMPLETED
    # (sigs_host) and the replay thread's time packing, submitting,
    # waiting and applying (t_sender, t_sender_host; the batch itself
    # runs in the recovery worker).  sigs_device / t_sender_device are
    # VESTIGIAL: constant 0 since the device ladder left the serving
    # path, kept because benchmarks/benchlib/replay_pass.py and
    # metrics/sigs_device_share.py read them by attribute — the next
    # `benchmark` issue retires them (PERF.md §7 follow-up 5; D20)
    sigs_device: int = 0
    sigs_host: int = 0
    t_sender_device: float = 0.0
    t_sender_host: float = 0.0
    # lanes of completed batches the native walk did not vouch for
    # (ok = 0: malformed bytes, foreign chain id, high s, recovery id
    # past 1, r or s out of range): signer.sender's per-transaction
    # path decided them.  0 on every chain the benchmark replays
    sigs_left_to_signer: int = 0
    # lanes the batch's fast path could not finish and its sequential
    # fallback (coreth_ecrecover) recovered instead (ok = 2): correct,
    # at 3-30x the cost, and a fault of the arithmetic under the ladder
    # that would otherwise pass silently.  0 on every valid chain
    sigs_slow_path: int = 0
    # batched recoveries that raised (packing, the worker's batch, its
    # result): their txs fell to per-tx recovery in signer.sender —
    # correct, but not the path the counters above describe
    recover_degraded: int = 0
    # max/mean per-shard lane occupancy of the sharded OCC windows
    # (1.0 = flat; n_shards = the one-hot-contract collapse key-range
    # placement removes).  0.0 until a sharded machine window ran.
    load_imbalance: float = 0.0

    def row(self) -> dict:
        return dict(self.__dict__)


def _in_phase(name: str):
    """Run an engine method inside account phase ``name`` (the method
    may ``switch`` it: the decorator closes whatever is on top)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            acct = self.account
            acct.enter(name)
            try:
                return fn(self, *args, **kwargs)
            finally:
                acct.exit()
        return wrapper
    return deco


def _public_call(fn):
    """A public entry of the engine: claims the account for the calling
    thread and runs under its ``loop`` phase (nested public calls ride
    the outer one's)."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        tok = self.account.begin()
        try:
            return fn(self, *args, **kwargs)
        finally:
            self.account.end(tok)
    return wrapper


# Packed tx-batch column layout — ONE host->device transfer per block
# (each separate transfer pays a dispatch and a sync of its own):
#   0 sender_idx | 1 recip_idx | 2 tx_nonce | 3 nonce_offset | 4 kind
#   5 coinbase_idx (broadcast) | 6:22 value16 | 22:38 fee16
#   38:54 required16 | 54 from_slot | 55 to_slot | 56:72 amount16
# Native transfers carry amount16 = 0 / slots = 0 (the reserved dummy);
# token transfers carry value16 = 0.  Both kinds batch into one step.
TXD_COLS = 72
# Column 4 says what a lane does.  Bit LANE_DEBIT: it takes value + fee
# from its sender under the sender's next nonce; bit LANE_CREDIT: it
# pays value to its recipient.  A transaction does both; an atomic tx's
# state transfer rides the same step as lanes after the block's
# transactions (_atomic_lanes): an export input debits alone, an import
# output credits alone.  A padding lane does neither.
LANE_DEBIT = 1
LANE_CREDIT = 2
LANE_TX = LANE_DEBIT | LANE_CREDIT
# Device nonces are int32: an export whose nonce (or the nonce it sets)
# does not fit takes the host path
NONCE_CAP = (1 << 31) - 1
# Lane (tx) axis of a transfer window: the smallest LANE_FLOOR *
# LANE_STEP**n that holds the window's largest block — 16, 64, 256,
# 1024, ... — so a window of one-tx blocks packs, uploads and scans 16
# lanes a block, a full C-Chain block (714 transfers) 1,024, and the
# padding never exceeds LANE_STEP x the real lanes.  x4 steps keep the
# compiled _transfer_window variants at four for mainnet traffic.
LANE_FLOOR = 16
LANE_STEP = 4


def lane_bucket(n_txs: int) -> int:
    pad = LANE_FLOOR
    while pad < n_txs:
        pad *= LANE_STEP
    return pad


def pack_txd(txd: np.ndarray, batch: dict, B: int) -> None:
    """Fill ``txd`` — one block's ZEROED [pad, TXD_COLS] rows of the
    window's staging buffer — in place."""
    txd[:B, 0] = batch["senders"]
    txd[:B, 1] = batch["recips"]
    txd[:B, 2] = batch["nonces"]
    txd[:B, 3] = batch["offsets"]
    txd[:B, 4] = batch.get("kinds", LANE_TX)
    txd[:, 5] = batch["coinbase"]
    txd[:B, 6:22] = u256.pack_np(batch["values"])
    txd[:B, 22:38] = u256.pack_np(batch["fees"])
    txd[:B, 38:54] = u256.pack_np(batch["required"])
    txd[:B, 54] = batch["from_slots"]
    txd[:B, 55] = batch["to_slots"]
    txd[:B, 56:72] = u256.pack_np(batch["amounts"])


def txd_cols(txd):
    """Column views of a packed tx batch — the ONE decoder of the
    pack_txd layout (both execution backends consume it through this,
    so a layout change cannot silently diverge them).  Returns
    (senders, recips, values16, fees16, required16, tx_nonce,
    nonce_offset, debits, coinbase, from_slots, to_slots, amount16,
    credits): ``debits`` / ``credits`` the lanes with bit LANE_DEBIT /
    LANE_CREDIT of column 4."""
    kind = txd[:, 4]
    return (txd[:, 0], txd[:, 1], txd[:, 6:22], txd[:, 22:38],
            txd[:, 38:54], txd[:, 2], txd[:, 3],
            (kind & LANE_DEBIT) != 0, txd[0, 5], txd[:, 54], txd[:, 55],
            txd[:, 56:72], (kind & LANE_CREDIT) != 0)


def _gather_fetch(balances, nonces, slot_vals, ok, t_idx, s_idx,
                  pre_ok=None):
    """[t_pad+s_pad+1, 17] fetch tensor: touched (balance, nonce) rows,
    touched storage-slot value rows, and a last row of flags: column 0
    the ok flag, column 1 the pre-block solvency rule's verdict
    (_transfer_step's pre_ok; a step that has one rule only — the mesh
    window — leaves it out and the column repeats ok)."""
    g = jnp.concatenate([balances[t_idx],
                         nonces[t_idx][:, None]], axis=1)
    s = jnp.concatenate([slot_vals[s_idx],
                         jnp.zeros((s_idx.shape[0], 1), dtype=jnp.int32)],
                        axis=1)
    flags = jnp.stack([ok, ok if pre_ok is None else pre_ok])
    ok_row = jnp.zeros((1, u256.LIMBS + 1), dtype=jnp.int32)
    ok_row = ok_row.at[0, :2].set(flags.astype(jnp.int32))
    return jnp.concatenate([g, s, ok_row], axis=0)


def _step_core(balances, nonces, slot_vals, txd, num_accounts: int,
               num_slots: int):
    """One block of transfers (native + token) from a packed batch."""
    (senders, recips, values, fees, required, tx_nonce, offsets, debits,
     coinbase, from_slots, to_slots, amounts, credits) = txd_cols(txd)
    nb, nn, ok, pre_ok = _transfer_step(
        balances, nonces, senders, recips, values, fees, required,
        tx_nonce, offsets, debits, coinbase, num_accounts=num_accounts,
        credits=credits)
    sv, ok_slots = _slot_step(
        slot_vals, from_slots, to_slots, amounts, debits,
        num_slots=num_slots)
    return nb, nn, sv, ok & ok_slots, pre_ok & ok_slots


@partial(jax.jit, static_argnames=("num_slots",))
def _slot_step(slot_vals, from_slot, to_slot, amount16, mask,
               num_slots: int):
    """Batched ERC-20 mapping-slot read/modify/write: per-slot debit and
    credit totals as segment sums (the device analog of the token's
    SLOAD/SSTORE pair, reference core/vm/instructions.go opSload/opSstore
    + core/state/state_object.go updateTrie).  The solvency check
    ignores same-block credits, so ok=True implies the sequential
    result, exactly like the account-balance check above."""
    mask_i = mask.astype(jnp.int32)
    amt = amount16 * mask_i[:, None]
    debit_tot = u256.normalize(jax.ops.segment_sum(
        amt, from_slot, num_segments=num_slots))
    credit_tot = u256.normalize(jax.ops.segment_sum(
        amt, to_slot, num_segments=num_slots))
    solvent = u256.gte(slot_vals, debit_tot)
    ok = jnp.all(solvent)
    new_vals = u256.sub(u256.add(slot_vals, credit_tot), debit_tot)
    return new_vals, ok


@jax.jit
def _transfer_window(balances, nonces, slot_vals, acct_gids, slot_gids,
                     txds, t_idxs, s_idxs):
    """A WINDOW of blocks in one device call, over a WINDOW-LOCAL
    working set: gather the touched accounts/slots into small local
    arrays, lax.scan the per-block batches against them (segment sums
    over L locals instead of the whole table), then scatter the finals
    back — so per-step device work scales with the window's touched
    set, not with global state size.  This is the shape that amortizes
    the host<->device round trip AND keeps the kernel
    capacity-independent (the commit-interval batching analog,
    core/state_manager.go:74: one upload, one scan, one download).

    acct_gids/slot_gids: [L]/[SL] global row ids of the local slots;
    padding entries are out-of-bounds and gather zeros / scatter-drop.
    txds carry LOCAL indices.
    """
    lb = balances.at[acct_gids].get(mode="fill", fill_value=0)
    ln = nonces.at[acct_gids].get(mode="fill", fill_value=0)
    ls = slot_vals.at[slot_gids].get(mode="fill", fill_value=0)
    L = acct_gids.shape[0]
    SL = slot_gids.shape[0]

    def body(carry, inp):
        bal, non, sv = carry
        txd, t_idx, s_idx = inp
        # the scanned step's stable name in a device trace
        with jax.named_scope("coreth/transfer_step"):
            nb, nn, nsv, ok, pre_ok = _step_core(bal, non, sv, txd, L, SL)
            fetch = _gather_fetch(nb, nn, nsv, ok, t_idx, s_idx, pre_ok)
        return (nb, nn, nsv), fetch

    (lb, ln, ls), fetches = jax.lax.scan(
        body, (lb, ln, ls), (txds, t_idxs, s_idxs))
    nb = balances.at[acct_gids].set(lb, mode="drop")
    nn = nonces.at[acct_gids].set(ln, mode="drop")
    nsv = slot_vals.at[slot_gids].set(ls, mode="drop")
    return nb, nn, nsv, fetches


def _window_shapes(dims):
    K, pad, t_pad, s_pad, L, SL = dims
    return ((L,), (SL,), (K, pad, TXD_COLS), (K, t_pad), (K, s_pad))


def window_words(dims) -> int:
    """int32 words in the staging buffer of a window of ``dims``."""
    return sum(math.prod(shape) for shape in _window_shapes(dims))


def window_views(buf, dims):
    """The five int32 inputs of a transfer window as slices of its ONE
    flat staging buffer — ``acct_gids [L]``, ``slot_gids [SL]``,
    ``txds [K, pad, TXD_COLS]``, ``t_idxs [K, t_pad]``,
    ``s_idxs [K, s_pad]``, in that order, end to end.  The one
    definition of the layout: _prepare_window fills the numpy views,
    _transfer_window_packed cuts the device buffer at the same (static)
    offsets.  ``dims`` = (K, pad, t_pad, s_pad, L, SL)."""
    if window_words(dims) != buf.shape[0]:
        raise ValueError(f"window buffer of {buf.shape[0]} words, dims "
                         f"{dims} tile {window_words(dims)}")
    views, at = [], 0
    for shape in _window_shapes(dims):
        n = math.prod(shape)
        views.append(buf[at:at + n].reshape(shape))
        at += n
    return views


@partial(jax.jit, static_argnames=("dims",))
def _transfer_window_packed(balances, nonces, slot_vals, buf, dims):
    """The entry the engine calls: _transfer_window on a window that
    arrived as ONE buffer in ONE host->device transfer (a transfer
    costs the host 0.14-0.29 ms on the chip whatever it carries,
    PERF.md §6: five a window were 28% of a one-tx-a-block pass).  The
    compile key is the six dims, as it was the five shapes."""
    return _transfer_window(balances, nonces, slot_vals,
                            *window_views(buf, dims))


# Widest lane axis the in-order solvency check builds its [B, B] lane
# masks for; past it _transfer_step keeps the pre-block rule alone.
ORDER_CHECK_MAX_LANES = 4096
# jnp.pad widths that give a [n, 16] limb array a 17th limb: sums of
# u256 values carry out of 2^256, and a compare must see the carry
_CARRY_LIMB = ((0, 0), (0, 1))


def _order_solvent(balances, sender_idx, recip_idx, credit, debit,
                   required):
    """[B] bool: lane j's sender holds, AT LANE j's PLACE IN THE BLOCK,
    what buyGas asks of it (state_transition.go:286) —
    ``bal0[s_j] + sum(value_i : i < j, recip_i == s_j)
      >= sum(value_i + fee_i : i < j, sender_i == s_j) + required_j``.
    A transfer's value, fee and requirement are in the transaction, not
    in the state, so the sequential check is two prefix sums and no
    loop: two strictly-lower-triangular equality masks [B, B] times the
    [B, 16] limb arrays, as int32 products (a limb sum stays under
    B x 65,535 < 2^28 at B <= 4,096).  ``credit`` / ``debit`` /
    ``required`` are already zero on masked-out lanes, so padding lanes
    add nothing and pass.  Both sides can pass 2^256 (1,024 credits of
    2^255): a 17th limb carries that through the compare.

    Measured on the chip beside two other forms (PERF.md §6, PR 35):
    the limbs split in bytes for an exact bf16 -> f32 product read the
    same at 1,024 lanes and 3 us a block more at 16; a sort by
    (account, lane) with a segmented running sum 78 us more at 1,024."""
    with jax.named_scope("coreth/transfer_order_check"):
        lane = jnp.arange(sender_idx.shape[0])
        earlier = lane[None, :] < lane[:, None]           # [j, i]: i < j
        mine = sender_idx[:, None]
        paid = (earlier & (recip_idx[None, :] == mine)).astype(jnp.int32)
        spent = (earlier & (sender_idx[None, :] == mine)).astype(jnp.int32)
        have = u256.normalize(jnp.pad(
            balances[sender_idx] + jnp.dot(paid, credit), _CARRY_LIMB))
        need = u256.normalize(jnp.pad(
            jnp.dot(spent, debit) + required, _CARRY_LIMB))
        return u256.gte(have, need)


@partial(jax.jit, static_argnames=("num_accounts",))
def _transfer_step(balances, nonces, sender_idx, recip_idx, value16, fee16,
                   required16, tx_nonce, nonce_offset, mask, coinbase_idx,
                   num_accounts: int, credits=None):
    """One block of pure transfers, batched.

    ``mask`` holds the lanes that debit their sender (value + fee) under
    its next nonce, ``credits`` those that pay their recipient the value
    (None: the same lanes).  A transaction is both; an atomic tx's
    export input debits alone and its import output credits alone, each
    in its place after the block's transactions (pack_txd's column 4).

    required16 carries the buyGas balance requirement per tx
    (gas_limit * gas_fee_cap + value, state_transition.go:286).  The
    solvency rule is the reference's own: every transaction is held,
    IN BLOCK ORDER (lane j is transaction j: pack_txd), to what its
    sender holds at that point — its pre-block balance, plus what
    earlier transactions of the block paid it, less what it spent in
    them (_order_solvent).  By induction no balance then goes below 0
    and the finals are the segment sums below, so ok=True is the
    sequential outcome and, for credits that arrive by transfer, ok=False
    is the sequential failure.  Fees credited to the block's coinbase
    stay OUTSIDE the check: a coinbase that sends what it earned
    earlier in the same block is refused and the caller falls back (on
    the C-Chain the coinbase is the blackhole address, which has no
    key).

    The check's two [B, B] int32 masks are at most 4 MB each at 1,024
    lanes — the widest bucket transfers in a 15M-gas block can reach
    (714); the TPU compiler fuses them into the products, 2.6 MB of
    temporaries for the whole window program there
    (tests/test_tpu_compile.py) — and 64 MB each at 4,096; past
    ORDER_CHECK_MAX_LANES the step keeps the pre-block rule below alone
    (conservative, so still exact: the caller falls back).

    Returns (new_balances, new_nonces, ok, pre_ok); ok False => caller
    falls back.  pre_ok is the PRE-BLOCK rule's verdict — every sender's
    pre-block balance against the sum of all it will need in the block,
    credits ignored — which implies ok; ``ok & ~pre_ok`` marks a block
    only the in-order rule could commit (ReplayStats.
    blocks_order_dependent).
    """
    mask_i = mask.astype(jnp.int32)
    debit = u256.add(value16, fee16)                      # [B, 16]
    debit = debit * mask_i[:, None]
    required = required16 * mask_i[:, None]
    credit_i = mask_i if credits is None else credits.astype(jnp.int32)
    credit = value16 * credit_i[:, None]
    # nonce sequence: state nonce + #earlier same-sender txs in block
    expected = nonces[sender_idx] + nonce_offset
    nonce_ok = jnp.all(jnp.where(mask, tx_nonce == expected, True))
    # per-account totals (16-bit limbs give segment-sum headroom)
    debit_tot = u256.normalize(jax.ops.segment_sum(
        debit, sender_idx, num_segments=num_accounts))
    # (a sender's requirements can add up past 2^256: the carry limb
    # keeps the pre-block rule from wrapping into a pass)
    required_tot = u256.normalize(jnp.pad(jax.ops.segment_sum(
        required, sender_idx, num_segments=num_accounts), _CARRY_LIMB))
    credit_tot = u256.normalize(jax.ops.segment_sum(
        credit, recip_idx, num_segments=num_accounts))
    fee_total = u256.normalize(jnp.sum(fee16 * mask_i[:, None], axis=0))
    credit_tot = credit_tot.at[coinbase_idx].add(fee_total)
    credit_tot = u256.normalize(credit_tot)
    send_counts = jax.ops.segment_sum(mask_i, sender_idx,
                                      num_segments=num_accounts)
    solvent = u256.gte(jnp.pad(balances, _CARRY_LIMB),
                       required_tot)                      # [A]
    pre_ok = nonce_ok & jnp.all(solvent | (send_counts == 0))
    if sender_idx.shape[0] <= ORDER_CHECK_MAX_LANES:
        ok = nonce_ok & jnp.all(_order_solvent(
            balances, sender_idx, recip_idx, credit, debit, required))
    else:
        ok = pre_ok
    new_balances = u256.sub(u256.add(balances, credit_tot), debit_tot)
    new_nonces = nonces + send_counts
    return new_balances, new_nonces, ok, pre_ok


@jax.jit
def _scatter_drop(arr, idx, val):
    """Jitted OOB-dropping scatter: the eager ``.at[].set`` pays
    several ms of host-side primitive lowering per call (gather-index
    normalization + broadcast), which flush_staged pays per block; the
    jitted twin amortizes it to a cache hit per (shape, dtype)
    bucket — the pow2 padding below bounds the bucket count."""
    return arr.at[idx].set(val, mode="drop")


class DeviceState:
    """Account- and storage-slot-indexed device arrays (the flat-state /
    snapshot analog, reference core/state/snapshot/ — here resident in
    HBM).  Slot index 0 is a reserved dummy that native-transfer and
    padding rows target with amount 0.

    With ``n_shards > 1`` (a dp mesh is driving replay) the device
    arrays become PER-SHARD tables: host indices (gids) stay contiguous
    in discovery order, but each gid's DEVICE ROW is allocated inside
    the arena of its owning shard — accounts bucket by
    keccak(address)[0], contract storage by the contract's bucket
    (parallel/shard.py), so placement is uniform and independent of
    discovery order.  ``row_of``/``slot_row_of`` carry the gid -> row
    indirection (identity when unsharded); every device-array
    scatter/gather goes through it."""

    def __init__(self, capacity: int = 1 << 14,
                 slot_capacity: int = 1 << 14, n_shards: int = 1):
        self.index: Dict[bytes, int] = {}
        self.addrs: List[bytes] = []
        self.capacity = capacity
        self.n_shards = n_shards
        self.row_of: List[int] = []
        self._arow = [0] * n_shards           # next local row per shard
        self.balances = jnp.zeros((capacity, u256.LIMBS), dtype=jnp.int32)
        self.nonces = jnp.zeros((capacity,), dtype=jnp.int32)
        # host-side metadata that gates device replay; roots/code_hashes
        # preserve non-device account fields across the trie fold
        self.has_code: List[bool] = []
        self.multicoin: List[bool] = []
        self.code_hashes: List[bytes] = []
        self.roots: List[bytes] = []
        # keccak(addr) memo for the secure-trie fold: addresses recur
        # across blocks, the key hash never changes
        self.addr_hashes: List[bytes] = []
        self._staged: List[Tuple[int, int, int]] = []
        # storage slots: (contract, slot_key32) -> index into slot_vals
        self.slot_capacity = slot_capacity
        self.slot_index: Dict[Tuple[bytes, bytes], int] = {}
        self.slot_keys: List[Tuple[bytes, bytes]] = [(b"", b"")]  # dummy 0
        self.slot_row_of: List[int] = [0]     # dummy -> shard 0 row 0
        self._srow = [1 if s == 0 else 0 for s in range(n_shards)]
        self._cbucket: Dict[bytes, int] = {}  # contract -> owning shard
        self.slot_vals = jnp.zeros((slot_capacity, u256.LIMBS),
                                   dtype=jnp.int32)
        # host mirror of slot values as of the last VALIDATED block —
        # the classifier's gas-variant simulation reads/extends it
        self.slot_host: List[int] = [0]
        self.slots_by_contract: Dict[bytes, List[int]] = {}
        self._staged_slots: List[Tuple[int, int]] = []

    def _grow(self, need: int) -> None:
        while self.capacity < need:
            self.capacity *= 2
        self.balances = jnp.zeros(
            (self.capacity, u256.LIMBS), dtype=jnp.int32
        ).at[:self.balances.shape[0]].set(self.balances)
        self.nonces = jnp.zeros(
            (self.capacity,), dtype=jnp.int32
        ).at[:self.nonces.shape[0]].set(self.nonces)

    def _grow_slots(self, need: int) -> None:
        while self.slot_capacity < need:
            self.slot_capacity *= 2
        self.slot_vals = jnp.zeros(
            (self.slot_capacity, u256.LIMBS), dtype=jnp.int32
        ).at[:self.slot_vals.shape[0]].set(self.slot_vals)

    def _grow_sharded(self) -> None:
        """Double every shard's arena: shard-major rows all move
        (row = shard*arena + local), so the device tables rebuild from
        a host round trip — rare (amortized doubling) and the ONLY
        point where sharded rows are remapped."""
        from coreth_tpu.parallel import remap_rows
        old = self.capacity // self.n_shards
        self.capacity *= 2
        new_rows = remap_rows(self.row_of, old,
                              self.capacity // self.n_shards)
        bal = np.asarray(self.balances)
        non = np.asarray(self.nonces)
        nb = np.zeros((self.capacity, u256.LIMBS), dtype=np.int32)
        nn = np.zeros((self.capacity,), dtype=np.int32)
        nb[new_rows] = bal[self.row_of]
        nn[new_rows] = non[self.row_of]
        self.balances = jnp.asarray(nb)
        self.nonces = jnp.asarray(nn)
        self.row_of = new_rows

    def _grow_slots_sharded(self) -> None:
        from coreth_tpu.parallel import remap_rows
        old = self.slot_capacity // self.n_shards
        self.slot_capacity *= 2
        new_rows = remap_rows(self.slot_row_of, old,
                              self.slot_capacity // self.n_shards)
        sv = np.asarray(self.slot_vals)
        nsv = np.zeros((self.slot_capacity, u256.LIMBS), dtype=np.int32)
        nsv[new_rows] = sv[self.slot_row_of]
        self.slot_vals = jnp.asarray(nsv)
        self.slot_row_of = new_rows

    def _alloc_row(self, addr_hash: bytes) -> int:
        """Device-table row for a new account gid (bucketed arena in
        shard mode, identity otherwise)."""
        if self.n_shards <= 1:
            row = len(self.row_of)
            if row >= self.capacity:
                self._grow(row + 1)
            return row
        from coreth_tpu.parallel import account_bucket
        s = account_bucket(addr_hash, self.n_shards)
        if self._arow[s] >= self.capacity // self.n_shards:
            self._grow_sharded()
        row = s * (self.capacity // self.n_shards) + self._arow[s]
        self._arow[s] += 1
        return row

    def _alloc_slot_row(self, contract: bytes) -> int:
        if self.n_shards <= 1:
            row = len(self.slot_row_of)
            if row >= self.slot_capacity:
                self._grow_slots(row + 1)
            return row
        s = self._cbucket.get(contract)
        if s is None:
            from coreth_tpu.crypto import keccak256
            from coreth_tpu.parallel import contract_bucket
            s = contract_bucket(keccak256(contract), self.n_shards)
            self._cbucket[contract] = s
        if self._srow[s] >= self.slot_capacity // self.n_shards:
            self._grow_slots_sharded()
        row = s * (self.slot_capacity // self.n_shards) + self._srow[s]
        self._srow[s] += 1
        return row

    def ensure(self, addr: bytes, account: Optional[StateAccount]) -> int:
        idx = self.index.get(addr)
        if idx is not None:
            return idx
        idx = len(self.addrs)
        self.index[addr] = idx
        self.addrs.append(addr)
        from coreth_tpu.crypto import keccak256
        self.addr_hashes.append(keccak256(addr))
        # two statements: _alloc_row may REPLACE row_of (arena growth
        # remaps rows into a fresh list), so the append must bind after
        row = self._alloc_row(self.addr_hashes[idx])
        self.row_of.append(row)
        if account is None:
            self.has_code.append(False)
            self.multicoin.append(False)
            self.code_hashes.append(EMPTY_CODE_HASH)
            self.roots.append(EMPTY_ROOT_HASH)
        else:
            self.has_code.append(account.code_hash != EMPTY_CODE_HASH)
            self.multicoin.append(account.is_multi_coin)
            self.code_hashes.append(account.code_hash)
            self.roots.append(account.root)
            if account.balance or account.nonce:
                # staged; one scatter per block (a per-account .at[].set
                # would copy the whole array each time)
                self._staged.append((idx, account.balance, account.nonce))
        return idx

    def ensure_slot(self, contract: bytes, key: bytes, value: int) -> int:
        s_idx = self.slot_index.get((contract, key))
        if s_idx is not None:
            return s_idx
        s_idx = len(self.slot_keys)
        self.slot_index[(contract, key)] = s_idx
        self.slot_keys.append((contract, key))
        row = self._alloc_slot_row(contract)  # may replace slot_row_of
        self.slot_row_of.append(row)
        self.slot_host.append(value)
        self.slots_by_contract.setdefault(contract, []).append(s_idx)
        if value:
            self._staged_slots.append((s_idx, value))
        return s_idx

    _staged: List[Tuple[int, int, int]]

    @staticmethod
    def _pad_pow2(n: int, floor: int = 64) -> int:
        v = floor
        while v < n:
            v *= 2
        return v

    def flush_staged(self):
        """Apply staged initial values; returns (accounts, slots) lists
        that were flushed so a speculative window can re-stage them if
        its arrays are discarded after a fallback rewind.

        Scatter batches pad to pow2 buckets (OOB rows drop): every
        distinct batch length would otherwise compile a fresh XLA
        scatter per fallback block."""
        flushed_a, flushed_s = self._staged, self._staged_slots
        if self._staged:
            n = len(self._staged)
            pad = self._pad_pow2(n)
            idx = np.full(pad, self.capacity, dtype=np.int32)
            idx[:n] = [self.row_of[s[0]] for s in self._staged]
            bal = u256.pack_np([s[1] for s in self._staged]
                               + [0] * (pad - n))
            non = np.zeros(pad, dtype=np.int32)
            non[:n] = [s[2] for s in self._staged]
            jidx = jnp.asarray(idx)
            self.balances = _scatter_drop(self.balances, jidx,
                                          jnp.asarray(bal))
            self.nonces = _scatter_drop(self.nonces, jidx,
                                        jnp.asarray(non))
            self._staged = []
        if self._staged_slots:
            n = len(self._staged_slots)
            pad = self._pad_pow2(n)
            idx = np.full(pad, self.slot_capacity, dtype=np.int32)
            idx[:n] = [self.slot_row_of[s[0]]
                       for s in self._staged_slots]
            val = u256.pack_np([s[1] for s in self._staged_slots]
                               + [0] * (pad - n))
            self.slot_vals = _scatter_drop(
                self.slot_vals, jnp.asarray(idx), jnp.asarray(val))
            self._staged_slots = []
        return flushed_a, flushed_s

    def read_accounts(self, indices: List[int]) -> List[Tuple[int, int]]:
        """Pull (balance, nonce) for given indices to host."""
        idx = np.asarray([self.row_of[i] for i in indices],
                         dtype=np.int32)
        bal = np.asarray(self.balances[jnp.asarray(idx)])
        non = np.asarray(self.nonces[jnp.asarray(idx)])
        balances = u256.to_ints(bal)
        return [(balances[i], int(non[i])) for i in range(len(indices))]


def _recover_segment(wire, offsets, chain_id: int):
    """One segment's native batch, on the recovery worker: phase
    ``sender/native`` of the worker's own account, its CPU seconds
    marked at both ends (two system calls a segment)."""
    from coreth_tpu.crypto import native
    acct = obs.current() or obs.NULL_ACCOUNT
    acct.mark_cpu()
    with acct.enter("sender/native"):
        out = native.recover_senders_wire(wire, offsets, chain_id)
        acct.mark_cpu()
    return out


class _SenderPipeline:
    """Segmented, look-ahead sender recovery for replay().

    The synchronous warm_senders() recovers every signature before the
    first window scan, serializing the whole chain's ECDSA ahead of
    execution.  This pipeline cuts the input into segments of whole
    blocks (a segment closes BEFORE the block that would take it past
    SEGMENT_SIGS; a single larger block is a segment of its own) and
    keeps AHEAD segments issued past the replay cursor.  The replay
    thread hands a segment over as ONE buffer — its transactions' wire
    bytes end to end (_pack_sigs) — and the native C++ batch
    (crypto/native.recover_senders_wire — the one batch engine) derives
    each signing hash, r, s and recovery id from the bytes and recovers
    the keys in the engine's recovery worker thread: one worker, in
    order; the ctypes call releases the GIL.  The worker keeps an
    account of its own (role ``recover``: ``sender/native`` round each
    segment's batch, ``idle`` between them), so the replay thread's
    ``sender/wait_host`` can be set beside what the worker was doing.
    Without the native library a segment stays lazy: signer.sender
    recovers per tx.  ensure(i) blocks only until block i's segment is
    applied.
    """

    AHEAD = 3
    SEGMENT_SIGS = 4096

    def __init__(self, engine: "ReplayEngine", blocks: List[Block]):
        self.engine = engine
        self.block_seg: List[int] = []
        self.segments: List[List[Block]] = []
        cur: List[Block] = []
        count = 0
        for b in blocks:
            n = len(b.transactions)
            if cur and count + n > self.SEGMENT_SIGS:
                self.segments.append(cur)
                cur, count = [], 0
            self.block_seg.append(len(self.segments))
            cur.append(b)
            count += n
        if cur:
            self.segments.append(cur)
        # (txs packed, the worker's Future) a segment issued; Future
        # None: nothing to recover, no native library, or issuing
        # raised — signer.sender's per-tx path recovers lazily
        self.issued: List[tuple] = []
        self.done = 0

    def _issue(self, s: int) -> None:
        from coreth_tpu.crypto import native
        eng = self.engine
        t0 = time.monotonic()
        todo, fut = [], None
        with eng.account.enter("sender/pack"):
            try:
                faults.fire(PT_RECOVER)  # degrade: lazy per-tx recovery
                todo, wire, offsets = eng._pack_sigs(self.segments[s])
                if todo and native.load() is not None:
                    fut = eng._recover_pool_get().submit(
                        _recover_segment, wire, offsets,
                        eng.signer.chain_id)
            except Exception:  # noqa: BLE001 — degrade to lazy per-tx
                eng.stats.recover_degraded += 1
        self.issued.append((todo, fut))
        self._account(fut, time.monotonic() - t0)

    def _account(self, fut, dt: float) -> None:
        stats = self.engine.stats
        stats.t_sender += dt
        if fut is not None:
            stats.t_sender_host += dt

    def _complete(self, s: int) -> None:
        todo, fut = self.issued[s]
        if fut is None:
            return
        eng = self.engine
        acct = eng.account
        t0 = time.monotonic()
        acct.enter("sender/wait_host")
        try:
            out, ok = fut.result()
            eng.stats.sigs_host += len(todo)
            acct.switch("sender/apply")
            eng._apply_recovered(todo, out, ok)
        except Exception:  # noqa: BLE001 — per-tx python path later
            eng.stats.recover_degraded += 1
        finally:
            acct.exit()
            self._account(fut, time.monotonic() - t0)

    def ensure(self, block_idx: int) -> None:
        """Senders for block_idx's segment are recovered on return;
        segments up to AHEAD past it are issued."""
        s = self.block_seg[block_idx]
        last = min(s + self.AHEAD, len(self.segments) - 1)
        while len(self.issued) <= last:
            self._issue(len(self.issued))
        while self.done <= s:
            self._complete(self.done)
            self.done += 1


class ReplayEngine:
    """Windowed replay over a shared state Database."""

    def __init__(self, config: ChainConfig, db: Database, state_root: bytes,
                 parent_header=None, batch_pad: int = 1024,
                 capacity: int = 1 << 14, window: int = 16,
                 slot_capacity: Optional[int] = None, mesh=None,
                 engine=None):
        """batch_pad: INERT since PR 30 — accepted so existing call
        sites keep working, read by nothing: a window's lane pad is
        lane_bucket() of its largest block (ROADMAP D4 removes the
        argument and its call sites).

        mesh: a jax.sharding.Mesh with >1 device switches execution
        to the mesh-sharded kernels (parallel/mesh.py): tx batches and
        state rows shard over the ``dp`` axis, per-account/per-slot
        totals reduce with psum_scatter over ICI.  Bit-identical to the
        single-device path (pinned by tests/test_parallel.py)."""
        # CORETH_TRACE=1 installs the span tracer; then the self-time
        # account of this engine's life (obs/account.py), opened first
        # so that construction is its first phase
        obs.arm_from_env()
        self.account = obs.Account()
        build = self.account.begin("engine/build")
        self.config = config
        self.db = db
        self.mesh = None
        self._n_shards = 1
        if mesh is not None and mesh.devices.size > 1:
            cap = capacity
            scap = slot_capacity or capacity
            n_dev = mesh.devices.size
            for name, dim in (("capacity", cap), ("slot_capacity", scap),
                              ("LANE_FLOOR", LANE_FLOOR)):
                if dim % n_dev:
                    raise ValueError(
                        f"{name}={dim} must divide by the mesh size "
                        f"{n_dev} (rows/txs shard over the dp axis); "
                        "table doubling and the lane buckets preserve "
                        "divisibility, so fix the initial value")
            self.mesh = mesh
            self._n_shards = n_dev
            # the transfer-window kernel itself is fetched per window
            # (_issue_window_mesh picks the exchange mode by density)
        from coreth_tpu.mpt import native_trie
        # commit-path backend: CORETH_TRIE=native|py (default: native
        # when the library loads); CORETH_TRIE_CHECK=1 arms the
        # python-twin differential oracle on every root derivation
        self._native = native_trie.backend() == "native"
        self._trie_check = native_trie.trie_check_armed()
        self.trie = db.open_trie(state_root)
        if self._native:
            # C++ trie for the hot fold (bit-identical roots pinned by
            # tests); python tries remain the interop format in the db
            if self._trie_check:
                self.trie = native_trie.CheckedSecureTrie(self.trie)
            else:
                self.trie = native_trie.NativeSecureTrie \
                    .from_python_trie(self.trie)
        self.state = DeviceState(capacity, slot_capacity or capacity,
                                 n_shards=self._n_shards)
        self.signer = LatestSigner(config.chain_id)
        # a DummyEngine with ConsensusCallbacks gives the engine the
        # atomic ExtData txs' semantics: the host fallback applies them
        # through onExtraStateChange (plugin/evm/vm.go:986), the device
        # path through on_extra_state_rows (_atomic_lanes)
        self.engine = engine or DummyEngine()
        self.engine.set_config(config)
        self.processor = Processor(config, engine=self.engine)
        self.stats = ReplayStats()
        self.window = window
        self.root = state_root
        # parent header of the next block to replay; needed by the
        # fallback path's engine.finalize (AP4 blockGasCost validation)
        self.parent_header = parent_header
        # device-managed contract storage tries (token fast path), keyed
        # by contract address; opened lazily from the account root
        self.storage_tries: Dict[bytes, "object"] = {}
        # classifier's view of slot values for blocks classified but not
        # yet validated (sequential sim across a pending window)
        self._slot_overlay: Dict[int, int] = {}
        # per-fork-schedule memo of the token transfer gas variants and
        # (contract, address) -> device slot index shortcuts — the
        # classifier runs per tx, so everything derivable per block or
        # per address is hoisted out of that loop
        self._vg_cache: Dict[tuple, dict] = {}
        self._addr_slot: Dict[Tuple[bytes, bytes], int] = {}
        # bumped whenever a non-machine path rewrites contract storage
        # (token fast path fold, host fallback) — the machine executor's
        # window runner drops its device-resident slot table when it
        # observes a bump (its mirror can no longer be trusted)
        self.storage_epoch = 0
        # asynchronous flat-state layer (state/flat): O(1) cold reads
        # for the engine, the device table fills, and host StateDBs;
        # generational diffs feed background checkpoints and the
        # quarantine-rollback primitive.  CORETH_FLAT=0 restores the
        # trie-walk-only read path (A/B + safety valve);
        # CORETH_FLAT_CHECK=1 arms the differential oracle — every
        # flat hit is re-derived from the trie and must match.
        self.flat = None
        self._flat_check = bool(os.environ.get("CORETH_FLAT_CHECK"))
        if bool(int(os.environ.get("CORETH_FLAT", "1"))):
            from coreth_tpu.state.flat import FlatStore
            self.flat = FlatStore()
        self._flat_view_memo = None
        # window-batched trie commit (replay/commit.py): finished
        # blocks stage deduped writes; flush() folds once per window
        from coreth_tpu.replay.commit import CommitPipeline
        self.commit_pipe = CommitPipeline(self)
        # fault supervision: retry/demote/probe over the execution
        # ladder (replay/supervisor.py); CORETH_FAULT_PLAN arms the
        # injection registry for this process if nothing armed it yet
        faults.arm_from_env()
        # divergence flight recorder (obs/recorder.py): armed by
        # CORETH_FORENSICS=1; the engine hands it the chain config
        # scalars + backend fingerprint every bundle embeds
        forensics.arm_from_env()
        forensics.note_config(config)
        forensics.merge_fingerprint({
            "trie_backend": "native" if self._native else "py",
            "n_shards": self._n_shards,
            "flat": self.flat is not None,
            "flat_check": self._flat_check,
            "trie_check": self._trie_check,
        })
        from coreth_tpu.replay.supervisor import BackendSupervisor
        self.supervisor = BackendSupervisor(self)
        # the hostexec bridge resolves its fault observer PER ENGINE
        # through the Database every StateDB of this engine shares
        # (bridge._observer_for) — N engines in one process (cluster
        # workers, per-worker supervisors in a test harness) keep
        # independent native demotion ladders instead of the last
        # constructor winning a module global
        self.db.fault_observer = self.supervisor
        # general-bytecode block executor (machine_block.py): counters
        # and its window (``CORETH_MACHINE_WINDOW``, default 8 blocks a
        # fused dispatch) and lookahead (``CORETH_MACHINE_LOOKAHEAD``,
        # default 32 blocks classified ahead for one run), read here;
        # the window runner and its kernels stay lazy.  Always there,
        # so whatever reads the machine's counters after a replay finds
        # zeros where no machine block ran, never a missing attribute
        from coreth_tpu.replay.machine_block import MachineBlockExecutor
        self._machine = MachineBlockExecutor(self)
        # processing blocks (replay_block(hold=True): consensus has
        # verified them and not decided), oldest first, one undo record
        # each.  retire_block drops the oldest, rollback_block pops the
        # newest
        self._held: List[_Held] = []
        # set by a caller that serves receipts (the chain behind the
        # VM): every path then leaves the block's in last_receipts
        # (consensus fields alone; replay() builds none where one
        # native call checks the receipt root)
        self.keep_receipts = False
        self.last_receipts: Optional[List[Receipt]] = None
        # atomic txs of the last block the device path validated (a
        # processing block whose window fold fails is counted back out)
        self._atomic_txs_last = 0
        self.account.end(build)

    # ---------------------------------------------------------------- index
    def _flat_view(self):
        """StateDB-facing flat adapter (host fallback / scratch
        StateDBs read flat-first too); None when the layer is off."""
        if self.flat is None:
            return None
        if self._flat_view_memo is None:
            from coreth_tpu.state.flat import FlatStateView
            self._flat_view_memo = FlatStateView(self.flat,
                                                 self._flat_check)
        return self._flat_view_memo

    def _flat_oracle_fail(self, what: str, addr: bytes, got,
                          want, key: Optional[bytes] = None) -> None:
        # the flight recorder learns the exact key and both sides
        # before the evidence unwinds with the raise
        forensics.note_trigger(
            forensics.TR_FLAT,
            f"flat oracle divergence ({what}) at {addr.hex()}",
            contract=addr, key=key, got=got, want=want,
            pre_value=(want.to_bytes(32, "big")
                       if key is not None and isinstance(want, int)
                       else None))
        raise ReplayError(
            f"flat oracle divergence ({what}) at {addr.hex()}: "
            f"flat={got!r} trie={want!r}")

    def _account(self, addr: bytes) -> int:
        idx = self.state.index.get(addr)
        if idx is not None:
            return idx
        flat = self.flat
        if flat is not None:
            v = flat.account(addr)
            if v is not None:
                account = None
                if v is not FLAT_DELETED:
                    account = StateAccount(
                        nonce=v[1], balance=v[0], root=v[2],
                        code_hash=v[3], is_multi_coin=v[4])
                if self._flat_check:
                    raw = self.trie.get(addr)
                    want = StateAccount.from_rlp(raw) \
                        if raw is not None else None
                    if (want is None) != (account is None) or (
                            want is not None
                            and want.rlp() != account.rlp()):
                        self._flat_oracle_fail("account", addr,
                                               account, want)
                return self.state.ensure(addr, account)
        raw = self.trie.get(addr)
        account = StateAccount.from_rlp(raw) if raw is not None else None
        if flat is not None:
            flat.fill_account(
                addr, FLAT_DELETED if account is None else (
                    account.balance, account.nonce, account.root,
                    account.code_hash, account.is_multi_coin))
        return self.state.ensure(addr, account)

    def _storage_trie(self, contract: bytes):
        """Per-contract storage-trie session, opened lazily from the
        account root and kept alive across commit windows."""
        st = self.storage_tries.get(contract)
        if st is None:
            idx = self.state.index[contract]
            st = self.db.open_trie(self.state.roots[idx])
            if self._native:
                from coreth_tpu.mpt.native_trie import (
                    CheckedSecureTrie, NativeSecureTrie)
                if self._trie_check:
                    st = CheckedSecureTrie(st)
                else:
                    st = NativeSecureTrie.from_python_trie(st)
            self.storage_tries[contract] = st
        return st

    def _slot(self, contract: bytes, key: bytes) -> int:
        """Device slot index for (contract, EVM-level storage key),
        loading the current value from the contract's storage trie on
        first touch.  Keys are partitioned exactly as the StateDB writes
        them: bit 0 of byte 0 cleared for normal storage (the Avalanche
        multicoin split, statedb.normalize_state_key)."""
        from coreth_tpu.state.statedb import normalize_state_key
        key = normalize_state_key(key)
        s_idx = self.state.slot_index.get((contract, key))
        if s_idx is not None:
            return s_idx
        value = self.commit_pipe.base_value(contract, key)
        if value is None and self.flat is not None:
            # flat layer before the trie walk (staged window writes
            # above stay authoritative — they have not folded yet)
            value = self.flat.storage_value(contract, key)
            if value is not None and self._flat_check:
                from coreth_tpu import rlp
                raw = self._storage_trie(contract).get(key)
                want = int.from_bytes(rlp.decode(raw), "big") \
                    if raw else 0
                if want != value:
                    self._flat_oracle_fail("slot", contract, value,
                                           want, key=key)
        if value is None:
            from coreth_tpu import rlp
            raw = self._storage_trie(contract).get(key)
            value = int.from_bytes(rlp.decode(raw), "big") if raw else 0
            if self.flat is not None:
                self.flat.fill_storage(contract, key, value)
        return self.state.ensure_slot(contract, key, value)

    # -------------------------------------------------------------- senders
    def _pack_sigs(self, blocks):
        """The transactions without a cached sender, and what the native
        batch recovers them from (crypto/native.recover_senders_wire):
        their wire encodings end to end, and the offsets that cut them.
        A decoded transaction kept its bytes (``inner._wire``); one
        built in process is encoded here.  Signing hash, r, s and
        recovery id are the native walk's to derive, on the thread that
        runs the batch: nothing is computed per transaction here."""
        todo = [tx for b in blocks for tx in b.transactions
                if tx.cached_sender() is None]
        wires = []
        for tx in todo:
            try:
                wires.append(tx.inner._wire)
            except AttributeError:
                wires.append(_encoded(tx))
        return todo, b"".join(wires), \
            list(itertools.accumulate(map(len, wires), initial=0))

    def _apply_recovered(self, todo, out, ok) -> None:
        """Prime the sender caches the native batch vouches for.  A lane
        it answered ``ok = 0`` (the refusals crypto/native.
        recover_senders_wire lists) stays uncached: signer.sender's
        per-transaction path decides it, as it did before.  A lane it
        answered ``ok = 2`` came from the batch's sequential fallback:
        primed like any other, and counted."""
        self.stats.sigs_left_to_signer += ok.count(0)
        self.stats.sigs_slow_path += ok.count(2)
        for i, tx in enumerate(todo):
            if ok[i]:
                tx.set_sender(out[20 * i:20 * i + 20])

    def warm_senders(self, blocks) -> None:
        """Batched sender recovery across a whole run of blocks
        (reference core/sender_cacher.go role): ONE native C++ batch
        (crypto/native.recover_senders_wire) on the calling thread.
        Without the native library, or where the batch raises, the txs
        stay uncached and signer.sender recovers them one by one.
        Accepts a single block or a list.

        This is the synchronous form; replay() uses _SenderPipeline to
        overlap segmented recovery with window execution."""
        if isinstance(blocks, Block):
            blocks = [blocks]
        # a thread with an account of its own (the serve prefetcher)
        # keeps this time there: it is not the replay thread's
        own = obs.current()
        if own is not None and own is not self.account:
            self._warm_senders_run(blocks, own)
            return
        # a thread with none gets the engine's, or nothing while the
        # replay thread holds it
        tok = self.account.begin()
        try:
            self._warm_senders_run(
                blocks, self.account if tok >= 0 else obs.NULL_ACCOUNT)
        finally:
            self.account.end(tok)

    def _warm_senders_run(self, blocks, acct) -> None:
        from coreth_tpu.crypto import native
        t0 = time.monotonic()
        acct.enter("sender/pack")
        try:
            todo, wire, offsets = self._pack_sigs(blocks)
            if not todo:
                return
            faults.fire(PT_RECOVER)  # degrade to per-tx recovery
            if native.load() is None:
                return  # per-tx python path in signer.sender
            # the batch runs ON this thread: work, not a wait
            acct.switch("sender/native")
            t1 = time.monotonic()
            out, ok = native.recover_senders_wire(wire, offsets,
                                                  self.signer.chain_id)
            self.stats.sigs_host += len(todo)
            self.stats.t_sender_host += time.monotonic() - t1
            acct.switch("sender/apply")
            self._apply_recovered(todo, out, ok)
        except Exception:  # noqa: BLE001 — fall back to per-tx path
            self.stats.recover_degraded += 1
        finally:
            acct.exit()
            self.stats.t_sender += time.monotonic() - t0

    def _recover_pool_get(self):
        if not hasattr(self, "_recover_pool"):
            from concurrent.futures import ThreadPoolExecutor
            # the worker opens its own account as it starts: inside
            # the call that issued the first segment
            self._recover_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="coreth-recover",
                initializer=obs.thread_account, initargs=("recover",))
        return self._recover_pool

    # ------------------------------------------------------------- classify
    def _classify(self, block: Block) -> Optional[dict]:
        """Batch inputs if the block is device-replayable, else None.

        Two tx shapes replay on device, freely mixed within a block:
        pure value transfers, and ERC-20 ``transfer()`` calls on
        contracts whose runtime is the known token (workloads/erc20).
        For token calls the classifier derives the exact per-tx gas by
        simulating the mapping-slot value sequence on host (scalar dict
        updates — the O(txs) bookkeeping that replaces O(gas) host
        interpretation) and pre-builds the Transfer log; the wide u256
        slot arithmetic itself runs batched on device (_slot_step).  A
        block's atomic txs (ExtData) ride the same batch as lanes after
        its transactions (_atomic_lanes)."""
        if not self.supervisor.allows("device"):
            # supervisor demoted the device scope: every block routes
            # through the host ladder until the cooldown lapses (the
            # first allowed classify after that IS the probe)
            return None
        base_fee = block.base_fee
        rules = self.config.rules(block.number, block.time)
        # precompile / prohibited targets have no code in state but DO
        # execute (or reject) — never classifiable as plain transfers
        from coreth_tpu.evm.precompiles import special_call_targets
        from coreth_tpu.processor.state_transition import is_prohibited
        avoid = special_call_targets(rules)
        # CORETH_NO_TOKEN_FASTPATH=1 routes token calls to the general
        # step machine instead (A/B benching of the machine path)
        no_token = bool(int(os.environ.get(
            "CORETH_NO_TOKEN_FASTPATH", "0")))
        token_ctx = self._token_block_ctx(rules, block) \
            if rules.is_apricot_phase1 and not no_token else None
        senders, recips, values, fees, required, nonces, offsets = \
            [], [], [], [], [], [], []
        from_slots, to_slots, amounts, gas_used, tx_logs = \
            [], [], [], [], []
        seen_count: Dict[bytes, int] = {}
        overlay: Dict[int, int] = {}  # this block's slot sim, uncommitted
        # local bindings: this loop runs for every tx in the replay
        state = self.state
        has_code = state.has_code
        multicoin = state.multicoin
        acct_index = state.index
        account = self._account
        classify_token = self._classify_token
        sender_of = self.signer.sender
        TX_GAS = P.TX_GAS
        for tx in block.transactions:
            if tx.to is None or tx.access_list:
                return None
            if tx.to in avoid or is_prohibited(tx.to):
                return None
            # always through Signer.sender: the recovery cache is primed
            # without chain-id validation ("prime it only"), and a
            # foreign-chain-id legacy tx must NOT classify clean here
            # while the host path rejects it (transaction.py:411-413)
            try:
                sender = sender_of(tx)
            except ValueError:
                return None  # host path raises the canonical rejection
            s_idx = acct_index.get(sender)
            if s_idx is None:
                s_idx = account(sender)
            r_idx = acct_index.get(tx.to)
            if r_idx is None:
                r_idx = account(tx.to)
            if has_code[s_idx] or multicoin[s_idx]:
                return None
            gas_fee_cap = tx.gas_fee_cap
            if base_fee is not None:
                tip = tx.gas_tip_cap
                if gas_fee_cap < base_fee or gas_fee_cap < tip:
                    return None
                price = base_fee + tip
                if gas_fee_cap < price:
                    price = gas_fee_cap
            else:
                price = tx.gas_price
            if tx.data:
                if token_ctx is None:
                    return None
                out = classify_token(tx, sender, r_idx, token_ctx,
                                     overlay)
                if out is None:
                    return None
                f_s, t_s, amt, used, log = out
                values.append(0)
                from_slots.append(f_s)
                to_slots.append(t_s)
                amounts.append(amt)
                tx_logs.append(log)
            else:
                if tx.gas != TX_GAS:
                    return None
                if has_code[r_idx] or multicoin[r_idx]:
                    return None
                used = TX_GAS
                values.append(tx.value)
                from_slots.append(0)
                to_slots.append(0)
                amounts.append(0)
                tx_logs.append(None)
            senders.append(s_idx)
            recips.append(r_idx)
            gas_used.append(used)
            fees.append(used * price)
            # buyGas requirement (cap-based for typed txs)
            required.append(tx.gas * gas_fee_cap + tx.value)
            nonces.append(tx.nonce)
            prev = seen_count.get(sender, 0)
            offsets.append(prev)
            seen_count[sender] = prev + 1
        batch = dict(senders=senders, recips=recips, values=values,
                     fees=fees, required=required, nonces=nonces,
                     offsets=offsets, from_slots=from_slots,
                     to_slots=to_slots, amounts=amounts,
                     gas_used=gas_used, logs=tx_logs)
        if block.ext_data():
            atomic = self._atomic_lanes(block, seen_count)
            if atomic is None:
                return None
            batch["atomic"], lanes = atomic
            batch["kinds"] = [LANE_TX] * len(senders)
            for kind, idx, wei, nonce, offset in lanes:
                batch["kinds"].append(kind)
                senders.append(idx)
                recips.append(idx)
                values.append(wei)
                fees.append(0)
                required.append(wei if kind == LANE_DEBIT else 0)
                nonces.append(nonce)
                offsets.append(offset)
                from_slots.append(0)
                to_slots.append(0)
                amounts.append(0)
        batch["coinbase"] = self._account(block.header.coinbase)
        # the block classified clean: its slot writes become visible to
        # the next block's classification within this pending window
        self._slot_overlay.update(overlay)
        return batch

    def _atomic_lanes(self, block: Block, seen_count: Dict[bytes, int]):
        """``(effect, lanes)`` for the atomic txs in a block's ExtData,
        or None: the host path takes the block.  The consensus
        callbacks keep the bookkeeping that is not state
        (``on_extra_state_rows``: decode, semantic verify with the
        credentials, input conflicts, the backend's record of the block;
        phase ``vm/atomic_verify``) and hand the state transfer back as
        rows, which become lanes here (``classify/atomic``): ``(kind,
        account, wei, nonce, offset)``, a credit of wei to the account,
        or a debit of wei under the account's nonce — its offset counts
        the block's earlier lanes from that account, so the nonce an
        export must carry is the one the block's transactions leave.
        None too without the callback (nothing to apply the atomic
        semantics with) or where it declines (another asset than AVAX)."""
        hook = self.engine.cb.on_extra_state_rows
        if hook is None:
            return None
        acct = self.account
        try:
            with acct.enter("vm/atomic_verify"):
                effect = hook(block)
        except (AtomicTxError, ValueError):
            return None  # the host path raises the canonical rejection
        if effect is None:
            return None
        lanes = []
        with acct.enter("classify/atomic"):
            for debit, addr, wei, nonce in effect.rows():
                idx = self.state.index.get(addr)
                if idx is None:
                    idx = self._account(addr)
                if not debit:
                    lanes.append((LANE_CREDIT, idx, wei, 0, 0))
                    continue
                if nonce >= NONCE_CAP:
                    return None
                offset = seen_count.get(addr, 0)
                seen_count[addr] = offset + 1
                lanes.append((LANE_DEBIT, idx, wei, nonce, offset))
        return effect, lanes

    def _slot_view(self, s_idx: int, overlay: Dict[int, int]) -> int:
        """Sequential slot value as of the current classification point:
        this block's sim, then the pending window's, then validated."""
        v = overlay.get(s_idx)
        if v is not None:
            return v
        v = self._slot_overlay.get(s_idx)
        if v is not None:
            return v
        return self.state.slot_host[s_idx]

    def _token_block_ctx(self, rules, block: Block) -> dict:
        """Per-block constants of the token fast path, computed ONCE per
        block instead of per tx: the three calibrated gas variants
        (memoized per fork schedule — measure_transfer_exec_gas runs
        the host interpreter and rebuilds Rules on every call, which at
        262k txs was ~29us/tx of pure bookkeeping) and the intrinsic-gas
        constants for the 68-byte transfer calldata."""
        key = tuple(v for f, v in sorted(vars(rules).items())
                    if f.startswith("is_"))
        vg = self._vg_cache.get(key)
        if vg is None:
            vg = {v: measure_transfer_exec_gas(
                    self.config, block.number, block.time, v)
                  for v in ("noop", "set", "reset")}
            self._vg_cache[key] = vg
        nz_gas = (P.TX_DATA_NON_ZERO_GAS_EIP2028 if rules.is_istanbul
                  else P.TX_DATA_NON_ZERO_GAS_FRONTIER)
        return dict(vg=vg, nz_gas=nz_gas, z_gas=P.TX_DATA_ZERO_GAS)

    def _classify_token(self, tx, sender: bytes, r_idx: int,
                        token_ctx: dict, overlay: Dict[int, int]):
        """Classify one ERC-20 transfer() call; returns
        (from_slot, to_slot, amount, gas_used, Log) or None.

        Gas is exact: intrinsic calldata gas + the calibrated execution
        gas of the variant this tx hits (workloads/erc20
        measure_transfer_exec_gas).  Post-AP1 only — with refunds alive
        (state_transition.go:449 pre-AP1) gas would depend on the refund
        counter, which this path does not model (callers gate on
        rules.is_apricot_phase1 when building token_ctx)."""
        if self.state.code_hashes[r_idx] != TOKEN_CODE_HASH:
            return None
        if tx.value != 0:
            return None
        data = tx.data
        parsed = parse_transfer_calldata(data)
        if parsed is None:
            return None
        to_addr, amt = parsed
        if to_addr == sender:
            return None  # self-transfer hits a different SSTORE sequence
        token = tx.to
        addr_slot = self._addr_slot
        f_s = addr_slot.get((token, sender))
        if f_s is None:
            f_s = self._slot(token, balance_slot(sender))
            addr_slot[(token, sender)] = f_s
        t_s = addr_slot.get((token, to_addr))
        if t_s is None:
            t_s = self._slot(token, balance_slot(to_addr))
            addr_slot[(token, to_addr)] = t_s
        fv = self._slot_view(f_s, overlay)
        tv = self._slot_view(t_s, overlay)
        if fv < amt:
            return None  # would revert sequentially -> host path
        vg = token_ctx["vg"]
        exec_gas = vg["noop"] if amt == 0 else (
            vg["set"] if tv == 0 else vg["reset"])
        nz = 68 - data.count(0)
        used = (P.TX_GAS + nz * token_ctx["nz_gas"]
                + (68 - nz) * token_ctx["z_gas"] + exec_gas)
        if tx.gas < used:
            return None  # would OOG mid-execution -> status-0 receipt
        overlay[f_s] = fv - amt
        overlay[t_s] = (tv + amt) & ((1 << 256) - 1)  # unchecked ADD wraps
        log = Log(address=token,
                  topics=[TRANSFER_TOPIC, b"\x00" * 12 + sender,
                          b"\x00" * 12 + to_addr],
                  data=amt.to_bytes(32, "big"))
        return f_s, t_s, amt, used, log

    # ---------------------------------------------------------------- replay
    def _prepare_window(self, items: List[Tuple[Block, dict]]):
        """Pack a run of classified blocks into stacked device inputs.

        The window is padded up to the next power of two of its length
        (so a 1-block call scans 1 slot, not ``self.window``) with no-op
        all-masked-out batches, bounding the number of compiled variants
        while never scanning more than 2x the real work.  With a
        non-power-of-two window the top bucket exceeds it (window=12
        compiles K=16); keep ``window`` a power of two to avoid the
        extra padded slots.  The lane axis is lane_bucket() of the
        window's largest block: the masked-out lanes it leaves off
        contributed zeros to every segment sum.

        Returns the five device inputs, the per-block touched lists,
        what flush_staged flushed and, last, ``(buf, dims)``: the one
        staging buffer the five are views into and the six sizes that
        cut it (window_views) — what _ship_window sends."""
        flushed = self.state.flush_staged()
        K = 1
        while K < len(items):
            K *= 2
        pad = lane_bucket(max(len(batch["senders"]) for _, batch in items))
        t_pad = 256
        s_pad = 8
        touched_lists = []
        slot_lists = []
        # window-local index spaces: the device works on gathered
        # locals, so kernel cost scales with the window's touched set,
        # not the global table capacity
        acct_local: Dict[int, int] = {}
        slot_local: Dict[int, int] = {0: 0}  # local slot 0 = the dummy

        def a_loc(g: int) -> int:
            l = acct_local.get(g)
            if l is None:
                l = len(acct_local)
                acct_local[g] = l
            return l

        def s_loc(g: int) -> int:
            l = slot_local.get(g)
            if l is None:
                l = len(slot_local)
                slot_local[g] = l
            return l

        local_batches = []
        for block, batch in items:
            lb = dict(batch)
            lb["senders"] = [a_loc(g) for g in batch["senders"]]
            lb["recips"] = [a_loc(g) for g in batch["recips"]]
            lb["coinbase"] = a_loc(batch["coinbase"])
            lb["from_slots"] = [s_loc(g) for g in batch["from_slots"]]
            lb["to_slots"] = [s_loc(g) for g in batch["to_slots"]]
            local_batches.append(lb)
            touched = sorted(set(batch["senders"]) | set(batch["recips"])
                             | {batch["coinbase"]})
            touched_lists.append(touched)
            while t_pad < len(touched):
                t_pad *= 2
            slots = sorted((set(batch["from_slots"])
                            | set(batch["to_slots"])) - {0})
            slot_lists.append(slots)
            while s_pad < len(slots):
                s_pad *= 2
        L = 256
        while L < len(acct_local):
            L *= 2
        SL = 8
        while SL < len(slot_local):
            SL *= 2
        cap = self.state.capacity
        scap = self.state.slot_capacity
        # ONE staging buffer a window, the five inputs views into it
        # (window_views), FRESH each time: the runtime may read a numpy
        # buffer after the upload call returns, and replay() prepares
        # window k+1 while window k is in flight
        dims = (K, pad, t_pad, s_pad, L, SL)
        buf = np.zeros(window_words(dims), dtype=np.int32)
        acct_gids, slot_gids, txds, t_idxs, s_idxs = window_views(
            buf, dims)
        # device-table ROWS of the window-locals (row == gid unsharded;
        # bucketed arena row on a mesh); OOB pad: fill/drop
        acct_gids[:] = cap
        for g, l in acct_local.items():
            acct_gids[l] = self.state.row_of[g]
        slot_gids[:] = scap
        for g, l in slot_local.items():
            slot_gids[l] = self.state.slot_row_of[g]
        for k, (block, batch) in enumerate(items):
            pack_txd(txds[k], local_batches[k], len(batch["senders"]))
            t_idxs[k, :len(touched_lists[k])] = \
                [acct_local[g] for g in touched_lists[k]]
            s_idxs[k, :len(slot_lists[k])] = \
                [slot_local[g] for g in slot_lists[k]]
        return (txds, t_idxs, s_idxs, acct_gids, slot_gids,
                touched_lists, slot_lists, flushed, (buf, dims))

    def _count_lanes(self, items, txds) -> None:
        self.stats.lanes_real += sum(
            len(batch["senders"]) for _, batch in items)
        self.stats.lanes_padded += txds.shape[0] * txds.shape[1]

    @_in_phase("window/prepare")
    def _issue_window_mesh(self, items: List[Tuple[Block, dict]],
                           fetch: bool = True) -> dict:
        """Mesh-sharded execution of a whole window in ONE dispatch
        (replay/shard.py): the persistent balance/nonce/slot tables are
        per-shard row arenas sharded over ``dp``, txs round-robin over
        devices, and each block's cross-shard effects (remote credits,
        coinbase fees, remote slot debits/credits) exchange with a
        single psum of packed effect tensors sized by the window's
        touched set.  The fetch tensor comes back in exactly the
        single-device layout, so _complete_window is shared — and the
        old per-block dispatch + per-block blocking sync that inverted
        the scaling curve is gone."""
        from coreth_tpu.parallel import exchange_mode
        from coreth_tpu.replay.shard import (
            interleave_txs, sharded_transfer_window)
        t0 = time.monotonic()
        acct = self.account
        (txds, t_idxs, s_idxs, acct_rows, slot_rows, touched_lists,
         slot_lists, flushed, _) = self._prepare_window(items)
        prev = (self.state.balances, self.state.nonces,
                self.state.slot_vals)
        perm = interleave_txs(txds.shape[1], self._n_shards)
        # per-window collective selection: the packed effect exchange
        # rides psum, or the bit-identical ppermute ring when the
        # window's touched set is sparse against the state tables
        # (CORETH_EXCHANGE forces one mode for the A/B)
        mode = exchange_mode(
            acct_rows.shape[0] + slot_rows.shape[0],
            self.state.capacity + self.state.slot_capacity,
            self._n_shards)
        win = sharded_transfer_window(self.mesh, mode)
        acct.switch("window/upload")
        # five uploads, not the single device's one staging buffer:
        # txds is sharded over dp on the lane axis after the interleave
        # while the other four are replicated, and one buffer cannot
        # carry both placements
        ups = (jnp.asarray(acct_rows), jnp.asarray(slot_rows),
               jnp.asarray(txds[:, perm]), jnp.asarray(t_idxs),
               jnp.asarray(s_idxs))
        self.stats.window_uploads += len(ups)
        self.stats.window_upload_bytes += sum(u.nbytes for u in ups)
        acct.switch("window/dispatch")
        new_bal, new_non, new_sv, fetches = win(
            prev[0], prev[1], prev[2], *ups)
        self.state.balances = new_bal
        self.state.nonces = new_non
        self.state.slot_vals = new_sv
        if fetch:
            # windowed device read, same as the single-device path
            try:
                fetches.copy_to_host_async()
                self.stats.reads_prefetched += 1
            except AttributeError:
                pass
            # (the fetch=False re-apply of _recover_window is no new
            # window: the single-device path does not count it either)
            self._count_lanes(items, txds)
        ticket = obs.device_issue(acct)
        self.stats.t_device += time.monotonic() - t0
        return dict(items=items, prev=prev, fetches=fetches,
                    touched_lists=touched_lists, slot_lists=slot_lists,
                    t_pad=t_idxs.shape[1], flushed=flushed,
                    ticket=ticket)

    def _issue_window(self, items: List[Tuple[Block, dict]]) -> dict:
        """Supervised window dispatch: transient faults retry with
        backoff, persistent ones strike toward device demotion and
        surface as BackendFault (replay()/_drive route the run through
        the exact host path).  The injected seam is PT_DISPATCH."""
        if forensics.enabled():
            self._record_window_dispatch(items)
        run = self._issue_window_run if self.mesh is None \
            else self._issue_window_mesh
        return self.supervisor.run("device", PT_DISPATCH, run, items)

    def _record_window_dispatch(self, items) -> None:
        """Flight-recorder ring entries for a transfer/token window:
        the block objects plus a light touched-set sketch (slot keys
        with their last-validated host-mirror pre-values — the premap
        evidence the classifier already computed).  Armed-only; the
        unarmed path is one module-global None check in the caller."""
        st = self.state
        parent = self.parent_header
        for block, batch in items:
            touched = None
            slots = sorted((set(batch["from_slots"])
                            | set(batch["to_slots"])) - {0})
            if slots:
                touched = {"slots": {
                    st.slot_keys[s][0].hex() + ":"
                    + st.slot_keys[s][1].hex():
                        st.slot_host[s] for s in slots[:256]}}
            forensics.record_dispatch(block, parent, "device/transfer",
                                      touched)
            parent = block.header

    @_in_phase("window/prepare")
    def _issue_window_run(self, items: List[Tuple[Block, dict]]) -> dict:
        """One device call for a whole run of transfer blocks: upload the
        window's one staging buffer, lax.scan the steps, download one
        stacked fetch tensor.  Round-trip latency amortizes over the
        window.  Three phases — pack, upload, dispatch — so host packing
        and a supervised retry's backoff never read as device time."""
        t0 = time.monotonic()
        (txds, t_idxs, _, _, _, touched_lists, slot_lists, flushed,
         packed) = self._prepare_window(items)
        prev = (self.state.balances, self.state.nonces,
                self.state.slot_vals)
        fetches = self._ship_window(packed)
        # windowed device READ: start the whole window's fetch-tensor
        # device->host copy now (async — it begins the moment the scan
        # finishes), so _complete_window's np.asarray lands on an
        # already-transferred host buffer instead of paying the device
        # round trip inside the validation phase.  One windowed read
        # replaces what a per-block pipeline would pay per block.
        try:
            fetches.copy_to_host_async()
            self.stats.reads_prefetched += 1
        except AttributeError:
            pass  # non-jax array (mesh path fetches are already np)
        self._count_lanes(items, txds)
        ticket = obs.device_issue(self.account)
        self.stats.t_device += time.monotonic() - t0
        return dict(items=items, prev=prev, fetches=fetches,
                    touched_lists=touched_lists, slot_lists=slot_lists,
                    t_pad=t_idxs.shape[1], flushed=flushed,
                    ticket=ticket)

    def _ship_window(self, packed):
        """Ship a prepared window on a single device — its staging
        buffer up in ONE transfer, the jitted window over the state
        tables, which become its outputs — and return the fetch tensor.
        The one copy of it: a window's issue and _recover_window's
        re-apply both come here.  Switches the account phase on top to
        ``window/upload``, then ``window/dispatch``; the caller closes
        it.

        The numpy buffer goes into the jitted call as it is and the
        call's own argument path makes the transfer: 0.135 ms a window
        on the chip against 0.285 for ``jnp.asarray`` first (PERF.md
        §6, PR 34).  So ``window/upload`` holds seconds only under
        CORETH_EAGER_FLUSH, which needs the device buffer to wait on."""
        buf, dims = packed
        acct = self.account
        acct.switch("window/upload")
        self.stats.window_uploads += 1
        self.stats.window_upload_bytes += buf.nbytes
        if _EAGER_FLUSH:
            # the upload and the dispatch may sit unflushed until the
            # next blocking sync — which would serialize the chip
            # behind the host's fold work; shipping the buffer here lets
            # the scan start while the host validates the window before
            buf = jax.block_until_ready(jnp.asarray(buf))
        acct.switch("window/dispatch")
        st = self.state
        st.balances, st.nonces, st.slot_vals, fetches = \
            _transfer_window_packed(st.balances, st.nonces, st.slot_vals,
                                    buf, dims=dims)
        return fetches

    def _discard_window(self, win: dict) -> None:
        """Drop a speculatively issued window whose base state was
        invalidated by a fallback rewind.  The device arrays themselves
        are restored from the failed window's snapshot; what would be
        lost are the values of accounts/slots FIRST TOUCHED by the
        discarded window (flushed into the discarded arrays at its
        issue).  Re-stage them from the CURRENT authoritative host state
        — trie and slot_host, which the fallback has already repaired —
        not from the captured pre-fallback tuples: the fallback block
        may itself have touched those very accounts/slots, and replaying
        stale captures would overwrite its refresh."""
        fa, fs = win["flushed"]
        for idx, _bal, _non in fa:
            raw = self.trie.get(self.state.addrs[idx])
            acct = StateAccount.from_rlp(raw) if raw else StateAccount()
            self.state._staged.append((idx, acct.balance, acct.nonce))
        for s_idx, _v in fs:
            self.state._staged_slots.append(
                (s_idx, self.state.slot_host[s_idx]))

    def _complete_window(self, win: dict, blocks: List[Block],
                         start_idx: int) -> Optional[int]:
        """Validate a window from its fetched tensors.  Returns None on
        full success, else the index (into ``blocks``) to resume from
        after the rewind+fallback recovery."""
        t0 = time.monotonic()
        acct = self.account
        acct.enter("window/fetch_wait")
        try:
            arr = np.asarray(win["fetches"])  # ONE device read per window
            obs.device_done(win["ticket"], acct)
        finally:
            acct.exit()
        self.stats.t_device += time.monotonic() - t0
        failed = self._validate_window(win, arr)
        if failed is not None:
            # fold the staged valid prefix [0, failed) before the
            # rewind: _fallback opens a StateDB at self.root
            self.commit_pipe.flush()
            return self._recover_window(win, arr, failed, blocks,
                                        start_idx)
        # ONE deduped fold + root check for the whole window
        self.commit_pipe.flush()
        # NOTE: the classifier's slot overlay is NOT cleared here — with
        # window speculation (replay() issues window k+1 before
        # validating window k) the overlay still carries the in-flight
        # window's sims.  After successful validation the overlay
        # entries equal slot_host (a divergence would have failed the
        # root check), so leaving them is safe; fallback and rewind
        # paths clear the overlay because there slot_host is repaired
        # from the trie.
        return None

    @_in_phase("validate")
    def _validate_window(self, win: dict, arr) -> Optional[int]:
        """Hold each block of a fetched window to its header and stage
        it; returns the index of the first block that failed, else
        None.  ONE ``validate`` phase for the window: a one-tx block is
        too short to carry boundaries of its own (10,000 a pass cost
        2% of ``valuetx``, my chip runs, PR 29), so the seconds the
        blocks spent staging — read by ``t_trie``'s clock pair in
        _validate_and_advance — are moved to ``commit/stage`` here."""
        staged = self.stats.t_trie
        blocks = self.stats.blocks_device
        try:
            for k, (block, batch) in enumerate(win["items"]):
                if arr[k, -1, 0] != 1:
                    return k
                try:
                    self._validate_and_advance(
                        block, batch, arr[k], win["touched_lists"][k],
                        win["slot_lists"][k], win["t_pad"])
                except ReplayError:
                    # device-path VALIDATION failed (a malformed
                    # block, or a gas/receipt-model gap): before
                    # giving up, rewind and retry the block on the
                    # exact host path — the same recovery an execution
                    # failure gets.  A block that fails there too
                    # re-raises with .block attached (the streaming
                    # pipeline's quarantine seam).
                    # _validate_and_advance raises before staging, so
                    # the staged set is exactly the valid prefix [0, k).
                    return k
                # the device committed it; would the pre-block rule
                # have? (column 1 of the flag row: _gather_fetch)
                self.stats.blocks_order_dependent += int(
                    arr[k, -1, 1] != 1)
            return None
        finally:
            self.account.move("validate", "commit/stage",
                              self.stats.t_trie - staged,
                              self.stats.blocks_device - blocks)

    def _rebuild_device_rows(self) -> None:
        """Rebuild every device-table row from the authoritative host
        state (engine trie + slot_host): used on rewind when a capacity
        growth landed while a window was speculatively in flight — the
        failed window's array snapshot then has a stale shape (and, on
        a mesh, stale shard-arena rows, which move on growth)."""
        st = self.state
        st._staged = []
        st._staged_slots = []
        bal = np.zeros((st.capacity, u256.LIMBS), dtype=np.int32)
        non = np.zeros((st.capacity,), dtype=np.int32)
        for idx, addr in enumerate(st.addrs):
            raw = self.trie.get(addr)
            if raw is None:
                continue
            a = StateAccount.from_rlp(raw)
            if a.balance or a.nonce:
                bal[st.row_of[idx]] = u256.pack_np([a.balance])[0]
                non[st.row_of[idx]] = a.nonce
        st.balances = jnp.asarray(bal)
        st.nonces = jnp.asarray(non)
        sv = np.zeros((st.slot_capacity, u256.LIMBS), dtype=np.int32)
        for s_idx in range(1, len(st.slot_keys)):
            v = st.slot_host[s_idx]
            if v:
                sv[st.slot_row_of[s_idx]] = u256.pack_np([v])[0]
        st.slot_vals = jnp.asarray(sv)

    @_in_phase("recover_window")
    def _recover_window(self, win, arr, k: int, blocks, start_idx: int) -> int:
        """Block k of the window failed the device validation: the valid
        prefix [0, k) has already been folded into the trie by the loop
        above; restore device arrays to the window start, re-apply the
        valid prefix on device, then run block k through the exact host
        path.  Returns the next block index to resume issuing from."""
        self._slot_overlay.clear()  # discard the pending window's sim
        if (win["prev"][0].shape[0] != self.state.capacity
                or win["prev"][2].shape[0] != self.state.slot_capacity):
            # a table growth landed after this window was issued: the
            # snapshot's layout is stale — rebuild the rows from the
            # host state at the already-folded valid prefix instead of
            # restoring + replaying it on device
            self._rebuild_device_rows()
        else:
            (self.state.balances, self.state.nonces,
             self.state.slot_vals) = win["prev"]
            if k > 0:
                items = win["items"][:k]
                if self.mesh is not None:
                    # state-only re-apply; no per-block host downloads
                    self._issue_window_mesh(items, fetch=False)
                else:
                    with self.account.enter("window/prepare"):
                        self._ship_window(self._prepare_window(items)[-1])
        self._fallback(blocks[start_idx + k])
        return start_idx + k + 1

    def _validate_and_advance(self, block: Block, batch: dict,
                              fetched: np.ndarray, touched: List[int],
                              touched_slots: List[int],
                              t_pad: int) -> None:
        """Host-side consensus checks + staged commit for one device
        block (the trie fold itself is window-batched).  Runs inside
        _validate_window's ``validate`` phase and costs the account no
        boundary of its own: the staging part is what ``t_trie``'s
        clock pair below reads, and the window moves it over."""
        B = len(block.transactions)
        gas_list = batch["gas_used"]
        logs = batch["logs"]
        cums = []
        cum = 0
        for g in gas_list:
            cum += g
            cums.append(cum)
        if cum != block.header.gas_used:
            raise ReplayError("gas used mismatch")
        # Receipt root + bloom: one C++ call when every log is the
        # uniform Transfer shape (native.receipt_root docstring); the
        # Python StackTrie path — pinned equivalent by
        # tests/test_replay.py — remains for exotic shapes / no native.
        uniform = self._native and all(
            lg is None or (len(lg.topics) == 3 and len(lg.data) == 32
                           and all(len(t) == 32 for t in lg.topics))
            for lg in logs)
        if uniform:
            from coreth_tpu.crypto import native as _n
            tx_types = bytes(tx.tx_type for tx in block.transactions)
            has_log = bytes(1 if lg is not None else 0 for lg in logs)
            log_blob = b"".join(
                lg.address + b"".join(lg.topics) + lg.data
                for lg in logs if lg is not None)
            rec_root, bloom = _n.receipt_root(
                cums, tx_types, has_log, log_blob)
            if rec_root != block.header.receipt_hash:
                raise ReplayError("receipt root mismatch")
            if bloom != block.header.bloom:
                raise ReplayError("bloom mismatch")
            receipts = None
        else:
            receipts = [Receipt(
                tx_type=tx.tx_type, status=1, cumulative_gas_used=cums[i],
                gas_used=gas_list[i],
                logs=[logs[i]] if logs[i] is not None else [])
                for i, tx in enumerate(block.transactions)]
            if derive_sha(receipts, derive_hasher()) \
                    != block.header.receipt_hash:
                raise ReplayError("receipt root mismatch")
            if create_bloom(receipts) != block.header.bloom:
                raise ReplayError("bloom mismatch")
        if self.keep_receipts:
            self.last_receipts = receipts or [Receipt(
                tx_type=tx.tx_type, status=1, cumulative_gas_used=cums[i],
                gas_used=gas_list[i],
                logs=[logs[i]] if logs[i] is not None else [])
                for i, tx in enumerate(block.transactions)]
        atomic = batch.get("atomic")
        if self.config.is_apricot_phase4(block.time):
            if receipts is None:
                # verify_block_fee reads only gas_used per receipt
                receipts = [Receipt(gas_used=g) for g in gas_list]
            # the atomic txs' gas and fee contribution, as Finalize
            # holds them (consensus/engine.py)
            ext_gas = 0
            contribution = None
            if atomic is not None:
                ext_gas = atomic.gas_used or 0
                contribution = atomic.contribution
            if block.header.ext_data_gas_used != ext_gas:
                raise _block_error(
                    f"invalid extDataGasUsed: have "
                    f"{block.header.ext_data_gas_used}, want {ext_gas}",
                    block)
            from coreth_tpu.consensus.engine import ConsensusError
            try:
                self.engine.verify_block_fee(
                    block.base_fee, block.header.block_gas_cost,
                    block.transactions, receipts, contribution)
            except ConsensusError as exc:
                # ReplayError so _complete_window's host retry (and
                # the pipeline quarantine) own it, with the block
                # attributed
                raise _block_error(f"block fee: {exc}", block) from exc
        t0 = time.monotonic()
        # STAGE this block's trie effects — the fold itself is
        # window-batched (replay/commit.py): _complete_window flushes
        # ONE deduped fold per window after the next window's device
        # scan is already in flight, so the trie phase overlaps it
        writes: Dict[Tuple[bytes, bytes], int] = {}
        if touched_slots:
            self.storage_epoch += 1
            slot_vals = u256.to_ints(
                fetched[t_pad:t_pad + len(touched_slots), :16])
            for i, s_idx in enumerate(touched_slots):
                contract, key = self.state.slot_keys[s_idx]
                v = slot_vals[i]
                self.state.slot_host[s_idx] = v
                writes[(contract, key)] = v
        n_touched = len(touched)
        balances = u256.to_ints(fetched[:n_touched, :16])
        nonces = fetched[:n_touched, 16]
        accounts = {self.state.addrs[idx]: (balances[i], int(nonces[i]))
                    for i, idx in enumerate(touched)}
        self.commit_pipe.stage(block.header, writes, accounts)
        self.stats.t_trie += time.monotonic() - t0
        self.parent_header = block.header
        self.stats.blocks_device += 1
        self.stats.txs += B
        self._atomic_txs_last = 0 if atomic is None else len(atomic.txs)
        if atomic is not None:
            self.stats.atomic_blocks_device += 1
            self.stats.atomic_txs_device += self._atomic_txs_last

    def _try_machine(self, block: Block) -> bool:
        """Execute an unclassifiable block on the general device step
        machine when every tx is device-eligible; False -> host path.
        One block is one run of one window (bucketed to the executor's
        default 8 all the same, so the first full window compiles
        nothing new).  CORETH_MACHINE=0 forces the host path (A/B
        benching)."""
        if not bool(int(os.environ.get("CORETH_MACHINE", "1"))):
            return False
        if not self.supervisor.allows("device"):
            return False
        mx = self._machine
        t0 = time.monotonic()
        with self.account.enter("classify"):
            plans = mx.classify(block)
        self.stats.t_classify += time.monotonic() - t0
        if plans is None:
            return False
        from coreth_tpu.replay.supervisor import BackendFault
        try:
            with self.account.enter("machine"):
                return self.supervisor.run(
                    "device", None, mx.execute_run,
                    [(block, plans)]) == 1
        except BackendFault:
            return False  # caller takes the exact host path

    def _machine_run(self, blocks: List[Block], i: int,
                     ensure=None) -> int:
        """Handle blocks the transfer classifier rejected, starting at
        `i`: collect CONSECUTIVE machine-eligible blocks into one run
        and execute them as fused device OCC windows
        (machine_block.execute_run — one dispatch covers a whole window
        of blocks), else the exact host path.  Returns how many blocks
        were processed (>= 1).  At the defaults a run is up to 32
        blocks (the executor's LOOKAHEAD) in windows of 8 (its WINDOW):
        account phase ``machine`` once a run, ``machine/*`` once a
        window or dispatch.

        Classifying ahead is safe: machine blocks cannot deploy code or
        set multicoin flags, which is all classify() reads — but a host
        FALLBACK block can, so execute_run stops its run at the first
        block it escalates and the remainder re-classifies here fresh.
        """
        if not bool(int(os.environ.get("CORETH_MACHINE", "1"))) \
                or not self.supervisor.allows("device"):
            self._fallback(blocks[i])
            return 1
        mx = self._machine
        # legacy mode consumes exactly one block per execute_run call:
        # collecting a LOOKAHEAD run would re-classify the same blocks
        # on every call (O(N*LOOKAHEAD)) and skew the A/B's t_classify
        lookahead = mx.LOOKAHEAD if bool(int(os.environ.get(
            "CORETH_DEVICE_OCC", "1"))) else 1
        items = []
        fork = None
        j = i
        while j < len(blocks) and len(items) < lookahead:
            if ensure is not None:
                ensure(j)
            t0 = time.monotonic()
            # machine eligibility is a SUPERSET of the transfer/token
            # fast path (a token transfer call is also machine
            # bytecode): blocks past the first stay with the cheaper
            # fast path when it can take them — stop the run there
            # (block i itself is only here because it was rejected).
            # The boundary block IS classified again by the outer loop:
            # the batch built here would be stale by then (classify
            # simulates token slot values against current state, and
            # the machine blocks before j move that state)
            with self.account.enter("classify"):
                fast = self._classify(blocks[j]) if j > i else None
                plans = mx.classify(blocks[j]) if fast is None else None
            if fast is not None:
                self.stats.t_classify += time.monotonic() - t0
                break
            self.stats.t_classify += time.monotonic() - t0
            if plans is None or (fork is not None
                                 and mx._fork != fork):
                break
            fork = mx._fork
            items.append((blocks[j], plans))
            j += 1
        if not items:
            self._fallback(blocks[i])
            return 1
        mx._fork = fork
        from coreth_tpu.replay.supervisor import BackendFault
        try:
            with self.account.enter("machine"):
                consumed = self.supervisor.run("device", None,
                                               mx.execute_run, items)
        except BackendFault:
            # persistent device fault with no progress: the run's
            # first block takes the exact host path; the rest
            # re-enter the loop (and re-route while demoted)
            self._fallback(blocks[i])
            return 1
        if consumed == 0:
            self._fallback(blocks[i])
            consumed = 1
        return consumed

    @_public_call
    def replay_block(self, block: Block, hold: bool = False) -> bytes:
        """Process one block synchronously (tests; replay() windows).

        ``hold=True`` is consensus's Verify: the block is PROCESSING —
        executed, held to its header, its nodes committed (a root this
        engine hands out is one a StateDB can open), and revertible
        until ``retire_block`` (Accept) or ``rollback_block`` (Reject,
        or the Accept of a sibling) decides it.  A block the engine
        refuses raises ReplayError and leaves the engine at the
        parent, ready for the next."""
        if not hold:
            return self._replay_one(block)
        if self.flat is None:
            raise ReplayError(
                "a processing block needs the flat layer (CORETH_FLAT=1)")
        prev_root, prev_header = self.root, self.parent_header
        sealed = self.flat.generations
        self.flat.pin_new = True
        try:
            self._replay_one(block)
            # the window's flush has run; the node commit is the same
            # layer's work, not the caller's bookkeeping
            with self.account.enter("commit/flush"):
                root = self.commit()
        except StateRootMismatch as exc:
            # every other refusal comes before the engine's state moves
            # (a window that fails its checks is restored and retried
            # on the host path, which raises before it commits); this
            # one comes after the fold
            self.stats.blocks_device -= 1
            self.stats.txs -= len(block.transactions)
            if block.ext_data():
                self.stats.atomic_blocks_device -= 1
                self.stats.atomic_txs_device -= self._atomic_txs_last
            self._restore(prev_root, prev_header, sorted(exc.accounts),
                          sorted(exc.writes))
            raise
        finally:
            self.flat.pin_new = False
        self._held.append(_Held(block.hash(), prev_root, prev_header,
                                self.flat.generations - sealed))
        return root

    def _replay_one(self, block: Block) -> bytes:
        self.warm_senders(block)
        t0 = time.monotonic()
        with self.account.enter("classify"):
            batch = self._classify(block)
        self.stats.t_classify += time.monotonic() - t0
        if batch is None:
            if self._try_machine(block):
                return self.root
            return self._fallback(block)
        from coreth_tpu.replay.supervisor import BackendFault
        try:
            win = self._issue_window([(block, batch)])
        except BackendFault:
            return self._fallback(block)
        self._complete_window(win, [block], 0)
        return self.root

    def retire_block(self, block_hash: bytes) -> None:
        """Consensus accepted the OLDEST processing block: its undo
        record goes, and its flat generations become ordinary sealed
        ones (exportable, prunable)."""
        if not self._held or self._held[0].block_hash != block_hash:
            raise ReplayError(
                "retire target is not the oldest processing block")
        self.flat.unpin_oldest(self._held.pop(0).generations)

    @_public_call
    def replay(self, blocks: List[Block],
               window: Optional[int] = None) -> bytes:
        """Windowed, PIPELINED replay.

        Three overlapping streams (the TPU-native analog of the
        reference's sender_cacher + prefetcher + acceptor pipeline,
        core/sender_cacher.go:49 / blockchain.go:566):

        - sender recovery runs in look-ahead segments (_SenderPipeline)
          on the native batch in the recovery worker thread — so ECDSA
          no longer serializes ahead of the first scan;
        - window k+1 is classified (host) and issued (device) BEFORE
          window k is validated, keeping the chip busy while the host
          folds tries;
        - window k's validation + trie fold (host, C++ releasing the
          GIL) then overlaps window k+1's scan.

        A validation failure rewinds exactly as before — the failed
        window's prefix is re-applied, the offending block re-runs on
        the exact host path, and the speculative window (computed on a
        now-stale base) is discarded and re-classified.  Tail resume is
        iterative (round-3 verdict: the recursive form was O(depth) in
        adversarial fallback-per-window chains)."""
        from coreth_tpu.replay.supervisor import BackendFault
        window = window or self.window
        n = len(blocks)
        acct = self.account
        pipe = _SenderPipeline(self, blocks)
        i = 0
        pending: Optional[Tuple[dict, int]] = None
        while i < n or pending is not None:
            # classify the next run (host work; overlaps in-flight scan)
            run: List[Tuple[Block, dict]] = []
            run_start = i
            hit_fallback = False
            # ONE classify phase a window, not one a block: the sender
            # phases that ensure() enters nest inside it and take their
            # own time out (self times), and a one-tx block is too
            # short to carry two more boundaries
            with acct.enter("classify"):
                while i < n and len(run) < window:
                    pipe.ensure(i)
                    t0 = time.monotonic()
                    batch = self._classify(blocks[i])
                    self.stats.t_classify += time.monotonic() - t0
                    if batch is None:
                        hit_fallback = True
                        break
                    run.append((blocks[i], batch))
                    i += 1
            win = None
            failed_run = None
            if run:
                try:
                    win = self._issue_window(run)
                except BackendFault:
                    # the supervisor struck (and possibly demoted) the
                    # device scope; the classified run replays on the
                    # exact host path after the pending window retires
                    failed_run = run
            # retire the previous window while the chip runs this one
            if pending is not None:
                p_win, p_start = pending
                pending = None
                resume = self._complete_window(p_win, blocks, p_start)
                if resume is not None:
                    if win is not None:
                        self._discard_window(win)
                    i = resume  # failed_run blocks re-enter from here
                    continue
            if failed_run is not None:
                for b, _batch in failed_run:
                    self._fallback(b)
                continue
            if win is not None:
                pending = (win, run_start)
                continue
            if hit_fallback:
                # pending retired, nothing speculative in flight: run
                # consecutive machine-eligible blocks as fused device
                # OCC windows, else the exact host path
                i += self._machine_run(blocks, i, ensure=pipe.ensure)
        return self.root

    @_public_call
    def quarantine_block(self, block: Block) -> List[str]:
        """Tolerant host application of a poison block — one that
        failed validation on EVERY backend (device, native, and the
        strict interpreter path).  The state transition still applies
        (the computed post-state is the only consistent base later
        blocks can build on) but the failed consensus checks are
        RECORDED instead of raised; the caller parks the block's
        reasons in its quarantine report.  Streaming-pipeline only —
        batch replay stays strict."""
        reasons: List[str] = []
        self._fallback(block, strict=False, reasons=reasons)
        # the tolerant fallback above just recorded this block's full
        # witness; the trigger freezes it into a replayable bundle
        forensics.note_trigger(
            forensics.TR_QUARANTINE,
            "; ".join(reasons) or "quarantined",
            number=block.number)
        self.supervisor.note_quarantined()
        self.stats.blocks_quarantined += 1
        return reasons

    @_public_call
    def rollback_block(self, block: Block) -> bytes:
        """Reorg primitive: pop the NEWEST revertible block and
        re-converge the engine to its pre-block state.

        Revertible are the processing blocks (``replay_block(hold=
        True)``, as deep as consensus holds them undecided, newest
        first) and a quarantined block at the tip.  The flat layer's
        undo log restores the flat view; the tries go back to the
        recorded ``prev_root`` (whose node closure was committed
        before the block ran); device rows, account metadata and slot
        mirrors are repaired from there for exactly the keys the block
        touched.  A strict block of replay() validated against its
        header and never comes back out."""
        if self.flat is None:
            raise ReplayError(
                "rollback requires the flat layer (CORETH_FLAT=1)")
        if self.commit_pipe.pending():
            raise ReplayError(
                "rollback with staged commits pending (flush first)")
        if self._held and self._held[-1].block_hash == block.hash():
            _, prev_root, prev_header, n_gens = self._held.pop()
        else:
            prev_root = prev_header = None
            n_gens = 1
        addrs, slots = set(), set()
        for _ in range(n_gens):
            # checkpoint markers stamped on the doomed tip carry no
            # diff; discard them so the block's generation is the target
            gen = self.flat.last_generation()
            while gen is not None and gen.kind == "checkpoint" \
                    and not gen.exported:
                self.flat.rollback_last()
                gen = self.flat.last_generation()
            if prev_root is None and (
                    gen is None or gen.kind != "quarantine"
                    or gen.number != block.number
                    or gen.block_hash != block.hash()):
                raise ReplayError(
                    "rollback target is not the newest processing or "
                    "quarantined block")
            gen = self.flat.rollback_last()
            if prev_root is None:
                prev_root, prev_header = gen.prev_root, gen.prev_header
            addrs.update(gen.accounts, gen.destructs)
            slots.update(gen.storage)
        self._restore(prev_root, prev_header, sorted(addrs),
                      sorted(slots))
        self.stats.blocks_rolled_back += 1
        return prev_root

    def _restore(self, prev_root: bytes, prev_header, addrs,
                 slot_keys) -> None:
        """Bring the tries, the device rows, the account metadata and
        the slot mirrors back to ``prev_root`` for exactly these keys
        (everything else is as it was there).  ``prev_root``'s nodes
        are in the database."""
        base = self.db.open_trie(prev_root)
        if self._native and not self._trie_check:
            # the resident C++ trie takes the old values key by key:
            # O(keys touched), not a reseed of the whole state
            for addr in addrs:
                raw = base.get(addr)
                if raw is None:
                    self.trie.delete(addr)
                else:
                    self.trie.update(addr, raw)
        elif self._native:
            from coreth_tpu.mpt.native_trie import CheckedSecureTrie
            self.trie = CheckedSecureTrie(base)
        else:
            self.trie = base
        # storage tries reopen lazily at the restored account roots
        for contract in set(addrs).union(c for c, _ in slot_keys):
            self.storage_tries.pop(contract, None)
        self._slot_overlay.clear()
        # the window runner's mirror/table saw the undone writes
        self.storage_epoch += 1
        st = self.state
        st.flush_staged()
        for addr in addrs:
            idx = st.index.get(addr)
            if idx is None:
                continue
            raw = self.trie.get(addr)
            account = StateAccount.from_rlp(raw) if raw \
                else StateAccount()
            st._staged.append((idx, account.balance, account.nonce))
            st.has_code[idx] = account.code_hash != EMPTY_CODE_HASH
            st.multicoin[idx] = account.is_multi_coin
            st.code_hashes[idx] = account.code_hash
            st.roots[idx] = account.root
        from coreth_tpu import rlp as _rlp
        for (contract, key) in slot_keys:
            s_idx = st.slot_index.get((contract, key))
            if s_idx is None or contract not in st.index:
                continue
            raw_v = self._storage_trie(contract).get(key)
            v = int.from_bytes(_rlp.decode(raw_v), "big") \
                if raw_v else 0
            if v != st.slot_host[s_idx]:
                st.slot_host[s_idx] = v
                st._staged_slots.append((s_idx, v))
        st.flush_staged()
        if self.trie.hash() != prev_root:
            raise ReplayError(
                "rollback: trie did not re-converge to the pre-block "
                "root")
        self.root = prev_root
        self.parent_header = prev_header

    def _harvest_prestate(self, statedb, complete: bool = True,
                          failed_tx_index: Optional[int] = None) -> dict:
        """The touched pre-state slice for a forensics witness: for
        every account the StateDB touched, its PRE-block tuple read
        from the engine trie (still at the pre-block root here), every
        touched storage slot's pre-value from the StateDB's
        committed-read cache (``origin_storage`` — populated by every
        SLOAD/SSTORE before ``intermediate_root`` rewrites it), and
        the contract code those accounts resolve to.  Plain-python
        dicts; hex/JSON encoding happens on the recorder's drain
        thread."""
        accounts: Dict[bytes, Optional[tuple]] = {}
        storage: Dict[Tuple[bytes, bytes], bytes] = {}
        code: Dict[bytes, bytes] = {}
        for addr, obj in list(statedb._objects.items()):
            raw = self.trie.get(addr)
            if raw is None:
                accounts[addr] = None
            else:
                a = StateAccount.from_rlp(raw)
                accounts[addr] = (a.balance, a.nonce, a.root,
                                  a.code_hash, a.is_multi_coin)
                if a.code_hash != EMPTY_CODE_HASH \
                        and a.code_hash not in code:
                    c = self.db.contract_code(a.code_hash)
                    if c:
                        code[a.code_hash] = c
            for key, val in obj.origin_storage.items():
                storage[(addr, key)] = val
        return {"accounts": accounts, "storage": storage, "code": code,
                "complete": complete,
                "failed_tx_index": failed_tx_index}

    @_in_phase("fallback")
    def _fallback(self, block: Block, strict: bool = True,
                  reasons: Optional[List[str]] = None) -> bytes:
        """Bit-exact host path for non-transfer blocks; device state for
        touched accounts is refreshed afterwards.  ``strict=False`` is
        the quarantine mode: consensus mismatches are appended to
        ``reasons`` instead of raised and the computed state still
        commits (see quarantine_block)."""
        self.commit_pipe.flush()  # staged windows precede this block
        prev_root = self.root
        prev_header = self.parent_header
        t0 = time.monotonic()
        if self._native:
            self.trie.commit_into(self.db.node_db)
            for st in self.storage_tries.values():
                st.commit_into(self.db.node_db)
        else:
            self.trie.commit()
            self.db.cache_trie(self.root, self.trie)
            # storage tries the device path touched must be readable too
            for st in self.storage_tries.values():
                self.db.cache_trie(st.commit(), st)
        statedb = StateDB(self.root, self.db, flat=self._flat_view())
        if (self.parent_header is None
                and self.config.is_apricot_phase4(block.time)):
            # the shim cannot supply parent block_gas_cost/time, which
            # AP4+ fee validation needs — refuse rather than mis-validate
            raise ReplayError(
                "ReplayEngine needs parent_header for AP4+ blocks; "
                "construct it with parent_header=...")
        parent = self.parent_header or _HeaderShim(block)
        rec = forensics.enabled()
        try:
            receipts, logs, used_gas = self.processor.process(
                block, parent, statedb)
        except BaseException as exc:  # noqa: BLE001 — re-raised unconditionally below: the recorder must witness the dying block's touched state before the evidence unwinds
            if rec:
                # the block DIED mid-execution (a flat-oracle trip, a
                # broken tx): freeze what the StateDB touched so far —
                # the witness stays replayable up to the failing tx
                forensics.record_witness(
                    block, prev_header,
                    self._harvest_prestate(
                        statedb, complete=False,
                        failed_tx_index=statedb._tx_index),
                    {"error": repr(exc),
                     "header_root": block.header.root,
                     "reasons": ["execution failed"]})
            raise
        # the pre-state slice must harvest BEFORE intermediate_root:
        # folding pending storage into the StateDB trie rewrites the
        # committed-read cache with POST values
        wit = self._harvest_prestate(statedb) if rec else None

        def _emit(rs: List[str], computed_root=None) -> None:
            forensics.record_witness(
                block, prev_header, wit,
                {"receipts": _receipt_rows(receipts),
                 "used_gas": used_gas,
                 "header_root": block.header.root,
                 "computed_root": computed_root,
                 "reasons": list(rs)})

        def _strict_fail(msg: str, computed_root=None) -> ReplayError:
            if rec:
                _emit([msg], computed_root)
                forensics.note_trigger(
                    forensics.TR_FALLBACK, f"{msg} at block "
                    f"{block.number}", number=block.number)
            return _block_error(f"{msg} (fallback)", block)

        if used_gas != block.header.gas_used:
            if strict:
                raise _strict_fail("gas used mismatch")
            reasons.append("gas used mismatch")
        if derive_sha(receipts, derive_hasher()) \
                != block.header.receipt_hash:
            if strict:
                raise _strict_fail("receipt root mismatch")
            reasons.append("receipt root mismatch")
        root = statedb.intermediate_root(True)
        if root != block.header.root:
            if strict:
                raise _strict_fail("state root mismatch", root)
            reasons.append("state root mismatch")
        if rec:
            _emit(reasons or [], root)
        statedb.commit(delete_empty_objects=True)
        # refresh engine trie + device copies of touched accounts (one
        # batched scatter via the staging buffer)
        from coreth_tpu import rlp as _rlp
        self._slot_overlay.clear()
        self.storage_epoch += 1
        if self._native:
            # apply the fallback's account changes incrementally to the
            # resident C++ trie and verify it lands on the same root
            for addr, obj in statedb._objects.items():
                if obj.deleted:
                    self.trie.delete(addr)
                else:
                    self.trie.update(addr, obj.account.rlp())
            if self.trie.hash() != root:
                forensics.note_trigger(
                    forensics.TR_ROOT,
                    "native trie diverged after host fallback",
                    number=block.number)
                raise ReplayError(
                    "native trie diverged after host fallback")
        else:
            self.trie = self.db.open_trie(root)
        self.state.flush_staged()
        for addr in list(statedb._objects):
            idx = self.state.index.get(addr)
            if idx is None:
                continue
            raw = self.trie.get(addr)
            account = StateAccount.from_rlp(raw) if raw else StateAccount()
            self.state._staged.append(
                (idx, account.balance, account.nonce))
            self.state.has_code[idx] = \
                account.code_hash != EMPTY_CODE_HASH
            self.state.multicoin[idx] = account.is_multi_coin
            self.state.code_hashes[idx] = account.code_hash
            old_root = self.state.roots[idx]
            self.state.roots[idx] = account.root
            if addr in self.storage_tries and account.root != old_root:
                # the host path rewrote this contract's storage: reload
                # every tracked slot from the committed trie
                del self.storage_tries[addr]
                st = self._storage_trie(addr)
                for s_idx in self.state.slots_by_contract.get(addr, []):
                    key = self.state.slot_keys[s_idx][1]
                    raw_v = st.get(key)
                    v = int.from_bytes(_rlp.decode(raw_v), "big") \
                        if raw_v else 0
                    if v != self.state.slot_host[s_idx]:
                        self.state.slot_host[s_idx] = v
                        self.state._staged_slots.append((s_idx, v))
        self.state.flush_staged()
        if self.flat is not None:
            # one generation per host-path block: the flat view learns
            # the block's diff (keeping cold reads current) and the
            # undo log makes a quarantined or processing block
            # revertible (rollback_block) — quarantine generations are applied
            # with hold=True so the background exporter cannot make
            # them durable before the chain accepts past them
            from coreth_tpu.state.flat import flat_diff_from_statedb
            accounts, storage, destructs = \
                flat_diff_from_statedb(statedb)
            self.flat.apply_generation(
                number=block.number, block_hash=block.hash(),
                root=root, header=block.header, prev_root=prev_root,
                prev_header=prev_header, accounts=accounts,
                storage=storage, destructs=destructs,
                kind="fallback" if strict else "quarantine",
                hold=not strict)
        self.root = root
        self.parent_header = block.header
        if self.keep_receipts:
            self.last_receipts = receipts
        self.stats.blocks_fallback += 1
        self.stats.atomic_blocks_host += bool(block.ext_data())
        self.stats.txs += len(block.transactions)
        self.stats.t_fallback += time.monotonic() - t0
        return root

    def publish_metrics(self, registry=None,
                        prefix: str = "replay") -> None:
        """Feed the replay phase split into a metrics registry (the
        engine-side analog of the blockchain.go timer metrics)."""
        from coreth_tpu.metrics import Gauge, get_or_register
        for name, value in self.stats.row().items():
            get_or_register(f"{prefix}/{name}", Gauge,
                            registry).update(value)
        if self.flat is not None:
            for name, value in self.flat.snapshot().items():
                get_or_register(f"flat/{name}", Gauge,
                                registry).update(value)

    def commit(self) -> bytes:
        """Persist the engine tries so host StateDBs can open the state."""
        self.commit_pipe.flush()
        if self._native:
            for st in self.storage_tries.values():
                st.commit_into(self.db.node_db)
            return self.trie.commit_into(self.db.node_db)
        root = self.trie.commit()
        self.db.cache_trie(root, self.trie)
        for st in self.storage_tries.values():
            srot = st.commit()
            self.db.cache_trie(srot, st)
        return root


class _HeaderShim:
    """Minimal parent-header stand-in when the true parent header was not
    supplied to the engine — correct only pre-AP4 (the AP4 blockGasCost
    validation needs the real parent's block_gas_cost/time)."""

    def __init__(self, block: Block):
        self.time = block.header.time
        self.number = block.header.number - 1
        self.block_gas_cost = None
        self.base_fee = None
        self.ext_data_gas_used = None

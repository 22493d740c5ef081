"""Device-sharded transfer windows: per-shard state tables + one
collective exchange per block.

Why the old mesh path collapsed (MULTICHIP_SCALING pre-PR-8: 4399
txs/s at 1 virtual device -> 65 at 2): ``_issue_window_mesh`` paid, PER
BLOCK, two separate shard_map dispatches whose psum_scatter reductions
ran over the FULL account/slot tables (capacity rows, not the touched
set), an all_gather of the whole nonce table, and a blocking
``bool(ok)`` device sync.  Partitioning overhead scaled with table
capacity and block count; parallelism never had a chance.

This module is the sharded twin of engine._transfer_window instead:

- the persistent balance/nonce/slot tables are **per-shard** — row
  blocks of a shard-major table (parallel/shard.py bucketing by
  keccak(address)), sharded over the ``dp`` mesh axis, so each device
  holds (on real chips: in its own HBM) only its arena;
- ONE dispatch covers a whole window: inside shard_map, each device
  gathers the window-local rows it owns, one psum replicates the small
  working set, and a ``lax.scan`` walks the blocks;
- per block, each device computes partial per-account/per-slot effect
  sums from its OWN tx shard (txs round-robin over devices) and the
  **cross-shard exchange** is ONE psum of a single packed effect
  tensor (debits | buyGas requirement | credits | send-counts and the
  slot debit|credit pair) sized by the window's touched set — the
  "annotate, reduce into the layout you need, never materialize the
  table" recipe, with the collective payload O(touched), not
  O(capacity);
- validation (nonce sequence on the tx's shard, solvency on the
  account's owning rows — both replicated after the exchange) combines
  with one scalar psum; the fetch tensor comes out replicated in
  exactly the single-device layout, so ``_complete_window`` is shared
  verbatim between backends.

Sums are integer and order-independent, so every width produces
bit-identical fetch tensors and roots (pinned by tests/test_shard_replay).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS

from coreth_tpu.ops import u256


# jitted window kernels memoized per (mesh, exchange mode): rebuilding
# per engine would retrace (and on the scaling harness recompile)
# every rep; the psum/ppermute variants coexist (at most two compiles)
_WINDOWS: Dict[Tuple, object] = {}


def sharded_transfer_window(mesh, mode: str = "psum"):
    """Build (memoized) the windowed sharded transfer kernel.

    Signature matches engine._transfer_window plus the row indirection:
      (balances, nonces, slot_vals,    # shard-major tables, PS("dp")
       acct_rows, slot_rows,           # (L,)/(SL,) device-table row of
                                       # each window-local; pad = OOB
       txds, t_idxs, s_idxs)           # txds (K, P, C), tx axis sharded
    -> (new_balances, new_nonces, new_slot_vals, fetches)

    txds carry LOCAL indices (the _prepare_window working set); the
    caller interleaves txs round-robin over the tx axis so every device
    gets P/n real lanes, not the zero-padded tail.

    ``mode`` selects the per-block effect exchange's collective: one
    psum, or the equivalent ppermute ring (parallel.collective_reduce)
    — integer sums, so fetch tensors and roots are bit-identical
    either way (the engine picks per window by touched-set density;
    CORETH_EXCHANGE overrides).
    """
    key = (tuple(mesh.devices.flat), mesh.axis_names, mode)
    fn = _WINDOWS.get(key)
    if fn is None:
        fn = _build_window(mesh, mode)
        _WINDOWS[key] = fn
    return fn


def _build_window(mesh, mode: str = "psum"):
    from coreth_tpu.parallel import collective_reduce
    from coreth_tpu.replay.engine import _gather_fetch, txd_cols
    n_dev = mesh.devices.size

    def window(balances, nonces, slot_vals, acct_rows, slot_rows,
               txds, t_idxs, s_idxs):
        d = jax.lax.axis_index("dp")
        arena = balances.shape[0]        # per-shard rows (A/n)
        sarena = slot_vals.shape[0]
        L = acct_rows.shape[0]
        SL = slot_rows.shape[0]

        # gather the window-locals each shard owns; one psum replicates
        # the (small) working set — rows are owned by exactly one shard
        # and pad rows (row == capacity) by none, so the sum IS the value
        own_a = (acct_rows >= d * arena) & (acct_rows < (d + 1) * arena)
        ia = jnp.where(own_a, acct_rows - d * arena, arena)
        lb = balances.at[ia].get(mode="fill", fill_value=0)
        ln = nonces.at[ia].get(mode="fill", fill_value=0)
        own_s = (slot_rows >= d * sarena) \
            & (slot_rows < (d + 1) * sarena)
        isl = jnp.where(own_s, slot_rows - d * sarena, sarena)
        ls = slot_vals.at[isl].get(mode="fill", fill_value=0)
        lb, ln, ls = jax.lax.psum((lb, ln, ls), "dp")

        def body(carry, inp):
            cb_bal, cb_non, cb_sv = carry
            txd, t_idx, s_idx = inp      # txd: (P/n, C) local tx shard
            (senders, recips, values, fees, required, tx_nonce,
             offsets, mask, coinbase, from_slots, to_slots,
             amounts, credits) = txd_cols(txd)
            mask_i = mask.astype(jnp.int32)
            debit = u256.add(values, fees) * mask_i[:, None]
            req = required * mask_i[:, None]
            credit = values * credits.astype(jnp.int32)[:, None]
            amt = amounts * mask_i[:, None]
            # full-working-set partials from the local tx shard
            debit_p = jax.ops.segment_sum(debit, senders,
                                          num_segments=L)
            req_p = jax.ops.segment_sum(req, senders, num_segments=L)
            credit_p = jax.ops.segment_sum(credit, recips,
                                           num_segments=L)
            counts_p = jax.ops.segment_sum(mask_i, senders,
                                           num_segments=L)
            fee_local = jnp.sum(fees * mask_i[:, None], axis=0)
            credit_p = credit_p.at[coinbase].add(fee_local)
            sdeb_p = jax.ops.segment_sum(amt, from_slots,
                                         num_segments=SL)
            scred_p = jax.ops.segment_sum(amt, to_slots,
                                          num_segments=SL)
            # nonce sequence validates on the tx's shard against the
            # replicated pre-block nonce view
            expected = cb_non[senders] + offsets
            nonce_ok = jnp.all(
                jnp.where(mask, tx_nonce == expected, True))
            # THE cross-shard exchange: one reduce of the packed effect
            # tensors (payload O(touched set), not O(table)) — a psum,
            # or the bit-identical ppermute ring when the engine judged
            # the touched set sparse
            pack_a = jnp.concatenate(
                [debit_p, req_p, credit_p, counts_p[:, None]], axis=1)
            pack_s = jnp.concatenate([sdeb_p, scred_p], axis=1)
            pack_a, pack_s, nonce_n = collective_reduce(
                (pack_a, pack_s, nonce_ok.astype(jnp.int32)), "dp",
                n_dev, mode, op="add")
            debit_t = u256.normalize(pack_a[:, 0:16])
            req_t = u256.normalize(pack_a[:, 16:32])
            credit_t = u256.normalize(pack_a[:, 32:48])
            counts = pack_a[:, 48]
            sdeb_t = u256.normalize(pack_s[:, 0:16])
            scred_t = u256.normalize(pack_s[:, 16:32])
            # validation on the (replicated) owning rows — identical on
            # every device, so ok needs no further collective.  The
            # CONSERVATIVE pre-block solvency rule, as
            # parallel.sharded_transfer_step says and why: a block whose
            # sender was funded earlier in the same block comes back
            # ok=False here and goes to the host path
            solvent = u256.gte(cb_bal, req_t)
            ok = (nonce_n == n_dev) \
                & jnp.all(solvent | (counts == 0)) \
                & jnp.all(u256.gte(cb_sv, sdeb_t))
            nb = u256.sub(u256.add(cb_bal, credit_t), debit_t)
            nn = cb_non + counts
            nsv = u256.sub(u256.add(cb_sv, scred_t), sdeb_t)
            return (nb, nn, nsv), _gather_fetch(nb, nn, nsv, ok,
                                                t_idx, s_idx)

        (lb, ln, ls), fetches = jax.lax.scan(
            body, (lb, ln, ls), (txds, t_idxs, s_idxs))
        # scatter each shard's locals back into its arena (drop: pads
        # and foreign rows keep indexing `arena` == OOB)
        nb = balances.at[jnp.where(own_a, ia, arena)].set(
            lb, mode="drop")
        nn = nonces.at[jnp.where(own_a, ia, arena)].set(
            ln, mode="drop")
        nsv = slot_vals.at[jnp.where(own_s, isl, sarena)].set(
            ls, mode="drop")
        return nb, nn, nsv, fetches

    tab2, tab1 = PS("dp", None), PS("dp")
    sharded = shard_map(
        window, mesh=mesh,
        in_specs=(tab2, tab1, tab2, PS(), PS(),
                  PS(None, "dp", None), PS(), PS()),
        out_specs=(tab2, tab1, tab2, PS()),
        # replicated outputs are identical by construction (integer
        # psums); vma tracking would reject the mixed replicated/sharded
        # carries without adding safety
        check_vma=False)
    return jax.jit(sharded)


def interleave_txs(P: int, n_dev: int):
    """Permutation putting txs d, d+n, d+2n, ... into device d's block
    of the sharded tx axis: real lanes sit in the padded prefix, so a
    contiguous split would starve the high shards."""
    import numpy as np
    return np.arange(P).reshape(-1, n_dev).T.reshape(-1)

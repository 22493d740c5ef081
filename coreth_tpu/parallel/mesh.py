"""Device-mesh sharded replay step.

The single-chip batched transfer step (replay/engine.py) generalizes to a
mesh by sharding BOTH the tx batch and the account-state rows over one
``dp`` axis:

- each device computes full-width per-account totals from its local tx
  shard (segment-sum into the global account range);
- one ``psum_scatter`` over ``dp`` reduces the partial totals AND leaves
  them sharded by account row — the collective rides ICI, and its output
  layout matches the local balance shard exactly (no all-gather);
- validation flags combine with a scalar ``psum``.

This is the sharding recipe the scaling-book prescribes: annotate,
reduce-scatter into the layout you need next, never materialize the full
array.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from coreth_tpu.ops import u256


def make_mesh(devices=None, axis: str = "dp") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    import numpy as np
    return Mesh(np.array(devices), (axis,))


def collective_reduce(x, axis: str, n_dev: int, mode: str = "psum",
                      op: str = "add"):
    """All-reduce `x` over the named mesh axis, as either ONE fused
    collective (``mode="psum"``: lax.psum / lax.pmax) or a RING of
    ``n_dev - 1`` point-to-point ``ppermute`` steps each device
    accumulates locally (``mode="ppermute"``).

    The ring moves the same payload as the all-reduce but as
    neighbor-to-neighbor sends — on real ICI the latency win for SMALL
    tensors (the sparse cross-shard exchange sets this repo ships) over
    the full all-reduce tree.  Every value reduced here is an int32
    add or max: associative + commutative, so both modes produce
    BIT-IDENTICAL results on every device (the exchange-equivalence
    tests pin this; do not reduce floats through the ring)."""
    if op not in ("add", "max"):
        raise ValueError(f"collective_reduce: unknown op {op!r}")
    if mode == "psum" or n_dev <= 1:
        if op == "add":
            return jax.lax.psum(x, axis)
        return jax.lax.pmax(x, axis)
    # ring all-reduce: rotate the payload one hop per step; after
    # n_dev - 1 steps every device has accumulated every shard's
    # contribution (in rotation order — exact for integer add/max)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    acc = x
    for _ in range(n_dev - 1):
        x = jax.tree_util.tree_map(
            lambda a: jax.lax.ppermute(a, axis, perm), x)
        acc = jax.tree_util.tree_map(
            (lambda a, b: a + b) if op == "add" else jnp.maximum,
            acc, x)
    return acc


def sharded_transfer_step(mesh: Mesh, num_accounts: int):
    """Build the mesh-sharded transfer step.

    Shapes (global): balances [A, 16], nonces [A], tx arrays [B, ...];
    A and B must divide by the mesh size.  Returns a jitted function
    (balances, nonces, sender_idx, recip_idx, value16, fee16, required16,
    tx_nonce, nonce_offset, mask) -> (new_balances, new_nonces, ok).

    Nonce-sequence validation is computed against gathered nonce rows for
    the local tx shard (an all_gather of one i32 row — cheap vs the limb
    traffic saved by psum_scatter on the totals).

    Solvency is the CONSERVATIVE pre-block rule — every sender's
    pre-block balance against the sum of all it will need in the block,
    credits ignored — not the single device's in-order rule
    (replay/engine.py _order_solvent): lanes are dealt round-robin over
    the devices and accounts are sharded, so the credits a sender was
    paid earlier in the block lie on other shards in another order.
    The rule is still exact end to end: ok=True implies the sequential
    outcome, ok=False sends the block to the host path.
    """
    n_dev = mesh.devices.size
    assert num_accounts % n_dev == 0

    def step(balances, nonces, sender_idx, recip_idx, value16, fee16,
             required16, tx_nonce, nonce_offset, mask, coinbase_idx):
        # local shards: balances [A/d, 16], tx arrays [B/d, ...]
        mask_i = mask.astype(jnp.int32)
        debit = u256.add(value16, fee16) * mask_i[:, None]
        required = required16 * mask_i[:, None]
        credit = value16 * mask_i[:, None]
        # full-width partial totals from the local tx shard
        debit_part = jax.ops.segment_sum(debit, sender_idx,
                                         num_segments=num_accounts)
        req_part = jax.ops.segment_sum(required, sender_idx,
                                       num_segments=num_accounts)
        credit_part = jax.ops.segment_sum(credit, recip_idx,
                                          num_segments=num_accounts)
        # tx fees accrue to the coinbase (state_transition.go:443)
        fee_local = jnp.sum(fee16 * mask_i[:, None], axis=0)
        credit_part = credit_part.at[coinbase_idx].add(fee_local)
        counts_part = jax.ops.segment_sum(mask_i, sender_idx,
                                          num_segments=num_accounts)
        # reduce across devices, scattering rows back onto the account
        # sharding (ICI collective; output [A/d, 16])
        debit_tot = u256.normalize(
            jax.lax.psum_scatter(debit_part, "dp", scatter_dimension=0,
                                 tiled=True))
        req_tot = u256.normalize(
            jax.lax.psum_scatter(req_part, "dp", scatter_dimension=0,
                                 tiled=True))
        credit_tot = u256.normalize(
            jax.lax.psum_scatter(credit_part, "dp", scatter_dimension=0,
                                 tiled=True))
        counts = jax.lax.psum_scatter(counts_part, "dp",
                                      scatter_dimension=0, tiled=True)
        # nonce check needs the global nonce row for local txs
        all_nonces = jax.lax.all_gather(nonces, "dp", tiled=True)
        expected = all_nonces[sender_idx] + nonce_offset
        nonce_ok = jnp.all(jnp.where(mask, tx_nonce == expected, True))
        solvent = u256.gte(balances, req_tot)
        ok_local = nonce_ok & jnp.all(solvent | (counts == 0))
        ok = jax.lax.psum(ok_local.astype(jnp.int32), "dp") == n_dev
        new_balances = u256.sub(u256.add(balances, credit_tot), debit_tot)
        new_nonces = nonces + counts
        return new_balances, new_nonces, ok

    spec_acc2 = PS("dp", None)
    spec_acc1 = PS("dp")
    spec_tx2 = PS("dp", None)
    spec_tx1 = PS("dp")
    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(spec_acc2, spec_acc1, spec_tx1, spec_tx1, spec_tx2,
                  spec_tx2, spec_tx2, spec_tx1, spec_tx1, spec_tx1, PS()),
        out_specs=(spec_acc2, spec_acc1, PS()),
        # psum_scatter/all_gather produce the vma the specs declare;
        # tracking adds nothing on these reduction-shaped bodies
        check_vma=False)
    return jax.jit(sharded)


def sharded_slot_step(mesh: Mesh, num_slots: int):
    """Mesh-sharded ERC-20 slot step: slot values sharded over dp, tx
    shards compute full-width partial debit/credit segment sums,
    psum_scatter reduces them back onto the slot sharding (the same
    annotate -> reduce-scatter recipe as the account step)."""
    n_dev = mesh.devices.size
    assert num_slots % n_dev == 0

    def step(slot_vals, from_slot, to_slot, amount16, mask):
        mask_i = mask.astype(jnp.int32)
        amt = amount16 * mask_i[:, None]
        debit_part = jax.ops.segment_sum(amt, from_slot,
                                         num_segments=num_slots)
        credit_part = jax.ops.segment_sum(amt, to_slot,
                                          num_segments=num_slots)
        debit_tot = u256.normalize(
            jax.lax.psum_scatter(debit_part, "dp", scatter_dimension=0,
                                 tiled=True))
        credit_tot = u256.normalize(
            jax.lax.psum_scatter(credit_part, "dp", scatter_dimension=0,
                                 tiled=True))
        solvent = u256.gte(slot_vals, debit_tot)
        ok = jax.lax.psum(jnp.all(solvent).astype(jnp.int32),
                          "dp") == n_dev
        new_vals = u256.sub(u256.add(slot_vals, credit_tot), debit_tot)
        return new_vals, ok

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(PS("dp", None), PS("dp"), PS("dp"), PS("dp", None),
                  PS("dp")),
        out_specs=(PS("dp", None), PS()),
        check_vma=False)
    return jax.jit(sharded)


def sharded_recover(mesh: Mesh):
    """Mesh-sharded batched ECDSA recovery: the signature batch shards
    over dp and every device runs the Shamir-ladder kernel on its
    shard (the sender_cacher fan-out, here across chips instead of
    goroutines — embarrassingly parallel, no collectives)."""
    from coreth_tpu.ops.secp import recover_kernel

    def step(x_bytes, parity, u1w, u2w):
        # pin dtypes: shard_map re-traces per shard and weak-typed
        # inputs would break the ladder's int32 carry scan
        return recover_kernel.__wrapped__(
            x_bytes.astype(jnp.uint8), parity.astype(jnp.int32),
            u1w.astype(jnp.int32), u2w.astype(jnp.int32))

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(PS("dp", None), PS("dp"), PS("dp", None),
                  PS("dp", None)),
        out_specs=PS("dp", None),
        # the ladder's internal scans build unvarying carries; this is
        # a per-shard elementwise kernel, so vma tracking adds nothing
        check_vma=False)
    return jax.jit(sharded)

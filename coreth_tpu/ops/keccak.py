"""Batched keccak-256 on device — uint32 lane pairs, jnp/XLA.

The reference's hot path leans on asm-optimized keccak everywhere: trie node
hashing (reference trie/hasher.go:69,195), tx/receipt roots (core/types/
hashing.go:97), secure-trie keys, the SHA3 opcode (core/vm/instructions.go),
and CREATE2.  On TPU there is no 64-bit integer datapath worth using, so
lanes are represented as (lo, hi) uint32 pairs and the permutation is
expressed with 32-bit XOR/AND/shift — all VPU-friendly element-wise ops that
vectorize across the batch dimension.

Layout: state arrays have shape (..., 25, 2) uint32, last axis = (lo, hi).
All rotation amounts are static Python ints (the rho schedule), so every
shift lowers to a constant-shift VPU op; the 24 rounds are unrolled at trace
time with round constants baked in as literals.

Entry points:
  - keccak_f1600(state): the permutation, batched over leading dims.
  - keccak256_fixed(words, nbytes): single-block messages (<=135 bytes) of a
    length fixed at trace time — the EVM mapping-slot path (64 bytes) and
    most trie leaf/short nodes.
  - keccak256_blocks(blocks, nblocks): variable-block messages, padded on
    host; masked absorb keeps finished items' states frozen.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# --- static schedule (derived, not transcribed) ----------------------------

_MASK64 = (1 << 64) - 1


def _derive_schedule():
    # Round constants via the LFSR, as in the host reference implementation.
    rc = []
    r = 1
    for _ in range(24):
        v = 0
        for j in range(7):
            r = ((r << 1) ^ ((r >> 7) * 0x71)) % 256
            if r & 2:
                v ^= 1 << ((1 << j) - 1)
        rc.append(v)
    # rho rotation per lane index (x + 5*y) and the pi permutation:
    # dest_index[src] after the rho+pi step.
    rho = [0] * 25
    pi_dest = list(range(25))
    x, y = 1, 0
    for t in range(24):
        # rotation amount belongs to the SOURCE lane of walk step t
        rho[x + 5 * y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    # pi: A'[y, (2x+3y)%5] = A[x, y]  (in (x, y) coords; index = x + 5*y)
    for xx in range(5):
        for yy in range(5):
            pi_dest[xx + 5 * yy] = yy + 5 * ((2 * xx + 3 * yy) % 5)
    return rc, rho, pi_dest


_RC, _RHO, _PI_DEST = _derive_schedule()
# src lane feeding each destination after rho+pi
_PI_SRC = [0] * 25
for _s, _d in enumerate(_PI_DEST):
    _PI_SRC[_d] = _s


# Static per-lane rho/pi vectors (numpy, baked into the graph as constants)
_RHO_ARR = np.array(_RHO, dtype=np.int64)
_PI_SRC_ARR = np.array(_PI_SRC, dtype=np.int32)
_MOVED_RHO = _RHO_ARR[_PI_SRC_ARR]          # rotation of each dest lane
_RC_LO = np.array([rc & 0xFFFFFFFF for rc in _RC], dtype=np.uint32)
_RC_HI = np.array([rc >> 32 for rc in _RC], dtype=np.uint32)


def _rotl_lanes(lo, hi, r: np.ndarray):
    """Rotate (lo, hi) uint32 lane-pair arrays left by static per-lane
    amounts r (numpy vector broadcast over the trailing lane axis).

    A 64-bit rotate by r over (lo, hi) = a conditional word swap
    (r >= 32) followed by a sub-word rotate by r % 32; all masks and
    shift counts are trace-time constants, so this lowers to a handful
    of elementwise VPU ops regardless of lane count."""
    r = r % 64
    swap = jnp.asarray(r >= 32)
    rr = (r % 32).astype(np.uint32)
    sh = jnp.asarray(rr)
    inv = jnp.asarray(np.where(rr == 0, 1, 32 - rr).astype(np.uint32))
    zero = jnp.asarray(rr == 0)
    l1 = jnp.where(swap, hi, lo)
    h1 = jnp.where(swap, lo, hi)
    nlo = jnp.where(zero, l1, (l1 << sh) | (h1 >> inv))
    nhi = jnp.where(zero, h1, (h1 << sh) | (l1 >> inv))
    return nlo, nhi


def _round(lo, hi, rc_lo, rc_hi):
    """One keccak-f[1600] round over (..., 25) uint32 lane-pair arrays."""
    # theta: column parity C[x] = xor over y of lane[x + 5y]
    vlo = lo.reshape(lo.shape[:-1] + (5, 5))    # [..., y, x]
    vhi = hi.reshape(hi.shape[:-1] + (5, 5))
    c_lo = vlo[..., 0, :] ^ vlo[..., 1, :] ^ vlo[..., 2, :] \
        ^ vlo[..., 3, :] ^ vlo[..., 4, :]
    c_hi = vhi[..., 0, :] ^ vhi[..., 1, :] ^ vhi[..., 2, :] \
        ^ vhi[..., 3, :] ^ vhi[..., 4, :]
    r1_lo, r1_hi = _rotl_lanes(jnp.roll(c_lo, -1, axis=-1),
                               jnp.roll(c_hi, -1, axis=-1),
                               np.array([1] * 5))
    d_lo = jnp.roll(c_lo, 1, axis=-1) ^ r1_lo
    d_hi = jnp.roll(c_hi, 1, axis=-1) ^ r1_hi
    lo = (vlo ^ d_lo[..., None, :]).reshape(lo.shape)
    hi = (vhi ^ d_hi[..., None, :]).reshape(hi.shape)
    # rho + pi: moved[d] = rotl(lane[pi_src[d]], rho[pi_src[d]])
    lo, hi = _rotl_lanes(lo[..., _PI_SRC_ARR], hi[..., _PI_SRC_ARR],
                         _MOVED_RHO)
    # chi: a ^ (~a[x+1] & a[x+2]) along x
    vlo = lo.reshape(lo.shape[:-1] + (5, 5))
    vhi = hi.reshape(hi.shape[:-1] + (5, 5))
    a1_lo = jnp.roll(vlo, -1, axis=-1)
    a1_hi = jnp.roll(vhi, -1, axis=-1)
    a2_lo = jnp.roll(vlo, -2, axis=-1)
    a2_hi = jnp.roll(vhi, -2, axis=-1)
    lo = (vlo ^ (~a1_lo & a2_lo)).reshape(lo.shape)
    hi = (vhi ^ (~a1_hi & a2_hi)).reshape(hi.shape)
    # iota
    lo = lo.at[..., 0].set(lo[..., 0] ^ rc_lo)
    hi = hi.at[..., 0].set(hi[..., 0] ^ rc_hi)
    return lo, hi


def keccak_f1600(state):
    """Apply the keccak-f[1600] permutation.

    state: uint32 array (..., 25, 2); returns the same shape.  The 24
    rounds run under lax.fori_loop with the round constants indexed from
    a baked array — the graph is one round body, so CPU compile stays in
    seconds (unrolling 24 rounds x 25 scalar lanes took ~10 minutes
    to compile)."""
    lo = state[..., 0]
    hi = state[..., 1]
    rc_lo = jnp.asarray(_RC_LO)
    rc_hi = jnp.asarray(_RC_HI)

    def body(rnd, carry):
        lo, hi = carry
        return _round(lo, hi, rc_lo[rnd], rc_hi[rnd])

    lo, hi = jax.lax.fori_loop(0, 24, body, (lo, hi))
    return jnp.stack([lo, hi], axis=-1)


_RATE_WORDS = 34  # 136 bytes / 4


def _absorb_words(state, words):
    """XOR 34 uint32 words (one rate block) into lanes 0..16 and permute."""
    # words: (..., 34) uint32 -> pairs (..., 17, 2)
    pairs = words.reshape(words.shape[:-1] + (17, 2))
    pad = jnp.zeros(words.shape[:-1] + (8, 2), dtype=jnp.uint32)
    full = jnp.concatenate([pairs, pad], axis=-2)
    return keccak_f1600(state ^ full)


def keccak256_fixed(words, nbytes: int):
    """keccak-256 of single-block messages with trace-time-static length.

    words: uint32 array (..., 34) — the message bytes as little-endian
    uint32 words, zero-padded.  nbytes must be <= 135.  Returns (..., 8)
    uint32 digest words (little-endian).
    """
    assert nbytes <= 135
    # keccak pad10*1: suffix 0x01 at nbytes, 0x80 at byte 135.
    w = words
    suffix = np.zeros(34, dtype=np.uint32)
    suffix[nbytes // 4] ^= np.uint32(0x01) << (8 * (nbytes % 4))
    suffix[33] ^= np.uint32(0x80) << 24
    w = w ^ jnp.asarray(suffix)
    state = jnp.zeros(w.shape[:-1] + (25, 2), dtype=jnp.uint32)
    state = _absorb_words(state, w)
    return state[..., :4, :].reshape(state.shape[:-2] + (8,))


@jax.jit
def keccak256_blocks(blocks, nblocks):
    """keccak-256 of host-padded multi-block messages.

    blocks: uint32 (batch, max_blocks, 34) — keccak padding already applied
    on host (suffix 0x01 / 0x80 in the final real block).
    nblocks: int32 (batch,) — real block count per item (>= 1).
    Returns (batch, 8) uint32 digest words.

    Jitted: the 24 unrolled rounds compile to one executable; callers
    should bucket (batch, max_blocks) shapes (pack_blocks pads) so the
    compile cache stays small.
    """
    blocks = jnp.asarray(blocks, dtype=jnp.uint32)
    nblocks = jnp.asarray(nblocks, dtype=jnp.int32)
    batch = blocks.shape[0]
    max_blocks = blocks.shape[1]
    state = jnp.zeros((batch, 25, 2), dtype=jnp.uint32)

    def body(i, st):
        absorbed = _absorb_words(st, blocks[:, i, :])
        keep = (i < nblocks)[:, None, None]
        return jnp.where(keep, absorbed, st)

    state = jax.lax.fori_loop(0, max_blocks, body, state)
    return state[:, :4, :].reshape(batch, 8)


# --- host-side packing helpers ---------------------------------------------


def pack_fixed(msgs: list[bytes], nbytes: int) -> np.ndarray:
    """Pack equal-length messages for keccak256_fixed."""
    buf = np.zeros((len(msgs), 136), dtype=np.uint8)
    for i, m in enumerate(msgs):
        assert len(m) == nbytes
        buf[i, :nbytes] = np.frombuffer(m, dtype=np.uint8)
    return buf.view(np.uint32).reshape(len(msgs), 34)


def pack_blocks(msgs: list[bytes],
                pad_batch: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Pack variable-length messages (keccak padding applied) for
    keccak256_blocks.  Batch and block-count dimensions are padded to
    powers of two so the jitted kernel compiles per bucket, not per
    call."""
    nblocks = np.array([len(m) // 136 + 1 for m in msgs], dtype=np.int32)
    max_blocks = int(nblocks.max()) if len(msgs) else 1
    max_blocks = 1 << (max_blocks - 1).bit_length()
    n = len(msgs)
    batch = 1 << (n - 1).bit_length() if (pad_batch and n) else n
    buf = np.zeros((batch, max_blocks * 136), dtype=np.uint8)
    for i, m in enumerate(msgs):
        buf[i, :len(m)] = np.frombuffer(m, dtype=np.uint8)
        end = nblocks[i] * 136
        buf[i, len(m)] ^= 0x01
        buf[i, end - 1] ^= 0x80
    if batch > n:
        nblocks = np.concatenate(
            [nblocks, np.ones(batch - n, dtype=np.int32)])
        buf[n:, 0] ^= 0x01   # empty-message keccak padding
        buf[n:, 135] ^= 0x80
    return (buf.view(np.uint32).reshape(batch, max_blocks, 34), nblocks)


def digest_words_to_bytes(words: np.ndarray) -> list[bytes]:
    """Convert (batch, 8) uint32 LE digest words to 32-byte digests."""
    w = np.asarray(words, dtype=np.uint32)
    return [w[i].tobytes() for i in range(w.shape[0])]

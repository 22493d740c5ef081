"""Batched secp256k1 public-key recovery on device — the TPU analog of
the reference's parallel sender recovery (core/sender_cacher.go, which
spreads cgo libsecp256k1 ecrecover across GOMAXPROCS goroutines).

Design: the expensive part of ECDSA recovery is the double-scalar
multiplication u1*G + u2*R (~thousands of 256-bit field multiplies).
The host (crypto/secp_device.py) does the cheap per-signature scalar
math with CPython bignums; this module runs ONE shared Shamir ladder —
256 iterations of point-double + conditional mixed-add — vmapped over
the whole signature batch with branchless (where-selected) complete
addition.  All field arithmetic is exact 20x13-bit-limb int32 math:
13-bit limbs keep every partial-product column under 2^31, so the
entire kernel is int32 VPU work with no 64-bit emulation.

Field-element representation
  (..., 20) int32, limbs little-endian base 2^13, all limbs in
  [0, 2^13), value < 2^257 (i.e. possibly p..4p above canonical; the
  is-zero tests compare against {0, p, 2p} and the host canonicalizes
  final outputs with one `% p`).

Reduction: p = 2^256 - 2^32 - 977, so
  2^260 = 2^36 + 15632  (mod p)      [folds for the 40-limb product]
  2^256 = 2^32 + 977    (mod p)      [final fold to < 2^257]
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
GX = 0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798
GY = 0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8

LIMBS = 20
LB = 13
LM = (1 << LB) - 1


def to_limbs_np(values) -> np.ndarray:
    """Python ints -> (n, 20) int32 13-bit-limb array (numpy-vectorized)."""
    blob = b"".join(int(v).to_bytes(33, "little") for v in values)
    raw = np.frombuffer(blob, dtype=np.uint8).reshape(len(values), 33)
    bits = np.unpackbits(raw, axis=1, bitorder="little")[:, :260]
    weights = (1 << np.arange(LB, dtype=np.int32))
    return (bits.reshape(len(values), LIMBS, LB).astype(np.int32)
            * weights).sum(axis=2, dtype=np.int32)


def from_limbs(arr) -> list:
    """(n, 20) limb array -> Python ints, numpy-vectorized: spread the
    13-bit limbs into bits, pack to little-endian bytes, convert.
    Requires limbs in [0, 2^13) (exact representation)."""
    a = np.asarray(arr)
    if a.size == 0:
        return []
    assert ((a >= 0) & (a < (1 << LB))).all()
    bits = ((a[:, :, None] >> np.arange(LB, dtype=np.int32)) & 1)
    flat = bits.reshape(a.shape[0], LIMBS * LB).astype(np.uint8)
    pad = np.zeros((a.shape[0], 264 - LIMBS * LB), dtype=np.uint8)
    packed = np.packbits(np.concatenate([flat, pad], axis=1),
                         axis=1, bitorder="little")
    return [int.from_bytes(packed[i].tobytes(), "little")
            for i in range(a.shape[0])]


def _const_limbs(v: int) -> np.ndarray:
    return to_limbs_np([v])[0]

_P_L = _const_limbs(P)
_2P_L = _const_limbs(2 * P)
_GX_L = _const_limbs(GX)
_GY_L = _const_limbs(GY)
_ONE_L = _const_limbs(1)


def _carry(cols, out_len: int):
    """Exact base-2^13 carry/borrow propagation via lax.scan over limbs.

    cols: (..., L) int32, possibly >13-bit and/or negative entries; the
    represented value must be non-negative and < 2^(13*out_len).
    Returns (..., out_len) limbs all in [0, 2^13)."""
    L = cols.shape[-1]
    if L < out_len:
        cols = jnp.concatenate(
            [cols, jnp.zeros(cols.shape[:-1] + (out_len - L,),
                             dtype=jnp.int32)], axis=-1)
    colsT = jnp.moveaxis(cols[..., :out_len], -1, 0)

    def step(carry, col):
        t = col + carry
        return t >> LB, t & LM

    # unroll matters: an un-unrolled scan lowers to a nested while-loop
    # inside the ladder's fori_loop, costing ~1us per step on TPU
    # (thousands of inner iterations per ladder round -> ~1.7s/batch);
    # unroll=8 keeps the graph compact while fusing the chain into a
    # handful of elementwise ops (measured: same steady-state as full
    # unroll, half the compile time).
    _, limbsT = jax.lax.scan(step, jnp.zeros(cols.shape[:-1],
                                             dtype=jnp.int32), colsT,
                             unroll=8)
    return jnp.moveaxis(limbsT, 0, -1)


def _fold260(w, hi_len: int, out_len: int):
    """w = lo(20) ++ hi(hi_len) limbs; replace hi*2^260 with
    hi*(2^36 + 15632), carry to out_len limbs."""
    lo, hi = w[..., :LIMBS], w[..., LIMBS:]
    width = max(LIMBS, hi_len + 3)
    acc = jnp.zeros(w.shape[:-1] + (width,), dtype=jnp.int32)
    acc = acc.at[..., :LIMBS].add(lo)
    acc = acc.at[..., :hi_len].add(hi * 15632)
    acc = acc.at[..., 2:hi_len + 2].add(hi * 1024)   # 2^36 = 2^(13*2+10)
    return _carry(acc, out_len)


def _fold256(w):
    """20-limb value < 2^260 -> congruent value < 2^257."""
    hi4 = w[..., 19] >> 9                            # bits 256..259
    acc = w.at[..., 19].set(w[..., 19] & 511)
    acc = acc.at[..., 0].add(hi4 * 977)
    acc = acc.at[..., 2].add(hi4 * 64)               # 2^32 = 2^(13*2+6)
    return _carry(acc, LIMBS)


def fe_mul(a, b):
    """(a * b) mod-ish p: output value < 2^257, congruent to a*b."""
    cols = jnp.zeros(a.shape[:-1] + (2 * LIMBS - 1,), dtype=jnp.int32)
    for i in range(LIMBS):
        cols = cols.at[..., i:i + LIMBS].add(a[..., i:i + 1] * b)
    w = _carry(cols, 41)                 # value < 2^514
    w = _fold260(w, 21, 25)              # < 2^311
    w = _fold260(w, 5, 21)               # < 2^261
    w = _fold260(w, 1, LIMBS)            # < 2^260
    return _fold256(w)                   # < 2^257


def fe_sq(a):
    return fe_mul(a, a)


def fe_add(a, b):
    w = _carry(a + b, 21)                # < 2^258
    return _fold256(_fold260(w, 1, LIMBS))


_4P_L = _const_limbs(4 * P)


def fe_sub(a, b):
    """(a - b) mod-ish p: a, b values < 2^257 -> output < 2^257.

    Adds 4p so the total stays positive; the borrow chain rides the
    same exact carry scan (arithmetic shifts propagate negatives)."""
    cols = a + jnp.asarray(_4P_L) - b    # value in (0, 2^257 + 4p) < 2^259
    return _fold256(_carry(cols, LIMBS))


def fe_is_zero(a):
    """a == 0 (mod p) for exact-limb values < 2^257: compare against
    the canonical representations of 0, p and 2p."""
    z = jnp.all(a == 0, axis=-1)
    z |= jnp.all(a == jnp.asarray(_P_L), axis=-1)
    z |= jnp.all(a == jnp.asarray(_2P_L), axis=-1)
    return z


def _limb_gte(a, b_const: np.ndarray):
    """Lexicographic a >= b over exact 13-bit limbs (b a constant row)."""
    decided = jnp.zeros(a.shape[:-1], dtype=bool)
    result = jnp.ones(a.shape[:-1], dtype=bool)
    for i in range(LIMBS - 1, -1, -1):
        b_i = int(b_const[i])
        gt = a[..., i] > b_i
        lt = a[..., i] < b_i
        result = jnp.where(~decided & gt, True, result)
        result = jnp.where(~decided & lt, False, result)
        decided = decided | gt | lt
    return result


def _cond_sub(a, b_const: np.ndarray):
    """a - b if a >= b else a (exact limbs, unrolled borrow chain)."""
    take = _limb_gte(a, b_const)
    diff = a - jnp.asarray(b_const)
    limbs = []
    borrow = jnp.zeros(a.shape[:-1], dtype=jnp.int32)
    for i in range(LIMBS):
        t = diff[..., i] - borrow
        borrow = (t < 0).astype(jnp.int32)
        limbs.append(t + (borrow << LB))
    sub = jnp.stack(limbs, axis=-1)
    return jnp.where(take[..., None], sub, a)


def fe_canon(a):
    """Reduce an exact-limb value < 2^257 to canonical [0, p)."""
    return _cond_sub(_cond_sub(a, _2P_L), _P_L)


# Static MSB-first exponent bit schedules: (p+1)/4 (the p = 3 mod 4
# square-root shortcut) and p-2 (Fermat inversion).
_SQRT_EXP_BITS = np.array(
    [(((P + 1) // 4) >> (255 - i)) & 1 for i in range(256)], dtype=np.int32)
_INV_EXP_BITS = np.array(
    [((P - 2) >> (255 - i)) & 1 for i in range(256)], dtype=np.int32)


def _fe_pow_static(base, exp_bits: np.ndarray):
    """base^e for a trace-time-constant exponent bit schedule."""
    bits = jnp.asarray(exp_bits)
    one = jnp.broadcast_to(jnp.asarray(_ONE_L), base.shape)

    def body(i, acc):
        acc = fe_mul(acc, acc)
        mul = fe_mul(acc, base)
        return jnp.where(bits[i] == 1, mul, acc)

    return jax.lax.fori_loop(0, 256, body, one)


def fe_sqrt(ysq):
    """(sqrt, is_residue) — canonical root of a quadratic residue."""
    y = fe_canon(_fe_pow_static(ysq, _SQRT_EXP_BITS))
    chk = fe_canon(fe_mul(y, y))
    ok = jnp.all(chk == fe_canon(ysq), axis=-1)
    return y, ok


def fe_inv(a):
    """1/a mod p (0 -> 0), lazy representation."""
    return _fe_pow_static(a, _INV_EXP_BITS)


# --------------------------------------------------------- byte packing
# Device-side (un)packing between 33-byte little-endian field elements
# and 13-bit limbs: a field element moves as 33 bytes instead of 80
# bytes of int32 limbs, ~2.5x less host<->device traffic.

def unpack_fe_bytes(b):
    """(B, 33) uint8 -> (B, 20) int32 limbs (values must be < 2^260)."""
    v = b.astype(jnp.int32)
    limbs = []
    for j in range(LIMBS):
        bit0 = LB * j
        byte0, off = divmod(bit0, 8)
        acc = v[..., byte0] >> off
        acc = acc | (v[..., byte0 + 1] << (8 - off))
        if byte0 + 2 < 33:
            acc = acc | (v[..., byte0 + 2] << (16 - off))
        limbs.append(acc & LM)
    return jnp.stack(limbs, axis=-1)


def pack_fe_bytes(limbs):
    """(B, 20) exact int32 limbs -> (B, 33) uint8 little-endian."""
    out = []
    for k in range(33):
        bit0 = 8 * k
        j, off = divmod(bit0, LB)
        acc = limbs[..., j] >> off
        if j + 1 < LIMBS and LB - off < 8:
            acc = acc | (limbs[..., j + 1] << (LB - off))
        out.append(acc & 255)
    return jnp.stack(out, axis=-1).astype(jnp.uint8)


def fe_bytes_np(values) -> np.ndarray:
    """Python ints -> (n, 33) uint8 little-endian (host side)."""
    blob = b"".join(int(v).to_bytes(33, "little") for v in values)
    return np.frombuffer(blob, dtype=np.uint8).reshape(len(values), 33)


# ---------------------------------------------------------------- points

def pt_double(X, Y, Z):
    """Jacobian doubling (a=0 curve).  Infinity (Z=0) stays Z=0."""
    A = fe_sq(X)
    Bb = fe_sq(Y)
    C = fe_sq(Bb)
    t = fe_sub(fe_sub(fe_sq(fe_add(X, Bb)), A), C)
    D = fe_add(t, t)
    E = fe_add(fe_add(A, A), A)
    F = fe_sq(E)
    nX = fe_sub(F, fe_add(D, D))
    C2 = fe_add(C, C)
    C8 = fe_add(fe_add(C2, C2), fe_add(C2, C2))
    nY = fe_sub(fe_mul(E, fe_sub(D, nX)), C8)
    nZ = fe_mul(fe_add(Y, Y), Z)
    return nX, nY, nZ


def _mixed_add(X, Y, Z, inf, ax, ay, a_inf, do):
    """Complete branchless Jacobian += affine.

    Returns (X', Y', Z', inf', collision): `collision` marks rows where
    the addend equals the accumulator (a doubling case) — statistically
    negligible, the host re-runs those rows on its exact path rather
    than paying 7 extra muls every ladder iteration for all rows."""
    z1z1 = fe_sq(Z)
    u2 = fe_mul(ax, z1z1)
    s2 = fe_mul(ay, fe_mul(Z, z1z1))
    h = fe_sub(u2, X)
    r = fe_sub(s2, Y)
    h0 = fe_is_zero(h)
    r0 = fe_is_zero(r)
    hh = fe_sq(h)
    hhh = fe_mul(h, hh)
    v = fe_mul(X, hh)
    nx = fe_sub(fe_sub(fe_sq(r), hhh), fe_add(v, v))
    ny = fe_sub(fe_mul(r, fe_sub(v, nx)), fe_mul(Y, hhh))
    nz = fe_mul(Z, h)

    eff = do & ~a_inf                    # performing a real add
    take_addend = eff & inf              # inf + Q = Q
    general = eff & ~inf
    collision = general & h0 & r0        # addend == acc -> host redo
    to_inf = general & h0 & ~r0          # addend == -acc

    ta = take_addend[..., None]
    ge = general[..., None]
    one = jnp.asarray(_ONE_L)
    Xo = jnp.where(ta, ax, jnp.where(ge, nx, X))
    Yo = jnp.where(ta, ay, jnp.where(ge, ny, Y))
    Zo = jnp.where(ta, jnp.broadcast_to(one, Z.shape),
                   jnp.where(ge, nz, Z))
    info = jnp.where(take_addend, False,
                     jnp.where(general, to_inf, inf))
    return Xo, Yo, Zo, info, collision


# affine 2G, for the R == G corner of the G+R table entry
_G2_LAM = (3 * GX * GX) * pow(2 * GY, P - 2, P) % P
_G2X = (_G2_LAM * _G2_LAM - 2 * GX) % P
_G2Y = (_G2_LAM * (GX - _G2X) - GY) % P
_G2X_L = _const_limbs(_G2X)
_G2Y_L = _const_limbs(_G2Y)


def _shamir(u1w, u2w, qx, qy, gqx, gqy, gq_inf):
    """u1*G + u2*Q, one shared 256-step ladder over the batch.

    u1w/u2w: (B, 8) int32 little-endian 32-bit scalar words.
    qx/qy:   (B, 20) affine R limbs; gqx/gqy: affine G+R limbs;
    gq_inf: (B,) bool (R == -G).
    Returns (X, Y, Z, inf, collision)."""
    Bsz = qx.shape[0]
    gx = jnp.broadcast_to(jnp.asarray(_GX_L), (Bsz, LIMBS))
    gy = jnp.broadcast_to(jnp.asarray(_GY_L), (Bsz, LIMBS))

    def body(i, st):
        X, Y, Z, inf, bad = st
        X, Y, Z = pt_double(X, Y, Z)
        pos = 255 - i
        w = pos // 32
        s = pos % 32
        b1 = (jax.lax.dynamic_index_in_dim(u1w, w, axis=1,
                                           keepdims=False) >> s) & 1
        b2 = (jax.lax.dynamic_index_in_dim(u2w, w, axis=1,
                                           keepdims=False) >> s) & 1
        both = (b1 & b2).astype(bool)
        q_only = b2.astype(bool)
        ax = jnp.where(both[:, None], gqx,
                       jnp.where(q_only[:, None], qx, gx))
        ay = jnp.where(both[:, None], gqy,
                       jnp.where(q_only[:, None], qy, gy))
        a_inf = both & gq_inf
        do = (b1 | b2).astype(bool)
        X, Y, Z, inf, coll = _mixed_add(X, Y, Z, inf, ax, ay, a_inf, do)
        return X, Y, Z, inf, bad | coll

    zeros = jnp.zeros((Bsz, LIMBS), dtype=jnp.int32)
    init = (zeros, zeros, zeros,
            jnp.ones((Bsz,), dtype=bool), jnp.zeros((Bsz,), dtype=bool))
    return jax.lax.fori_loop(0, 256, body, init)


@jax.jit
def recover_kernel(x_bytes, parity, u1w, u2w):
    """The full device side of batched ECDSA recovery, one call:

      unpack x -> y = sqrt(x^3+7) -> parity-select y -> build the
      G+R table entry (one batched Fermat inversion) -> Shamir ladder
      u1*G + u2*R -> pack.

    x_bytes: (B, 33) uint8 LE canonical x coordinates.
    parity:  (B,) int32 — required y parity (recid & 1).
    u1w/u2w: (B, 8) int32 LE scalar words.
    Returns (B, 102) uint8: X(33) ++ Y(33) ++ Z(33) canonical Jacobian
    bytes ++ [inf, collision, is_residue] flag bytes."""
    # the kernel's stable name in a device trace
    with jax.named_scope("coreth/recover_ladder"):
        return _recover_body(x_bytes, parity, u1w, u2w)


def _recover_body(x_bytes, parity, u1w, u2w):
    x = unpack_fe_bytes(x_bytes)
    Bsz = x.shape[0]
    seven = jnp.broadcast_to(jnp.asarray(_const_limbs(7)), x.shape)
    ysq = fe_add(fe_mul(fe_mul(x, x), x), seven)
    y, residue = fe_sqrt(ysq)
    yneg = fe_canon(fe_sub(jnp.zeros_like(y), y))
    flip = (y[..., 0] & 1) != parity
    y = jnp.where(flip[:, None], yneg, y)

    # G+R affine add, branchless: general case via Fermat inversion;
    # R == G -> constant 2G; R == -G -> infinity flag.
    gx = jnp.broadcast_to(jnp.asarray(_GX_L), x.shape)
    gy = jnp.broadcast_to(jnp.asarray(_GY_L), x.shape)
    dx = fe_sub(x, gx)
    x_eq = fe_is_zero(dx)
    lam = fe_mul(fe_sub(y, gy), fe_inv(dx))
    gqx = fe_sub(fe_sub(fe_mul(lam, lam), gx), x)
    gqy = fe_sub(fe_mul(lam, fe_sub(gx, gqx)), gy)
    y_eq = fe_is_zero(fe_sub(y, gy))
    is_2g = (x_eq & y_eq)[:, None]
    gqx = jnp.where(is_2g, jnp.asarray(_G2X_L), gqx)
    gqy = jnp.where(is_2g, jnp.asarray(_G2Y_L), gqy)
    gq_inf = x_eq & ~y_eq

    X, Y, Z, inf, bad = _shamir(u1w, u2w, x, y, gqx, gqy, gq_inf)
    flags = jnp.stack([inf, bad, residue], axis=-1).astype(jnp.uint8)
    return jnp.concatenate(
        [pack_fe_bytes(fe_canon(X)), pack_fe_bytes(fe_canon(Y)),
         pack_fe_bytes(fe_canon(Z)), flags], axis=-1)

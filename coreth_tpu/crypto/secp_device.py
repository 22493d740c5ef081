"""Device-batched ECDSA recovery — host orchestration.

Splits recovery the TPU-native way (SURVEY.md section 2.7: "batched
ECDSA-recover kernel"; reference analog core/sender_cacher.go):

  1. host: parse + range-check, and u1/u2 = (-z/r, s/r) mod n via ONE
     Montgomery batch inversion across the whole batch (a few CPython
     modmuls per signature, no per-signature pow)
  2. device, one call (ops/secp.recover_kernel): y = sqrt(x^3+7),
     parity select, the G+R table entry (batched Fermat inversion),
     and the dominant Shamir ladder u1*G + u2*R
  3. host: Jacobian -> affine via one more batch inversion + keccak

Inputs and outputs of the device call are byte-packed (~2.6 MB per 16k
signatures round trip): one upload and one download per chunk.  What a
sync and a byte cost on a locally attached chip is not measured.

ABI mirrors crypto.native.recover_addresses_batch:
  recover_addresses_device(hashes, rs, ss, recids) -> (addrs20, ok)

A LIBRARY: nothing in the program calls it.  The serving path (replay/,
serve/) recovers on the native batch alone, which finishes first at
every batch size on any host with two or more cores; this kernel keeps
its own tests and its proof on the chip (chip_smoke.py --phase recover).

Rows the branchless ladder flags as doubling collisions (addend ==
accumulator; statistically negligible, constructible adversarially) are
re-run on the exact host path.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from coreth_tpu.crypto.keccak import keccak256
from coreth_tpu.crypto import secp256k1 as _ref

P = _ref.P
N = _ref.N


def _batch_inv(vals: List[int], mod: int) -> List[int]:
    """Montgomery batch inversion: one pow + 3 muls per element.
    All vals must be nonzero mod `mod`."""
    if not vals:
        return []
    prefix = []
    acc = 1
    for v in vals:
        acc = acc * v % mod
        prefix.append(acc)
    inv = pow(acc, mod - 2, mod)
    out = [0] * len(vals)
    for i in range(len(vals) - 1, -1, -1):
        out[i] = inv * (prefix[i - 1] if i else 1) % mod
        inv = inv * (vals[i] % mod) % mod
    return out


def _words_le(values: List[int]) -> np.ndarray:
    """ints -> (B, 8) int32 little-endian 32-bit words."""
    blob = b"".join(v.to_bytes(32, "little") for v in values)
    return np.frombuffer(blob, dtype="<u4").reshape(
        len(values), 8).astype(np.int32)


def _pad_pow2(n: int, floor: int = 64) -> int:
    b = max(n, floor)
    return 1 << (b - 1).bit_length()


# Largest single kernel launch: batches beyond this are chunked so
# padding waste, HBM footprint, and the set of compiled shape variants
# all stay bounded (pow2 buckets 64..4096 — at most 7 executables).
# 4096 was chosen where launches stopped being dispatch-bound and pow2
# padding waste was still small; not re-measured on a locally attached
# chip.
MAX_CHUNK = 4096


def issue_recover(hashes: bytes, rs: bytes, ss: bytes,
                  recids: bytes, kernel=None) -> list:
    """Host prep + async kernel dispatch for a packed signature batch.

    Returns a list of per-chunk contexts; pass to complete_recover to
    block on the device results and finish on host.  The kernel calls
    are dispatched asynchronously (jax), so the caller can do host work
    — or enqueue more device work — while the ladder runs.

    kernel: alternative device entry with recover_kernel's signature —
    the mesh-sharded ladder (parallel/mesh.py sharded_recover) plugs in
    here so multi-chip recovery reuses all of the host prep/finish."""
    n = len(recids)
    ctxs = []
    for lo in range(0, n, MAX_CHUNK):
        hi = min(lo + MAX_CHUNK, n)
        ctxs.append(_issue_chunk(
            hashes[32 * lo:32 * hi], rs[32 * lo:32 * hi],
            ss[32 * lo:32 * hi], recids[lo:hi], kernel))
    return ctxs


def complete_recover(ctxs: list) -> Tuple[bytes, bytes]:
    """Block on issued chunks; returns (addresses, ok) packed bytes."""
    addrs = bytearray()
    okb = bytearray()
    for ctx in ctxs:
        a, o = _complete_chunk(ctx)
        addrs += a
        okb += o
    return bytes(addrs), bytes(okb)


def recover_addresses_device(hashes: bytes, rs: bytes, ss: bytes,
                             recids: bytes) -> Tuple[bytes, bytes]:
    """Batched recovery over packed buffers; returns (addresses, ok)."""
    return complete_recover(issue_recover(hashes, rs, ss, recids))


def _issue_chunk(hashes: bytes, rs: bytes, ss: bytes, recids: bytes,
                 kernel=None):
    from coreth_tpu.ops import secp as S

    n = len(recids)
    if n == 0:
        return None
    # host prep in C++ when available (range checks + the u1/u2 batch
    # inversion — Python bigint math would sit on the critical path),
    # pure-python fallback otherwise
    from coreth_tpu.crypto import native
    prep = native.recover_prep(hashes, rs, ss, recids) \
        if native.load() is not None else None
    if prep is not None:
        xs_le, u1_le, u2_le, okb = prep
        ok = [bool(b) for b in okb]
        pad = _pad_pow2(n)
        x_arr = np.zeros((pad, 33), dtype=np.uint8)
        x_arr[:n] = np.frombuffer(xs_le, dtype=np.uint8).reshape(n, 33)
        u1_arr = np.zeros((pad, 8), dtype=np.int32)
        u2_arr = np.zeros((pad, 8), dtype=np.int32)
        u1_arr[:n] = np.frombuffer(u1_le, dtype="<u4").reshape(
            n, 8).astype(np.int32)
        u2_arr[:n] = np.frombuffer(u2_le, dtype="<u4").reshape(
            n, 8).astype(np.int32)
    else:
        r_l = [int.from_bytes(rs[32 * i:32 * i + 32], "big")
               for i in range(n)]
        s_l = [int.from_bytes(ss[32 * i:32 * i + 32], "big")
               for i in range(n)]
        z_l = [int.from_bytes(hashes[32 * i:32 * i + 32], "big")
               for i in range(n)]
        ok = [True] * n
        xs = [0] * n
        for i in range(n):
            r, s, recid = r_l[i], s_l[i], recids[i]
            if not (0 < r < N and 0 < s < N and recid <= 3):
                ok[i] = False
                continue
            x = r + N if recid & 2 else r
            if x >= P:
                ok[i] = False
                continue
            xs[i] = x
        live = [i for i in range(n) if ok[i]]
        rinv = dict(zip(live, _batch_inv([r_l[i] for i in live], N)))
        u1s = [0] * n
        u2s = [0] * n
        for i in live:
            u1s[i] = (-z_l[i] * rinv[i]) % N
            u2s[i] = (s_l[i] * rinv[i]) % N
        pad = _pad_pow2(n)
        padz = [0] * (pad - n)
        x_arr = S.fe_bytes_np(xs + padz)
        u1_arr = _words_le(u1s + padz)
        u2_arr = _words_le(u2s + padz)

    # --- device: sqrt + G+R table + Shamir ladder, async dispatch ------
    parity = np.frombuffer(recids, dtype=np.uint8).astype(np.int32) & 1
    parity = np.concatenate([parity, np.zeros(pad - n, np.int32)])
    dev_out = (kernel or S.recover_kernel)(x_arr, parity, u1_arr, u2_arr)
    return dict(n=n, dev_out=dev_out, ok=ok, hashes=hashes, rs=rs, ss=ss,
                recids=recids)


def _redo_collision(hashes, rs, ss, recids, i, addrs, okb):
    """Ladder doubling-collision row: exact host re-run (rare)."""
    try:
        addr = _ref.recover_address_py(
            hashes[32 * i:32 * i + 32],
            int.from_bytes(rs[32 * i:32 * i + 32], "big"),
            int.from_bytes(ss[32 * i:32 * i + 32], "big"), recids[i])
    except ValueError:
        return
    addrs[20 * i:20 * i + 20] = addr
    okb[i] = 1


def _complete_chunk(ctx) -> Tuple[bytes, bytes]:
    if ctx is None:
        return b"", b""
    n = ctx["n"]
    ok = ctx["ok"]
    hashes, rs, ss = ctx["hashes"], ctx["rs"], ctx["ss"]
    recids = ctx["recids"]
    out = np.asarray(ctx["dev_out"])[:n]

    from coreth_tpu.crypto import native
    if native.load() is not None:
        # C++ finish: batched Z inversion + affine + keccak
        rows = out.tobytes()
        addrs_b, okb_b = native.recover_finish(rows, n, bytes(ok))
        addrs = bytearray(addrs_b)
        okb = bytearray(okb_b)
        for i in range(n):
            if okb[i] == 2:
                okb[i] = 0
                _redo_collision(hashes, rs, ss, recids, i, addrs, okb)
        return bytes(addrs), bytes(okb)

    inf = out[:, 99].astype(bool)
    bad = out[:, 100].astype(bool)
    residue = out[:, 101].astype(bool)

    # --- host: to affine (one batch inversion) + keccak ----------------
    zj = {}
    for i in range(n):
        if ok[i] and residue[i] and not inf[i] and not bad[i]:
            z = int.from_bytes(out[i, 66:99].tobytes(), "little")
            if z:
                zj[i] = z
    fin = sorted(zj)
    zinv = dict(zip(fin, _batch_inv([zj[i] for i in fin], P)))

    addrs = bytearray(20 * n)
    okb = bytearray(n)
    for i in range(n):
        if not ok[i]:
            continue
        if not residue[i]:
            continue                 # x not on curve
        if bad[i]:
            _redo_collision(hashes, rs, ss, recids, i, addrs, okb)
            continue
        if i not in zinv:
            continue                 # u1*G + u2*R = infinity: invalid
        xi = int.from_bytes(out[i, 0:33].tobytes(), "little")
        yi = int.from_bytes(out[i, 33:66].tobytes(), "little")
        zi = zinv[i]
        zi2 = zi * zi % P
        ax = xi * zi2 % P
        ay = yi * zi2 % P * zi % P
        pub = ax.to_bytes(32, "big") + ay.to_bytes(32, "big")
        addrs[20 * i:20 * i + 20] = keccak256(pub)[12:]
        okb[i] = 1
    return bytes(addrs), bytes(okb)

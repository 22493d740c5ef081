"""The call kernel's light opcodes, stepped inside one Pallas kernel.

The XLA step of the call-frame OCC kernel (machine._build_exec under
feature "call") pays for its form, not its work: a step is ~370 small
device operations whatever the lanes execute, and copies the loop's
carried state between HBM and VMEM every iteration.  Most of a
contract's steps are light (PUSH, DUP, SWAP, POP, JUMP*, the cheap ALU,
MUL, SHL / SHR, MLOAD / MSTORE, CALLDATALOAD, context reads, a root
STOP, SLOAD / SSTORE of an entry the lane's storage cache holds), and
lanes never had to step in lockstep: they share a loop, not a pc.

``build(params)`` gives ``step(st, pre) -> st``: ONE ``pallas_call``
that walks the chunk's lanes one after another on the scalar core, each
at its own pc, with the lane state in SMEM, and executes every light
opcode that succeeds.  A lane whose next opcode is outside that set, or
would fail (out of gas, a bad jump, a stack or memory limit), PARKS: it
stays RUN with its pc on that opcode, and the XLA step executes it with
its full semantics.  So each lane runs the same opcodes in the same
order with the same results as under the lockstep loop, bit for bit.

Layout inside the kernel (all int32, flat, SMEM): the running lane's
stack pool as (slot, limb), its memory packed four bytes a word, big
endian, and the DMA tile of storage-cache keys, values and originals
that holds its entries, each brought in from HBM when the lane starts
and sent back when it parks; the cache's flags and address ids, the
per-lane registers (rows of ``K`` lanes) and the calldata (packed like
memory) whole; the code table packed like memory and its
jump-destination bitmap 32 bits a word.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device import tables as T
from coreth_tpu.ops import u256
from coreth_tpu.params import protocol as P

LIMBS = u256.LIMBS
_MASK = 0xFFFF

# opcode classes of the fused step (0: park)
(C_PARK, C_PUSH, C_DUP, C_SWAP, C_NOP, C_ARITH, C_CMP, C_BIT, C_JUMP,
 C_JUMPI, C_CDLOAD, C_MEM, C_SHIFT, C_WORD, C_SCALAR, C_STOP,
 C_MUL, C_SLOAD, C_SSTORE) = range(19)

_ARITH = (0x01, 0x03)                          # ADD SUB
_CMP = (0x10, 0x11, 0x12, 0x13, 0x14, 0x15)    # LT GT SLT SGT EQ ISZERO
_BIT = (0x16, 0x17, 0x18, 0x19)                # AND OR XOR NOT
_MEM = (0x51, 0x52, 0x53)                      # MLOAD MSTORE MSTORE8
_SHIFT = (0x1B, 0x1C)                          # SHL SHR
# ADDRESS ORIGIN CALLER CALLVALUE: rows 0-3 of the per-lane word table
_WORD = (0x30, 0x32, 0x33, 0x34)
# CALLDATASIZE CODESIZE RETURNDATASIZE TIMESTAMP NUMBER PC MSIZE GAS
_SCALAR = (0x36, 0x38, 0x3D, 0x42, 0x43, 0x58, 0x59, 0x5A)

# per-lane read-only rows (``lro``) and in/out registers (``regs``)
(L_RUN, L_SB, L_MB, L_CDB, L_CDLEN, L_DEEP, L_CID, L_RDL, L_CLEN,
 L_AID, L_STAT) = range(11)
_NLRO = 11
(R_PC, R_GAS, R_SP, R_MSIZE, R_LSTEPS, R_FSTEPS, R_STATUS,
 R_REFUND) = range(8)
_NREGS = 8

# SMEM a fused step may hold (of the chip's 1 MiB), scratch included
SMEM_BUDGET = 768 * 1024
_TILE = 1024


def interpret() -> bool:
    """Pallas interpret mode off the TPU (the tier-1 suite runs the
    kernel on the CPU this way); the choice follows the backend."""
    return jax.default_backend() != "tpu"


def class_table(fork: str, feats) -> np.ndarray:
    """(256,) opcode -> class: the light opcodes the fork defines and
    the device runs (``tables.op_tables``), every other opcode 0."""
    sup = T.op_tables(fork).supported
    cls = np.zeros(256, dtype=np.int32)
    ops = {op: C_PUSH for op in range(0x5F, 0x80)}
    ops.update({op: C_DUP for op in range(0x80, 0x90)})
    ops.update({op: C_SWAP for op in range(0x90, 0xA0)})
    ops.update({0x50: C_NOP, 0x5B: C_NOP, 0x56: C_JUMP, 0x57: C_JUMPI,
                0x35: C_CDLOAD, 0x00: C_STOP})
    ops.update({op: C_ARITH for op in _ARITH})
    ops.update({op: C_CMP for op in _CMP})
    ops.update({op: C_BIT for op in _BIT})
    ops.update({op: C_MEM for op in _MEM})
    ops.update({op: C_WORD for op in _WORD})
    ops.update({op: C_SCALAR for op in _SCALAR})
    if "shift" in feats:
        ops.update({op: C_SHIFT for op in _SHIFT})
    if "mul" in feats:
        ops[0x02] = C_MUL
    if "storage" in feats:
        ops.update({0x54: C_SLOAD, 0x55: C_SSTORE})
    for op, c in ops.items():
        if sup[op] == 1:
            cls[op] = c
    return cls


def _dims(p):
    K = p.batch
    CW = p.code_cap + 33
    return dict(K=K, SC=p.stack_cap, MW=p.mem_cap // 4, DW=p.data_cap // 4,
                S=p.scache_cap,
                CW=CW, CWP=(CW + 3) // 4, JW=p.code_cap // 32,
                NC=p.code_rows)


def smem_bytes(p) -> int:
    """SMEM the fused step of `p` takes: its inputs and its scratch."""
    d = _dims(p)
    K = d["K"]
    words = (256 + 2 + _NLRO * K + 4 * K * LIMBS + d["NC"] * d["CWP"]
             + d["NC"] * d["JW"] + K * d["DW"]
             + _NREGS * K + d["SC"] * LIMBS + d["MW"]
             + 3 * max(d["S"] * LIMBS, _TILE) + 2 * K * d["S"])
    return 4 * words


def fits(p) -> bool:
    """The fused step's state fits SMEM and its lanes' rows whole
    DMA tiles."""
    d = _dims(p)
    return (smem_bytes(p) <= SMEM_BUDGET
            and (d["SC"] * LIMBS) % _TILE == 0 and d["MW"] % _TILE == 0
            and (d["K"] * d["S"] * LIMBS) % _TILE == 0)


def _pack_bytes(x):
    """(..., 4n) bytes -> (..., n) words, big endian."""
    w = x.reshape(x.shape[:-1] + (x.shape[-1] // 4, 4))
    return (w[..., 0] << 24) | (w[..., 1] << 16) | (w[..., 2] << 8) \
        | w[..., 3]


def _unpack_bytes(w):
    """_pack_bytes' inverse."""
    sh = jnp.array([24, 16, 8, 0], dtype=jnp.int32)
    b = (w[..., None] >> sh) & 0xFF
    return b.reshape(w.shape[:-1] + (w.shape[-1] * 4,))


def _pack_bits(bits):
    """(..., 32n) 0/1 -> (..., n) words, bit i of word j = bits[32j+i]."""
    b = bits.reshape(bits.shape[:-1] + (bits.shape[-1] // 32, 32))
    return jnp.sum(b << jnp.arange(32, dtype=jnp.int32), axis=-1,
                   dtype=jnp.int32)


def _mem_cost(w):
    return w * P.MEMORY_GAS + ((w * w) >> 9)


assert P.QUAD_COEFF_DIV == 512


def build(p):
    """The fused step for a chunk of ``p.batch`` lanes (a call kernel's
    K): ``prepare(inputs)`` packs what stays fixed across an exec (the
    code and jump tables, calldata, the block's context), once;
    ``step(st, pre)`` runs the kernel over the exec's state dict."""
    d = _dims(p)
    K, SC, MW, DW, S = d["K"], d["SC"], d["MW"], d["DW"], d["S"]
    CW, CWP, JW, NC = d["CW"], d["CWP"], d["JW"], d["NC"]
    MEMCAP, CODECAP, DATACAP = p.mem_cap, p.code_cap, p.data_cap
    MAXS = p.max_steps
    # a DMA of a 1-D array moves whole tiles of 1,024 words: a lane's
    # stack and memory are a tile each, its cache keys and values half
    # of one at the smallest cache
    KBLK = max(S * LIMBS, _TILE)
    tabs = T.op_tables(p.fork)
    # one word an opcode: class, stack in / out, constant gas (a parked
    # opcode's gas is never read)
    meta = (class_table(p.fork, p.features) | (tabs.nin << 5)
            | (tabs.nout << 10)
            | (np.minimum(tabs.const_gas, 0x7FFF) << 16)).astype(np.int32)

    def kernel(meta_r, ctx_r, lro_r, words_r, code_r, jd_r, cd_r,
               skey_h, sorig_h, saddr_h, regs_h, stack_h, mem_h, sflag_h,
               sval_h, regs_o, stack_o, mem_o, sflag_o, sval_o,
               regs, stack, mem, skey, sorig, saddr, sflag, sval, sem):
        def copy(pairs):
            cps = [pltpu.make_async_copy(a, b, sem.at[i])
                   for i, (a, b) in enumerate(pairs)]
            for c in cps:
                c.start()
            for c in cps:
                c.wait()

        # the storage cache's flags and address ids whole (K x S words);
        # its keys and values a lane's aligned block at a time below
        copy([(regs_h, regs), (saddr_h, saddr), (sflag_h, sflag)])

        def lane(ln, carry):
            def ro(row):
                return lro_r[row * K + ln]

            sb, mb, cid = ro(L_SB), ro(L_MB), ro(L_CID)
            cdb, cdlen, deep = ro(L_CDB), ro(L_CDLEN), ro(L_DEEP)
            # the lane's stack, memory and cache block sit in lane-sized
            # scratch (the lanes step one after another): its words at
            # 0, its cache entries at kbase of the block
            sbase = mbase = 0
            kbase = (ln * S * LIMBS) % KBLK
            cbase = cid * CWP

            def word(slot):
                """The 16 limbs of the stack slot (absolute)."""
                b = sbase + slot * LIMBS
                return [stack[b + i] for i in range(LIMBS)]

            def put(slot, limbs):
                b = sbase + slot * LIMBS
                for i in range(LIMBS):
                    stack[b + i] = limbs[i]

            def small(v):
                """A non-negative int32 as limbs (word_of_scalar)."""
                return [v & _MASK, (v >> 16) & _MASK] + [0] * (LIMBS - 2)

            def fits25(a):
                """(value, fits) of a word as _fits25 reads it."""
                hi = a[2]
                for i in range(3, LIMBS):
                    hi = hi | a[i]
                ok = (hi == 0) & (a[1] < (1 << 9))
                return a[0] + (a[1] << 16), ok

            def code_byte(q):
                w = code_r[cbase + (jnp.clip(q, 0, CW - 1) >> 2)]
                v = (w >> (24 - ((q & 3) << 3))) & 0xFF
                return jnp.where((q >= 0) & (q < CW), v, 0)

            def mem_byte(q):
                w = mem[mbase + (q >> 2)]
                return (w >> (24 - ((q & 3) << 3))) & 0xFF

            def mem_set(q, v):
                i = mbase + (q >> 2)
                sh = 24 - ((q & 3) << 3)
                mem[i] = (mem[i] & ~(0xFF << sh)) | (v << sh)

            def put_be(slot, src, off):
                """The big-endian word of bytes src(off..off+31) into the
                slot, a limb an iteration (the rare, unaligned paths)."""
                b = sbase + slot * LIMBS

                def limb(i, c):
                    stack[b + i] = (src(off + 30 - 2 * i) << 8) \
                        | src(off + 31 - 2 * i)
                    return c

                jax.lax.fori_loop(0, LIMBS, limb, jnp.int32(0))

            # ---- the op families: each returns (ok, next_pc, extra gas,
            # msize) and writes only when ok
            def f_park(pc, gas, sp, msize, op, nsp):
                return jnp.int32(0), pc, jnp.int32(0), msize

            def f_stop(pc, gas, sp, msize, op, nsp):
                # the root frame's STOP ends the lane; a called frame's
                # resumes its parent (the XLA step's frame pop)
                return (deep == 0).astype(jnp.int32), pc, jnp.int32(0), \
                    msize

            def f_push(pc, gas, sp, msize, op, nsp):
                n = op - 0x5F

                def short():
                    lo = jnp.where(n >= 1, code_byte(pc + n), 0)
                    hi = jnp.where(n >= 2, code_byte(pc + n - 1), 0)
                    put(sp, [lo | (hi << 8)] + [0] * (LIMBS - 1))

                def long():
                    def byte(q):      # the code byte, 0 past the push
                        return jnp.where(q > pc, code_byte(q), 0)
                    put_be(sp, byte, pc + n - 31)

                jax.lax.cond(n <= 2, short, long)
                return jnp.int32(1), pc + 1 + n, jnp.int32(0), msize

            def f_dup(pc, gas, sp, msize, op, nsp):
                put(sp, word(sp - 1 - (op - 0x80)))
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_swap(pc, gas, sp, msize, op, nsp):
                k = sp - 1 - (op - 0x8F)
                top, oth = word(sp - 1), word(k)
                put(sp - 1, oth)
                put(k, top)
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_nop(pc, gas, sp, msize, op, nsp):
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def borrow(a, b):
                """(a - b limbs, borrow out)."""
                out, br = [], jnp.int32(0)
                for i in range(LIMBS):
                    v = a[i] - b[i] - br
                    br = (v < 0).astype(jnp.int32)
                    out.append(v & _MASK)
                return out, br

            def f_arith(pc, gas, sp, msize, op, nsp):
                a, b = word(sp - 1), word(sp - 2)

                def add():
                    out, c = [], jnp.int32(0)
                    for i in range(LIMBS):
                        v = a[i] + b[i] + c
                        out.append(v & _MASK)
                        c = v >> 16
                    return tuple(out)

                put(sp - 2, list(jax.lax.cond(
                    op == 0x01, add, lambda: tuple(borrow(a, b)[0]))))
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_cmp(pc, gas, sp, msize, op, nsp):
                a = word(sp - 1)

                def binary():
                    b = word(sp - 2)
                    flip = jnp.where((op == 0x12) | (op == 0x13), 0x8000, 0)
                    a2 = a[:LIMBS - 1] + [a[LIMBS - 1] ^ flip]
                    b2 = b[:LIMBS - 1] + [b[LIMBS - 1] ^ flip]
                    lt = borrow(a2, b2)[1]
                    gt = borrow(b2, a2)[1]
                    ne = a[0] ^ b[0]
                    for i in range(1, LIMBS):
                        ne = ne | (a[i] ^ b[i])
                    return jnp.where(
                        op == 0x14, (ne == 0).astype(jnp.int32),
                        jnp.where((op == 0x10) | (op == 0x12), lt, gt))

                def iszero():
                    nz = a[0]
                    for i in range(1, LIMBS):
                        nz = nz | a[i]
                    return (nz == 0).astype(jnp.int32)

                v = jax.lax.cond(op == 0x15, iszero, binary)
                put(nsp - 1, small(v))
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_bit(pc, gas, sp, msize, op, nsp):
                a = word(sp - 1)
                b = word(jnp.maximum(sp - 2, 0))
                out = [jnp.where(op == 0x16, a[i] & b[i],
                                 jnp.where(op == 0x17, a[i] | b[i],
                                           jnp.where(op == 0x18, a[i] ^ b[i],
                                                     a[i] ^ _MASK)))
                       for i in range(LIMBS)]
                put(nsp - 1, out)
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def jump_ok(a):
                v, fit = fits25(a)
                vc = jnp.clip(v, 0, CODECAP - 1)
                bit = (jd_r[cid * JW + (vc >> 5)] >> (vc & 31)) & 1
                return fit & (v < CODECAP) & (bit == 1), v

            def f_jump(pc, gas, sp, msize, op, nsp):
                ok, v = jump_ok(word(sp - 1))
                return ok.astype(jnp.int32), jnp.where(ok, v, pc), \
                    jnp.int32(0), msize

            def f_jumpi(pc, gas, sp, msize, op, nsp):
                b = word(sp - 2)
                nz = b[0]
                for i in range(1, LIMBS):
                    nz = nz | b[i]
                ok, v = jump_ok(word(sp - 1))
                take = nz != 0
                ok = ok | ~take
                npc = jnp.where(take, v, pc + 1)
                return ok.astype(jnp.int32), jnp.where(ok, npc, pc), \
                    jnp.int32(0), msize

            def f_cdload(pc, gas, sp, msize, op, nsp):
                av, fit = fits25(word(sp - 1))

                def src(q):
                    # a called frame's calldata is a region of its
                    # parent's memory; the root's, the tx's calldata
                    w = jnp.where(
                        deep != 0,
                        mem[mbase + (jnp.minimum(cdb + q, MEMCAP - 1) >> 2)],
                        cd_r[ln * DW + jnp.minimum(q, DATACAP - 1) // 4])
                    qq = jnp.where(deep != 0, cdb + q, q)
                    v = (w >> (24 - ((qq & 3) << 3))) & 0xFF
                    return jnp.where(
                        (q < cdlen) & ((deep != 0) | (q < DATACAP)), v, 0)

                @pl.when(fit)
                def _():
                    put_be(sp - 1, src, av)

                @pl.when(~fit)
                def _():
                    put(sp - 1, [0] * LIMBS)

                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_mem(pc, gas, sp, msize, op, nsp):
                av, fit = fits25(word(sp - 1))
                need = av + jnp.where(op == 0x53, 1, 32)
                ok = fit & (mb + need <= MEMCAP)
                new_ms = jnp.maximum(msize, (need + 31) & ~31)
                xg = _mem_cost(new_ms >> 5) - _mem_cost(msize >> 5)
                ok = ok & (gas - xg >= 0)
                o = mb + av

                al = (o & 3) == 0
                sl = sbase + (sp - 1) * LIMBS

                @pl.when(ok & (op == 0x51) & al)
                def _():
                    for j in range(8):
                        w = mem[mbase + (o >> 2) + j]
                        stack[sl + 15 - 2 * j] = (w >> 16) & _MASK
                        stack[sl + 14 - 2 * j] = w & _MASK

                @pl.when(ok & (op == 0x51) & ~al)
                def _():
                    put_be(sp - 1, mem_byte, o)

                vb = sbase + (sp - 2) * LIMBS

                @pl.when(ok & (op == 0x52) & al)
                def _():
                    for j in range(8):
                        mem[mbase + (o >> 2) + j] = \
                            (stack[vb + 15 - 2 * j] << 16) \
                            | stack[vb + 14 - 2 * j]

                @pl.when(ok & (op == 0x52) & ~al)
                def _():
                    def byte(k, c):
                        limb = stack[vb + 15 - (k >> 1)]
                        mem_set(o + k, (limb >> (8 - ((k & 1) << 3))) & 0xFF)
                        return c

                    jax.lax.fori_loop(0, 32, byte, jnp.int32(0))

                @pl.when(ok & (op == 0x53))
                def _():
                    mem_set(o, stack[sbase + (sp - 2) * LIMBS] & 0xFF)

                return ok.astype(jnp.int32), jnp.where(ok, pc + 1, pc), \
                    jnp.where(ok, xg, 0), jnp.where(ok, new_ms, msize)

            def f_shift(pc, gas, sp, msize, op, nsp):
                a = word(sp - 1)
                xb = sbase + (sp - 2) * LIMBS
                hi = a[1]
                for i in range(2, LIMBS):
                    hi = hi | a[i]
                over = (hi != 0) | (a[0] > 255)
                s = jnp.where(over, 0, a[0])
                q, r = s >> 4, s & 15

                def g(i):               # limb i of x, 0 outside
                    ok = (i >= 0) & (i < LIMBS)
                    return jnp.where(ok, stack[xb + jnp.clip(i, 0, LIMBS - 1)],
                                     0)

                def shl():
                    return tuple(((g(i - q) << r) & _MASK)
                                 | (g(i - q - 1) >> (16 - r))
                                 for i in range(LIMBS))

                def shr():
                    return tuple((g(i + q) >> r)
                                 | ((g(i + q + 1) << (16 - r)) & _MASK)
                                 for i in range(LIMBS))

                out = jax.lax.cond(op == 0x1B, shl, shr)
                put(sp - 2, [jnp.where(over, 0, x) for x in out])
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_mul(pc, gas, sp, msize, op, nsp):
                # the low 256 bits of a * b: 16-bit limbs of a times
                # 8-bit digits of b, summed in byte columns (each sum
                # < 2^29), then one carry pass
                a, b = word(sp - 1), word(sp - 2)
                bd = [b[j >> 1] >> 8 if j & 1 else b[j >> 1] & 0xFF
                      for j in range(32)]
                col = [jnp.int32(0)] * 32
                for i in range(LIMBS):
                    for j in range(32 - 2 * i):
                        col[2 * i + j] = col[2 * i + j] + a[i] * bd[j]
                out, c = [], jnp.int32(0)
                for k in range(32):
                    v = col[k] + c
                    out.append(v & 0xFF)
                    c = v >> 8
                put(sp - 2, [out[2 * i] | (out[2 * i + 1] << 8)
                             for i in range(LIMBS)])
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def entry(sp):
                """(found, index) of the storage-cache entry the key on
                top names: the running frame's contract, the key as the
                trie holds it (statedb.normalize_state_key), valid; the
                first that matches, as the XLA step's argmax takes it."""
                a = word(sp - 1)
                key = a[:LIMBS - 1] + [a[LIMBS - 1] & 0xFEFF]
                eb = ln * S
                aid = ro(L_AID)

                def more(c):
                    return (c[0] < S) & (c[1] < 0)

                def probe(c):
                    e, hit = c
                    kb = kbase + e * LIMBS
                    d = skey[kb] ^ key[0]
                    for i in range(1, LIMBS):
                        d = d | (skey[kb + i] ^ key[i])
                    ok = ((sflag[eb + e] & M.F_VALID) != 0) \
                        & (saddr[eb + e] == aid) & (d == 0)
                    return e + 1, jnp.where(ok, e, hit)

                _e, hit = jax.lax.while_loop(more, probe,
                                             (jnp.int32(0), jnp.int32(-1)))
                return hit >= 0, eb + jnp.maximum(hit, 0), \
                    kbase + jnp.maximum(hit, 0) * LIMBS

            def f_sload(pc, gas, sp, msize, op, nsp):
                # a cached entry: a warm or cold read, the entry marked
                # read and warm; a key the cache lacks goes to the XLA
                # step (the slot table's hash index, a miss)
                found, h, hv = entry(sp)
                fl = sflag[h]
                cost = jnp.where((fl & M.F_WARM) != 0,
                                 P.WARM_STORAGE_READ_COST_EIP2929,
                                 P.COLD_SLOAD_COST_EIP2929)
                ok = found & (gas - (meta_r[op] >> 16) >= cost)

                @pl.when(ok)
                def _():
                    sflag[h] = fl | M.F_VALID | M.F_WARM | M.F_READ
                    put(sp - 1, [sval[hv + i] for i in range(LIMBS)])

                return ok.astype(jnp.int32), jnp.where(ok, pc + 1, pc), \
                    jnp.where(ok, cost, 0), msize

            def f_sstore(pc, gas, sp, msize, op, nsp):
                # a cached entry outside a static frame, above the
                # sentry: EIP-2200 / 2929's gas, EIP-3529's refund
                # (forks with refunds), the value written and the entry
                # marked written; the rest to the XLA step
                found, h, hv = entry(sp)
                fl = sflag[h]
                new = word(sp - 2)
                cur = [sval[hv + i] for i in range(LIMBS)]
                orig = [sorig[hv + i] for i in range(LIMBS)]

                def eq(x, y):
                    d = x[0] ^ y[0]
                    for i in range(1, LIMBS):
                        d = d | (x[i] ^ y[i])
                    return d == 0

                def zero(x):
                    d = x[0]
                    for i in range(1, LIMBS):
                        d = d | x[i]
                    return d == 0

                eq_cn, eq_oc, eq_on = eq(cur, new), eq(orig, cur), \
                    eq(orig, new)
                o_zero, c_zero, n_zero = zero(orig), zero(cur), zero(new)
                WR = P.WARM_STORAGE_READ_COST_EIP2929
                COLD = P.COLD_SLOAD_COST_EIP2929
                base = jnp.where(eq_cn, WR, jnp.where(
                    eq_oc, jnp.where(o_zero, P.SSTORE_SET_GAS_EIP2200,
                                     P.SSTORE_RESET_GAS_EIP2200 - COLD),
                    WR))
                cost = jnp.where((fl & M.F_WARM) != 0, 0, COLD) + base
                rd = jnp.int32(0)
                if p.refunds:
                    CL = P.SSTORE_CLEARS_SCHEDULE_REFUND_EIP3529
                    dirty = ~eq_cn & ~eq_oc
                    rd = rd + jnp.where(~eq_cn & eq_oc & ~o_zero & n_zero,
                                        CL, 0)
                    rd = rd + jnp.where(dirty & ~o_zero & c_zero, -CL, 0)
                    rd = rd + jnp.where(dirty & ~o_zero & ~c_zero & n_zero,
                                        CL, 0)
                    rd = rd + jnp.where(dirty & eq_on & o_zero,
                                        P.SSTORE_SET_GAS_EIP2200 - WR, 0)
                    rd = rd + jnp.where(
                        dirty & eq_on & ~o_zero,
                        P.SSTORE_RESET_GAS_EIP2200 - COLD - WR, 0)
                ok = found & (ro(L_STAT) == 0) \
                    & (gas > P.SSTORE_SENTRY_GAS_EIP2200) \
                    & (gas - (meta_r[op] >> 16) >= cost)

                @pl.when(ok)
                def _():
                    sflag[h] = fl | M.F_VALID | M.F_WARM | M.F_WRITTEN \
                        | M.F_READ
                    for i in range(LIMBS):
                        sval[hv + i] = new[i]
                    regs[R_REFUND * K + ln] = regs[R_REFUND * K + ln] + rd

                return ok.astype(jnp.int32), jnp.where(ok, pc + 1, pc), \
                    jnp.where(ok, cost, 0), msize

            def f_word(pc, gas, sp, msize, op, nsp):
                w = jnp.where(op == 0x30, 0, jnp.where(
                    op == 0x32, 1, jnp.where(op == 0x33, 2, 3)))
                b = (w * K + ln) * LIMBS
                put(sp, [words_r[b + i] for i in range(LIMBS)])
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            def f_scalar(pc, gas, sp, msize, op, nsp):
                cg = meta_r[op] >> 16
                v = jnp.where(op == 0x36, cdlen, jnp.where(
                    op == 0x38, ro(L_CLEN), jnp.where(
                        op == 0x3D, ro(L_RDL), jnp.where(
                            op == 0x42, ctx_r[0], jnp.where(
                                op == 0x43, ctx_r[1], jnp.where(
                                    op == 0x58, pc, jnp.where(
                                        op == 0x59, msize, gas - cg)))))))
                put(sp, small(v))
                return jnp.int32(1), pc + 1, jnp.int32(0), msize

            # the families by class number
            fams = (f_park, f_push, f_dup, f_swap, f_nop, f_arith, f_cmp,
                    f_bit, f_jump, f_jumpi, f_cdload, f_mem, f_shift,
                    f_word, f_scalar, f_stop, f_mul, f_sload, f_sstore)
            assert len(fams) == C_SSTORE + 1

            def cond(c):
                return c[-1] != 0

            def body(c):
                pc, gas, sp, msize, ls, fs, status, _go = c
                op = code_byte(pc)
                mt = meta_r[op]
                cg = mt >> 16
                nin = (mt >> 5) & 31
                nsp = sp - nin + ((mt >> 10) & 31)
                ok = ((mt & 31) != 0) & (sp - sb >= nin) \
                    & (nsp - sb <= P.STACK_LIMIT) & (nsp <= SC) \
                    & (gas >= cg) & (ls < MAXS)
                k = jnp.where(ok, mt & 31, 0)

                def dispatch(lo, hi):
                    # a balanced tree of branches over the classes (a
                    # switch nests its branches one inside the next,
                    # and Mosaic's layout pass recurses through the nest)
                    if hi - lo == 1:
                        return fams[lo](pc, gas, sp, msize, op, nsp)
                    mid = (lo + hi) // 2
                    return jax.lax.cond(k < mid,
                                        lambda: dispatch(lo, mid),
                                        lambda: dispatch(mid, hi))

                done, npc, xg, nms = dispatch(0, len(fams))
                go = done != 0
                halt = go & (k == C_STOP)
                return (npc, jnp.where(go, gas - cg - xg, gas),
                        jnp.where(go, nsp, sp), nms,
                        ls + done, fs + done,
                        jnp.where(halt, M.STOP, status),
                        (go & ~halt).astype(jnp.int32))

            @pl.when(ro(L_RUN) != 0)
            def _():
                # a lane's stack and memory come in and go back alone:
                # after a block's first round few lanes of a chunk run
                rs = pl.ds(pl.multiple_of(ln * SC * LIMBS, SC * LIMBS),
                           SC * LIMBS)
                rm = pl.ds(pl.multiple_of(ln * MW, MW), MW)
                rk = pl.ds(pl.multiple_of(
                    (ln * S * LIMBS) // KBLK * KBLK, KBLK), KBLK)
                # (in from the outputs: a block of keys and values may
                # hold a lane this call has already written back)
                copy([(stack_o.at[rs], stack), (mem_o.at[rm], mem),
                      (skey_h.at[rk], skey), (sorig_h.at[rk], sorig),
                      (sval_o.at[rk], sval)])
                # the refund counter stays in SMEM (SSTORE adds to it)
                c0 = tuple(regs[r * K + ln] for r in range(R_REFUND))
                out = jax.lax.while_loop(cond, body, c0 + (jnp.int32(1),))
                for r in range(R_REFUND):
                    regs[r * K + ln] = out[r]
                copy([(stack, stack_o.at[rs]), (mem, mem_o.at[rm]),
                      (sval, sval_o.at[rk])])

            return carry

        jax.lax.fori_loop(0, K, lane, jnp.int32(0))
        copy([(regs, regs_o), (sflag, sflag_o)])

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    i32 = jnp.int32
    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((_NREGS * K,), i32),
                   jax.ShapeDtypeStruct((K * SC * LIMBS,), i32),
                   jax.ShapeDtypeStruct((K * MW,), i32),
                   jax.ShapeDtypeStruct((K * S,), i32),
                   jax.ShapeDtypeStruct((K * S * LIMBS,), i32)),
        in_specs=[smem] * 7 + [hbm] * 8,
        out_specs=(hbm,) * 5,
        scratch_shapes=[pltpu.SMEM((_NREGS * K,), i32),
                        pltpu.SMEM((SC * LIMBS,), i32),
                        pltpu.SMEM((MW,), i32),
                        pltpu.SMEM((KBLK,), i32),
                        pltpu.SMEM((KBLK,), i32),
                        pltpu.SMEM((K * S,), i32),
                        pltpu.SMEM((K * S,), i32),
                        pltpu.SMEM((KBLK,), i32),
                        pltpu.SemaphoreType.DMA((5,))],
        input_output_aliases={10: 0, 11: 1, 12: 2, 13: 3, 14: 4},
        interpret=interpret(),
        name="coreth_fused_step")

    def prepare(inputs):
        """What a lane's exec keeps fixed: the opcode tables, the
        block's TIMESTAMP / NUMBER, the packed code, jump bitmap and
        calldata, the ORIGIN / CALLVALUE words."""
        ctx = jnp.stack([jnp.asarray(inputs["timestamp"], i32),
                         jnp.asarray(inputs["number"], i32)])
        return dict(
            meta=jnp.asarray(meta), ctx=ctx,
            code=_pack_bytes(jnp.pad(
                inputs["code_tab"], ((0, 0), (0, 4 * CWP - CW)))).reshape(-1),
            jd=_pack_bits(inputs["jdest_tab"]).reshape(-1),
            cd=_pack_bytes(inputs["calldata"]).reshape(-1),
            data_len=inputs["data_len"],
            clen=inputs["clen_tab"],
            origin=inputs["origin_w"], callvalue=inputs["callvalue"])

    def step(st, pre):
        """One kernel over the exec state `st` (machine field dict):
        every RUN lane stepped until it parks or stops; lanes at the
        step bound leave as HOST / R_STEPS, as the lockstep loop's
        bound left them."""
        deep = st["dep"] > 0
        run = (st["status"] == M.RUN) & (st["lsteps"] < MAXS)
        lro = jnp.stack([
            run.astype(i32), st["sb"], st["mb"], st["cdb"],
            jnp.where(deep, st["cdl"], pre["data_len"]), deep.astype(i32),
            st["cid"], st["rdl"], pre["clen"][st["cid"]],
            st["aid"], st["stat"]]).reshape(-1)
        words = jnp.stack([
            st["addr_w"], pre["origin"], st["caller_w"],
            jnp.where(deep[:, None], 0, pre["callvalue"])]).reshape(-1)
        regs = jnp.stack([st["pc"], st["gas"], st["sp"], st["msize"],
                          st["lsteps"], st["fsteps"],
                          st["status"], st["refund"]]).reshape(-1)
        regs, stack, mem, sflag, sval = call(
            pre["meta"], pre["ctx"], lro, words, pre["code"], pre["jd"],
            pre["cd"], st["skey"].reshape(-1), st["sorig"].reshape(-1),
            st["saddr"].reshape(-1), regs, st["stack"].reshape(-1),
            _pack_bytes(st["mem"]).reshape(-1), st["sflag"].reshape(-1),
            st["sval"].reshape(-1))
        regs = regs.reshape(_NREGS, K)
        out = dict(st)
        out.update(pc=regs[R_PC], gas=regs[R_GAS], sp=regs[R_SP],
                   msize=regs[R_MSIZE], lsteps=regs[R_LSTEPS],
                   fsteps=regs[R_FSTEPS], status=regs[R_STATUS],
                   stack=stack.reshape(st["stack"].shape),
                   sflag=sflag.reshape(st["sflag"].shape),
                   sval=sval.reshape(st["sval"].shape),
                   refund=regs[R_REFUND],
                   mem=_unpack_bytes(mem.reshape(K, MW)))
        return out

    return prepare, step

"""Host adapter for the device step machine.

Packs a batch of same-block transactions into machine inputs, runs the
miss-and-rerun storage rounds, and unpacks per-tx results
(status / gas_used / refund / logs / storage read- and write-sets) for
the replay engine or tests.

The cross-tx ordering problem (txs of one block executing in parallel
against block-start state) is solved by the caller via optimistic
validate-retry (replay/engine.py): this module only executes a batch
against the pre-states it is handed.
"""

from __future__ import annotations

import os
import threading as _threading
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from coreth_tpu import faults
from coreth_tpu.crypto.keccak import keccak256_many
from coreth_tpu import obs
from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device import tables as T
from coreth_tpu.evm.device.specialize import KDIG_CAP
from coreth_tpu.ops import u256

# Same seam the transfer path's supervised _issue_window fires
# (replay/engine.py declares the doc for it): a fused-OCC window
# dispatch raising mid-run.  Fired BEFORE any packing mutates the
# runner, so a faulted issue() is safe to retry.
PT_DISPATCH = faults.declare(
    "device/dispatch", "raise at window dispatch (transfer + fused OCC)")


# One shared background compile thread for pre-warm traces: on CPU
# hosts the pre-bucket compile was SYNCHRONOUS inside issue() (ROADMAP
# PR-9 follow-up), serializing a full XLA trace behind the dispatch it
# was supposed to hide.  A single worker keeps compile order
# deterministic; _get_kernel joins any in-flight warm for the bucket
# it is about to dispatch, so the retrace accounting (and the
# kernel_retraces == 0 gate) is unchanged.
_COMPILE_POOL = None


def _compile_pool():
    global _COMPILE_POOL
    if _COMPILE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _COMPILE_POOL = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="coreth-compile")
    return _COMPILE_POOL



def wait_warm_compiles() -> None:
    """Block until every pre-warm compile queued so far has finished
    (the pool is one FIFO worker) — for callers that count compiles
    per phase."""
    if _COMPILE_POOL is not None:
        _COMPILE_POOL.submit(lambda: None).result()


WORD_ZERO = b"\x00" * 32

# Per-runner cap on specialized programs compiled into one OCC kernel:
# every program is a straight-line sub-program in the same XLA build,
# so an unbounded set would bloat compile time; past the cap new
# contracts stay on the generic kernel (counted as escapes).
SPEC_SET_CAP = 8

# Device dispatches issued through this module (single-shot machine
# runs AND fused OCC windows).  The bench prints dispatches-per-block
# from it and the OCC-equivalence tests assert the O(txs) -> O(1)
# reduction against it.  Mutated under _DISPATCH_MU: dispatch can move
# off the main thread (warm-compile pool, future scale-out workers)
# and a bare += loses increments exactly when the count matters most.
DISPATCH_COUNT = 0
_DISPATCH_MU = _threading.Lock()


def _count_dispatch() -> int:
    """Counts one dispatch and marks it in flight (obs.device_issue:
    the calling thread's account is ticked, so a machine block never
    reads as an empty chip).  Returns the ticket the result fetch
    retires with obs.device_done."""
    global DISPATCH_COUNT
    with _DISPATCH_MU:
        DISPATCH_COUNT += 1
    return obs.device_issue()


@jax.jit
def _scatter_rows(tab, idx, rows):
    """Jitted row scatter for the appended-gid table sync: the eager
    ``.at[].set`` pays ms-scale host-side lowering per call; jit
    amortizes it to a cache hit per append-batch shape."""
    return tab.at[idx].set(rows, mode="drop")


def addr_word(addr: bytes) -> int:
    return int.from_bytes(addr, "big")


def word16(v: int) -> np.ndarray:
    """u256 int -> 16 little-endian int32 limbs (the machine layout)."""
    return np.frombuffer(
        v.to_bytes(32, "little"), dtype=np.uint16).astype(np.int32)


_WORD16_CACHE: Dict[int, np.ndarray] = {}


def word16c(v: int) -> np.ndarray:
    """Cached, read-only word16: the window packer converts the same
    caller/contract/gas-price words every window (senders recur all
    chain), so the per-lane to_bytes/frombuffer pair amortizes to a
    dict hit.  Returned arrays are frozen — callers ASSIGN them into
    batch tensors (a copy), never mutate."""
    w = _WORD16_CACHE.get(v)
    if w is None:
        if len(_WORD16_CACHE) > (1 << 16):
            _WORD16_CACHE.clear()  # unbounded value streams: reset
        w = word16(v)
        w.setflags(write=False)
        _WORD16_CACHE[v] = w
    return w


def _norm_slot_key(key: bytes) -> bytes:
    """Normal-storage partition of a raw 32-byte slot key: bit 0 of
    byte 0 cleared — the twin of statedb.normalize_state_key and of the
    machine's limb-15 `& 0xFEFF` mask, applied host-side to predicted
    keccak keys so they compare equal to the keys the kernel reports."""
    return bytes([key[0] & 0xFE]) + key[1:]


def _cd_word(data: bytes, w: int) -> bytes:
    """ABI calldata word `w` (32 bytes past the 4-byte selector),
    zero-padded exactly like CALLDATALOAD past the end."""
    word = data[4 + 32 * w:4 + 32 * w + 32]
    return word + b"\x00" * (32 - len(word))


_ARR_BASE: Dict[int, int] = {}


def _arr_base(slot: int) -> int:
    """keccak(pad32(slot)) as an int — the Solidity dynamic-array data
    base; element i lives at base + i.  Depends only on the (small,
    recipe-recorded) slot index, so it caches process-wide and the
    per-lane array-key derivation is pure host arithmetic (no keccak
    batch at premap time at all)."""
    v = _ARR_BASE.get(slot)
    if v is None:
        from coreth_tpu.crypto import keccak256
        v = int.from_bytes(keccak256(slot.to_bytes(32, "big")), "big")
        _ARR_BASE[slot] = v
    return v


# Process-wide learned-recipe store (see MachineWindowRunner.__init__:
# recipes are pure code-derived facts, shared across runners/engines)
RECIPES: Dict[bytes, Dict[tuple, None]] = {}


_STATIC_PREMAP: Dict[bytes, Tuple[bytes, ...]] = {}


def _static_premap(code: bytes) -> Tuple[bytes, ...]:
    """PUSH-constant storage footprint of `code` as normalized premap
    keys (census.static_storage_keys — the swap pool's reserve slots),
    () when any key is computed.  Statically-footprinted contracts
    premap with no discovery cycle at all."""
    cached = _STATIC_PREMAP.get(code)
    if cached is None:
        from coreth_tpu.evm.census import static_storage_keys
        ks = static_storage_keys(code)
        out: Dict[bytes, None] = {}
        if ks is not None:
            for k in ks[0] + ks[1]:
                out[_norm_slot_key(k)] = None
        cached = _STATIC_PREMAP[code] = tuple(out)
    return cached


@dataclass
class TxSpec:
    """One machine transaction: a plain call into device-eligible code."""
    code: bytes
    calldata: bytes
    gas: int                      # gas available for execution
    value: int
    caller: bytes                 # 20-byte address
    address: bytes                # 20-byte contract address
    origin: bytes
    gas_price: int
    # (key32 -> (current, original)) pre-resolved storage view
    storage: Dict[bytes, Tuple[int, int]] = field(default_factory=dict)
    # access-list pre-warmed slots (EIP-2930); also marked warm
    warm_slots: Tuple[bytes, ...] = ()


@dataclass
class BlockEnv:
    coinbase: bytes
    timestamp: int
    number: int
    gas_limit: int
    chain_id: int
    base_fee: int = 0


@dataclass
class TxResult:
    status: int                   # machine status code (M.STOP, ...)
    gas_left: int
    refund: int
    logs: List[Tuple[List[bytes], bytes]]   # (topics, data)
    reads: Dict[bytes, int]       # key -> observed pre-tx value
    writes: Dict[bytes, int]      # key -> final value (uncommitted)
    host_reason: int = 0

    @property
    def ok(self) -> bool:
        return self.status == M.STOP

    @property
    def needs_host(self) -> bool:
        return self.status == M.HOST


def _pow2(n: int, floor: int) -> int:
    v = floor
    while v < n:
        v *= 2
    return v


class MachineRunner:
    """Executes batches of TxSpecs under one fork + block env.

    storage_resolver(address, key32) -> int supplies committed values
    for keys the machine discovered (miss rounds).
    """

    def __init__(self, fork: str, env: BlockEnv,
                 storage_resolver: Callable[[bytes, bytes], int],
                 max_rounds: int = 6):
        self.fork = fork
        self.env = env
        self.resolver = storage_resolver
        self.max_rounds = max_rounds

    def _params(self, txs: List[TxSpec]) -> M.MachineParams:
        feats = set()
        max_code = 64
        max_data = 64
        max_slots = 4
        for t in txs:
            info = T.scan_code(t.code, self.fork)
            feats |= set(info.features)
            max_code = max(max_code, len(t.code))
            max_data = max(max_data, len(t.calldata))
            max_slots = max(max_slots, len(t.storage) + 8)
        return M.MachineParams(
            fork=self.fork,
            batch=_pow2(len(txs), 8),
            code_cap=_pow2(max_code, 256),
            data_cap=_pow2(max_data, 128),
            scache_cap=_pow2(max_slots, 8),
            features=frozenset(feats),
        )

    def _pack(self, txs: List[TxSpec], p: M.MachineParams) -> dict:
        B = p.batch
        code = np.zeros((B, p.code_cap + 33), dtype=np.int32)
        code_len = np.zeros((B,), dtype=np.int32)
        jdest = np.zeros((B, p.code_cap), dtype=np.int32)
        calldata = np.zeros((B, p.data_cap), dtype=np.int32)
        data_len = np.zeros((B,), dtype=np.int32)
        start_gas = np.zeros((B,), dtype=np.int32)
        active = np.zeros((B,), dtype=bool)
        S = p.scache_cap
        skey = np.zeros((B, S, u256.LIMBS), dtype=np.int32)
        sval = np.zeros((B, S, u256.LIMBS), dtype=np.int32)
        sorig = np.zeros((B, S, u256.LIMBS), dtype=np.int32)
        sflag = np.zeros((B, S), dtype=np.int32)
        scnt = np.zeros((B,), dtype=np.int32)
        words = {k: np.zeros((B, u256.LIMBS), dtype=np.int32)
                 for k in ("callvalue", "caller_w", "address_w",
                           "origin_w", "gasprice_w")}

        def wordify(v: int):
            return np.frombuffer(
                v.to_bytes(32, "little"), dtype=np.uint16
            ).astype(np.int32)

        for i, t in enumerate(txs):
            cb = np.frombuffer(t.code, dtype=np.uint8)
            code[i, :len(cb)] = cb
            code_len[i] = len(cb)
            info = T.scan_code(t.code, self.fork)
            for d in info.jumpdests:
                if d < p.code_cap:
                    jdest[i, d] = 1
            db = np.frombuffer(t.calldata, dtype=np.uint8)
            calldata[i, :len(db)] = db
            data_len[i] = len(db)
            start_gas[i] = t.gas
            active[i] = True
            words["callvalue"][i] = wordify(t.value)
            words["caller_w"][i] = wordify(addr_word(t.caller))
            words["address_w"][i] = wordify(addr_word(t.address))
            words["origin_w"][i] = wordify(addr_word(t.origin))
            words["gasprice_w"][i] = wordify(t.gas_price)
            for j, (key, (cur, orig)) in enumerate(t.storage.items()):
                skey[i, j] = wordify(int.from_bytes(key, "big"))
                sval[i, j] = wordify(cur)
                sorig[i, j] = wordify(orig)
                sflag[i, j] = M.F_VALID | (
                    M.F_WARM if key in t.warm_slots else 0)
            scnt[i] = len(t.storage)

        env = self.env
        inputs = dict(
            code=jnp.asarray(code), jdest=jnp.asarray(jdest),
            code_len=jnp.asarray(code_len),
            calldata=jnp.asarray(calldata),
            data_len=jnp.asarray(data_len),
            start_gas=jnp.asarray(start_gas),
            active=jnp.asarray(active),
            skey=jnp.asarray(skey), sval=jnp.asarray(sval),
            sorig=jnp.asarray(sorig), sflag=jnp.asarray(sflag),
            scnt=jnp.asarray(scnt),
            callvalue=jnp.asarray(words["callvalue"]),
            caller_w=jnp.asarray(words["caller_w"]),
            address_w=jnp.asarray(words["address_w"]),
            origin_w=jnp.asarray(words["origin_w"]),
            gasprice_w=jnp.asarray(words["gasprice_w"]),
            timestamp=jnp.int32(env.timestamp),
            number=jnp.int32(env.number),
            gaslimit=jnp.int32(min(env.gas_limit, (1 << 31) - 1)),
            coinbase_w=jnp.asarray(wordify(addr_word(env.coinbase))),
            chainid_w=jnp.asarray(wordify(env.chain_id)),
            basefee_w=jnp.asarray(wordify(env.base_fee)),
        )
        return inputs

    def run(self, txs: List[TxSpec]) -> List[TxResult]:
        """Execute txs (independently, against their given pre-states),
        resolving storage misses through rerun rounds.

        Raises ValueError when a TxSpec's code is not device-eligible:
        scan_code returns empty jumpdests for ineligible code, so any
        taken JUMP would silently become a bad_jump ERR (gas burned)
        instead of a HOST escape — callers must route such txs to the
        host interpreter themselves (machine_block.classify does)."""
        txs = list(txs)
        for t in txs:
            info = T.scan_code(t.code, self.fork)
            if not info.eligible:
                raise ValueError(
                    f"TxSpec code not device-eligible: {info.reason}")
        for _ in range(self.max_rounds):
            p = self._params(txs)
            fn = M.get_machine(p)
            ticket = _count_dispatch()
            out = PackedOut(np.asarray(fn(self._pack(txs, p))["packed"]),
                            p)
            obs.device_done(ticket)
            missing = self._collect_misses(out, txs)
            if not missing:
                return self._unpack(out, txs)
            for i, keys in missing.items():
                t = txs[i]
                for key in keys:
                    v = self.resolver(t.address, key)
                    t.storage[key] = (v, v)
        # rounds exhausted: anything still missing goes to host
        out_res = self._unpack(out, txs)
        for i in self._collect_misses(out, txs):
            out_res[i].status = M.HOST
            out_res[i].host_reason = M.R_SCACHE
        return out_res

    def _collect_misses(self, out: "PackedOut",
                        txs) -> Dict[int, List[bytes]]:
        missing: Dict[int, List[bytes]] = {}
        for i, t in enumerate(txs):
            # HOST lanes go to the host interpreter anyway; ERR lanes
            # may have mispriced on a speculative miss value, so they
            # must resolve + rerun too
            keys = []
            for key in miss_keys(out, i):
                if key not in t.storage:
                    keys.append(key)
            if keys:
                missing[i] = keys
        return missing

    def _unpack(self, out: "PackedOut", txs) -> List[TxResult]:
        return results_for_rows(out, np.arange(len(txs)))


# ------------------------------------------------------------ unpack
def _be_blob(arr: np.ndarray) -> bytes:
    """Little-endian 16-limb words -> one flat blob of 32-byte
    BIG-endian values (limb order reversed, each limb written as a
    big-endian u16): the bulk twin of the old per-entry join — the
    unpack path runs once per LANE per window, and python-level byte
    joins were ~20% of the whole replay wall on the specialized
    erc20-machine profile."""
    return np.ascontiguousarray(arr[..., ::-1]).astype(">u2").tobytes()


class PackedOut:
    """View over the machine's single packed output tensor (one
    device->host transfer; see machine.py 'packed').  Byte-level
    views (storage keys/values, log topics/data) convert ONCE per
    window via numpy and are sliced per entry."""

    def __init__(self, blob: np.ndarray, p: M.MachineParams):
        S, LC, LD = p.scache_cap, p.log_cap, p.log_data_cap
        self.S, self.LC, self.LD = S, LC, LD
        o = 0

        def take(n, shape=None):
            nonlocal o
            v = blob[:, o:o + n]
            o += n
            return v if shape is None else v.reshape(
                (blob.shape[0],) + shape)

        self.status = take(1)[:, 0]
        self.gas = take(1)[:, 0]
        self.refund = take(1)[:, 0]
        self.host_reason = take(1)[:, 0]
        self.scnt = take(1)[:, 0]
        self.sflag = take(S)
        self.skey = take(S * 16, (S, 16))
        self.sval = take(S * 16, (S, 16))
        self.sorig = take(S * 16, (S, 16))
        self.log_nt = take(LC)
        self.log_dlen = take(LC)
        self.log_cnt = take(1)[:, 0]
        self.log_top = take(LC * 4 * 16, (LC, 4, 16))
        self.log_data = take(LC * LD, (LC, LD))
        self._kb = self._vb = self._ob = None
        self._tb = self._db = None

    def key_blob(self) -> bytes:
        if self._kb is None:
            self._kb = _be_blob(self.skey)
        return self._kb

    def val_blob(self) -> bytes:
        if self._vb is None:
            self._vb = _be_blob(self.sval)
        return self._vb

    def orig_blob(self) -> bytes:
        if self._ob is None:
            self._ob = _be_blob(self.sorig)
        return self._ob

    def topic_blob(self) -> bytes:
        if self._tb is None:
            self._tb = _be_blob(self.log_top)
        return self._tb

    def data_blob(self) -> bytes:
        if self._db is None:
            self._db = self.log_data.astype(np.uint8).tobytes()
        return self._db


def _key_bytes(limbs: np.ndarray) -> bytes:
    return b"".join(
        int(limbs[l]).to_bytes(2, "little") for l in range(16)
    )[::-1]


def _word_int(limbs: np.ndarray) -> int:
    v = 0
    for l in range(16):
        v |= int(limbs[l]) << (16 * l)
    return v


def miss_keys(out: PackedOut, i: int) -> List[bytes]:
    """Storage keys lane i touched that were NOT in its seeded cache
    (F_MISS entries — executed against a speculative zero)."""
    keys = []
    n = int(out.scnt[i])
    if not n:
        return keys
    kb = out.key_blob()
    flags = out.sflag[i]
    for j in range(n):
        if flags[j] & M.F_MISS:
            off = (i * out.S + j) * 32
            keys.append(kb[off:off + 32])
    return keys


def _kreq_ctx_bytes(op: int, t, env) -> bytes:
    """The 32-byte context word a lane's traced keccak request reads —
    must equal the DEVICE input word bit-for-bit (specialize.HOST_CTX
    admits only full-width words, so these are plain paddings)."""
    if op == 0x33:
        return b"\x00" * 12 + t.caller
    if op == 0x30:
        return b"\x00" * 12 + t.address
    if op == 0x32:
        return b"\x00" * 12 + t.origin
    if op == 0x34:
        return t.value.to_bytes(32, "big")
    if op == 0x3A:
        return t.gas_price.to_bytes(32, "big")
    if op == 0x41:
        return b"\x00" * 12 + env.coinbase
    if op == 0x46:
        return env.chain_id.to_bytes(32, "big")
    if op == 0x48:
        return env.base_fee.to_bytes(32, "big")
    # a HOST_CTX opcode this function does not know would silently
    # produce a wrong keccak input the specialized kernel TRUSTS —
    # fail loudly instead of diverging downstream at the root check
    raise ValueError(f"unhandled kdig ctx opcode {op:#04x}")


def fill_kdig(kdig: np.ndarray, jobs) -> None:
    """Evaluate collected keccak requests and write their digest limbs.

    jobs: (bi, fl, t, env, reqs) per specialized lane.  Requests
    nest (("kdig", j) words reference earlier slots), so evaluation
    batches by readiness level — one keccak256_many crossing per
    level, vectorized limb scatter at the end."""
    if not jobs:
        return
    done: List[List[Optional[bytes]]] = [
        [None] * len(reqs) for (_bi, _fl, _t, _env, reqs) in jobs]
    while True:
        msgs, where = [], []
        pending = False
        for ji, (_bi, _fl, t, env, reqs) in enumerate(jobs):
            for k, desc in enumerate(reqs):
                if done[ji][k] is not None:
                    continue
                parts, ready = [], True
                for d in desc:
                    kind = d[0]
                    if kind == "const":
                        parts.append(d[1].to_bytes(32, "big"))
                    elif kind == "ctx":
                        parts.append(_kreq_ctx_bytes(d[1], t, env))
                    elif kind == "data":
                        b = t.calldata[d[1]:d[1] + 32]
                        parts.append(b + b"\x00" * (32 - len(b)))
                    else:  # ("kdig", j): an earlier slot's digest
                        dj = done[ji][d[1]]
                        if dj is None:
                            ready = False
                            break
                        parts.append(dj)
                if not ready:
                    pending = True
                    continue
                msgs.append(b"".join(parts))
                where.append((ji, k))
        if not msgs:
            break
        for (ji, k), dg in zip(where, keccak256_many(msgs)):
            done[ji][k] = dg
        if not pending:
            break
    fills = [(jobs[ji][0], jobs[ji][1], k, dg)
             for ji, row in enumerate(done)
             for k, dg in enumerate(row) if dg is not None]
    if fills:
        idx = np.array([(bi, fl, k) for bi, fl, k, _ in fills],
                       dtype=np.int64)
        blob = b"".join(dg[::-1] for _bi, _fl, _k, dg in fills)
        limbs = np.frombuffer(blob, dtype=np.uint16).reshape(
            -1, u256.LIMBS).astype(np.int32)
        kdig[idx[:, 0], idx[:, 1], idx[:, 2]] = limbs


def result_from_row(out: PackedOut, i: int) -> TxResult:
    """One lane's TxResult from a PackedOut row."""
    reads: Dict[bytes, int] = {}
    writes: Dict[bytes, int] = {}
    n = int(out.scnt[i])
    if n:
        kb, vb, ob = out.key_blob(), out.val_blob(), out.orig_blob()
        flags = out.sflag[i]
        for j in range(n):
            fl = int(flags[j])
            if not fl & M.F_VALID:
                continue
            off = (i * out.S + j) * 32
            key = kb[off:off + 32]
            if fl & M.F_READ:
                reads[key] = int.from_bytes(ob[off:off + 32], "big")
            if fl & M.F_WRITTEN:
                writes[key] = int.from_bytes(vb[off:off + 32], "big")
    logs = []
    nl = int(out.log_cnt[i])
    if nl:
        tb, db = out.topic_blob(), out.data_blob()
        LC, LD = out.LC, out.LD
        for j in range(nl):
            base = ((i * LC + j) * 4) * 32
            topics = [tb[base + 32 * k:base + 32 * (k + 1)]
                      for k in range(int(out.log_nt[i, j]))]
            doff = (i * LC + j) * LD
            data = db[doff:doff + int(out.log_dlen[i, j])]
            logs.append((topics, data))
    return TxResult(
        status=int(out.status[i]), gas_left=int(out.gas[i]),
        refund=int(out.refund[i]), logs=logs, reads=reads,
        writes=writes, host_reason=int(out.host_reason[i]))


def results_for_rows(out: PackedOut, rows) -> List[TxResult]:
    """TxResults for many PackedOut rows in one pass.

    The per-lane ``result_from_row`` pays a numpy scalar index + bounds
    check per field per lane (~86us/lane on the erc20-machine shape —
    ~15% of replay wall).  Here the validity masks, flag tests, and
    int conversions happen once per call as array ops; the remaining
    Python loop touches only entries that exist (``nonzero`` of the
    mask), not the padded S/LC capacity."""
    rows = np.asarray(rows, dtype=np.int64)
    n = rows.shape[0]
    if not n:
        return []
    status = out.status[rows].tolist()
    gas = out.gas[rows].tolist()
    refund = out.refund[rows].tolist()
    hreason = out.host_reason[rows].tolist()
    reads_l: List[Dict[bytes, int]] = [{} for _ in range(n)]
    writes_l: List[Dict[bytes, int]] = [{} for _ in range(n)]
    logs_l: List[list] = [[] for _ in range(n)]
    scnt = out.scnt[rows]
    if scnt.any():
        S = out.S
        sf = out.sflag[rows]
        valid = (np.arange(S)[None, :] < scnt[:, None]) \
            & ((sf & M.F_VALID) != 0)
        ki, si = np.nonzero(valid)
        if ki.size:
            kb, vb, ob = out.key_blob(), out.val_blob(), out.orig_blob()
            fl = sf[ki, si]
            rd = ((fl & M.F_READ) != 0).tolist()
            wr = ((fl & M.F_WRITTEN) != 0).tolist()
            offs = ((rows[ki] * S + si) * 32).tolist()
            which = ki.tolist()
            for t, o in enumerate(offs):
                key = kb[o:o + 32]
                k = which[t]
                if rd[t]:
                    reads_l[k][key] = int.from_bytes(ob[o:o + 32], "big")
                if wr[t]:
                    writes_l[k][key] = int.from_bytes(vb[o:o + 32], "big")
    lc = out.log_cnt[rows]
    if lc.any():
        LC, LD = out.LC, out.LD
        li, lj = np.nonzero(np.arange(LC)[None, :] < lc[:, None])
        if li.size:
            tb, db = out.topic_blob(), out.data_blob()
            nt = out.log_nt[rows][li, lj].tolist()
            dl = out.log_dlen[rows][li, lj].tolist()
            base = (((rows[li] * LC + lj) * 4) * 32).tolist()
            doff = ((rows[li] * LC + lj) * LD).tolist()
            which = li.tolist()
            for t, b in enumerate(base):
                topics = [tb[b + 32 * k:b + 32 * (k + 1)]
                          for k in range(nt[t])]
                d = doff[t]
                logs_l[which[t]].append((topics, db[d:d + dl[t]]))
    return [TxResult(status=status[k], gas_left=gas[k],
                     refund=refund[k], logs=logs_l[k],
                     reads=reads_l[k], writes=writes_l[k],
                     host_reason=hreason[k])
            for k in range(n)]


# ----------------------------------------------------------- OCC window
@dataclass
class WindowResult:
    """Per-block outcome of one fused OCC window (see
    machine.build_occ_machine).  `clean[k]` means every lane of block k
    committed on device; a dirty block (and everything after it, whose
    base table is speculative) must be redone by the caller."""
    results: List[List[TxResult]]       # per block, per call lane
    committed: List[np.ndarray]         # (lanes,) bool per block
    escape: List[np.ndarray]            # (lanes,) bool per block
    clean: List[bool]
    rounds: List[int]                   # device OCC rounds per block
    attempts: int                       # dispatches this window took


class MachineWindowRunner:
    """Device-resident OCC over WINDOWS of machine blocks.

    One dispatch executes up to `blocks`-many machine blocks: the
    Block-STM round loop, read-set validation, and cross-block state
    folding all run inside the jitted program against a global
    slot-value table resident in HBM (machine.build_occ_machine).  The
    host only supplies per-lane inputs and a premapped slot-id layout,
    and fetches one packed result tensor per window — dispatches per
    machine block drop from O(txs) (one per OCC round, round-5 design)
    to O(1).

    Persistent across windows:
    - ``slot_gid``: (contract, key32) -> global table row;
    - ``vals``: host mirror of committed slot values at the last fold
      point (rebuild source when the device table is invalidated);
    - ``table``/``key_tab``: the device-resident value/key tables; the
      value table is DONATED through each dispatch so the
      window-to-window handoff aliases HBM instead of copying;
    - ``recipes``: per-contract, selector-scoped PREMAP PREDICTORS
      learned from misses — a recipe (selector, "caller"|"data"+word,
      slot) says lanes calling `selector` touch
      ``keccak(pad32(source) || pad32(slot))`` (the Solidity
      mapping rule); applying a lane's recipes to ITS OWN calldata
      derives the keccak-keyed slots it will touch BEFORE dispatch, so
      erc20-style fresh recipients no longer pay the miss-and-rerun
      second dispatch every window.  PUSH-constant footprints
      (census.static_storage_keys — the swap reserves) premap with no
      learning at all;
    - ``common``: per-contract keys observed in every lane so far (the
      residual heuristic for keys neither static nor keccak-derivable;
      anything still outside the premap surfaces as an F_MISS escape
      and resolves through the bounded re-dispatch loop, counted in
      ``discovery_dispatches``).
    """

    COMMON_CAP = 8   # premapped common keys per contract
    RECIPE_CAP = 8   # learned keccak recipes per contract
    SLOT_SCAN = 4    # mapping slot indices a miss is explained against
    DATA_WORDS = 4   # calldata words considered as mapping sources
    ARRAY_SPAN = 1 << 32  # max index an array recipe explains with

    def __init__(self, fork: str,
                 storage_resolver: Callable[[bytes, bytes], int],
                 max_attempts: int = 6):
        self.fork = fork
        self.resolver = storage_resolver
        self.max_attempts = max_attempts
        self.slot_gid: Dict[Tuple[bytes, bytes], int] = {}
        self.gid_keys: List[Tuple[bytes, bytes]] = []
        self.vals: List[int] = []
        # contract -> {key32: None} (dict-as-ordered-set: deterministic
        # iteration, unlike a set)
        self.common: Dict[bytes, Dict[bytes, None]] = {}
        # bytecode -> {recipe: None}; recipe =
        # (selector, "caller", slot) | (selector, "data", word, slot)
        # — selector-scoped so one function's mapping pattern never
        # predicts (and permanently maps) keys for another's lanes.
        # The store is MODULE-level (shared, monotone, capped): a
        # recipe is a pure fact about a bytecode's keccak structure —
        # like trace eligibility or an XLA compile, not state — so a
        # fresh engine skips the discovery dispatches an earlier runner
        # already paid for the same contract.
        self.recipes = RECIPES
        self.table = None
        self.key_tab = None
        self.table_cap = 0
        self._synced = 0          # gids present in the device tables
        self._stale = True        # device table != mirror: full rebuild
        # predicted premaps + pre-bucketed recompile-free growth are
        # each independently A/B-able (the equivalence tests pin the
        # legacy miss-and-rerun / rebuild-and-retrace paths)
        self._predict = bool(int(os.environ.get(
            "CORETH_PREMAP_PREDICT", "1")))
        # second-level (nested-mapping) recipes — allowance-style
        # keccak(pad32(b) || keccak(pad32(a) || pad32(p))) keys — are
        # separately A/B-able under the prediction umbrella
        self._nest = bool(int(os.environ.get(
            "CORETH_PREMAP_NEST", "1")))
        # array-slot arithmetic recipes (keccak(slot) + i) — the third
        # learned premap shape (dynamic-array elements indexed by a
        # calldata word), separately A/B-able
        self._arr = bool(int(os.environ.get(
            "CORETH_PREMAP_ARR", "1")))
        self._prebucket = bool(int(os.environ.get(
            "CORETH_GROWTH_PREBUCKET", "1")))
        # per-contract traced specialization (evm/device/specialize):
        # machine-eligible code whose bytecode traces to a straight-
        # line program executes with no opcode switch; CORETH_
        # SPECIALIZE=0 keeps every lane on the generic interpreter
        self._specialize = bool(int(os.environ.get(
            "CORETH_SPECIALIZE", "1")))
        # code -> program index (sticky: the set only grows, so the
        # kernel memo key ratchets like the feature set); codes the
        # tracer rejected are cached separately
        self._spec_progs: Dict[bytes, int] = {}
        self._spec_bad: set = set()
        # code -> host-evaluated keccak requests (specialize.
        # spec_requests): the issue path computes these digests per
        # lane in one C++ batch and ships them as the `kdig` input
        self._spec_reqs: Dict[bytes, Tuple] = {}
        # (code, code_cap) -> (dense code row, jdest row, len): the
        # window packer copies these per lane instead of re-scanning
        # bytecode and re-walking jumpdests (hot-path profile item)
        self._code_rows: Dict[Tuple[bytes, int], Tuple] = {}
        # window code-assignment signature -> converted device arrays
        # (code, jdest, code_len); see issue() — capped at 2 entries
        self._win_code_cache: Dict[Tuple, Tuple] = {}
        # pre-warm compiles ride the background compile thread by
        # default; CORETH_COMPILE_THREAD=0 restores the synchronous
        # compile for A/B (and the legacy CORETH_GROWTH_PREBUCKET=0
        # path never pre-warms at all)
        self._compile_async = bool(int(os.environ.get(
            "CORETH_COMPILE_THREAD", "1")))
        self._warm_pending: Dict[tuple, object] = {}
        self._hw: Dict[str, int] = {}   # sticky pow2 shape high-water
        self._hw_feats: frozenset = frozenset()
        self._dispatched = 0
        # kernel buckets this runner has used or pre-warmed; a dispatch
        # outside the set after the first window is a mid-run retrace
        self._buckets_used: set = set()
        # arena floor projected from a short lead window (see _prewarm)
        self._table_floor = 0
        # cold start spans the FIRST window including its discovery
        # attempts (their scache/shape buckets are first-compile cost,
        # not regressions); retraces count from the second window on
        self._cold = True
        # ---- counters (surfaced via machine stats + bench)
        self.premap_predicted = 0   # predicted keys seeded into premaps
        self.premap_hits = 0        # predicted keys lanes then touched
        self.premap_nested = 0      # keys derived via 2nd-level recipes
        self.premap_array = 0       # keys derived via array recipes
        self.discovery_dispatches = 0  # re-dispatches for missed keys
        self.kernel_retraces = 0    # mid-run compiles at dispatch time
        self.warm_failures = 0      # background pre-warms that raised
        self.lanes_specialized = 0  # lanes run on a traced sub-program
        self.specialize_escapes = 0  # lanes kept on the generic kernel
        self.programs_traced = 0    # contracts compiled to sub-programs
        # the self-time account the runner's phases are charged to
        # (machine/upload, dispatch, fetch_wait): the owning engine's,
        # which its executor sets; nobody's for a bare runner
        self.account = obs.NULL_ACCOUNT
        # lane fill of the windows dispatched (every attempt): call
        # lanes packed against blocks x lanes uploaded and scanned
        self.lanes_real = 0
        self.lanes_padded = 0
        # key-range sharding surface (evm/device/shard.py overrides
        # populate these; the single-chip runner has no shards, so they
        # stay zero — machine_counters() reads them uniformly)
        self.kr_lanes = 0           # lanes placed by key-range bucket
        self.load_imb_sum = 0       # sum of per-window max/mean lane
        #                             occupancy ratios, in PERMILLE
        #                             (integer: this package is in the
        #                             determinism lint scope)
        self.load_imb_windows = 0   # windows that ratio covers
        self.exchange_psum = 0      # sync-exchange windows by mode
        self.exchange_ppermute = 0

    # ------------------------------------------------------------ state
    def reset(self) -> None:
        """Drop every mapping and device buffer (another execution path
        rewrote storage: mirror values can no longer be trusted).
        Learned recipes survive — they derive keys from code+calldata
        shape, not from any storage value."""
        self.slot_gid.clear()
        self.gid_keys = []
        self.vals = []
        self.common.clear()
        self.table = None
        self.key_tab = None
        self.table_cap = 0
        self._synced = 0
        self._stale = True

    def invalidate(self) -> None:
        """Device table no longer matches the committed state (a dirty
        window left partial writes in it); the next issue() rebuilds it
        from the host mirror."""
        self._stale = True

    def commit_block(self,
                     writes: Dict[Tuple[bytes, bytes], int]) -> None:
        """Fold one committed block's storage writes into the host
        mirror (device-committed blocks already carry them in the
        resident table; legacy-path blocks require invalidate())."""
        for (contract, key), v in writes.items():
            g = self.slot_gid.get((contract, key))
            if g is None:
                # map it with the known committed value so future
                # windows can premap without a trie read
                g = len(self.vals)
                self.slot_gid[(contract, key)] = g
                self.gid_keys.append((contract, key))
                self.vals.append(v)
            else:
                self.vals[g] = v

    def _gid(self, contract: bytes, key: bytes) -> int:
        g = self.slot_gid.get((contract, key))
        if g is None:
            g = len(self.vals)
            self.slot_gid[(contract, key)] = g
            self.gid_keys.append((contract, key))
            self.vals.append(self.resolver(contract, key))
        return g

    def _key_mapped(self, contract: bytes, key: bytes) -> bool:
        return (contract, key) in self.slot_gid

    def _mapped_rows(self) -> int:
        """Rows the (largest) table arena must hold right now."""
        return len(self.vals)

    # ----------------------------------------------------- specialization
    def _spec_id(self, code: bytes) -> int:
        """Specialized-program index for `code` (-1 = generic kernel).
        First sighting of eligible code ADDS it to the sticky program
        set (the kernel key ratchets exactly like the feature set —
        workloads stabilize their hot-contract set in the cold first
        window, so steady state adds nothing)."""
        if not self._specialize:
            return -1
        idx = self._spec_progs.get(code)
        if idx is not None:
            return idx
        if code in self._spec_bad \
                or len(self._spec_progs) >= SPEC_SET_CAP:
            return -1
        from coreth_tpu.evm.device.specialize import trace_eligible
        ok, _reason = trace_eligible(code, self.fork)
        if not ok:
            self._spec_bad.add(code)
            return -1
        idx = len(self._spec_progs)
        self._spec_progs[code] = idx
        from coreth_tpu.evm.device.specialize import spec_requests
        self._spec_reqs[code] = spec_requests(code, self.fork)
        self.programs_traced += 1
        return idx

    def _spec_key(self) -> Tuple:
        """The kernel-memo component: SpecProgram descriptors in
        program-index order."""
        if not self._spec_progs:
            return ()
        from coreth_tpu.evm.device.specialize import SpecProgram
        return tuple(SpecProgram(code=c, fork=self.fork)
                     for c, _i in sorted(self._spec_progs.items(),
                                         key=lambda kv: kv[1]))

    def _code_pack(self, code: bytes, code_cap: int) -> Tuple:
        """Dense (code row, jdest row, code_len) for one bytecode under
        one code_cap bucket (memoized; rows are assigned whole into the
        batch tensors — a contiguous copy instead of per-lane scan +
        jumpdest walk)."""
        key = (code, code_cap)
        rows = self._code_rows.get(key)
        if rows is None:
            cb = np.zeros((code_cap + 33,), dtype=np.int32)
            arr = np.frombuffer(code, dtype=np.uint8)
            cb[:len(arr)] = arr
            jd = np.zeros((code_cap,), dtype=np.int32)
            for d in T.scan_code(code, self.fork).jumpdests:
                if d < code_cap:
                    jd[d] = 1
            cb.setflags(write=False)
            jd.setflags(write=False)
            rows = (cb, jd, len(arr))
            self._code_rows[key] = rows
        return rows

    # -------------------------------------------------------- prediction
    def _rc_src(self, t: TxSpec, tag: tuple) -> bytes:
        """A recipe source tag's padded 32-byte value for THIS lane."""
        if tag[0] == "caller":
            return b"\x00" * 12 + t.caller
        return _cd_word(t.calldata, tag[1])

    def _learn_recipes(self, t: TxSpec, missed: List[bytes]) -> None:
        """Explain a lane's missed keys as
        ``keccak(pad32(source) || pad32(slot))`` over the lane's caller
        and calldata words (the Solidity mapping rule); every match
        becomes a recipe that derives FUTURE lanes' keys from their own
        inputs before dispatch.  One erc20 discovery cycle teaches
        ("caller", 0) and ("data", 0, 0) — from then on fresh
        recipients premap without a second dispatch.

        A miss no first-level derivation explains is tried one level
        deeper: ``keccak(pad32(src2) || inner)`` where ``inner`` is one
        of the first-level digests — the Solidity NESTED-mapping rule
        (``mapping(a => mapping(b => v))`` at slot p stores ``v`` at
        ``keccak(pad32(b) || keccak(pad32(a) || pad32(p)))``, the
        allowance shape).  A match records a second-level recipe
        ``(sel, "nest", outer_tag, inner_tag, slot)``, so
        allowance-style lanes stop falling back to discovery
        (CORETH_PREMAP_NEST=0 restores the miss-and-rerun A/B)."""
        if not self._predict or not missed:
            return
        recipes = self.recipes.setdefault(t.code, {})
        if len(recipes) >= self.RECIPE_CAP:
            return
        # recipes are scoped to the calldata SELECTOR they were learned
        # from: a transfer()-derived mapping recipe must not predict
        # keys for approve()/burn() lanes of the same contract (each
        # wrong prediction would claim a permanent table row)
        sel = bytes(t.calldata[:4])
        srcs: List[Tuple[tuple, bytes]] = [
            (("caller",), b"\x00" * 12 + t.caller)]
        n_words = min(self.DATA_WORDS,
                      max(0, (len(t.calldata) - 4 + 31) // 32))
        for w in range(n_words):
            srcs.append((("data", w), _cd_word(t.calldata, w)))
        msgs = [src + slot.to_bytes(32, "big")
                for _tag, src in srcs
                for slot in range(self.SLOT_SCAN)]
        digs = keccak256_many(msgs)
        want = dict.fromkeys(missed)
        explained: Dict[bytes, None] = {}
        i = 0
        for tag, _src in srcs:
            for slot in range(self.SLOT_SCAN):
                if _norm_slot_key(digs[i]) in want \
                        and len(recipes) < self.RECIPE_CAP:
                    recipes[(sel,) + tag + (slot,)] = None
                    explained[_norm_slot_key(digs[i])] = None
                i += 1
        if self._nest and len(recipes) < self.RECIPE_CAP:
            leftover = dict.fromkeys(
                k for k in want if k not in explained)
            if leftover:
                # second level: outer keccaks over every first-level
                # digest as the candidate inner hash — |srcs| * |srcs|
                # * SLOT_SCAN keccaks, one batched call, only for
                # unexplained misses
                msgs2 = [src2 + digs[i]
                         for _tag2, src2 in srcs
                         for i in range(len(digs))]
                digs2 = keccak256_many(msgs2)
                j = 0
                for tag2, _src2 in srcs:
                    for i in range(len(digs)):
                        k2 = _norm_slot_key(digs2[j])
                        if k2 in leftover \
                                and len(recipes) < self.RECIPE_CAP:
                            tag1 = srcs[i // self.SLOT_SCAN][0]
                            slot = i % self.SLOT_SCAN
                            recipes[(sel, "nest", tag2, tag1,
                                     slot)] = None
                            explained[k2] = None
                        j += 1
        # third shape: array-slot arithmetic — a dynamic array at slot
        # p stores element i at keccak(pad32(p)) + i (no keccak over
        # the lane's inputs at all), the last discovery-fallback class.
        # A leftover miss that equals base(slot) + v for a SMALL source
        # word v (an index argument, never an address) records
        # (sel, "arr", tag, slot); future lanes derive their element
        # keys by pure host arithmetic before dispatch.
        if not self._arr or len(recipes) >= self.RECIPE_CAP:
            return
        left2 = dict.fromkeys(k for k in want if k not in explained)
        if not left2:
            return
        for tag, src in srcs:
            v = int.from_bytes(src, "big")
            if v >= self.ARRAY_SPAN:
                continue
            for slot in range(self.SLOT_SCAN):
                cand = _norm_slot_key((
                    (_arr_base(slot) + v) % (1 << 256)
                ).to_bytes(32, "big"))
                if cand in left2 and len(recipes) < self.RECIPE_CAP:
                    recipes[(sel, "arr", tag, slot)] = None
                    # a second source word carrying the same value must
                    # not burn another RECIPE_CAP slot on the same key
                    del left2[cand]
                    explained[cand] = None
            if not left2:
                return

    # ------------------------------------------------------------- shape
    def _occ_params(self, items, premaps):
        feats = set()
        max_code = 64
        max_data = 64
        max_lanes = 1
        max_slots = 4
        unmapped = 0  # premap keys that will claim gids during packing
        for (_env, specs), block_pre in zip(items, premaps):
            max_lanes = max(max_lanes, len(specs))
            for t, pre in zip(specs, block_pre):
                info = T.scan_code(t.code, self.fork)
                if not info.eligible:
                    raise ValueError(
                        f"TxSpec code not device-eligible: {info.reason}")
                self._spec_id(t.code)  # program set settles pre-build
                feats |= set(info.features)
                max_code = max(max_code, len(t.code))
                max_data = max(max_data, len(t.calldata))
                max_slots = max(max_slots, len(pre) + 8)
                for k in pre:
                    if (t.address, k) not in self.slot_gid:
                        unmapped += 1
        p = M.MachineParams(
            fork=self.fork,
            batch=_pow2(max_lanes, 8),
            code_cap=_pow2(max_code, 256),
            data_cap=_pow2(max_data, 128),
            scache_cap=_pow2(max_slots, 8),
            features=frozenset(feats))
        occ = M.OccParams(
            blocks=_pow2(len(items), 1),
            table_cap=_pow2(len(self.vals) + unmapped + 1, 64),
            rounds=p.batch + 1)
        return self._apply_buckets(p, occ)

    def _apply_buckets(self, p: M.MachineParams,
                       occ: M.OccParams) -> Tuple:
        """Sticky pow2 shape buckets (CORETH_GROWTH_PREBUCKET): every
        bucket dimension only ratchets UP across a runner's lifetime —
        a shrinking tail window (fewer blocks/lanes, a feature-free
        batch) reuses the already-compiled kernel instead of tracing a
        smaller sibling, and the table arena never re-buckets downward
        (growth pads the donated HBM tables on device, see
        _device_tables).  Extra features / inactive lanes are
        semantically free: features only add compiled op families, and
        inactive lanes exit the OCC loop immediately."""
        if not self._prebucket:
            return p, occ
        hw = self._hw
        feats = frozenset(p.features | self._hw_feats)
        self._hw_feats = feats
        p = M.MachineParams(
            fork=p.fork,
            batch=max(p.batch, hw.get("batch", 0)),
            code_cap=max(p.code_cap, hw.get("code_cap", 0)),
            data_cap=max(p.data_cap, hw.get("data_cap", 0)),
            scache_cap=max(p.scache_cap, hw.get("scache_cap", 0)),
            features=feats)
        occ = M.OccParams(
            blocks=max(occ.blocks, hw.get("blocks", 0)),
            table_cap=max(occ.table_cap, self.table_cap,
                          self._table_floor),
            rounds=p.batch + 1)
        hw.update(batch=p.batch, code_cap=p.code_cap,
                  data_cap=p.data_cap, scache_cap=p.scache_cap,
                  blocks=occ.blocks)
        return p, occ

    def _device_tables(self, G: int):
        n = len(self.vals)
        if (self._prebucket and self.table is not None
                and not self._stale and G > self.table_cap):
            # recompile-free cap re-bucket: PAD the resident (donated)
            # tables on device — no host-mirror round trip, and the
            # pre-warmed bigger-bucket kernel (see _prewarm) takes the
            # next dispatch without a trace
            pad = G - self.table_cap
            z = jnp.zeros((pad, u256.LIMBS), dtype=jnp.int32)
            self.table = jnp.concatenate([self.table, z])
            self.key_tab = jnp.concatenate(
                [self.key_tab, jnp.zeros((pad, u256.LIMBS),
                                         dtype=jnp.int32)])
            self.table_cap = G
        if self.table is None or self.table_cap != G or self._stale:
            tv = np.zeros((G, u256.LIMBS), dtype=np.int32)
            tk = np.zeros((G, u256.LIMBS), dtype=np.int32)
            for g in range(n):
                tv[g] = word16(self.vals[g])
                tk[g] = word16(int.from_bytes(self.gid_keys[g][1],
                                              "big"))
            self.table = jnp.asarray(tv)
            self.key_tab = jnp.asarray(tk)
            self.table_cap = G
            self._synced = n
            self._stale = False
        elif self._synced < n:
            # append newly mapped rows; already-synced rows are live on
            # device (committed by the kernel itself)
            cnt = n - self._synced
            pad = 64
            while pad < cnt:
                pad *= 2
            # pow2-padded batch (OOB rows drop): a fresh jit trace per
            # distinct append length would serialize compiles mid-run
            idx = np.full((pad,), G, dtype=np.int32)
            idx[:cnt] = np.arange(self._synced, n, dtype=np.int32)
            tv = np.zeros((pad, u256.LIMBS), dtype=np.int32)
            tk = np.zeros((pad, u256.LIMBS), dtype=np.int32)
            for j, g in enumerate(range(self._synced, n)):
                tv[j] = word16(self.vals[g])
                tk[j] = word16(int.from_bytes(self.gid_keys[g][1],
                                              "big"))
            jidx = jnp.asarray(idx)
            self.table = _scatter_rows(self.table, jidx,
                                       jnp.asarray(tv))
            self.key_tab = _scatter_rows(self.key_tab, jidx,
                                         jnp.asarray(tk))
            self._synced = n
        return self.table, self.key_tab

    def _premaps(self, items, discovered):
        """Per-lane premapped key lists: PREDICTED keys first (the
        static PUSH-constant footprint + learned keccak recipes applied
        to the lane's own caller/calldata), then the seeded storage
        view, the common-key residue, and keys discovered by earlier
        attempts.  Recipe keccaks batch across the whole window: one
        call for every first-level digest (which doubles as the INNER
        hash of the nested recipes), then one call for the nested
        recipes' outer keccaks (crypto.keccak256_many ->
        coreth_keccak256_batch).  Returns (premaps, predicted) where
        ``predicted[bi][li]`` is the prediction-only key set (hit-rate
        accounting in _update_common)."""
        msgs: List[bytes] = []
        meta: List[List[List[tuple]]] = []
        if self._predict:
            for _env, specs in items:
                block_meta = []
                for t in specs:
                    sel = bytes(t.calldata[:4])
                    lane = []
                    for rc in self.recipes.get(t.code, ()):
                        if rc[0] != sel:
                            continue
                        if rc[1] == "nest":
                            if not self._nest:
                                continue
                            _sel, _n, tag2, tag1, slot = rc
                            msgs.append(self._rc_src(t, tag1)
                                        + slot.to_bytes(32, "big"))
                            lane.append(("nest",
                                         self._rc_src(t, tag2)))
                        elif rc[1] == "arr":
                            if not self._arr:
                                continue
                            _sel, _a, tag, slot = rc
                            v = int.from_bytes(self._rc_src(t, tag),
                                               "big")
                            if v >= self.ARRAY_SPAN:
                                continue
                            lane.append(("key", _norm_slot_key((
                                (_arr_base(slot) + v) % (1 << 256)
                            ).to_bytes(32, "big"))))
                        elif rc[1] == "caller":
                            msgs.append(b"\x00" * 12 + t.caller
                                        + rc[2].to_bytes(32, "big"))
                            lane.append(("flat",))
                        else:
                            msgs.append(_cd_word(t.calldata, rc[2])
                                        + rc[3].to_bytes(32, "big"))
                            lane.append(("flat",))
                    block_meta.append(lane)
                meta.append(block_meta)
        digs = keccak256_many(msgs)
        # second batch: the nested recipes' outer keccaks consume the
        # raw inner digests (the kernel computes keccak of the raw
        # 32-byte hash; only the FINAL key normalizes via the bit-0
        # storage-partition mask)
        msgs2: List[bytes] = []
        di = 0
        for block_meta in meta:
            for lane in block_meta:
                for entry in lane:
                    if entry[0] == "key":
                        continue  # host-derived; no digest consumed
                    if entry[0] == "nest":
                        msgs2.append(entry[1] + digs[di])
                    di += 1
        digs2 = keccak256_many(msgs2)
        di = 0
        dj = 0
        premaps = []
        predicted = []
        for bi, ((_env, specs), disc) in enumerate(
                zip(items, discovered)):
            block_pre = []
            block_predicted = []
            for li, t in enumerate(specs):
                keys: Dict[bytes, None] = {}
                pred: Dict[bytes, None] = {}
                if self._predict:
                    for k in _static_premap(t.code):
                        keys[k] = None
                        pred[k] = None
                    for entry in meta[bi][li]:
                        if entry[0] == "key":
                            k = entry[1]
                            self.premap_array += 1
                        elif entry[0] == "nest":
                            k = _norm_slot_key(digs2[dj])
                            dj += 1
                            self.premap_nested += 1
                            di += 1
                        else:
                            k = _norm_slot_key(digs[di])
                            di += 1
                        keys[k] = None
                        pred[k] = None
                for k in self.common.get(t.address, ()):
                    keys[k] = None
                for k in t.storage:
                    keys[k] = None
                    pred.pop(k, None)
                for k in disc[li]:
                    keys[k] = None
                    pred.pop(k, None)
                block_pre.append(list(keys))
                block_predicted.append(pred)
            premaps.append(block_pre)
            predicted.append(block_predicted)
        return premaps, predicted

    # ------------------------------------------------------------- issue
    def issue(self, items, discovered=None, attempt: int = 1) -> dict:
        """Pack + dispatch one window; returns a handle for complete().

        items: [(BlockEnv, [TxSpec, ...]), ...] in chain order.
        The dispatch is ASYNC (jax queues it): callers overlap host
        trie folding of the previous window with this one's execution
        and only block in complete()'s fetch.
        """
        faults.fire(PT_DISPATCH)
        if discovered is None:
            discovered = [[{} for _t in specs] for _env, specs in items]
        premaps, predicted = self._premaps(items, discovered)
        p, occ = self._occ_params(items, premaps)
        W, L, S, G = occ.blocks, p.batch, p.scache_cap, occ.table_cap

        # the lane -> bytecode assignment recurs window after window
        # (workloads run a stable hot-contract set), and the code /
        # jumpdest tensors are by far the largest window inputs — reuse
        # the converted device arrays whenever the assignment signature
        # matches instead of re-assembling ~100MB per window
        code_sig = (W, L, p.code_cap,
                    tuple(tuple(t.code for t in specs)
                          for _env, specs in items))
        code_cached = self._win_code_cache.get(code_sig)
        if code_cached is None:
            code = np.zeros((W, L, p.code_cap + 33), dtype=np.int32)
            code_len = np.zeros((W, L), dtype=np.int32)
            jdest = np.zeros((W, L, p.code_cap), dtype=np.int32)
        else:
            code = code_len = jdest = None
        calldata = np.zeros((W, L, p.data_cap), dtype=np.int32)
        data_len = np.zeros((W, L), dtype=np.int32)
        start_gas = np.zeros((W, L), dtype=np.int32)
        active = np.zeros((W, L), dtype=bool)
        sgid = np.full((W, L, S), G, dtype=np.int32)
        prog_id = np.full((W, L), -1, dtype=np.int32)
        kdig = np.zeros((W, L, KDIG_CAP, u256.LIMBS), dtype=np.int32)
        kjobs: List[Tuple] = []
        words = {k: np.zeros((W, L, u256.LIMBS), dtype=np.int32)
                 for k in ("callvalue", "caller_w", "address_w",
                           "origin_w", "gasprice_w")}
        timestamp = np.zeros((W,), dtype=np.int32)
        number = np.zeros((W,), dtype=np.int32)
        gaslimit = np.zeros((W,), dtype=np.int32)
        coinbase_w = np.zeros((W, u256.LIMBS), dtype=np.int32)
        basefee_w = np.zeros((W, u256.LIMBS), dtype=np.int32)
        chain_id = 0
        for bi, ((env, specs), block_pre) in enumerate(
                zip(items, premaps)):
            timestamp[bi] = env.timestamp
            number[bi] = env.number
            gaslimit[bi] = min(env.gas_limit, (1 << 31) - 1)
            coinbase_w[bi] = word16(addr_word(env.coinbase))
            basefee_w[bi] = word16(env.base_fee)
            chain_id = env.chain_id
            for li, t in enumerate(specs):
                if code_cached is None:
                    cb, jd, ln = self._code_pack(t.code, p.code_cap)
                    code[bi, li] = cb
                    code_len[bi, li] = ln
                    jdest[bi, li] = jd
                db = np.frombuffer(t.calldata, dtype=np.uint8)
                calldata[bi, li, :len(db)] = db
                data_len[bi, li] = len(db)
                start_gas[bi, li] = t.gas
                active[bi, li] = True
                words["callvalue"][bi, li] = word16c(t.value)
                words["caller_w"][bi, li] = word16c(addr_word(t.caller))
                words["address_w"][bi, li] = word16c(
                    addr_word(t.address))
                words["origin_w"][bi, li] = word16c(addr_word(t.origin))
                words["gasprice_w"][bi, li] = word16c(t.gas_price)
                pid = self._spec_progs.get(t.code, -1) \
                    if self._specialize else -1
                prog_id[bi, li] = pid
                if pid >= 0 and self._spec_reqs.get(t.code):
                    kjobs.append((bi, li, t, env,
                                  self._spec_reqs[t.code]))
                if attempt == 1:
                    if pid >= 0:
                        self.lanes_specialized += 1
                    elif self._specialize:
                        self.specialize_escapes += 1
                for j, key in enumerate(block_pre[li]):
                    sgid[bi, li, j] = self._gid(t.address, key)
        fill_kdig(kdig, kjobs)
        self.lanes_real += int(active.sum())
        self.lanes_padded += active.size
        # the packing above is the caller's phase (machine/prepare);
        # the host->device copies and the jitted call are two more
        acct = self.account
        acct.enter("machine/upload")
        try:
            table, key_tab = self._device_tables(G)
            if code_cached is None:
                code_cached = (jnp.asarray(code), jnp.asarray(jdest),
                               jnp.asarray(code_len))
                if len(self._win_code_cache) >= 2:
                    # steady state needs two signatures at most (the
                    # short lead window + the full window); a shifting
                    # workload just rebuilds
                    self._win_code_cache.clear()
                self._win_code_cache[code_sig] = code_cached
            code_j, jdest_j, code_len_j = code_cached
            inputs = dict(
                code=code_j, jdest=jdest_j,
                code_len=code_len_j,
                calldata=jnp.asarray(calldata),
                data_len=jnp.asarray(data_len),
                start_gas=jnp.asarray(start_gas),
                active=jnp.asarray(active), sgid=jnp.asarray(sgid),
                prog_id=jnp.asarray(prog_id),
                kdig=jnp.asarray(kdig),
                callvalue=jnp.asarray(words["callvalue"]),
                caller_w=jnp.asarray(words["caller_w"]),
                address_w=jnp.asarray(words["address_w"]),
                origin_w=jnp.asarray(words["origin_w"]),
                gasprice_w=jnp.asarray(words["gasprice_w"]),
                timestamp=jnp.asarray(timestamp),
                number=jnp.asarray(number),
                gaslimit=jnp.asarray(gaslimit),
                coinbase_w=jnp.asarray(coinbase_w),
                basefee_w=jnp.asarray(basefee_w),
                chainid_w=jnp.asarray(word16(chain_id)),
            )
            acct.switch("machine/dispatch")
            fn = self._get_kernel(p, occ)
            ticket = _count_dispatch()
            out = self._dispatch(fn, table, key_tab, inputs)
        finally:
            acct.exit()
        # the input table was donated into the dispatch; the output
        # handle (post-window committed state) replaces it
        self.table = out["table"]
        self._dispatched += 1
        self._prewarm(p, occ, n_blocks=len(items))
        return dict(out=out, items=items, discovered=discovered, p=p,
                    occ=occ, premaps=premaps, predicted=predicted,
                    attempt=attempt, ticket=ticket)

    def _dispatch(self, fn, *args):
        """Run the window kernel.  The value table is DONATED into the
        call, so when the call raises, the handle this runner still
        holds may already be consumed: mark the device table stale, and
        a supervised retry (BackendSupervisor.run re-invokes issue())
        rebuilds it from the host mirror instead of handing the kernel
        a deleted buffer."""
        try:
            return fn(*args)
        except Exception:  # noqa: BLE001 — bookkeeping only; re-raised
            self._stale = True
            raise

    # ------------------------------------------------------------ kernels
    def seed_window_hint(self, blocks: int) -> None:
        """Executor hint: steady-state windows hold `blocks` machine
        blocks — bucket the scan axis there from the FIRST dispatch so
        a short leading window (replay_block's single block) doesn't
        compile a small sibling that the first full window then
        re-buckets.  Inactive trailing blocks exit the OCC loop on the
        first condition check, so over-bucketing costs ~nothing."""
        if self._prebucket:
            self._hw["blocks"] = max(self._hw.get("blocks", 0),
                                     _pow2(max(1, blocks), 1))

    def _kernel(self, p: M.MachineParams, occ: M.OccParams,
                sk: Optional[Tuple] = None):
        sk = self._spec_key() if sk is None else sk
        return M.get_occ_machine(p, occ, sk)

    def _kernel_compiled(self, p: M.MachineParams,
                         occ: M.OccParams) -> bool:
        return M.occ_compiled(p, occ, self._spec_key())

    def _bucket_key(self, p: M.MachineParams, occ: M.OccParams,
                    sk: Tuple) -> Tuple:
        """Identity of one compiled kernel bucket for the retrace
        accounting and the pre-warm joins.  The sharded runner extends
        it with its exchange bucket, so an exchange-capacity re-bucket
        counts (and pre-warms) exactly like a table-cap one."""
        return (p, occ, sk)

    def _get_kernel(self, p: M.MachineParams, occ: M.OccParams):
        """Kernel for a dispatch, accounting retraces: a shape bucket
        this runner first reaches AFTER its first dispatch — without
        having pre-warmed it — is a mid-run retrace (the
        recompile-regression test pins this at zero on the pre-bucketed
        path; the legacy path pays one per cap bucket).  Tracked
        per-runner, not via the process-global kernel cache, so the
        count is deterministic across bench reps and test order.  The
        specialized-program set is part of the bucket identity: a new
        hot contract mid-run retraces exactly like a new op family
        would."""
        key = self._bucket_key(p, occ, self._spec_key())
        if key not in self._buckets_used:
            self._buckets_used.add(key)
            if not self._cold:
                self.kernel_retraces += 1
                obs.instant("device/kernel_retrace",
                            table_cap=occ.table_cap)
        fut = self._warm_pending.pop(key, None)
        if fut is not None:
            # a background pre-warm of THIS bucket is in flight: join
            # it — the trace lands in the kernel cache exactly once
            # and the dispatch below finds a ready executable
            try:
                fut.result()
            except Exception:  # noqa: BLE001 — warm compile is advisory; the dispatch below compiles synchronously if it failed
                self.warm_failures += 1
        return self._kernel(p, occ)

    def _lane_count(self, p: M.MachineParams) -> int:
        return p.batch

    def _table_rows(self, G: int) -> int:
        return G

    def _warm_args(self, p: M.MachineParams, occ: M.OccParams):
        """All-inactive zero inputs of a (p, occ) bucket: dispatching
        them compiles the bucket while costing ~no device time (every
        while_loop exits on the first condition check)."""
        W, S, G = occ.blocks, p.scache_cap, occ.table_cap
        L = self._lane_count(p)
        rows = self._table_rows(G)
        i32 = jnp.int32
        word = jnp.zeros((W, L, u256.LIMBS), dtype=i32)
        inputs = dict(
            code=jnp.zeros((W, L, p.code_cap + 33), dtype=i32),
            jdest=jnp.zeros((W, L, p.code_cap), dtype=i32),
            code_len=jnp.zeros((W, L), dtype=i32),
            calldata=jnp.zeros((W, L, p.data_cap), dtype=i32),
            data_len=jnp.zeros((W, L), dtype=i32),
            start_gas=jnp.zeros((W, L), dtype=i32),
            active=jnp.zeros((W, L), dtype=bool),
            sgid=jnp.full((W, L, S), G, dtype=i32),
            prog_id=jnp.full((W, L), -1, dtype=i32),
            kdig=jnp.zeros((W, L, KDIG_CAP, u256.LIMBS), dtype=i32),
            callvalue=word, caller_w=word, address_w=word,
            origin_w=word, gasprice_w=word,
            timestamp=jnp.zeros((W,), dtype=i32),
            number=jnp.zeros((W,), dtype=i32),
            gaslimit=jnp.zeros((W,), dtype=i32),
            coinbase_w=jnp.zeros((W, u256.LIMBS), dtype=i32),
            basefee_w=jnp.zeros((W, u256.LIMBS), dtype=i32),
            chainid_w=jnp.zeros((u256.LIMBS,), dtype=i32),
        )
        table = jnp.zeros((rows, u256.LIMBS), dtype=i32)
        key_tab = jnp.zeros((rows, u256.LIMBS), dtype=i32)
        return table, key_tab, inputs

    def _prewarm(self, p: M.MachineParams, occ: M.OccParams,
                 n_blocks: Optional[int] = None) -> None:
        """Compile the NEXT table bucket's kernel while the current
        window executes: once the arena is half full a cap re-bucket is
        imminent, and pre-tracing now means the growth dispatch later
        finds a ready executable — zero mid-run retraces.  A LEAD
        window shorter than the steady bucket (replay_block's single
        block ahead of full windows) maps only a fraction of a full
        window's keys, so the first full window can jump the cap with
        no half-full warning — prewarm unconditionally behind it.  The
        warm dispatch runs all-inactive lanes, so it costs one compile
        (once per bucket), not a window of compute."""
        if not self._prebucket:
            return
        mapped = self._mapped_rows()
        steady = self._hw.get("blocks", occ.blocks)
        lead = _pow2(max(1, n_blocks), 1) if n_blocks else steady
        if lead < steady and mapped:
            # a lead window maps ~lead/steady of a full window's keys:
            # project the full-size arena linearly and PIN it as the
            # arena floor, so the first full window lands exactly on
            # the bucket warmed here (projection overshoot costs rows,
            # never a retrace; clamp bounds the HBM bet)
            self._table_floor = max(self._table_floor, min(
                _pow2(mapped * (steady // lead) + 1, 64), 1 << 20))
        if self._table_floor <= occ.table_cap \
                and 2 * mapped < occ.table_cap:
            return
        nxt = M.OccParams(blocks=occ.blocks,
                          table_cap=max(occ.table_cap * 2,
                                        self._table_floor),
                          rounds=occ.rounds)
        sk = self._spec_key()
        bk = self._bucket_key(p, nxt, sk)
        if bk in self._buckets_used:
            return
        self._buckets_used.add(bk)
        if self._kernel_compiled(p, nxt):
            return  # cache-warm from an earlier runner/rep
        if self._compile_async:
            # the trace runs on the compile thread while the CURRENT
            # window executes on the main thread — on CPU hosts this
            # hides the whole compile instead of serializing it here.
            # The FULL bucket identity is captured NOW via the thunk
            # (spec key here; the sharded runner adds its exchange
            # bucket/mode): the warm must compile the bucket the
            # scheduling dispatch saw, not whatever state exists when
            # the worker gets to it.
            self._warm_pending[bk] = _compile_pool().submit(
                self._warm_thunk(p, nxt, sk))
            return
        fn = self._kernel(p, nxt, sk)
        fn(*self._warm_args(p, nxt))

    def _warm_thunk(self, p: M.MachineParams, occ: M.OccParams,
                    sk: Tuple):
        """Zero-arg warm-compile body with the bucket identity bound
        at SCHEDULING time (the sharded override additionally pins its
        live exchange bucket/mode — the pool worker must compile the
        bucket recorded in _buckets_used, not whatever those values
        are when it runs)."""
        return lambda: self._warm_compile(p, occ, sk)

    def _warm_compile(self, p: M.MachineParams, occ: M.OccParams,
                      sk: Tuple = ()) -> None:
        """Body of one background pre-warm: build + trace + dispatch
        the all-inactive warm batch for a bucket (compile-thread)."""
        with obs.span("device/prewarm_compile",
                      table_cap=occ.table_cap):
            fn = self._kernel(p, occ, sk)
            fn(*self._warm_args(p, occ))

    # ---------------------------------------------------------- complete
    def _block_stride(self, handle: dict) -> int:
        """Flat packed rows per block (lane axis width)."""
        return handle["p"].batch

    def _lane_idx(self, handle: dict, bi: int, li: int) -> int:
        """In-block lane index of tx li (identity here; the sharded
        runner places lanes by contract shard via its lane_map)."""
        return li

    def _on_result_fetch(self, handle: dict) -> None:
        """The window's packed result is on the host: its dispatch is
        no longer in flight.  The sharded runner adds its
        dispatch-ordering trace entry."""
        obs.device_done(handle["ticket"])

    def _discover_key(self, handle: dict, bi: int, li: int,
                      contract: bytes, key: bytes) -> None:
        """Map a key a lane's F_MISS escape discovered.  The sharded
        override allocates it on the DISCOVERING lane's shard — the
        retry premaps it locally instead of minting a replica of a
        hash-bucket copy no lane runs next to."""
        self._gid(contract, key)

    def complete(self, handle: dict) -> WindowResult:
        """Fetch a window's results; resolve any storage keys that
        escaped the premap, LEARN keccak recipes from them (so future
        windows predict instead of rediscovering), and re-dispatch
        (bounded attempts, counted in ``discovery_dispatches``) until
        the window needs no further key resolution."""
        while True:
            p = handle["p"]
            Lp = self._block_stride(handle)
            # the blocking read alone; the caller's phase
            # (machine/fold) takes the unpacking below
            with self.account.enter("machine/fetch_wait"):
                packed = np.asarray(handle["out"]["packed"])
                self._on_result_fetch(handle)
            pw = packed.shape[2] - 4
            pout = PackedOut(
                packed[:, :, :pw].reshape(-1, pw), p)
            extra = packed[:, :, pw:]
            missing = False
            for bi, (_env, specs) in enumerate(handle["items"]):
                for li, t in enumerate(specs):
                    fl = self._lane_idx(handle, bi, li)
                    if not extra[bi, fl, 1]:
                        continue  # escaped lanes only carry misses
                    disc = handle["discovered"][bi][li]
                    fresh: List[bytes] = []
                    for key in miss_keys(pout, bi * Lp + fl):
                        if not self._key_mapped(t.address, key):
                            self._discover_key(handle, bi, li,
                                               t.address, key)
                        if key not in disc:
                            disc[key] = None
                            fresh.append(key)
                            missing = True
                    self._learn_recipes(t, fresh)
            if missing and handle["attempt"] < self.max_attempts:
                # re-run the WHOLE window from the host mirror (the
                # failed attempt's device table holds partial commits)
                self.discovery_dispatches += 1
                self._stale = True
                with self.account.enter("machine/prepare"):
                    handle = self.issue(handle["items"],
                                        handle["discovered"],
                                        attempt=handle["attempt"] + 1)
                continue
            break
        self._cold = False
        results, committed, escape, clean, rounds = [], [], [], [], []
        for bi, (_env, specs) in enumerate(handle["items"]):
            slots = [self._lane_idx(handle, bi, li)
                     for li in range(len(specs))]
            res = results_for_rows(
                pout, np.asarray(slots, dtype=np.int64) + bi * Lp)
            if slots:
                com = extra[bi, slots, 0].astype(bool)
                esc = (extra[bi, slots, 1]
                       | extra[bi, slots, 2]).astype(bool)
                # per-shard round counts may differ; report the max
                rnd = int(extra[bi, slots, 3].max())
            else:
                com = np.zeros((0,), dtype=bool)
                esc = np.zeros((0,), dtype=bool)
                rnd = 0
            results.append(res)
            committed.append(com)
            escape.append(esc)
            clean.append(bool(com.all()) if slots else True)
            rounds.append(rnd)
        self._update_common(handle, pout, clean)
        return WindowResult(results=results, committed=committed,
                            escape=escape, clean=clean, rounds=rounds,
                            attempts=handle["attempt"])

    def _update_common(self, handle, pout: PackedOut,
                       clean: List[bool]) -> None:
        """Count predicted-premap keys and hits (both against the
        FINAL attempt's prediction sets, so premap_hit_rate pairs a
        window's numerator and denominator even when discovery
        re-dispatched it), and narrow each contract's residual
        common-key set to the keys EVERY lane touched (the shared-slot
        contention shape prediction cannot derive)."""
        Lp = self._block_stride(handle)
        predicted = handle.get("predicted")
        for bi, (_env, specs) in enumerate(handle["items"]):
            if not clean[bi]:
                continue
            for li, t in enumerate(specs):
                row = bi * Lp + self._lane_idx(handle, bi, li)
                touched: Dict[bytes, None] = {}
                kb = pout.key_blob()
                flags = pout.sflag[row]
                for j in range(int(pout.scnt[row])):
                    if flags[j] & (M.F_READ | M.F_WRITTEN):
                        off = (row * pout.S + j) * 32
                        touched[kb[off:off + 32]] = None
                if predicted is not None:
                    self.premap_predicted += len(predicted[bi][li])
                    self.premap_hits += sum(
                        1 for k in predicted[bi][li] if k in touched)
                cur = self.common.get(t.address)
                if cur is None:
                    keep = list(touched)[:self.COMMON_CAP]
                    self.common[t.address] = dict.fromkeys(keep)
                else:
                    self.common[t.address] = {
                        k: None for k in cur if k in touched}

"""Device-sharded OCC machine windows: per-shard slot tables +
per-shard OCC inside shard_map + a collective exchange step.

The single-chip fused OCC kernel (machine.build_occ_machine via
adapter.MachineWindowRunner) keeps ONE global (contract, key) -> gid
map and ONE HBM slot table.  On a dp mesh that replication is what
inverted the scaling curve: every chip would carry the whole table and
re-execute every lane.  This module shards the machine path instead:

- **per-shard state tables**: each shard owns the storage of the
  contracts in its bucket (parallel/shard.py contract_bucket over
  keccak(address)), with its own (contract, key) -> local-gid map,
  host value mirror, and a shard-major device table row block — the
  ``(n_shards * G, 16)`` value/key tables shard over ``dp`` so every
  device holds (on real chips: in its own HBM) only its arena;

- **shard-local OCC**: at window build time every call tx classifies
  shard-local — a device-eligible tx touches exactly ONE contract's
  storage, and (default placement) a contract's storage lives wholly
  on one shard, so cross-shard READ-WRITE conflicts are impossible by
  construction and each shard's Block-STM round loop + sequential
  validation sweep runs unmodified inside ``shard_map`` over its own
  lanes and table.  The remaining genuinely cross-shard effects — a
  lane's CALLER living in a different account bucket than its callee
  contract (value moves and fees crossing shards) — are counted per
  window (``cross_shard``) and settle in the host account sweep,
  which is exact and O(txs);

- **KEY-RANGE placement for hot contracts** (ISSUE 14, the FAFO
  ceiling): contract-bucket placement serializes the realistic heavy
  shape — ONE hot token/pool taking every lane — onto a single shard.
  A contract whose per-block lane count reaches
  ``CORETH_KEYRANGE_THRESHOLD`` goes HOT (sticky): its storage keys
  spread by ``slot_bucket(keccak(key))`` and its lanes place by
  per-block CONFLICT COMPONENTS — lanes sharing any premapped key
  union into one component (they must co-locate so the in-shard OCC
  sweep serializes them exactly), components spread over shards by
  copy affinity then load (deterministic; placement affects only
  performance — results are validated per shard, so roots are
  bit-identical under ANY placement).  A lane reading range A while
  writing range B (the transfer-touches-two-balance-keys shape) gets
  a local REPLICA row for the remote-range key, and replicas settle
  in the per-block packed exchange below.  Every touched key is
  premapped (an unmapped touch F_MISS-escapes into discovery), so
  within one block a key is touched by ONE shard only — co-location
  guarantees it — and the exchange's tie-breaking never decides
  semantics;

- **the exchange step**: a separate collective program psums each
  shard's per-block packed effect flags (all-lanes-committed,
  any-escape) into one tiny replicated tensor.  The scheduler fetches
  THAT — not the full packed result — to decide a window is clean, and
  then dispatches the NEXT window's per-shard OCC before fetching this
  window's (large) packed results: the cross-shard exchange overlaps
  the next window's dispatch, the execute/fold-overlap idiom (PR 4)
  applied to the exchange phase (pinned by the dispatch-ordering test
  in tests/test_shard_replay.py against EVENT_LOG below).  With
  key-range placement on, a second per-BLOCK exchange inside the
  fused program carries (shard, gid, value) triples for the window's
  multi-copy keys: after each block every shard compares its replica
  rows against their pre-block values, a deterministic winner (the
  shard that changed the row; shard-index tie-break) is elected with
  one max-reduce, and one add-reduce broadcasts the winning value
  into every copy — so the NEXT block's reads see cross-range writes
  regardless of which shard made them.  Both exchanges ride either
  ``psum`` or a ring of ``ppermute`` steps (parallel.collective_reduce),
  density-selected per window with ``CORETH_EXCHANGE=psum|ppermute``
  as the A/B override; integer sums/maxes make the two modes
  bit-identical.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PS

from coreth_tpu import faults, obs
from coreth_tpu.crypto import keccak256
from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device import tables as T
from coreth_tpu.evm.device.adapter import (
    PT_DISPATCH, MachineWindowRunner, _count_dispatch, _pow2, addr_word,
    fill_kdig, word16, word16c,
)
from coreth_tpu.evm.device.specialize import KDIG_CAP
from coreth_tpu.ops import u256
from coreth_tpu.parallel import (
    account_bucket, collective_reduce, contract_bucket,
    exchange_mode, slot_bucket,
)

# Injection point: the cross-shard collective exchange fails (ICI
# flake, a device dropping out of the mesh).  Armed plans raise at the
# exchange dispatch inside issue(); the machine executor's fault
# containment invalidates the runner and routes the run down the
# ladder.
PT_EXCHANGE = faults.declare(
    "device/shard_exchange", "cross-shard collective exchange failure")

# Injection point: the INTRA-contract key-range exchange (the per-block
# replica-sync collective a key-range window compiles in).  Fired at
# the dispatch that carries the sync set; contained exactly like
# PT_EXCHANGE — execute_run keeps the committed prefix, invalidates
# the runner, and the supervisor strikes toward device demotion.
PT_KEY_EXCHANGE = faults.declare(
    "device/key_exchange",
    "intra-contract key-range exchange collective failure")

# Dispatch/fetch ordering trace for the overlap test: entries are
# "dispatch:<seq>", "exchange_fetch:<seq>", "result_fetch:<seq>".
# An obs.EventRing — a small ALWAYS-ON bounded ring with the exact
# deque semantics the dispatch-ordering test in
# tests/test_shard_replay.py pins (a long-running mesh service appends
# a few entries per window forever), which additionally mirrors each
# entry into the active span tracer as an instant event when
# CORETH_TRACE=1, so the Perfetto timeline shows the same
# dispatch/fetch ordering.  seq is MODULE-global so two runners in one
# process (e.g. a mempool-fed builder + replica pair) never emit
# colliding entries.
EVENT_LOG = obs.EventRing("shard", maxlen=512)
_SEQ = [0]


def _next_seq() -> int:
    _SEQ[0] += 1
    return _SEQ[0]

# blocks_in leaves whose axis 1 is the (sharded) lane axis
_LANE_KEYS = ("code", "jdest", "code_len", "calldata", "data_len",
              "start_gas", "active", "sgid", "prog_id", "kdig",
              "callvalue", "caller_w", "address_w", "origin_w",
              "gasprice_w")
# per-block (replicated) leaves
_BLOCK_KEYS = ("timestamp", "number", "gaslimit", "coinbase_w",
               "basefee_w", "chainid_w")


def _mesh_key(mesh):
    return (tuple(mesh.devices.flat), mesh.axis_names)


_OCC_SHARDED: Dict[Tuple, object] = {}
_EXCHANGES: Dict[Tuple, object] = {}


def build_sharded_occ_machine(params: M.MachineParams, occ: M.OccParams,
                              mesh, spec: Tuple = (), xchg: int = 0,
                              mode: str = "psum"):
    """Per-shard OCC: the single-chip fused kernel body runs unchanged
    on every device over its lane slice and table arena.  params.batch
    and occ.table_cap are PER-SHARD shapes; the caller passes
    (n_shards * G, 16) tables and (W, n_shards * batch, ...) lanes.
    `spec` (the specialized-program set) composes transparently: the
    per-lane prog_id selection happens inside the inner kernel body,
    so each shard runs its own lanes' traced sub-programs.

    ``xchg > 0`` builds the KEY-RANGE variant: the same (unmodified)
    kernel body compiled for ONE block and scanned here, with the
    replica-sync exchange between blocks.  A 4th input carries the
    window's (xchg, n_shards) sync-row matrix: ``sync_rows[j, s]`` is
    the LOCAL arena row of multi-copy key j on shard s (table_cap =
    absent).  After each block every shard diff's its copies against
    their pre-block values; the shard that changed a row wins (a
    deterministic shard-index max tie-break — co-location makes real
    ties impossible among premapped keys) and one add-reduce
    broadcasts the winning value into every copy, so the NEXT block's
    reads observe cross-range writes from any shard.  ``mode`` picks
    psum/pmax or the ppermute ring for both reduces."""
    n = mesh.devices.size
    specs = {k: PS(None, "dp") for k in _LANE_KEYS}
    specs.update({k: PS() for k in _BLOCK_KEYS})
    if not xchg:
        inner = M.build_occ_machine(params, occ, spec)

        def run(table, key_tab, blocks_in):
            return inner(table, key_tab, blocks_in)

        return shard_map(
            run, mesh=mesh,
            in_specs=(PS("dp"), PS("dp"), specs),
            out_specs={"table": PS("dp"), "packed": PS(None, "dp")},
            # per-shard OCC is collective-free inside (the partition
            # makes lanes shard-local); vma has nothing to verify
            check_vma=False)

    occ1 = M.OccParams(blocks=1, table_cap=occ.table_cap,
                       rounds=occ.rounds)
    inner = M.build_occ_machine(params, occ1, spec)
    G = occ.table_cap
    nc = mesh.devices.size  # sync_rows = (xchg, n + 1): rows | owner

    def run_kr(table, key_tab, blocks_in, sync_rows):
        d = jax.lax.axis_index("dp")
        rows_d = sync_rows[:, d]
        own = sync_rows[:, nc]                 # authoritative shard
        has = rows_d < G
        idx = jnp.where(has, rows_d, G)        # table_cap == OOB
        chain_w = blocks_in["chainid_w"]       # window-constant leaf
        xs = {k: v for k, v in blocks_in.items() if k != "chainid_w"}

        # window-start seed sync: broadcast the OWNER copy's live value
        # into every copy — a replica allocated while the previous
        # window was still in flight was seeded from a one-window-stale
        # host mirror, and only the device holds the fresh value
        cur0 = table.at[idx].get(mode="fill", fill_value=0)
        contrib0 = jnp.where((own == d)[:, None], cur0, 0)
        val0 = collective_reduce(contrib0, "dp", n, mode, op="add")
        table = table.at[idx].set(
            jnp.where(has[:, None], val0, cur0), mode="drop")

        def body(tab, blk):
            pre = tab.at[idx].get(mode="fill", fill_value=0)
            blk1 = {k: v[None] for k, v in blk.items()}
            blk1["chainid_w"] = chain_w
            out = inner(tab, key_tab, blk1)
            tab = out["table"]
            # the (shard, gid, value) sync: elect the writer, then
            # broadcast its value into every copy of the key
            cur = tab.at[idx].get(mode="fill", fill_value=0)
            changed = has & jnp.any(cur != pre, axis=1)
            cand = jnp.where(changed, d + 1, 0).astype(jnp.int32)
            win = collective_reduce(cand, "dp", n, mode, op="max")
            contrib = jnp.where((changed & (cand == win))[:, None],
                                cur, 0)
            val = collective_reduce(contrib, "dp", n, mode, op="add")
            newv = jnp.where((win > 0)[:, None], val, cur)
            tab = tab.at[idx].set(newv, mode="drop")
            return tab, out["packed"][0]

        tab, packed = jax.lax.scan(body, table, xs)
        return {"table": tab, "packed": packed}

    return shard_map(
        run_kr, mesh=mesh,
        in_specs=(PS("dp"), PS("dp"), specs, PS()),
        out_specs={"table": PS("dp"), "packed": PS(None, "dp")},
        check_vma=False)


def occ_sharded_compiled(params: M.MachineParams, occ: M.OccParams,
                         mesh, spec: Tuple = (), xchg: int = 0,
                         mode: str = "psum") -> bool:
    return (params, occ, _mesh_key(mesh), spec,
            xchg, mode) in _OCC_SHARDED


def get_sharded_occ_machine(params: M.MachineParams, occ: M.OccParams,
                            mesh, spec: Tuple = (), xchg: int = 0,
                            mode: str = "psum"):
    key = (params, occ, _mesh_key(mesh), spec, xchg, mode)
    fn = _OCC_SHARDED.get(key)
    if fn is None:
        fn = jax.jit(build_sharded_occ_machine(params, occ, mesh, spec,
                                               xchg, mode),
                     donate_argnums=(0,))
        _OCC_SHARDED[key] = fn
        M.count_occ_build()
    return fn


def get_shard_exchange(mesh, mode: str = "psum"):
    """The collective exchange program: reduce each shard's per-block
    packed (all-committed, any-escape-or-pending) flags into one tiny
    replicated (W, 2) tensor — what the scheduler needs to overlap the
    next window's dispatch with this window's result fetch.  ``mode``
    rides the same psum-vs-ppermute selection as the window's sync
    exchange (integer sums: bit-identical either way)."""
    n = mesh.devices.size
    key = (_mesh_key(mesh), mode)
    fn = _EXCHANGES.get(key)
    if fn is None:
        def ex(packed, active):
            committed = packed[:, :, -4] != 0
            escape = (packed[:, :, -3] != 0) | (packed[:, :, -2] != 0)
            clean_l = jnp.all(~active | committed, axis=1)
            esc_l = jnp.any(active & escape, axis=1)
            flags = jnp.stack([clean_l.astype(jnp.int32),
                               esc_l.astype(jnp.int32)], axis=1)
            return collective_reduce(flags, "dp", n, mode, op="add")

        fn = jax.jit(shard_map(
            ex, mesh=mesh,
            in_specs=(PS(None, "dp"), PS(None, "dp")),
            out_specs=PS(), check_vma=False))
        _EXCHANGES[key] = fn
    return fn


class ShardedWindowRunner(MachineWindowRunner):
    """MachineWindowRunner with per-shard gid maps/mirrors/tables and
    the exchange-overlap scheduling hooks (poll_clean / can_pipeline).

    Lane placement: block bi's call tx li goes to flat lane
    ``shard * batch + local`` of its contract's shard; ``lane_map``
    in the handle translates back to tx order for unpacking."""

    def __init__(self, fork: str, storage_resolver, mesh,
                 max_attempts: int = 6):
        super().__init__(fork, storage_resolver, max_attempts)
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        n = self.n_shards
        # per-shard twins of the parent's global structures
        self.slot_gid = [dict() for _ in range(n)]
        self.gid_keys = [[] for _ in range(n)]
        self.vals = [[] for _ in range(n)]
        self._synced = [0] * n
        # (contract, key) -> [(shard, local gid), ...] — EVERY copy of
        # a key.  Contract-bucket keys have exactly one copy on their
        # contract's shard; key-range keys grow replicas wherever a
        # conflict component lands, and multi-copy keys premapped by a
        # window form its sync set.
        self.copies: Dict[Tuple[bytes, bytes], List[Tuple[int, int]]] \
            = {}
        self._bucket_memo: Dict[bytes, int] = {}
        self._abucket_memo: Dict[bytes, int] = {}
        self._kr_bucket_memo: Dict[bytes, int] = {}
        # key-range placement: sticky per-contract HOT set, crossed by
        # a per-block lane-count threshold (the FAFO shape detector);
        # CORETH_KEYRANGE=0 pins every contract to its contract bucket
        self._kr = bool(int(os.environ.get("CORETH_KEYRANGE", "1")))
        self._kr_threshold = int(os.environ.get(
            "CORETH_KEYRANGE_THRESHOLD", "16"))
        self.hot_contracts: Dict[bytes, None] = {}
        self._place_cache = None      # (premaps ref, placement dict)
        # sync-exchange bucket (multi-copy keys per window): sticky
        # pow2 high-water like every other shape bucket — part of the
        # kernel identity, pre-warmed on growth (kernel_retraces gate)
        self._xchg_hw = 0
        self._xchg_mode = "psum"
        # the mode locks at the first window with a NONEMPTY sync set
        # (real density evidence): re-evaluating every window could
        # flip psum<->ppermute as density wobbles around the
        # threshold, and each flip is a kernel recompile
        self._xchg_locked = False
        self._sync_last = 0
        self.cross_shard = 0          # caller-bucket != callee-bucket
        self.multi_shard_blocks = 0   # blocks spanning > 1 shard
        self._probe = None            # can_pipeline's prepared shapes

    # ------------------------------------------------------------ state
    def shard_of(self, contract: bytes) -> int:
        s = self._bucket_memo.get(contract)
        if s is None:
            s = contract_bucket(keccak256(contract), self.n_shards)
            self._bucket_memo[contract] = s
        return s

    def _account_bucket(self, addr: bytes) -> int:
        s = self._abucket_memo.get(addr)
        if s is None:
            s = account_bucket(keccak256(addr), self.n_shards)
            self._abucket_memo[addr] = s
        return s

    def _kr_home(self, key: bytes) -> int:
        """KEY-RANGE owning shard of one storage slot (the ISSUE-14
        placement: keccak-derived slot bucket % n)."""
        s = self._kr_bucket_memo.get(key)
        if s is None:
            s = slot_bucket(keccak256(key), self.n_shards)
            self._kr_bucket_memo[key] = s
        return s

    def reset(self) -> None:
        n = self.n_shards
        self.slot_gid = [dict() for _ in range(n)]
        self.gid_keys = [[] for _ in range(n)]
        self.vals = [[] for _ in range(n)]
        self._synced = [0] * n
        self.copies = {}
        self._place_cache = None
        self.common.clear()
        self.table = None
        self.key_tab = None
        self.table_cap = 0
        self._stale = True

    def _alloc_copy(self, contract: bytes, key: bytes, s: int,
                    v: int) -> int:
        g = len(self.vals[s])
        self.slot_gid[s][(contract, key)] = g
        self.gid_keys[s].append((contract, key))
        self.vals[s].append(v)
        self.copies.setdefault((contract, key), []).append((s, g))
        return g

    def _default_home(self, contract: bytes, key: bytes) -> int:
        if self._kr and contract in self.hot_contracts:
            return self._kr_home(key)
        return self.shard_of(contract)

    def commit_block(self, writes) -> None:
        for (contract, key), v in writes.items():
            cps = self.copies.get((contract, key))
            if not cps:
                self._alloc_copy(contract, key,
                                 self._default_home(contract, key), v)
            else:
                # EVERY copy's mirror entry learns the committed value
                # (the device synced its copies in the exchange; the
                # mirror is the rebuild source and must agree)
                for s, g in cps:
                    self.vals[s][g] = v

    def _gid(self, contract: bytes, key: bytes,
             home: Optional[int] = None) -> int:
        """Shard-LOCAL gid of `key`'s copy on ``home`` (allocating a
        replica there if the key lives elsewhere).  ``home=None`` (the
        base runner's discovery path) reuses any existing copy, else
        allocates at the key's default placement."""
        cps = self.copies.get((contract, key))
        if home is None:
            if cps:
                return cps[0][1]
            home = self._default_home(contract, key)
        if cps:
            for s, g in cps:
                if s == home:
                    return g
            # new replica: seed from the authoritative mirror value
            v = self.vals[cps[0][0]][cps[0][1]]
        else:
            v = self.resolver(contract, key)
        return self._alloc_copy(contract, key, home, v)

    def _key_mapped(self, contract: bytes, key: bytes) -> bool:
        return (contract, key) in self.copies

    def _mapped_rows(self) -> int:
        # the hottest shard's arena decides the per-shard cap
        return max(len(v) for v in self.vals)

    # ------------------------------------------------------------ kernels
    def _kernel(self, p, occ, sk=None, xchg=None, mode=None):
        sk = self._spec_key() if sk is None else sk
        xchg = self._xchg_hw if xchg is None else xchg
        mode = self._xchg_mode if mode is None else mode
        return get_sharded_occ_machine(p, occ, self.mesh, sk, xchg,
                                       mode)

    def _kernel_compiled(self, p, occ) -> bool:
        return occ_sharded_compiled(p, occ, self.mesh,
                                    self._spec_key(), self._xchg_hw,
                                    self._xchg_mode)

    def _bucket_key(self, p, occ, sk) -> Tuple:
        # the exchange bucket + collective mode are kernel identity:
        # growing (or flipping) one mid-run retraces exactly like a
        # table-cap re-bucket, so both ride the retrace accounting and
        # the pre-warm joins
        return (p, occ, sk, self._xchg_hw, self._xchg_mode)

    def _warm_args(self, p, occ, xchg=None):
        args = super()._warm_args(p, occ)
        xchg = self._xchg_hw if xchg is None else xchg
        if not xchg:
            return args
        rows = jnp.full((xchg, self.n_shards + 1), occ.table_cap,
                        dtype=jnp.int32)
        return args + (rows,)

    def _prewarm(self, p, occ, n_blocks=None) -> None:
        super()._prewarm(p, occ, n_blocks)
        x = self._xchg_hw
        if not x or self._sync_last * 2 < x:
            return
        # the sync set is at least half its bucket: pre-trace the
        # doubled exchange bucket behind the current window, so the
        # growth dispatch finds a ready executable (the table-cap
        # pre-warm logic applied to the exchange axis)
        sk = self._spec_key()
        nxt = (p, occ, sk, x * 2, self._xchg_mode)
        if nxt in self._buckets_used:
            return
        self._buckets_used.add(nxt)
        if occ_sharded_compiled(p, occ, self.mesh, sk, x * 2,
                                self._xchg_mode):
            return
        if self._compile_async:
            from coreth_tpu.evm.device.adapter import _compile_pool
            self._warm_pending[nxt] = _compile_pool().submit(
                self._warm_xchg_compile, p, occ, sk, x * 2,
                self._xchg_mode)
            return
        fn = self._kernel(p, occ, sk, x * 2, self._xchg_mode)
        fn(*self._warm_args(p, occ, xchg=x * 2))

    def _warm_thunk(self, p, occ, sk):
        # pin the LIVE exchange bucket/mode at scheduling time: the
        # base thunk's deferred self._kernel()/self._warm_args() would
        # otherwise read whatever values exist when the pool worker
        # runs, compiling a different bucket than _buckets_used
        # recorded (and mismatching arity if xchg crossed 0)
        xchg, mode = self._xchg_hw, self._xchg_mode
        return lambda: self._warm_xchg_compile(p, occ, sk, xchg, mode)

    def _warm_xchg_compile(self, p, occ, sk, xchg, mode) -> None:
        with obs.span("device/prewarm_compile", xchg=xchg):
            fn = self._kernel(p, occ, sk, xchg, mode)
            fn(*self._warm_args(p, occ, xchg=xchg))

    def _lane_count(self, p) -> int:
        return self.n_shards * p.batch

    def _table_rows(self, G: int) -> int:
        return self.n_shards * G

    def _block_stride(self, handle: dict) -> int:
        return self.n_shards * handle["p"].batch

    def _lane_idx(self, handle: dict, bi: int, li: int) -> int:
        return handle["lane_map"][bi][li]

    def _on_result_fetch(self, handle: dict) -> None:
        super()._on_result_fetch(handle)
        EVENT_LOG.append(f"result_fetch:{handle['seq']}")

    def _discover_key(self, handle: dict, bi: int, li: int,
                      contract: bytes, key: bytes) -> None:
        # allocate on the lane's CURRENT shard: the discovery rerun
        # places the lane's component around its existing copies, so
        # a cold-start discovery cycle converges with zero replicas
        # (hash-bucket allocation here measurably left the sync set
        # nonempty on chains with fully disjoint keys)
        self._gid(contract, key,
                  self._lane_idx(handle, bi, li) // handle["p"].batch)

    # --------------------------------------------------------- placement
    def _placements(self, items, premaps) -> dict:
        """Lane placement for one window (memoized on the premaps
        object, so the can_pipeline probe and the issue() that follows
        share one computation).  Cold contracts place whole-block on
        their contract bucket (the PR-8 layout); HOT contracts place
        by per-block CONFLICT COMPONENT: lanes sharing any premapped
        key union together (the in-shard OCC sweep then serializes
        them exactly), and each component lands on the shard holding
        most of its keys' copies, ties broken toward the lightest
        shard.  Placement is deterministic but affects ONLY load
        balance — every touched key is premapped and co-located, so
        results (and roots) are placement-independent."""
        cached = self._place_cache
        if cached is not None and cached[0] is premaps:
            return cached[1]
        n = self.n_shards
        homes: List[List[int]] = []
        locs: List[List[int]] = []
        occupancy = [0] * n
        unmapped = [0] * n
        max_lanes = 1
        sync_keys: Dict[Tuple[bytes, bytes], None] = {}
        # shards each key will hold copies on AFTER this window packs
        # (existing copies + allocations planned by earlier blocks of
        # THIS window — a later block replicating an earlier block's
        # fresh key is still a multi-copy sync entry)
        planned: Dict[Tuple[bytes, bytes], set] = {}
        kr_active = False
        for (_env, specs), block_pre in zip(items, premaps):
            if self._kr and n > 1:
                per_contract: Dict[bytes, int] = {}
                for t in specs:
                    per_contract[t.address] = \
                        per_contract.get(t.address, 0) + 1
                for c, cnt in per_contract.items():
                    if cnt >= self._kr_threshold:
                        self.hot_contracts[c] = None  # sticky
            counters = [0] * n
            bh = [0] * len(specs)
            bl = [0] * len(specs)
            hot_lanes = []
            for li, t in enumerate(specs):
                if self._kr and n > 1 \
                        and t.address in self.hot_contracts:
                    hot_lanes.append(li)
                else:
                    s = self.shard_of(t.address)
                    bh[li] = s
                    bl[li] = counters[s]
                    counters[s] += 1
            if hot_lanes:
                kr_active = True
                self._place_hot(specs, block_pre, hot_lanes, counters,
                                bh, bl, planned)
            # allocation plan: copies the packing loop will create on
            # each lane's home, and the keys that end up multi-copy
            # (this window's sync set)
            for li, t in enumerate(specs):
                s = bh[li]
                for k in block_pre[li]:
                    ck = (t.address, k)
                    have = planned.get(ck)
                    if have is None:
                        have = planned[ck] = {
                            cs for cs, _g in self.copies.get(ck, ())}
                    if s not in have:
                        unmapped[s] += 1
                        have.add(s)
                    if len(have) >= 2:
                        sync_keys[ck] = None
            max_lanes = max(max_lanes, max(counters))
            occupancy = [o + c for o, c in zip(occupancy, counters)]
            homes.append(bh)
            locs.append(bl)
        place = dict(homes=homes, locs=locs, occupancy=occupancy,
                     unmapped=unmapped, max_lanes=max_lanes,
                     sync_need=len(sync_keys), kr_active=kr_active)
        self._place_cache = (premaps, place)
        return place

    def _place_hot(self, specs, block_pre, hot_lanes, counters, bh,
                   bl, planned) -> None:
        """Union-find conflict components over one block's hot-contract
        lanes, then deterministic affinity/load assignment.  Affinity
        votes consult ``planned`` (allocations earlier blocks of THIS
        window will make) before the durable copy registry, so a
        stable sender does not flip shards between blocks of one
        window and mint pointless replicas."""
        n = self.n_shards
        parent = {li: li for li in hot_lanes}

        def find(x):
            while parent[x] != x:
                parent[x] = parent[parent[x]]
                x = parent[x]
            return x

        owner: Dict[Tuple[bytes, bytes], int] = {}
        for li in hot_lanes:
            addr = specs[li].address
            for k in block_pre[li]:
                o = owner.get((addr, k))
                if o is None:
                    owner[(addr, k)] = li
                else:
                    ra, rb = find(o), find(li)
                    if ra != rb:
                        parent[max(ra, rb)] = min(ra, rb)
        comps: Dict[int, List[int]] = {}
        for li in hot_lanes:
            comps.setdefault(find(li), []).append(li)
        # AFFINITY IS LOAD-CAPPED: preferring the voted shard
        # absolutely lets hot keys ACCRETE every component onto their
        # shard window after window (measured: load_imbalance -> n,
        # the collapse key-range placement exists to remove).  A
        # component follows its copies only while that shard stays
        # near its fair share; past the cap it moves (replicas are
        # exactly what the sync exchange makes affordable).  A
        # component bigger than the cap is irreducible serial work
        # (its lanes genuinely conflict) and takes the lightest shard.
        cap = max(1, (len(specs) * 5 + 4 * n - 1) // (4 * n))
        # biggest components place first (they constrain balance most);
        # stable tie-break by root lane index
        for root in sorted(comps, key=lambda r: (-len(comps[r]), r)):
            lanes = comps[root]
            votes = [0] * n
            for li in lanes:
                addr = specs[li].address
                for k in block_pre[li]:
                    have = planned.get((addr, k))
                    if have is not None:
                        for s in have:  # order-free: votes[] += only
                            votes[s] += 1
                    else:
                        for s, _g in self.copies.get((addr, k), ()):
                            votes[s] += 1
            if any(votes):
                cands = sorted(range(n),
                               key=lambda s: (-votes[s], counters[s], s))
            else:
                # fresh component: anchor on its smallest key's range.
                # A KEYLESS lane (cold start, nothing premapped yet)
                # spreads to the lightest shard instead of piling on
                # the contract bucket: its storage touches F_MISS into
                # a whole-window discovery rerun anyway, and pinning it
                # would ratchet the batch bucket to the full lane count
                anchor = min((k for li in lanes for k in block_pre[li]),
                             default=None)
                a = self._kr_home(anchor) if anchor is not None \
                    else None
                cands = sorted(range(n), key=lambda s: (counters[s], s))
                if a is not None:
                    cands = [a] + [s for s in cands if s != a]
            best = next((s for s in cands
                         if counters[s] + len(lanes) <= cap), None)
            if best is None:
                best = min(range(n), key=lambda s: (counters[s], s))
            for li in lanes:
                bh[li] = best
                bl[li] = counters[best]
                counters[best] += 1

    # ------------------------------------------------------------- shape
    def _occ_params(self, items, premaps):
        feats = set()
        max_code = 64
        max_data = 64
        max_slots = 4
        place = self._placements(items, premaps)
        for (_env, specs), block_pre in zip(items, premaps):
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
        p = M.MachineParams(
            fork=self.fork,
            batch=_pow2(place["max_lanes"], 8),
            code_cap=_pow2(max_code, 256),
            data_cap=_pow2(max_data, 128),
            scache_cap=_pow2(max_slots, 8),
            features=frozenset(feats))
        g_need = max(len(v) + u
                     for v, u in zip(self.vals, place["unmapped"]))
        occ = M.OccParams(
            blocks=_pow2(len(items), 1),
            table_cap=_pow2(g_need + 1, 64),
            rounds=p.batch + 1)
        return self._apply_buckets(p, occ)

    def _device_tables(self, G: int):
        n = self.n_shards
        if (self._prebucket and self.table is not None
                and not self._stale and G > self.table_cap):
            # recompile-free per-shard cap re-bucket: every shard's
            # arena pads IN PLACE on device (rows move s*G_old+g ->
            # s*G+g, a pure reshape/concat — no host-mirror round trip)
            Go = self.table_cap

            def _grow(tab):
                t = tab.reshape(n, Go, u256.LIMBS)
                z = jnp.zeros((n, G - Go, u256.LIMBS), dtype=jnp.int32)
                return jnp.concatenate([t, z], axis=1).reshape(
                    n * G, u256.LIMBS)

            self.table = _grow(self.table)
            self.key_tab = _grow(self.key_tab)
            self.table_cap = G
            obs.instant("device/table_grow", per_shard_rows=G)
        if self.table is None or self.table_cap != G or self._stale:
            tv = np.zeros((n * G, u256.LIMBS), dtype=np.int32)
            tk = np.zeros((n * G, u256.LIMBS), dtype=np.int32)
            for s in range(n):
                for g in range(len(self.vals[s])):
                    tv[s * G + g] = word16(self.vals[s][g])
                    tk[s * G + g] = word16(int.from_bytes(
                        self.gid_keys[s][g][1], "big"))
            self.table = jnp.asarray(tv)
            self.key_tab = jnp.asarray(tk)
            self.table_cap = G
            self._synced = [len(v) for v in self.vals]
            self._stale = False
        else:
            rows, tv, tk = [], [], []
            for s in range(n):
                for g in range(self._synced[s], len(self.vals[s])):
                    rows.append(s * G + g)
                    tv.append(word16(self.vals[s][g]))
                    tk.append(word16(int.from_bytes(
                        self.gid_keys[s][g][1], "big")))
                self._synced[s] = len(self.vals[s])
            if rows:
                jidx = jnp.asarray(np.asarray(rows, dtype=np.int32))
                self.table = self.table.at[jidx].set(
                    jnp.asarray(np.stack(tv)))
                self.key_tab = self.key_tab.at[jidx].set(
                    jnp.asarray(np.stack(tk)))
        return self.table, self.key_tab

    # ---------------------------------------------------------- schedule
    def poll_clean(self, handle: dict) -> bool:
        """Fetch ONLY the exchange tensor (tiny) and decide whether the
        window committed clean on every shard — cheap enough to gate
        dispatching the next window before the packed-result fetch."""
        clean = handle.get("clean")
        if clean is None:
            # a blocking read like the packed results': the same phase
            with self.account.enter("machine/fetch_wait"):
                ex = np.asarray(handle["ex"])
            EVENT_LOG.append(f"exchange_fetch:{handle['seq']}")
            clean = bool((ex[:, 0] == self.n_shards).all()
                         and (ex[:, 1] == 0).all())
            handle["clean"] = clean
        if clean:
            # a clean exchange means this window needs no further
            # discovery attempts: the cold-start phase is over BEFORE
            # any pipelined early dispatch, so a new kernel bucket
            # there counts as the mid-run retrace it is
            self._cold = False
        return clean

    def can_pipeline(self, items) -> bool:
        """True when issuing `items` now is provably rebuild-free: the
        per-shard table caps hold and the device table is trusted, so
        the dispatch cannot consult the (not-yet-updated) host mirror.
        The derived premaps/shapes are cached for the issue() that
        immediately follows (same items object) — the probe would
        otherwise double the per-window host prep on the very path the
        early dispatch exists to shrink."""
        self._probe = None
        if self._stale or self.table is None:
            return False
        discovered = [[{} for _t in specs] for _env, specs in items]
        premaps, predicted = self._premaps(items, discovered)
        try:
            p, occ = self._occ_params(items, premaps)
        except ValueError:
            return False
        if occ.table_cap != self.table_cap:
            return False
        # an exchange-bucket growth compiles a new kernel — not a
        # rebuild, but not the dispatch to run ahead of a result fetch
        if self._xchg_bucket(self._place_cache[1]) != self._xchg_hw:
            return False
        self._probe = (items, discovered, premaps, predicted, p, occ)
        return True

    def _xchg_bucket(self, place: dict) -> int:
        """Sync-exchange bucket a window needs: 0 until key-range
        placement first activates, then a pow2 ratchet over the
        multi-copy key count (floor 64 — the first hot window compiles
        WITH the exchange even when its sync set is still empty, so
        replicas appearing later stay inside the warmed bucket)."""
        if not place["kr_active"] and not self._xchg_hw:
            return 0
        return max(self._xchg_hw, _pow2(max(place["sync_need"], 1), 64))

    # ------------------------------------------------------------- issue
    def issue(self, items, discovered=None, attempt: int = 1) -> dict:
        faults.fire(PT_DISPATCH)  # same seam as the base runner
        probe, self._probe = self._probe, None
        if (discovered is None and probe is not None
                and probe[0] is items):
            _items, discovered, premaps, predicted, p, occ = probe
        else:
            if discovered is None:
                discovered = [[{} for _t in specs]
                              for _env, specs in items]
            premaps, predicted = self._premaps(items, discovered)
            p, occ = self._occ_params(items, premaps)
        n = self.n_shards
        W, L, S, G = occ.blocks, p.batch, p.scache_cap, occ.table_cap
        Lp = n * L
        place = self._placements(items, premaps)

        # lane placement (contract bucket / key-range components) +
        # cross-shard classification + the load-imbalance counter
        lane_map: List[List[int]] = []
        for bi, ((_env, specs), _pre) in enumerate(zip(items, premaps)):
            bh, bl = place["homes"][bi], place["locs"][bi]
            slots = []
            shards_used = set()
            for li, t in enumerate(specs):
                s = bh[li]
                shards_used.add(s)
                slots.append(s * L + bl[li])
                if attempt == 1 and self._kr \
                        and t.address in self.hot_contracts:
                    self.kr_lanes += 1
                if self._account_bucket(t.caller) != s:
                    # value/fee effects cross account buckets; they
                    # settle in the host account sweep (exact, O(txs))
                    self.cross_shard += 1
            if len(shards_used) > 1:
                self.multi_shard_blocks += 1
            lane_map.append(slots)
        total_lanes = sum(place["occupancy"])
        if attempt == 1 and total_lanes:
            # max/mean per-shard lane occupancy over the window, in
            # PERMILLE (1000 = perfectly flat, n*1000 = everything on
            # one shard — the pre-key-range hot-contract collapse)
            imb = (max(place["occupancy"]) * 1000 * n) // total_lanes
            self.load_imb_sum += imb
            self.load_imb_windows += 1
            obs.instant("shard/load_imbalance", permille=imb,
                        lanes=total_lanes)

        code = np.zeros((W, Lp, p.code_cap + 33), dtype=np.int32)
        code_len = np.zeros((W, Lp), dtype=np.int32)
        jdest = np.zeros((W, Lp, p.code_cap), dtype=np.int32)
        calldata = np.zeros((W, Lp, p.data_cap), dtype=np.int32)
        data_len = np.zeros((W, Lp), dtype=np.int32)
        start_gas = np.zeros((W, Lp), dtype=np.int32)
        active = np.zeros((W, Lp), dtype=bool)
        sgid = np.full((W, Lp, S), G, dtype=np.int32)
        prog_id = np.full((W, Lp), -1, dtype=np.int32)
        kdig = np.zeros((W, Lp, KDIG_CAP, u256.LIMBS), dtype=np.int32)
        kjobs = []
        win_keys: Dict[Tuple[bytes, bytes], None] = {}
        words = {k: np.zeros((W, Lp, u256.LIMBS), dtype=np.int32)
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
                fl = lane_map[bi][li]
                cb, jd, ln = self._code_pack(t.code, p.code_cap)
                code[bi, fl] = cb
                code_len[bi, fl] = ln
                jdest[bi, fl] = jd
                db = np.frombuffer(t.calldata, dtype=np.uint8)
                calldata[bi, fl, :len(db)] = db
                data_len[bi, fl] = len(db)
                start_gas[bi, fl] = t.gas
                active[bi, fl] = True
                words["callvalue"][bi, fl] = word16c(t.value)
                words["caller_w"][bi, fl] = word16c(addr_word(t.caller))
                words["address_w"][bi, fl] = word16c(
                    addr_word(t.address))
                words["origin_w"][bi, fl] = word16c(addr_word(t.origin))
                words["gasprice_w"][bi, fl] = word16c(t.gas_price)
                pid = self._spec_progs.get(t.code, -1) \
                    if self._specialize else -1
                prog_id[bi, fl] = pid
                if pid >= 0 and self._spec_reqs.get(t.code):
                    kjobs.append((bi, fl, t, env,
                                  self._spec_reqs[t.code]))
                if attempt == 1:
                    if pid >= 0:
                        self.lanes_specialized += 1
                    elif self._specialize:
                        self.specialize_escapes += 1
                for j, key in enumerate(block_pre[li]):
                    sgid[bi, fl, j] = self._gid(t.address, key,
                                                fl // L)
                    win_keys[(t.address, key)] = None
        fill_kdig(kdig, kjobs)
        # the window's sync set: premapped keys with >= 2 copies — the
        # (shard, gid, value) triples the per-block exchange carries
        sync = [ck for ck in win_keys
                if len(self.copies.get(ck, ())) >= 2]
        self._sync_last = len(sync)
        self._xchg_hw = max(self._xchg_bucket(place),
                            _pow2(max(len(sync), 1), 64)
                            if sync else 0)
        rows_j = None
        if self._xchg_hw:
            if not self._xchg_locked:
                self._xchg_mode = exchange_mode(
                    len(sync), max(1, total_lanes), n)
                if sync or os.environ.get("CORETH_EXCHANGE"):
                    self._xchg_locked = True
            if attempt == 1:
                if self._xchg_mode == "ppermute":
                    self.exchange_ppermute += 1
                else:
                    self.exchange_psum += 1
            # (xchg, n + 1): per-shard local rows | the owner shard
            # (first copy — always synced by the previous window's
            # exchange, so its device row is the authoritative value)
            rows = np.full((self._xchg_hw, n + 1), G, dtype=np.int32)
            for j, ck in enumerate(sync):
                cps = self.copies[ck]
                for s, g in cps:
                    rows[j, s] = g
                rows[j, n] = cps[0][0]
            rows_j = jnp.asarray(rows)
        self.lanes_real += int(active.sum())
        self.lanes_padded += active.size
        acct = self.account
        acct.enter("machine/upload")
        try:
            table, key_tab = self._device_tables(G)
            active_j = jnp.asarray(active)
            inputs = dict(
                code=jnp.asarray(code), jdest=jnp.asarray(jdest),
                code_len=jnp.asarray(code_len),
                calldata=jnp.asarray(calldata),
                data_len=jnp.asarray(data_len),
                start_gas=jnp.asarray(start_gas),
                active=active_j, sgid=jnp.asarray(sgid),
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
            seq = _next_seq()
            EVENT_LOG.append(f"dispatch:{seq}")
            if rows_j is not None:
                # PT_KEY_EXCHANGE: the intra-contract replica-sync
                # collective compiled into THIS dispatch.  Contained like
                # PT_EXCHANGE below — execute_run keeps the committed
                # prefix and the supervisor strikes the device scope.
                faults.fire(PT_KEY_EXCHANGE)
            if rows_j is None:
                out = self._dispatch(fn, table, key_tab, inputs)
            else:
                out = self._dispatch(fn, table, key_tab, inputs, rows_j)
        finally:
            acct.exit()
        self.table = out["table"]
        self._dispatched += 1
        # the exchange rides the same device queue, right behind the
        # window — its (tiny) result is what poll_clean fetches.
        # PT_EXCHANGE is the cross-shard collective's failure seam: a
        # raise here is contained by execute_run (the runner is
        # invalidated and rebuilt from the host mirror).
        faults.fire(PT_EXCHANGE)
        # the flags exchange honors the forced CORETH_EXCHANGE A/B on
        # EVERY sharded run (contract-bucketed included); auto density
        # selection only has evidence when the key-range sync is live,
        # so un-forced contract-bucket runs keep the psum default
        forced = os.environ.get("CORETH_EXCHANGE", "")
        flags_mode = forced if forced in ("psum", "ppermute") \
            else self._xchg_mode
        ex = get_shard_exchange(self.mesh, flags_mode)(
            out["packed"], active_j)
        self._prewarm(p, occ, n_blocks=len(items))
        return dict(out=out, ex=ex, items=items, discovered=discovered,
                    p=p, occ=occ, premaps=premaps, predicted=predicted,
                    attempt=attempt, lane_map=lane_map, seq=seq,
                    sync=len(sync), ticket=ticket)

    # complete() / _update_common are fully inherited: the base walks
    # packed rows through _block_stride/_lane_idx (the lane_map
    # placement), learns recipes from misses, counts discovery
    # re-dispatches and predicted-premap hits, and _on_result_fetch
    # records the dispatch-ordering trace entry.

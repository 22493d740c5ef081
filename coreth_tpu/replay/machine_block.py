"""Machine-block execution: general contract blocks on device with an
optimistic execute-validate-retry scheduler.

The ReplayEngine's transfer/token fast path covers two tx shapes; this
module covers the general case (SURVEY.md §7.6): every tx whose callee
bytecode is device-eligible executes on the batched step machine
(evm/device/machine.py) against block-start state, and cross-tx
ordering is repaired optimistically, Block-STM style:

1. round 0 executes the whole block in one device batch;
2. a sequential host sweep validates each tx's observed read set
   against the in-block state produced by the valid prefix; txs whose
   reads diverge are re-executed (only them — a conflict no longer
   drops the whole block to the host path) with the best-known
   pre-state snapshot;
3. the first mismatched tx always receives its exact pre-state, so
   every round validates at least one more tx — worst case (a fully
   serial conflict chain, the reference's ring workload,
   core/bench_test.go:64) degrades to one device round per tx, and
   independent txs in the same block still batch.

Account-level effects (nonces, buyGas solvency, value moves, fees) are
applied by a host sweep over python ints — exact, and O(txs), not
O(gas).  Reference semantics: core/state_processor.go:95 (the
sequential loop this replaces), core/state_transition.go TransitionDb.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from coreth_tpu import obs
from coreth_tpu.obs import recorder as forensics
from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device import tables as DT
from coreth_tpu.evm.device.adapter import (
    BlockEnv, MachineRunner, MachineWindowRunner, TxSpec,
)
from coreth_tpu.params import protocol as P
from coreth_tpu.processor.state_transition import (
    intrinsic_gas, is_prohibited,
)
from coreth_tpu.mpt.native_trie import derive_hasher
from coreth_tpu.types import (
    Block, Log, Receipt, StateAccount, create_bloom, derive_sha,
)
from coreth_tpu import rlp


@dataclass
class TxPlan:
    kind: str                  # "xfer" | "call"
    sender: bytes
    to: bytes
    nonce: int
    value: int
    gas_limit: int
    intrinsic: int
    price: int                 # effective gas price
    fee_cap: int
    data: bytes = b""
    code: bytes = b""


def _put_writes(into: dict, pl: TxPlan, res) -> None:
    """Every slot a lane wrote into ``into`` by (contract, key): its
    root contract's and, under call frames, the other contracts'."""
    for key, v in res.writes.items():
        into[(pl.to, key)] = v
    if res.xwrites:
        into.update(res.xwrites)


def _lane_reads(pl: TxPlan, res):
    for key, v in res.reads.items():
        yield (pl.to, key), v
    yield from res.xreads.items()


def _uses_frames(plans, fork: str) -> bool:
    return any(pl.kind == "call"
               and "call" in DT.scan_code(pl.code, fork).features
               for pl in plans)


class MachineBlockExecutor:
    """Owns classification + execution of machine blocks for one
    ReplayEngine (shares its tries and DeviceState mirrors).

    Two execution paths:
    - ``execute_run`` (default): WINDOWS of consecutive machine blocks
      fuse into single device dispatches — the OCC round loop,
      validation, and cross-block state folding run inside the jitted
      program (adapter.MachineWindowRunner), so the full-conflict swap
      shape pays O(1) device round-trips per block instead of O(txs).
    - ``execute`` (legacy; CORETH_DEVICE_OCC=0, and the fallback for
      blocks the fused kernel marks dirty): the round-5 host round
      loop — one dispatch per OCC round plus the sequential
      conflict-suffix host interpreter.
    """

    def __init__(self, engine):
        """Defaults (what a deployment and the benchmark's contract
        cell run under): ``WINDOW`` 8 machine blocks fused into one
        device dispatch, ``LOOKAHEAD`` 32 blocks classified ahead by
        ``engine._machine_run`` for one run — so a backlog of machine
        blocks goes out as runs of four windows of eight.  Both are
        read from the environment here, per executor (not at import),
        so tests and callers can retune between engine constructions
        like the other CORETH_* toggles this module consults at call
        time."""
        self.e = engine
        self.WINDOW = int(os.environ.get("CORETH_MACHINE_WINDOW", "8"))
        self.LOOKAHEAD = int(
            os.environ.get("CORETH_MACHINE_LOOKAHEAD", "32"))
        self.rounds = 0            # OCC re-execution rounds (stats)
        self.blocks = 0
        self.host_txs = 0          # conflict-suffix txs resolved on host
        self.native_txs = 0        # host-side txs served by evm/hostexec
        self.serial_blocks = 0     # blocks the serial short-circuit took
        self.windows = 0           # fused OCC windows completed
        self.window_attempts = 0   # dispatches those windows took
        self.dirty_blocks = 0      # blocks the fused path escalated
        # call frames: frames entered and device steps run by the lanes
        # the fused path committed (of those steps, the ones the fused
        # step ran, and the times it returned to the XLA step over the
        # clean blocks' rounds); txs a frame handed to the host, by
        # R_FRAME sub-reason (adapter.FRAME_REASONS; "mesh": a call-
        # frame block on a mesh engine, which runs them on the host)
        self.frames = 0
        self.lane_steps = 0
        self.fused_lane_steps = 0
        self.fused_exits = 0
        self.frame_host: Dict[str, int] = {}
        self._avoid: frozenset = frozenset()
        self.last_writes: Dict[Tuple[bytes, bytes], int] = {}
        # blocks fully finished+staged by the current _chunk_loop call
        # (read by execute_run's fault containment)
        self._inflight_consumed = 0
        self._runner: Optional[MachineWindowRunner] = None
        self._runner_fork: Optional[str] = None
        self._runner_epoch = -1
        # premap-prediction / recompile-free-growth counters accumulate
        # across runner rebuilds (an epoch bump discards the runner)
        self._runner_totals = dict(
            premap_predicted=0, premap_hits=0, premap_nested=0,
            premap_array=0, discovery_dispatches=0, kernel_retraces=0,
            warm_failures=0,
            lanes_specialized=0, specialize_escapes=0,
            programs_traced=0, lanes_real=0, lanes_padded=0,
            kr_lanes=0, load_imb_sum=0, load_imb_windows=0,
            exchange_psum=0, exchange_ppermute=0)

    def machine_counters(self) -> dict:
        """Predicted-premap, kernel-retrace and lane-fill counters over
        every window runner this executor has owned (bench machine
        section; the CI gates pin kernel_retraces and the discovery
        rate; ``lanes_real`` / ``lanes_padded`` feed
        ``ReplayStats.machine_lanes_*``)."""
        out = dict(self._runner_totals)
        r = self._runner
        if r is not None:
            for k in out:
                out[k] += getattr(r, k)
        out.update(frames=self.frames, lane_steps=self.lane_steps,
                   fused_lane_steps=self.fused_lane_steps,
                   xla_lane_steps=self.lane_steps - self.fused_lane_steps,
                   fused_exits=self.fused_exits,
                   frame_host_txs=dict(self.frame_host))
        return out

    def _count_frames(self) -> None:
        """The call-frame counters into the engine's stats."""
        st = self.e.stats
        st.machine_frames = self.frames
        st.machine_lane_steps = self.lane_steps
        st.machine_fused_lane_steps = self.fused_lane_steps
        st.machine_xla_lane_steps = self.lane_steps - self.fused_lane_steps
        st.machine_fused_exits = self.fused_exits
        st.machine_frame_host_txs = dict(self.frame_host)

    def _frame_host(self, why: Optional[str], n: int = 1) -> None:
        if why is not None:
            self.frame_host[why] = self.frame_host.get(why, 0) + n
            self._count_frames()

    # ------------------------------------------------------------ classify
    def classify(self, block: Block) -> Optional[List[TxPlan]]:
        """TxPlans if every tx is a pure transfer or a device-eligible
        contract call, else None."""
        e = self.e
        if block.ext_data():
            return None  # atomic ExtData needs the host engine hooks
        rules = e.config.rules(block.number, block.time)
        fork = DT.fork_key(rules)
        if fork is None:
            return None
        base_fee = block.base_fee
        from coreth_tpu.evm.precompiles import special_call_targets
        avoid = special_call_targets(rules)
        self._avoid = frozenset(avoid)
        plans: List[TxPlan] = []
        for tx in block.transactions:
            if tx.to is None or tx.access_list:
                return None
            if tx.to in avoid or is_prohibited(tx.to):
                return None
            try:
                sender = e.signer.sender(tx)
            except ValueError:
                return None
            s_idx = e._account(sender)
            if e.state.has_code[s_idx] or e.state.multicoin[s_idx]:
                return None
            gas_fee_cap = tx.gas_fee_cap
            if base_fee is not None:
                tip = tx.gas_tip_cap
                if gas_fee_cap < base_fee or gas_fee_cap < tip:
                    return None
                price = min(base_fee + tip, gas_fee_cap)
            else:
                price = tx.gas_price
            r_idx = e._account(tx.to)
            has_code = e.state.has_code[r_idx]
            if e.state.multicoin[r_idx]:
                return None
            intrinsic = intrinsic_gas(tx.data, [], False, rules)
            if tx.gas < intrinsic:
                return None
            if not has_code:
                if tx.data:
                    # data to an EOA burns intrinsic only — still a
                    # "transfer" shape for the account sweep
                    pass
                plans.append(TxPlan(
                    kind="xfer", sender=sender, to=tx.to,
                    nonce=tx.nonce, value=tx.value, gas_limit=tx.gas,
                    intrinsic=intrinsic, price=price,
                    fee_cap=gas_fee_cap))
                continue
            code = e.db.contract_code(e.state.code_hashes[r_idx])
            info = DT.scan_code(code, fork)
            if not info.eligible:
                return None
            if len(tx.data) > 4096:
                return None
            plans.append(TxPlan(
                kind="call", sender=sender, to=tx.to, nonce=tx.nonce,
                value=tx.value, gas_limit=tx.gas, intrinsic=intrinsic,
                price=price, fee_cap=gas_fee_cap, data=tx.data,
                code=code))
        self._fork = fork
        return plans

    # -------------------------------------------------- host conflict path
    def _host_resolve(self, block: Block, plans, call_idx, results,
                      first: int) -> None:
        """Sequentially re-execute every call tx at index >= `first`
        through the exact host interpreter, against a scratch StateDB
        carrying the device-valid prefix's storage writes.  One host
        pass resolves an arbitrarily deep conflict chain; results slot
        into the same validation sweep (reads empty = exact by
        construction)."""
        from coreth_tpu.evm.device.adapter import TxResult
        from coreth_tpu.evm.evm import (
            EVM, BlockContext, Config, TxContext)
        from coreth_tpu.evm.hostexec import counters as hx_counters
        from coreth_tpu import vmerrs
        from coreth_tpu.state import StateDB
        e = self.e
        hx0 = hx_counters().get("native_calls", 0)
        rules = e.config.rules(block.number, block.time)
        e.commit()  # persist engine tries so the scratch db can read
        scratch = StateDB(e.root, e.db, flat=e._flat_view())
        block_ctx = BlockContext(
            coinbase=block.header.coinbase, number=block.number,
            time=block.time, gas_limit=block.header.gas_limit,
            base_fee=block.base_fee)
        # ONE EVM for the whole suffix (reset per tx): the hostexec
        # bridge caches its native session on the EVM object, so a
        # deep conflict chain pays one session setup, not one per tx
        evm = EVM(block_ctx, TxContext(), scratch, e.config, Config())
        boosted = set()
        for i in call_idx:
            pl = plans[i]
            if i < first:
                res = results[i]
                if res is not None and res.status == M.STOP:
                    for key, v in res.writes.items():
                        scratch.set_state(pl.to, key,
                                          v.to_bytes(32, "big"))
                    for (c, key), v in res.xwrites.items():
                        scratch.set_state(c, key, v.to_bytes(32, "big"))
                    scratch.finalise(True)
                continue
            # solvency is validated later by the account sweep over
            # exact sequential balances; the scratch db carries
            # block-START balances, so boost the sender to keep the
            # interpreter's CanTransfer from mis-failing mid-block
            if pl.sender not in boosted:
                scratch.add_balance(pl.sender, 1 << 200)
                boosted.add(pl.sender)
            scratch.prepare(rules, pl.sender, block.header.coinbase,
                            pl.to, list(rules.active_precompiles), [])
            evm.reset(TxContext(origin=pl.sender, gas_price=pl.price),
                      scratch)
            n_logs = len(scratch.logs)
            ret, gas_left, err = evm.call(
                pl.sender, pl.to, pl.data,
                pl.gas_limit - pl.intrinsic, pl.value)
            if err is None:
                status = M.STOP
            elif isinstance(err, vmerrs.ErrExecutionReverted):
                status = M.REVERT
            else:
                status = M.ERR
            logs = []
            log_addrs = []
            writes = {}
            xwrites = {}
            if status == M.STOP:
                logs = [([bytes(t) for t in lg.topics], bytes(lg.data))
                        for lg in scratch.logs[n_logs:]]
                log_addrs = [bytes(lg.address)
                             for lg in scratch.logs[n_logs:]]
                # a call frame writes other contracts' storage too
                for c, obj in scratch._objects.items():
                    for key in list(obj.dirty_storage):
                        v = int.from_bytes(scratch.get_state(
                            c, key, _normalize=False), "big")
                        if c == pl.to:
                            writes[key] = v
                        else:
                            xwrites[(c, key)] = v
            else:
                del scratch.logs[n_logs:]
            scratch.finalise(True)
            results[i] = TxResult(
                status=status, gas_left=gas_left, refund=0, logs=logs,
                reads={}, writes=writes, xwrites=xwrites,
                log_addrs=log_addrs)
            self.host_txs += 1
        # which executor actually served the suffix: EVM.call routes
        # eligible txs through the native backend (evm/hostexec bridge)
        self.native_txs += hx_counters().get("native_calls", 0) - hx0

    # ------------------------------------------------------------- storage
    def _base_value(self, contract: bytes, key: bytes) -> int:
        # staged-but-unfolded window writes are authoritative over the
        # trie (the commit pipeline defers folds past the next
        # window's dispatch)
        e = self.e
        v = e.commit_pipe.base_value(contract, key)
        if v is not None:
            return v
        if e.flat is not None:
            # flat layer next: device table fills (the window runner's
            # storage_resolver routes here) hit a dict, not the trie
            v = e.flat.storage_value(contract, key)
            if v is not None:
                if e._flat_check:
                    raw = e._storage_trie(contract).get(key)
                    want = int.from_bytes(rlp.decode(raw), "big") \
                        if raw else 0
                    if want != v:
                        e._flat_oracle_fail("machine-slot", contract,
                                            v, want)
                return v
        st = e._storage_trie(contract)
        raw = st.get(key)
        v = int.from_bytes(rlp.decode(raw), "big") if raw else 0
        if e.flat is not None:
            e.flat.fill_storage(contract, key, v)
        return v

    # ------------------------------------------------------------- execute
    def execute(self, block: Block,
                plans: List[TxPlan]) -> Optional[bytes]:
        """Run the block; returns the post-state root, or None when a
        lane escapes to the host (caller falls back).  Raises
        ReplayError on consensus validation failure, like the transfer
        path.  Account phase ``machine/host_occ``: the whole of it, a
        block at a time — an exit the fused path takes for a dirty
        block, never its steady state."""
        with self.e.account.enter("machine/host_occ"):
            return self._execute_host_occ(block, plans)

    def _execute_host_occ(self, block: Block,
                          plans: List[TxPlan]) -> Optional[bytes]:
        e = self.e
        # a fused window may have staged earlier blocks of this run;
        # _host_resolve commits the engine tries for its scratch
        # StateDB, so the pending folds must land first
        e.commit_pipe.flush()
        t0 = time.monotonic()
        env = BlockEnv(
            coinbase=block.header.coinbase, timestamp=block.time,
            number=block.number, gas_limit=block.header.gas_limit,
            chain_id=e.config.chain_id, base_fee=block.base_fee or 0)
        call_idx = [i for i, pl in enumerate(plans)
                    if pl.kind == "call"]
        results: Dict[int, object] = {}
        base_cache: Dict[Tuple[bytes, bytes], int] = {}

        def base(contract, key):
            v = base_cache.get((contract, key))
            if v is None:
                v = self._base_value(contract, key)
                base_cache[(contract, key)] = v
            return v

        # OCC loop: execute pending lanes, then sequentially validate.
        # After DEVICE_ROUNDS optimistic device rounds, any txs still
        # conflicting resolve SEQUENTIALLY on the exact host
        # interpreter (per tx — independent txs keep their device
        # results): a serial conflict chain costs one host pass, not
        # one device dispatch per chain link (SURVEY §7.6's
        # "sequential fallback identical to state_processor.go for
        # conflicts", applied per tx instead of per block).
        DEVICE_ROUNDS = int(os.environ.get(
            "CORETH_OCC_DEVICE_ROUNDS", "2"))
        pending: List[Tuple[int, Dict]] = [(i, {}) for i in call_idx]
        max_rounds = len(call_idx) + 3
        for rnd in range(max_rounds):
            if pending and rnd >= DEVICE_ROUNDS:
                # serialize the conflict suffix on the exact host
                # interpreter: everything from the first still-pending
                # tx onward re-executes sequentially at its exact
                # position (device keeps the conflict-free prefix)
                self._host_resolve(block, plans, call_idx, results,
                                   pending[0][0])
                pending = []
            if pending:
                specs = []
                for i, overlay in pending:
                    pl = plans[i]
                    storage = {}
                    ext = {}
                    for (c, k), v in overlay.items():
                        if c == pl.to:
                            storage[k] = (v, v)
                        else:
                            ext[(c, k)] = (v, v)
                    specs.append(TxSpec(
                        code=pl.code, calldata=pl.data,
                        gas=pl.gas_limit - pl.intrinsic,
                        value=pl.value, caller=pl.sender,
                        address=pl.to, origin=pl.sender,
                        gas_price=pl.price, storage=storage,
                        ext_storage=ext))

                def resolver(addr, key):
                    # per-batch resolver: misses fall to block-start
                    # state (overlay entries were preloaded in specs)
                    return base(addr, key)

                runner = MachineRunner(self._fork, env, resolver,
                                       code_resolver=self._callee_code)
                batch = runner.run(specs)
                for (i, _), res in zip(pending, batch):
                    results[i] = res
            # sequential validation sweep
            state: Dict[Tuple[bytes, bytes], int] = {}
            pending = []
            for i in call_idx:
                pl = plans[i]
                res = results.get(i)
                if res is None:
                    pending.append((i, dict(state)))
                    continue
                if res.needs_host:
                    e.stats.t_device += time.monotonic() - t0
                    return None
                ok = True
                for ck, observed in _lane_reads(pl, res):
                    cur = state.get(ck)
                    if cur is None:
                        cur = base(*ck)
                    if cur != observed:
                        ok = False
                        break
                if not ok:
                    pending.append((i, dict(state)))
                    continue
                if res.status == M.STOP:
                    _put_writes(state, pl, res)
            if not pending:
                break
            self.rounds += 1
        else:
            e.stats.t_device += time.monotonic() - t0
            return None  # conflict storm: host path takes the block
        e.stats.t_device += time.monotonic() - t0
        return self._finish_block(block, plans, results)

    # ---------------------------------------------------- finish (shared)
    def _finish_block(self, block: Block, plans: List[TxPlan],
                      results: Dict[int, object],
                      defer: bool = False) -> Optional[bytes]:
        """Account sweep + receipts + staged trie commit for one block
        whose per-call-tx results are final (device-committed by the
        fused OCC kernel, or converged by the legacy host loop).  Host
        work is O(txs), not O(gas).

        The trie fold itself is window-batched (replay/commit.py):
        this stages the block's deduped writes and, unless ``defer``,
        flushes immediately (per-block semantics — the legacy paths).
        With ``defer=True`` the caller owns the flush, so a fused
        window folds ONCE while the next window's dispatch is already
        in flight."""
        e = self.e
        t1 = time.monotonic()
        accounts: Dict[bytes, List[int]] = {}  # addr -> [bal, nonce]

        def acct(addr: bytes) -> List[int]:
            st = accounts.get(addr)
            if st is None:
                pend = e.commit_pipe.account_view(addr)
                if pend is not None:
                    # written by an earlier block of this window; the
                    # fold is still pending
                    st = [pend[0], pend[1]]
                else:
                    raw = e.trie.get(addr)
                    if raw is not None:
                        a = StateAccount.from_rlp(raw)
                        st = [a.balance, a.nonce]
                    else:
                        st = [0, 0]
                accounts[addr] = st
            return st

        from coreth_tpu.replay.engine import _block_error, \
            transfer_log_record
        # rows: (tx_type, status, used, cum, logs) — Receipt objects
        # materialize only on the non-uniform fallback; the uniform
        # Transfer log shape (status-1, <=1 log of 3*topic32+data32)
        # derives root AND bloom in ONE C++ call (native.receipt_check,
        # as the engine's _validate_and_advance) — Python Receipt
        # construction + consensus-RLP was ~8% of the specialized
        # erc20-machine replay wall.  log_recs: the packed logs, None
        # once a receipt is outside that shape
        rows: List[tuple] = []
        log_recs: Optional[List[bytes]] = [] if e._native else None
        cum = 0
        writes_final: Dict[Tuple[bytes, bytes], int] = {}
        for i, pl in enumerate(plans):
            s = acct(pl.sender)
            if pl.nonce != s[1]:
                raise _block_error(
                    f"machine block: nonce mismatch tx {i}", block)
            if s[0] < pl.gas_limit * pl.fee_cap + pl.value:
                raise _block_error(
                    f"machine block: insufficient funds tx {i}", block)
            if pl.kind == "xfer":
                used = pl.intrinsic
                status = 1
                logs: List[Log] = []
                value_moves = True
            else:
                res = results[i]
                used = pl.gas_limit - pl.intrinsic - res.gas_left \
                    + pl.intrinsic
                status = 1 if res.status == M.STOP else 0
                value_moves = res.status == M.STOP
                logs = []
                if status == 1:
                    addrs = res.log_addrs
                    for j, (topics, data) in enumerate(res.logs):
                        logs.append(Log(
                            address=addrs[j] if addrs else pl.to,
                            topics=topics, data=data))
                    _put_writes(writes_final, pl, res)
                    for c, _k in res.xwrites:
                        # a callee's new storage root re-folds with its
                        # account
                        acct(c)
            s[1] += 1
            s[0] -= used * pl.price
            if value_moves:
                s[0] -= pl.value
                acct(pl.to)[0] += pl.value
            acct(block.header.coinbase)[0] += used * pl.price
            cum += used
            if log_recs is not None:
                if status != 1 or len(logs) > 1:
                    log_recs = None
                elif logs:
                    rec = transfer_log_record(logs[0])
                    if rec is None:
                        log_recs = None
                    else:
                        log_recs.append(rec)
            rows.append((block.transactions[i].tx_type, status, used,
                         cum, logs))
        if cum != block.header.gas_used:
            raise _block_error("machine block: gas used mismatch", block)
        receipts: Optional[List[Receipt]] = None
        if log_recs is not None:
            from coreth_tpu.crypto import native as _n
            root, bloom, _ = _n.receipt_check(
                [r[2] for r in rows],
                bytes(r[0] for r in rows),
                bytes(1 if r[4] else 0 for r in rows),
                b"".join(log_recs))
            if root != block.header.receipt_hash:
                raise _block_error(
                    "machine block: receipt root mismatch", block)
            if bloom != block.header.bloom:
                raise _block_error("machine block: bloom mismatch",
                                   block)
        else:
            receipts = [Receipt(tx_type=t, status=st,
                                cumulative_gas_used=c, gas_used=u,
                                logs=lgs)
                        for t, st, u, c, lgs in rows]
            if derive_sha(receipts, derive_hasher()) \
                    != block.header.receipt_hash:
                raise _block_error(
                    "machine block: receipt root mismatch", block)
            if create_bloom(receipts) != block.header.bloom:
                raise _block_error("machine block: bloom mismatch",
                                   block)
        if e.keep_receipts:
            e.last_receipts = receipts or [
                Receipt(tx_type=t, status=st, cumulative_gas_used=c,
                        gas_used=u, logs=lgs)
                for t, st, u, c, lgs in rows]
        if e.config.is_apricot_phase4(block.time):
            if receipts is None:
                # verify_block_fee reads only gas_used per receipt
                receipts = [Receipt(gas_used=r[2]) for r in rows]
            from coreth_tpu.consensus.engine import ConsensusError
            try:
                e.engine.verify_block_fee(
                    block.base_fee, block.header.block_gas_cost,
                    block.transactions, receipts, None)
            except ConsensusError as exc:
                # block-attributed so the streaming pipeline can
                # quarantine exactly this block (never a device strike)
                raise _block_error(
                    f"machine block: {exc}", block) from exc

        # ---------------- stage storage + accounts for the window fold
        self.last_writes = writes_final
        e.commit_pipe.stage(
            block.header, writes_final,
            {addr: (st[0], st[1]) for addr, st in accounts.items()})

        # ---------------- refresh the device-state mirrors
        e._slot_overlay.clear()
        for addr in accounts:
            # ensure device rows exist (fresh recipients/coinbase) —
            # the account fold that used to do this is now deferred
            e._account(addr)
        e.state.flush_staged()
        for addr, (bal, nonce) in accounts.items():
            idx = e.state.index[addr]
            e.state._staged.append((idx, bal, nonce))
        for (contract, key), v in writes_final.items():
            s_idx = e.state.slot_index.get((contract, key))
            if s_idx is not None and e.state.slot_host[s_idx] != v:
                e.state.slot_host[s_idx] = v
                e.state._staged_slots.append((s_idx, v))
        e.state.flush_staged()
        e.parent_header = block.header
        self.blocks += 1
        e.stats.blocks_device += 1
        e.stats.receipt_blocks_python += log_recs is None
        e.stats.txs += len(block.transactions)
        e.stats.t_trie += time.monotonic() - t1
        if defer:
            return None  # window owner flushes (and root-checks)
        return e.commit_pipe.flush()

    # -------------------------------------------- serial short-circuit
    def _serial_eligible(self, plans: List[TxPlan]) -> bool:
        """Provably-serial machine block: >=2 call txs, ONE shared
        contract, and a statically-known (PUSH-constant) storage
        footprint with writes — any two txs then conflict through the
        same keys (the swap shape), so device OCC would degrade to one
        lane per round anyway.  Such blocks dispatch straight to the
        sequential native executor; blocks with computed keys (the
        token's keccak mapping slots) keep their real independence and
        stay on device OCC."""
        if not bool(int(os.environ.get(
                "CORETH_SERIAL_SHORTCIRCUIT", "1"))):
            return False
        if os.environ.get("CORETH_HOST_EXEC", "native") != "native":
            return False
        sup = getattr(self.e, "supervisor", None)
        if sup is not None and not sup.allows("native"):
            return False  # supervisor demoted the native engine
        calls = [pl for pl in plans if pl.kind == "call"]
        if len(calls) < 2:
            return False
        target = calls[0].to
        for pl in calls[1:]:
            if pl.to != target:
                return False
        from coreth_tpu.evm.census import static_storage_keys
        keys = static_storage_keys(calls[0].code)
        if keys is None or not keys[1]:
            return False  # computed or write-free footprint
        from coreth_tpu.evm.hostexec.eligibility import native_eligible
        ok, _reason = native_eligible(calls[0].code, self._fork)
        if not ok:
            return False
        from coreth_tpu.evm.hostexec.backend import load_hostexec
        return load_hostexec() is not None

    def _execute_serial_run(self, items) -> int:
        """Sequentially execute a run of provably-serial blocks through
        the native host executor (no device rounds at all); returns
        blocks consumed.  A native escape (a CALL into unknown code,
        say) demotes THAT block to the legacy OCC path and the run
        continues; consensus failures raise like every other path."""
        from coreth_tpu.evm.device.adapter import TxResult
        from coreth_tpu.evm.forks import COINBASE_WARM_FORKS
        from coreth_tpu.evm.hostexec.backend import HostExecBackend
        e = self.e

        def resolver(contract: bytes, key: bytes) -> bytes:
            return self._base_value(contract, key).to_bytes(32, "big")

        def code_resolver(_addr: bytes):
            # any dynamic callee routes the tx (and block) off the
            # serial path — the detector only proved the ROOT contract
            return None

        be = HostExecBackend(self._fork, e.config.chain_id, resolver,
                             code_resolver)
        warm_coinbase = self._fork in COINBASE_WARM_FORKS  # EIP-3651
        consumed = 0
        try:
            for block, plans in items:
                t0 = time.monotonic()
                be.set_env(block.header.coinbase, block.time,
                           block.number, block.header.gas_limit,
                           block.base_fee or 0)
                results: Dict[int, object] = {}
                escaped = False
                for i, pl in enumerate(plans):
                    if pl.kind != "call":
                        continue
                    be.set_code(pl.to, pl.code)
                    warm = [pl.sender, pl.to]
                    if warm_coinbase:
                        warm.append(block.header.coinbase)
                    try:
                        res = be.call(pl.sender, pl.to, pl.value,
                                      pl.price, pl.data,
                                      pl.gas_limit - pl.intrinsic,
                                      warm_addrs=warm)
                    except Exception as exc:  # noqa: BLE001 — native boundary fault (injected error rc / session loss): strike the native scope and escalate this block off the serial path
                        sup = getattr(e, "supervisor", None)
                        if sup is not None:
                            sup.strike("native", exc)
                        escaped = True
                        break
                    if res.needs_host or any(
                            c != pl.to for c, _k in res.writes):
                        escaped = True
                        break
                    if res.status == M.STOP:
                        be.commit()  # sequential carry within the block
                    results[i] = TxResult(
                        status=res.status, gas_left=res.gas_left,
                        refund=res.refund,
                        logs=[(topics, data)
                              for _a, topics, data in res.logs],
                        reads={},  # exact by construction
                        writes={k: int.from_bytes(v, "big")
                                for (_c, k), v in res.writes.items()})
                e.stats.t_device += time.monotonic() - t0
                if escaped:
                    root = self.execute(block, plans)
                    if root is None:
                        return consumed
                    be.clear_storage()  # execute() moved the tries
                else:
                    n_calls = len(results)
                    # deferred: one deduped fold per serial run (the
                    # session's committed cache carries cross-block
                    # reads; _base_value consults the staged writes)
                    self._finish_block(block, plans, results,
                                       defer=True)
                    self.serial_blocks += 1
                    self.native_txs += n_calls
                consumed += 1
            e.commit_pipe.flush()
        finally:
            be.close()
            if self._runner is not None:
                # the window runner's mirror/table never saw these
                # writes; epoch bump forces its rebuild on next use
                e.storage_epoch += 1
        return consumed

    # ------------------------------------------------- fused OCC windows
    def _window_runner(self) -> MachineWindowRunner:
        """The persistent fused-OCC runner; rebuilt when the fork
        changes or another execution path (host fallback, token fast
        path) rewrote storage since the last machine window — the
        runner's host mirror and device table can then no longer be
        trusted (engine.storage_epoch tracks those writes)."""
        e = self.e
        if (self._runner is None or self._runner_fork != self._fork
                or self._runner_epoch != e.storage_epoch):
            if self._runner is not None:
                for k in self._runner_totals:
                    self._runner_totals[k] += getattr(self._runner, k)
            if (getattr(e, "mesh", None) is not None and bool(int(
                    os.environ.get("CORETH_SHARD_OCC", "1")))):
                # dp mesh: per-shard slot tables + per-shard OCC inside
                # shard_map, with the collective exchange step
                # (evm/device/shard.py); CORETH_SHARD_OCC=0 keeps the
                # replicated single-chip runner for A/B comparison
                from coreth_tpu.evm.device.shard import (
                    ShardedWindowRunner)
                self._runner = ShardedWindowRunner(
                    self._fork, self._base_value, e.mesh)
            else:
                self._runner = MachineWindowRunner(
                    self._fork, self._base_value,
                    code_resolver=self._callee_code)
            # the runner charges its own phases (machine/upload,
            # dispatch, fetch_wait) to this engine's account
            self._runner.account = e.account
            self._runner.seed_window_hint(self.WINDOW)
            self._runner_fork = self._fork
        self._runner_epoch = e.storage_epoch
        return self._runner

    def _callee_code(self, addr: bytes) -> Optional[bytes]:
        """A call frame's callee, for the window's code table: its
        runtime code, b"" for an account without code, None for a
        precompile, a prohibited address or a multicoin account (the
        host takes a tx that calls one)."""
        e = self.e
        if addr in self._avoid or is_prohibited(addr):
            return None
        idx = e._account(addr)
        if e.state.multicoin[idx]:
            return None
        if not e.state.has_code[idx]:
            return b""
        return e.db.contract_code(e.state.code_hashes[idx])

    def _window_items(self, chunk):
        """(BlockEnv, [TxSpec]) pairs for the call lanes of a chunk."""
        e = self.e
        out = []
        for block, plans in chunk:
            env = BlockEnv(
                coinbase=block.header.coinbase, timestamp=block.time,
                number=block.number, gas_limit=block.header.gas_limit,
                chain_id=e.config.chain_id,
                base_fee=block.base_fee or 0)
            specs = [TxSpec(
                code=pl.code, calldata=pl.data,
                gas=pl.gas_limit - pl.intrinsic, value=pl.value,
                caller=pl.sender, address=pl.to, origin=pl.sender,
                gas_price=pl.price) for pl in plans
                if pl.kind == "call"]
            out.append((env, specs))
        return out

    def execute_run(self, items) -> int:
        """Execute a run of consecutive machine blocks through the
        fused device-resident OCC kernel; returns how many blocks of
        `items` were fully processed (machine or internal host
        fallback).  0 means the FIRST block could not be handled here
        and the caller must route it to the engine's host path.

        Blocks chunk into WINDOW-sized fused dispatches.  The next
        chunk is dispatched BEFORE the previous chunk's tries fold
        (the device table carries committed state across dispatches
        with no host round-trip), so host trie folding of window N
        overlaps device execution of window N+1 — the _SenderPipeline
        overlap pattern extended to the execute phase.  A dirty block
        (host-escape lane or an OCC round-cap hit) re-runs through the
        legacy per-block path; the run then stops so the engine can
        re-classify against the repaired state.
        """
        if forensics.enabled():
            # flight-recorder ring entries for the machine run: block
            # + parent refs and the backend tag (serial-eligible runs
            # retag below); the premapped pre-state the kernel reads
            # is already host-visible via the engine's slot mirror and
            # lands in any later host-path witness
            parent = self.e.parent_header
            backend = "native/serial" \
                if self._serial_eligible(items[0][1]) else "device/occ"
            runner = self._runner
            forensics.merge_fingerprint(
                {"spec_set": len(getattr(runner, "_spec_progs", None)
                                 or {}),
                 "premap_recipes": sum(
                    len(v or {}) for v in (getattr(runner, "recipes",
                                                   None) or {}
                                           ).values())})
            for block, _plans in items:
                forensics.record_dispatch(block, parent, backend)
                parent = block.header
        with obs.span("machine/execute_run", blocks=len(items)):
            return self._execute_run(items)

    def _execute_run(self, items) -> int:
        e = self.e
        # serial-block short-circuit: provably-serial blocks skip the
        # device entirely (before ANY round is dispatched) and run on
        # the sequential native executor at the compiled floor
        if self._serial_eligible(items[0][1]):
            k = 1
            while k < len(items) and self._serial_eligible(items[k][1]):
                k += 1
            with obs.span("machine/serial_run", blocks=k), \
                    e.account.enter("machine/serial"):
                return self._execute_serial_run(items[:k])
        # ... and a serial block mid-run ends this window batch so the
        # NEXT execute_run call gives it the short-circuit
        for n in range(1, len(items)):
            if self._serial_eligible(items[n][1]):
                items = items[:n]
                break
        if not bool(int(os.environ.get("CORETH_DEVICE_OCC", "1"))):
            block, plans = items[0]
            return 1 if self.execute(block, plans) is not None else 0
        if getattr(self.e, "mesh", None) is not None:
            # the mesh runner has no call frames: a block whose code
            # makes them goes to the host path (counted), and a run
            # stops before the first such block
            for n, (_block, plans) in enumerate(items):
                if _uses_frames(plans, self._fork):
                    if n == 0:
                        self._frame_host("mesh", sum(
                            1 for pl in plans if pl.kind == "call"))
                        return 0
                    items = items[:n]
                    break
        runner = self._window_runner()
        chunks = [items[k:k + self.WINDOW]
                  for k in range(0, len(items), self.WINDOW)]
        t0 = time.monotonic()
        # the FIRST dispatch propagates failures: nothing is staged
        # yet, so the supervisor wrapping this call (engine
        # _machine_run) can safely retry or strike toward demotion.
        with obs.span("machine/window_issue", blocks=len(chunks[0])):
            inflight = self._issue(runner, chunk=chunks[0])
        e.stats.t_device += time.monotonic() - t0
        from coreth_tpu.consensus.engine import ConsensusError
        from coreth_tpu.replay.engine import ReplayError
        self._inflight_consumed = 0
        try:
            return self._chunk_loop(runner, chunks, inflight)
        except (ReplayError, ConsensusError):
            raise  # block-validity failure: never contained here
        except Exception as exc:  # noqa: BLE001 — a mid-run device fault: keep the committed prefix, hand the tail back for re-classification (a PERSISTENT fault then re-fires at the next run's clean first dispatch, where the supervisor can retry or demote)
            runner.invalidate()
            consumed = self._inflight_consumed
            sup = getattr(e, "supervisor", None)
            if sup is not None:
                sup.strike("device", exc)
            e.commit_pipe.flush()  # fully finished blocks stay committed
            if not consumed:
                raise
            return consumed

    def _issue(self, runner, chunk=None, items=None) -> dict:
        """One window from plans to a dispatch in flight, in account
        phase ``machine/prepare`` (lanes from plans, premap, packing);
        the runner nests ``machine/upload`` and ``machine/dispatch``
        inside it.  ``items``: the lanes, where ``_chunk_loop`` built
        them ahead (in an entry of the same phase)."""
        with self.e.account.enter("machine/prepare"):
            if items is None:
                items = self._window_items(chunk)
            return runner.issue(items)

    def _chunk_loop(self, runner, chunks, inflight) -> int:
        """The fused-window chunk loop of execute_run (split out so the
        fault containment above can recover progress: every fully
        finished-and-staged block bumps ``_inflight_consumed``).

        Account phases, entered by the WINDOW, never by the block (a
        boundary a block cost 1.5% in PR 29): ``machine/fold`` from
        before the read to the last block staged; nested inside it and
        taking their own time out, the next window's ``machine/prepare``
        (twice: its lanes are built BEFORE the read, while this window
        is on the chip, and issued after it) and the read itself
        (``machine/fetch_wait``, in the runner); ``commit/flush``
        follows outside."""
        e = self.e
        consumed = 0
        ci = 0
        while ci < len(chunks):
            with e.account.enter("machine/fold"):
                chunk = chunks[ci]
                # sharded runner: the collective exchange tensor (tiny) is
                # fetched FIRST; if every shard committed clean and the
                # next window provably needs no table rebuild, its
                # per-shard dispatch goes out BEFORE this window's packed
                # results are fetched — the cross-shard exchange overlaps
                # the next window's dispatch (pinned by the EVENT_LOG
                # ordering test).  The mirror still learns this window's
                # writes before any future rebuild: can_pipeline proved
                # the early dispatch itself cannot rebuild.
                early = None
                next_items = None
                if ci + 1 < len(chunks):
                    # the next window's lanes, built while this one is
                    # still on the chip
                    with e.account.enter("machine/prepare"):
                        next_items = self._window_items(chunks[ci + 1])
                if next_items is not None and hasattr(runner, "poll_clean"):
                    t0 = time.monotonic()
                    if (runner.poll_clean(inflight)
                            and runner.can_pipeline(next_items)):
                        early = self._issue(runner, items=next_items)
                    e.stats.t_device += time.monotonic() - t0
                t0 = time.monotonic()
                with obs.span("machine/window_complete",
                              blocks=len(chunk)):
                    wres = runner.complete(inflight)
                e.stats.t_device += time.monotonic() - t0
                inflight = None
                self.windows += 1
                self.window_attempts += wres.attempts
                # lane fill of every dispatch so far, re-dispatches and
                # discarded runners included: machine_counters() owns
                # the count, the stats carry its last reading
                mc = self.machine_counters()
                e.stats.machine_lanes_real = mc["lanes_real"]
                e.stats.machine_lanes_padded = mc["lanes_padded"]
                imb_w = (self._runner_totals["load_imb_windows"]
                         + runner.load_imb_windows)
                if imb_w:
                    # max/mean per-shard lane occupancy (permille counts),
                    # averaged over EVERY sharded window this executor has
                    # run — including runners a fault rebuild discarded
                    # (ReplayStats -> metrics registry -> bench
                    # multichip/hot_contract sections)
                    e.stats.load_imbalance = round(
                        (self._runner_totals["load_imb_sum"]
                         + runner.load_imb_sum) / imb_w / 1000, 3)
                if early is not None and not all(wres.clean):
                    # cannot happen (a clean exchange implies clean packed
                    # results); distrust the device table if it ever does
                    runner.invalidate()
                    early = None
                # pipeline: issue the NEXT chunk before folding this one —
                # its base state is the device-resident table, so the
                # dispatch needs nothing from the folds below.  The
                # runner's HOST MIRROR must still learn this chunk's
                # committed writes FIRST: if the next chunk's premap grows
                # the table past its pow2 cap, issue() rebuilds the device
                # table from the mirror, and a mirror lagging one chunk
                # would resurrect pre-chunk values (root mismatch).  The
                # trie folds below stay deferred — only the cheap dict
                # update moves ahead of the dispatch.
                pre_committed = False
                if ci + 1 < len(chunks) and all(wres.clean):
                    for k, (_block, plans) in enumerate(chunk):
                        calls = [pl for pl in plans if pl.kind == "call"]
                        writes: Dict[Tuple[bytes, bytes], int] = {}
                        for pl, res in zip(calls, wres.results[k]):
                            if res.status == M.STOP:
                                _put_writes(writes, pl, res)
                        runner.commit_block(writes)
                    pre_committed = True
                    if early is not None:
                        inflight = early
                    else:
                        t0 = time.monotonic()
                        inflight = self._issue(runner, items=next_items)
                        e.stats.t_device += time.monotonic() - t0
                for k, (block, plans) in enumerate(chunk):
                    if wres.clean[k]:
                        call_idx = [i for i, pl in enumerate(plans)
                                    if pl.kind == "call"]
                        results = {i: wres.results[k][n]
                                   for n, i in enumerate(call_idx)}
                        self.rounds += max(0, wres.rounds[k] - 1)
                        if wres.exits:
                            self.fused_exits += wres.exits[k]
                        for r in wres.results[k]:
                            self.frames += r.frames
                            self.lane_steps += r.steps
                            self.fused_lane_steps += r.fused_steps
                        self._count_frames()
                        # deferred: the whole window's writes dedupe to
                        # last-value-per-(contract, slot) and fold in ONE
                        # batch per contract below, after the next
                        # window's dispatch is already in flight
                        self._finish_block(block, plans, results,
                                           defer=True)
                        if not pre_committed:
                            # mirror already learned this chunk's writes
                            # ahead of the pipelined issue() above
                            runner.commit_block(self.last_writes)
                        consumed += 1
                        self._inflight_consumed = consumed
                        continue
                    # dirty: partial commits may sit in the device table,
                    # and every later block of the window ran against a
                    # speculative base — escalate THIS block to the legacy
                    # path and hand the rest back for re-classification
                    # (execute() flushes the staged clean prefix first)
                    self.dirty_blocks += 1
                    obs.instant("machine/dirty_block", number=block.number)
                    for r in wres.results[k]:
                        self._frame_host(r.frame_why)
                    runner.invalidate()
                    root = self.execute(block, plans)
                    if root is None:
                        if consumed == 0:
                            return 0  # caller owns the first block's fate
                        e._fallback(block)
                    else:
                        runner.commit_block(self.last_writes)
                    return consumed + 1
            # ONE deduped fold + root check per fused window — the
            # commit-phase analog of the O(1)-dispatch execute phase
            e.commit_pipe.flush()
            ci += 1
        return consumed

#!/usr/bin/env python
"""Benchmark: batched TPU replay vs the sequential compiled baselines.

Workloads:
- transfer (BASELINE config[2] shape): value-transfer chain, the
  reference's core/bench_test.go:45 InsertChain shape, replayed from
  wire bytes with full sender recovery + per-block root validation.
- erc20 (BASELINE config[1] shape): transfer() call spam on the
  workloads/erc20 token — batched storage-slot read/modify/write +
  Transfer logs/bloom + storage-trie rehash, bit-identical roots.
  Measured twice: through the token fast path, and (erc20_machine)
  forced through the GENERAL device step machine.
- swap (BASELINE config[3] shape): shared-slot constant-product pool —
  every tx conflicts through reserve slots 0/1 (the Uniswap-V2/ring
  contention analog, reference core/bench_test.go:64); exercises the
  optimistic scheduler's device rounds + host conflict-suffix.

Baselines:
- py host: BlockChain.insert_chain (the Python twin of the Go
  StateProcessor loop).
- native: compiled C++ replays — baseline.cc for transfers, evm.cc
  (a real C++ EVM interpreter) for the contract workloads — so every
  vs_baseline ratio has a compiled denominator (BASELINE.md round 5).

Prints ONE json line; the primary metric is the transfer workload.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# Persistent XLA compile cache: the replay-window kernels compile once per
# machine, not once per bench run.  Children (mesh_scaling, cluster
# workers) apply the same rule themselves.
from coreth_tpu import compile_cache  # noqa: E402

compile_cache.configure()

# Default shape: a 1024-block replay (toward the 10k-block length of
# BASELINE config[2]) with 1024 senders and a
# growing account table (half of every block's recipients are fresh
# addresses, ~65k accounts by the end of the chain).
N_BLOCKS = int(os.environ.get("BENCH_BLOCKS", "1024"))
TXS_PER_BLOCK = int(os.environ.get("BENCH_TXS", "128"))
# >=64 blocks so the extrapolated py-host denominator is not a ~1s
# noise-dominated sample (round-3 verdict weak #9)
BASELINE_BLOCKS = int(os.environ.get("BENCH_BASELINE_BLOCKS", "64"))
# ~45k avg gas/tx against the 15M Cortina block gas limit caps token
# blocks at ~300 txs; 256 keeps a pow2 batch shape
ERC20_TXS = int(os.environ.get("BENCH_ERC20_TXS", "256"))
# erc20 chain BUILD costs ~1.2 s/block (signing + host EVM): 256
# blocks (~65k txs) keeps a cold-cache build inside the section slice
# while the timed region still spans two engine windows
ERC20_BLOCKS = int(os.environ.get("BENCH_ERC20_BLOCKS", "256"))
ERC20_BASELINE_BLOCKS = int(
    os.environ.get("BENCH_ERC20_BASELINE_BLOCKS", "32"))
# contention + general-machine shapes: the fused OCC kernel re-executes
# every still-pending lane each device round, so a fully-conflicting
# L-lane block costs O(L^2) lane-execs — 16x16 measures the contention
# semantics (and the O(1)-dispatch tentpole) without the quadratic
# blow-up that kept round 5's 64x32 shape from ever completing
SWAP_BLOCKS = int(os.environ.get("BENCH_SWAP_BLOCKS", "16"))
SWAP_TXS = int(os.environ.get("BENCH_SWAP_TXS", "8"))
MACHINE_BLOCKS = int(os.environ.get("BENCH_MACHINE_BLOCKS", "16"))
MIXED_BLOCKS = int(os.environ.get("BENCH_MIXED_BLOCKS", "128"))
MIXED_TXS = int(os.environ.get("BENCH_MIXED_TXS", "32"))
_DIR = os.path.dirname(os.path.abspath(__file__))

GWEI = 10**9
N_KEYS = int(os.environ.get("BENCH_KEYS", "1024"))
TOKEN = bytes([0x77]) * 20
POOL = bytes([0x78]) * 20

# Single-run ratios on this contended 1-core host proved unfalsifiable
# (round-3 recorded 0.29x while reruns gave 1.30x and 2.61x) — every
# timed region now runs BENCH_REPS times and the JSON reports the
# median with min/max spread.
REPS = int(os.environ.get("BENCH_REPS", "3"))

# Time budget: a bench of 5 workloads x 3 reps over 1024-block chains
# once blew the driver's budget and ended with rc 124 and NO result
# line, despite the in-process watchdog thread: a wedged
# section holding the GIL (a C call that never returns) starves every
# Python thread, timer included.  Four layers of defense now:
# 1. per-SECTION deadlines: each workload owns a slice of the budget;
#    its rep loops degrade to fewer reps (never below 1) and its chain
#    build truncates at a chunk boundary when the slice runs out;
# 2. later sections are skipped outright (fields emit null);
# 3. incremental emission: after EVERY section the accumulated RESULT
#    is flushed to a state file AND printed as a partial JSON line on
#    stderr — progress survives any later catastrophe;
# 4. a CHILD-PROCESS watchdog (immune to the parent's GIL) that, at
#    the deadline, SIGKILLs the parent and prints the last recorded
#    state as the stdout JSON line itself.  The in-process timer
#    thread stays as the faster, richer path for non-GIL wedges.
T0 = time.monotonic()
DEADLINE = float(os.environ.get("BENCH_DEADLINE", "600"))
STATE_PATH = os.path.join(_DIR, ".bench_cache",
                          f"partial_{os.getpid()}.json")

# one stdout JSON line, exactly once — main() on success, a watchdog
# on overrun.  The lock makes check-and-set atomic AND holds through
# the print, so the watchdog firing while main() finishes cannot
# double-print or os._exit mid-line.
RESULT = {}
_EMITTED = False
_EMIT_LOCK = threading.Lock()
_WD_CHILD = None


def _snapshot_json(extra=None):
    """Serialize RESULT, retrying across concurrent mutation (a timer
    thread may race a main-thread RESULT.update())."""
    for _ in range(5):
        try:
            obj = dict(RESULT)
            if extra:
                obj.update(extra)
            return json.dumps(obj)
        except RuntimeError:
            time.sleep(0.05)
    return json.dumps({"metric": "transfer_replay_throughput",
                       "value": None, "unit": "txs/s",
                       "error": "result emit race"})


def _write_state(tag):
    """Persist the accumulated RESULT for the child watchdog; called
    after every completed section (and at startup)."""
    try:
        os.makedirs(os.path.dirname(STATE_PATH), exist_ok=True)
        line = _snapshot_json({"partial": tag})
        tmp = STATE_PATH + ".tmp"
        with open(tmp, "w") as f:
            f.write(line)
        os.replace(tmp, STATE_PATH)
    except OSError:
        pass


def _section_done(name):
    """Incremental emission (defense layer 3): state file + a partial
    JSON line on stderr as each section completes."""
    RESULT.setdefault("sections_done", []).append(name)
    _write_state(name)
    print(_snapshot_json({"partial": True}), file=sys.stderr, flush=True)


def _emit():
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        RESULT["elapsed_s"] = round(time.monotonic() - T0, 1)
        print(_snapshot_json(), flush=True)
        if _WD_CHILD is not None:
            try:
                _WD_CHILD.kill()
            except OSError:
                pass
        try:
            os.unlink(STATE_PATH)
        except OSError:
            pass


def _watchdog():
    try:
        _emit()
    finally:
        os._exit(0)


_WATCHDOG = threading.Timer(max(5.0, DEADLINE - 12.0), _watchdog)
_WATCHDOG.daemon = True

# The child watchdog: a separate interpreter sharing our stdout.  It
# polls until its deadline; if the parent is still alive then, it
# SIGKILLs it and prints the state file as the result line (leading
# newline: the parent may have died mid-write, and the child's line
# must still start fresh).  A GIL-holding wedge cannot touch it.
_WD_CODE = (
    "import json,os,signal,sys,time\n"
    "pid=int(sys.argv[1]); path=sys.argv[2]\n"
    "end=time.monotonic()+float(sys.argv[3])\n"
    "while time.monotonic()<end:\n"
    "    time.sleep(0.5)\n"
    "    try: os.kill(pid,0)\n"
    "    except OSError: sys.exit(0)\n"  # parent exited (and emitted)
    "try: payload=open(path).read()\n"
    "except OSError: payload=''\n"
    "try: obj=json.loads(payload)\n"
    "except ValueError: obj={}\n"
    "if not obj:\n"
    "    obj={'metric':'transfer_replay_throughput','value':None,\n"
    "         'unit':'txs/s','error':'watchdog: no state recorded'}\n"
    "obj['watchdog']='child'\n"
    "try: os.kill(pid,signal.SIGKILL)\n"
    "except OSError: pass\n"
    "time.sleep(0.3)\n"
    "sys.stdout.write('\\n'+json.dumps(obj)+'\\n')\n"
    "sys.stdout.flush()\n"
    "try: os.unlink(path)\n"
    "except OSError: pass\n"
)


def _spawn_watchdog_child():
    global _WD_CHILD
    import subprocess
    _write_state("init")
    budget = max(4.0, DEADLINE - 6.0 - (time.monotonic() - T0))
    _WD_CHILD = subprocess.Popen(
        [sys.executable, "-c", _WD_CODE, str(os.getpid()), STATE_PATH,
         str(budget)])


def _maybe_wedge():
    """BENCH_WEDGE deliberately wedges the run (watchdog regression
    harness): 'gil' blocks the main thread INSIDE a C call that never
    releases the GIL — the timer thread starves and only the child
    watchdog can produce the JSON line; any other value parks the
    main thread GIL-free, exercising the in-process timer path."""
    mode = os.environ.get("BENCH_WEDGE")
    if not mode:
        return
    if mode == "gil":
        import ctypes
        libc = ctypes.PyDLL(None)  # PyDLL: calls DO hold the GIL
        while True:
            libc.sleep(1 << 20)
    threading.Event().wait()

# end of the CURRENT workload's budget slice (absolute monotonic time);
# main() advances it section by section
SECTION_END = T0 + DEADLINE


def _remaining():
    return DEADLINE - (time.monotonic() - T0)


def _section_left():
    return min(SECTION_END, T0 + DEADLINE) - time.monotonic()


def _deadline_tight(margin=30.0):
    """True once the current section's slice (or the tail of the global
    budget) is nearly spent — rep loops stop early, keeping at least
    the one rep they already ran."""
    return _section_left() < margin or _remaining() < 30.0


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def _spread(xs):
    return [round(min(xs), 1), round(max(xs), 1)]


def _txs_per_block(workload):
    if workload == "erc20":
        return ERC20_TXS
    if workload == "swap":
        return SWAP_TXS
    return TXS_PER_BLOCK


def _n_blocks(workload):
    if workload == "swap":
        return SWAP_BLOCKS
    if workload == "erc20":
        return ERC20_BLOCKS
    return N_BLOCKS


def _cache_path(workload, n=None):
    n = _n_blocks(workload) if n is None else n
    return os.path.join(
        _DIR, ".bench_cache",
        f"{workload}_{n}x{_txs_per_block(workload)}"
        f"k{N_KEYS}.bin")


def _partial_cache(workload):
    """Largest partial-chain cache for this shape (a deadline-truncated
    earlier build), or None."""
    import glob
    pat = _cache_path(workload, n="*").replace("*", "[0-9]*")
    best, best_n = None, 0
    for path in glob.glob(pat):
        stem = os.path.basename(path)
        try:
            n = int(stem.split("_")[-1].split("x")[0])
        except ValueError:
            continue
        # never a LARGER chain than configured: this path only runs
        # when the budget slice is nearly spent, and a bigger cached
        # shape would inflate the very work the deadline is rationing
        if best_n < n <= _n_blocks(workload):
            best, best_n = path, n
    return best


def _genesis(workload):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    keys = [0xC0FFEE + i for i in range(N_KEYS)]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {a: GenesisAccount(balance=10**27) for a in addrs}
    if workload == "erc20":
        from coreth_tpu.workloads.erc20 import token_genesis_account
        alloc[TOKEN] = token_genesis_account({a: 10**24 for a in addrs})
    elif workload == "swap":
        from coreth_tpu.workloads.swap import pool_genesis_account
        alloc[POOL] = pool_genesis_account(10**24, 10**24)
    genesis = Genesis(config=TEST_CHAIN_CONFIG, gas_limit=8_000_000,
                      alloc=alloc)
    return genesis, keys, addrs


def build_or_load_chain(workload):
    """Build the chain once, cache the wire bytes (signing + host EVM
    execution dominate chain construction).  The build is CHUNKED and
    deadline-guarded: when the section's budget slice runs out the
    chain truncates at a chunk boundary (identical prefix — the gen
    callbacks are offset-wrapped) and the partial chain is cached under
    its actual length, so a later run resumes from a shorter-but-valid
    chain instead of timing out with nothing."""
    from coreth_tpu import rlp
    from coreth_tpu.types import Block
    genesis, keys, addrs = _genesis(workload)
    cache = _cache_path(workload)
    if not os.path.exists(cache):
        partial = _partial_cache(workload)
        if partial is not None and _section_left() < 60:
            # not enough slice left to extend the build: run on the
            # truncated chain from the previous attempt
            cache = partial
    if os.path.exists(cache):
        blob = open(cache, "rb").read()
        blocks = [Block.decode(b) for b in rlp.decode(blob)]
        return genesis, blocks
    from coreth_tpu.chain import generate_chain
    from coreth_tpu.state import Database
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * N_KEYS

    def gen_transfer(i, bg):
        for j in range(TXS_PER_BLOCK):
            n = i * TXS_PER_BLOCK + j
            k = n % N_KEYS
            if j % 2 == 0:
                # fresh recipient: the account table grows all chain
                to = b"\xf0" + n.to_bytes(4, "big") * 4 + b"\xf0" * 3
            else:
                to = bytes([0x10 + (j % 199)]) * 20
            # fee cap above the AP4 max base fee (1000 gwei) so the
            # chain stays valid as sustained load drives the fee up
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI, gas=21_000,
                to=to, value=10**12 + j,
            ), keys[k], CFG.chain_id))
            nonces[k] += 1

    def gen_erc20(i, bg):
        from coreth_tpu.workloads.erc20 import transfer_calldata
        for j in range(ERC20_TXS):
            k = (i * ERC20_TXS + j) % N_KEYS
            # mix of repeat token holders (SSTORE reset) and a rotating
            # pool of fresh recipients (SSTORE set)
            if j % 3 == 0:
                to = addrs[(k + 1) % N_KEYS]
            else:
                to = (0x5000 + (i * 7 + j) % 1999).to_bytes(2, "big") * 10
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI, gas=100_000,
                to=TOKEN, value=0, data=transfer_calldata(to, 10 + j),
            ), keys[k], CFG.chain_id))
            nonces[k] += 1

    def gen_swap(i, bg):
        from coreth_tpu.workloads.swap import swap_calldata
        for j in range(SWAP_TXS):
            k = (i * SWAP_TXS + j) % N_KEYS
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI, gas=100_000,
                to=POOL, value=0,
                data=swap_calldata(10**6 + i * 131 + j),
            ), keys[k], CFG.chain_id))
            nonces[k] += 1

    gen = {"erc20": gen_erc20, "swap": gen_swap}.get(
        workload, gen_transfer)
    # gap=10s: one block per fee window keeps the chain under the AP5
    # gas target so the base fee stays bounded over any chain length.
    # Chunked so the deadline check lands every few seconds; the wrapped
    # gen offsets the block index, so a chunked build emits the exact
    # blocks a single-shot build would
    target = _n_blocks(workload)
    blocks = []
    parent = gblock
    chunk = 8
    while len(blocks) < target:
        done = len(blocks)
        m = min(chunk, target - done)
        part, _ = generate_chain(
            CFG, parent, db, m,
            lambda i, bg, _o=done: gen(_o + i, bg), gap=10)
        blocks.extend(part)
        parent = part[-1]
        if len(blocks) < target and _deadline_tight(margin=45.0) \
                and len(blocks) >= 16:
            if os.environ.get("BENCH_VERBOSE"):
                print(f"[{workload}] chain build truncated at "
                      f"{len(blocks)}/{target} blocks (deadline)",
                      file=sys.stderr)
            cache = _cache_path(workload, n=len(blocks))
            break
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    with open(cache, "wb") as f:
        f.write(rlp.encode([b.encode() for b in blocks]))
    return genesis, blocks


def run_native_baseline(genesis, wire_blocks):
    """Compiled single-threaded C++ replay (native/baseline.cc) — the
    Go-proxy denominator for the north-star ratio; validates the same
    bit-identical roots.  Python packing below is prep, excluded from
    the timed region (which favors the baseline)."""
    from coreth_tpu.crypto import native
    from coreth_tpu.types import Block, LatestSigner
    blocks = [Block.decode(w) for w in wire_blocks]
    signer = LatestSigner(genesis.config.chain_id)
    recs, offs, roots, cbs = bytearray(), [0], bytearray(), bytearray()
    for b in blocks:
        for tx in b.transactions:
            r, s, recid = tx.inner.raw_signature()
            price = min(tx.gas_fee_cap, b.base_fee + tx.gas_tip_cap)
            fee = 21_000 * price
            required = tx.gas * tx.gas_fee_cap + tx.value
            recs += signer.sig_hash(tx)
            recs += r.to_bytes(32, "big") + s.to_bytes(32, "big") \
                + bytes([recid])
            recs += tx.to
            recs += tx.value.to_bytes(32, "big") + fee.to_bytes(32, "big") \
                + required.to_bytes(32, "big")
            recs += tx.nonce.to_bytes(8, "big")
        offs.append(offs[-1] + len(b.transactions))
        roots += b.root
        cbs += b.header.coinbase
    accounts = b"".join(
        addr + acct.balance.to_bytes(32, "big")
        + acct.nonce.to_bytes(8, "big")
        for addr, acct in genesis.alloc.items())
    txs = sum(len(b.transactions) for b in blocks)
    return _native_reps(
        native.baseline_replay,
        (bytes(recs), offs, bytes(roots), bytes(cbs), accounts,
         len(genesis.alloc)), txs, "transfer")


def _native_reps(native_fn, args, txs, label):
    """REPS timed runs of a compiled baseline entry point; rc != 0 is
    a root/validation failure."""
    tps_runs, phases = [], None
    for _ in range(REPS):
        t0 = time.monotonic()
        rc, phases = native_fn(*args)
        dt = time.monotonic() - t0
        if rc != 0:
            raise RuntimeError(f"native {label} baseline failed rc={rc}")
        tps_runs.append(txs / dt)
        if _deadline_tight():
            break
    return tps_runs, {"t_sender": round(phases[0], 3),
                      "t_exec": round(phases[1], 3),
                      "t_trie": round(phases[2], 3)}


def run_native_evm(genesis, wire_blocks):
    """Compiled single-threaded C++ EVM replay (native/evm.cc) — the
    contract-workload denominator; validates bit-identical roots."""
    from coreth_tpu.crypto import native
    from coreth_tpu.types import Block
    from coreth_tpu.workloads.pack_native import pack_evm_replay
    blocks = [Block.decode(w) for w in wire_blocks]
    txs = sum(len(b.transactions) for b in blocks)
    return _native_reps(native.evm_replay,
                        pack_evm_replay(genesis, blocks), txs, "evm")


def _native_evm_rep(genesis, blocks, sink):
    """One timed native-EVM rep per call (chain packed once up
    front), appending txs/s into ``sink``; None when the native build
    is unavailable.  Passed as ``run_tpu(interleave=...)`` so native
    and device reps ALTERNATE within one section: a ratio's numerator
    and denominator then sample the same machine-load window instead
    of sections minutes apart — the PR-15 noise rule that fixed the
    mesh-scaling curve, applied to the vs_native denominators."""
    from coreth_tpu.crypto import native
    from coreth_tpu.workloads.pack_native import pack_evm_replay
    if native.load() is None:
        return None
    args = pack_evm_replay(genesis, blocks)
    txs = sum(len(b.transactions) for b in blocks)

    def one_rep():
        t0 = time.monotonic()
        rc, _phases = native.evm_replay(*args)
        dt = time.monotonic() - t0
        if rc != 0:
            raise RuntimeError(f"native evm interleave failed rc={rc}")
        sink.append(txs / dt)
    return one_rep


def run_baseline(genesis, wire_blocks, n_blocks):
    """Sequential host insert (fresh sender cache) over a block subset."""
    from coreth_tpu.chain import BlockChain
    from coreth_tpu.types import Block
    tps_runs, timers = [], None
    for _ in range(REPS):
        blocks = [Block.decode(w) for w in wire_blocks[:n_blocks]]
        chain = BlockChain(genesis)
        t0 = time.monotonic()
        chain.insert_chain(blocks)
        dt = time.monotonic() - t0
        txs = sum(len(b.transactions) for b in blocks)
        tps_runs.append(txs / dt)
        timers = chain.timers.row()
        if _deadline_tight():
            break
    return tps_runs, timers


def _fresh_engine(genesis, txs_per_block):
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    db = Database()
    gblock = genesis.to_block(db)
    # size the device account table for the workload's growth up front:
    # capacity is a static arg of the compiled window kernels, so
    # in-flight growth would recompile at every pow2 step
    need = N_KEYS + N_BLOCKS * TXS_PER_BLOCK // 2 + 1024
    capacity = 1 << max(14, (need - 1).bit_length())
    return ReplayEngine(genesis.config, db, gblock.root,
                        parent_header=gblock.header,
                        batch_pad=txs_per_block, capacity=capacity,
                        slot_capacity=1 << 14,
                        window=int(os.environ.get("BENCH_WINDOW", "128")))


def run_tpu(genesis, wire_blocks, txs_per_block, machine_stats=None,
            interleave=None):
    from coreth_tpu.types import Block

    # Warm-up pass on throwaway blocks/engine: compiles (or cache-loads)
    # every device executable this workload shape needs — the window
    # scan buckets, the rehash kernel.  XLA
    # compile/load is a per-process one-time cost, excluded from timing
    # exactly like the first-block warm-up the round-1 bench did.
    # A PREFIX suffices: every bucket the full chain exercises appears
    # within the first two engine windows (the shapes are constant per
    # workload), so warming 2*window+1 blocks compiles everything while
    # costing ~1/4 of a timed rep instead of a whole one.
    window = int(os.environ.get("BENCH_WINDOW", "128"))
    warm_n = min(len(wire_blocks),
                 int(os.environ.get("BENCH_WARM_BLOCKS",
                                    str(2 * window + 1))))
    warm_blocks = [Block.decode(w) for w in wire_blocks[:warm_n]]
    warm = _fresh_engine(genesis, txs_per_block)
    warm.replay_block(warm_blocks[0])
    warm.replay(warm_blocks[1:])
    assert warm.root == warm_blocks[-1].header.root
    assert warm.stats.blocks_fallback == 0, warm.stats.row()

    # Timed passes: fresh Block objects (no cached senders), fresh state
    # each rep; compiled executables are shared via the XLA cache.
    from coreth_tpu.evm.device import adapter as _adapter
    tps_runs, stats = [], None
    for r in range(REPS):
        # interleave (when given) runs one rep of the section's OTHER
        # engine — the compiled denominator — between device reps,
        # alternating device-first/native-first per round so neither
        # side systematically samples a colder machine; both calls sit
        # OUTSIDE the timed region below
        if interleave is not None and r % 2 == 1:
            interleave()
        blocks = [Block.decode(w) for w in wire_blocks]
        engine = _fresh_engine(genesis, txs_per_block)
        engine.replay_block(blocks[0])
        d0 = _adapter.DISPATCH_COUNT
        # snapshot commit counters AFTER block 0: the attribution
        # below must cover exactly the timed region
        cp = engine.commit_pipe
        trie0, fold_s0 = engine.stats.t_trie, cp.fold_s
        fold_b0, fold_c0 = cp.fold_blocks, cp.fold_calls
        t0 = time.monotonic()
        engine.replay(blocks[1:])
        dt = time.monotonic() - t0
        txs = sum(len(b.transactions) for b in blocks[1:])
        assert engine.root == blocks[-1].header.root
        assert engine.stats.blocks_fallback == 0, engine.stats.row()
        tps_runs.append(txs / dt)
        stats = engine.stats.row()
        # commit-phase attribution (replay/commit.py): pure fold+rehash
        # time per block and the t_trie share of replay wall time pin
        # the window-batched trie-commit speedup in the JSON
        stats["fold_ms_per_block"] = round(
            1000 * (cp.fold_s - fold_s0)
            / max(1, cp.fold_blocks - fold_b0), 3)
        stats["fold_windows"] = cp.fold_calls - fold_c0
        stats["t_trie_share"] = round(
            (stats["t_trie"] - trie0) / dt, 3)
        if machine_stats is not None and hasattr(engine, "_machine"):
            mx = engine._machine
            disp = _adapter.DISPATCH_COUNT - d0
            mc = mx.machine_counters()
            machine_stats.update(
                occ_rounds=mx.rounds,
                host_txs=mx.host_txs,
                # predicted premaps + recompile-free growth (the CI
                # gates pin kernel_retraces == 0 and the erc20
                # dispatches_per_block bound in tier-1)
                discovery_dispatches=mc["discovery_dispatches"],
                premap_predicted=mc["premap_predicted"],
                premap_hit_rate=round(
                    mc["premap_hits"]
                    / max(1, mc["premap_predicted"]), 3),
                premap_array=mc["premap_array"],
                kernel_retraces=mc["kernel_retraces"],
                # key-range placement surface (0 on single-device /
                # cold-contract runs): lanes placed by key range and
                # the max/mean per-shard occupancy ratio
                kr_lanes=mc["kr_lanes"],
                load_imbalance=round(
                    mc["load_imb_sum"]
                    / max(1, mc["load_imb_windows"]) / 1000, 3),
                # per-contract traced specialization (ISSUE 13): how
                # many lanes ran straight-line sub-programs vs the
                # generic interpreter escape hatch
                lanes_specialized=mc["lanes_specialized"],
                specialize_escapes=mc["specialize_escapes"],
                programs_traced=mc["programs_traced"],
                # which executor served host-side txs: native_txs ran
                # on the compiled backend (evm/hostexec — serial
                # short-circuit blocks + natively-served conflict
                # suffix), host_txs - suffix natives on the Python
                # interpreter
                native_txs=mx.native_txs,
                serial_blocks=mx.serial_blocks,
                machine_blocks=mx.blocks,
                dirty_blocks=mx.dirty_blocks,
                occ_windows=mx.windows,
                window_attempts=mx.window_attempts,
                # the tentpole metric: device dispatches per machine
                # block (round-5 host OCC loop paid O(txs); the fused
                # device-resident loop pays O(1))
                dispatches=disp,
                dispatches_per_block=round(disp / max(1, mx.blocks), 2))
        if interleave is not None and r % 2 == 0 \
                and not _deadline_tight():
            interleave()
        if _deadline_tight():
            break
    return tps_runs, stats


def run_trie_backend_compare(workload, n_blocks=64):
    """fold_ms_per_block per trie backend, ONE rep each on the same
    truncated chain — pins the native-vs-python commit-path ratio
    (ISSUE 4 acceptance: >= 3x) in the JSON instead of claiming it."""
    from coreth_tpu.types import Block
    from coreth_tpu.mpt import native_trie
    genesis, blocks = build_or_load_chain(workload)
    wire = [b.encode() for b in blocks[:n_blocks]]
    txs_per_block = _txs_per_block(workload)
    out = {}
    saved = os.environ.get("CORETH_TRIE")
    try:
        for backend in ("native", "py"):
            if backend == "native" and not native_trie.available():
                continue
            os.environ["CORETH_TRIE"] = backend
            blks = [Block.decode(w) for w in wire]
            engine = _fresh_engine(genesis, txs_per_block)
            engine.replay_block(blks[0])
            cp = engine.commit_pipe
            fold_s0, fold_b0 = cp.fold_s, cp.fold_blocks
            engine.replay(blks[1:])
            assert engine.root == blks[-1].header.root
            # a host-fallback block would shrink this backend's fold
            # coverage and skew the published ratio — fail loudly
            assert engine.stats.blocks_fallback == 0, engine.stats.row()
            out[f"fold_ms_per_block_{backend}"] = round(
                1000 * (cp.fold_s - fold_s0)
                / max(1, cp.fold_blocks - fold_b0), 3)
            if _deadline_tight():
                break
    finally:
        if saved is None:
            os.environ.pop("CORETH_TRIE", None)
        else:
            os.environ["CORETH_TRIE"] = saved
    native_ms = out.get("fold_ms_per_block_native")
    py_ms = out.get("fold_ms_per_block_py")
    if native_ms and py_ms:
        out["fold_speedup"] = round(py_ms / native_ms, 2)
    return out


def run_workload(workload, baseline_blocks, tpu_blocks=None,
                 machine_stats=None, skip_baselines=False,
                 commit_stats=None, interleave=None):
    genesis, blocks = build_or_load_chain(workload)
    wire = [b.encode() for b in blocks]
    base_runs = base_timers = None
    native_runs = native_phases = None
    from coreth_tpu.crypto import native as _native
    if not skip_baselines:
        base_runs, base_timers = run_baseline(genesis, wire,
                                              baseline_blocks)
    # the TPU reps run BEFORE the native baseline: when the section
    # slice is tight, the primary measurement degrades last — the
    # compiled denominator gives up reps first
    tpu_wire = wire[:tpu_blocks] if tpu_blocks else wire
    tpu_runs, tpu_stats = run_tpu(genesis, tpu_wire,
                                  _txs_per_block(workload),
                                  machine_stats=machine_stats,
                                  interleave=interleave)
    if commit_stats is not None and tpu_stats is not None:
        from coreth_tpu.mpt import native_trie
        commit_stats.update(
            trie_backend=native_trie.backend(),
            fold_ms_per_block=tpu_stats.get("fold_ms_per_block"),
            fold_windows=tpu_stats.get("fold_windows"),
            t_trie_share=tpu_stats.get("t_trie_share"))
    if not skip_baselines and _native.load() is not None:
        if workload == "transfer":
            native_runs, native_phases = run_native_baseline(
                genesis, wire)
        else:
            native_runs, native_phases = run_native_evm(genesis, wire)
    if os.environ.get("BENCH_VERBOSE"):
        if base_runs:
            print(f"[{workload}] py-host baseline",
                  [round(x) for x in base_runs], "txs/s", base_timers,
                  file=sys.stderr)
        if native_runs:
            print(f"[{workload}] native baseline",
                  [round(x) for x in native_runs], "txs/s", native_phases,
                  file=sys.stderr)
        print(f"[{workload}] tpu", [round(x) for x in tpu_runs], "txs/s",
              tpu_stats, file=sys.stderr)
    return base_runs, tpu_runs, native_runs


def run_specialize():
    """Specialization section (ISSUE 13 / ROADMAP direction 1): the
    erc20-machine path replayed with CORETH_SPECIALIZE=1 and =0, each
    under an installed tracer, so the before/after is ATTRIBUTED — the
    dispatch (machine/window_issue), fetch (machine/window_complete)
    and fold (commit/flush) span shares of replay wall time — instead
    of argued from aggregate txs/s.  The regression signal is the
    spec/generic RATIO (the bench-drift rule: ratios, never absolute
    txs/s); the tentpole acceptance gate (erc20-machine >= 1x the
    native sequential engine) is recorded next to it in main()."""
    from coreth_tpu import obs
    from coreth_tpu.evm.census import jump_profile
    from coreth_tpu.types import Block
    from coreth_tpu.workloads.erc20 import TOKEN_RUNTIME
    genesis, blocks = build_or_load_chain("erc20")
    n = min(len(blocks), MACHINE_BLOCKS)
    wire = [b.encode() for b in blocks[:n]]
    # static eligibility profile of the hot contract: how much of its
    # jump structure is the direct-push idiom the tracer resolves
    jumps, push_jumps = jump_profile(TOKEN_RUNTIME)
    out = {"blocks": n,
           "eligibility": {"jumps": jumps, "push_jumps": push_jumps}}
    os.environ["CORETH_NO_TOKEN_FASTPATH"] = "1"
    prev_env = os.environ.pop("CORETH_TRACE", None)
    try:
        for label, spec in (("specialized", "1"), ("generic", "0")):
            os.environ["CORETH_SPECIALIZE"] = spec
            # warm rep: each side owns distinct kernel buckets (the
            # program set is part of the kernel key), so compiles must
            # not skew the A/B
            warm = [Block.decode(w) for w in wire]
            engine = _fresh_engine(genesis, ERC20_TXS)
            engine.replay_block(warm[0])
            engine.replay(warm[1:])
            assert engine.root == warm[-1].header.root
            tracer = obs.install()
            try:
                fresh = [Block.decode(w) for w in wire]
                engine = _fresh_engine(genesis, ERC20_TXS)
                engine.replay_block(fresh[0])
                t0 = time.monotonic()
                engine.replay(fresh[1:])
                dt = time.monotonic() - t0
            finally:
                obs.uninstall()
            assert engine.root == fresh[-1].header.root
            txs = sum(len(b.transactions) for b in fresh[1:])
            mc = engine._machine.machine_counters()
            # self times: a span's children (commit/flush under
            # machine/window_complete) are taken out of it, so the
            # shares never count one second twice
            sums = obs.self_times(tracer.export()["traceEvents"])
            total = max(dt, 1e-9)
            out[label] = {
                "txs_s": round(txs / dt, 1),
                "lanes_specialized": mc["lanes_specialized"],
                "specialize_escapes": mc["specialize_escapes"],
                "programs_traced": mc["programs_traced"],
                "kernel_retraces": mc["kernel_retraces"],
                "shares": {
                    "dispatch": round(
                        sums.get("machine/window_issue", 0) / total, 3),
                    "fetch": round(
                        sums.get("machine/window_complete", 0) / total,
                        3),
                    "fold": round(
                        sums.get("commit/flush", 0) / total, 3),
                },
            }
            if _deadline_tight():
                break
    finally:
        os.environ.pop("CORETH_SPECIALIZE", None)
        del os.environ["CORETH_NO_TOKEN_FASTPATH"]
        if prev_env is not None:
            os.environ["CORETH_TRACE"] = prev_env
    if "specialized" in out and "generic" in out:
        out["spec_vs_generic"] = round(
            out["specialized"]["txs_s"]
            / max(out["generic"]["txs_s"], 1e-9), 3)
    return out


def run_mixed():
    """BASELINE config[4]: Avalanche-semantics segment (atomic ExtData
    imports + nativeAssetCall + transfer spam) under the AP5 rule set.
    Atomic/multicoin blocks ride the exact host path via the engine
    callbacks; the fallback fraction is part of the result."""
    from coreth_tpu.params import TEST_APRICOT_PHASE5_CONFIG
    from coreth_tpu.workloads import mixed as MX
    from coreth_tpu.types import Block
    keys = [0xB0B + i for i in range(64)]
    genesis, blocks = MX.build_mixed_chain(
        TEST_APRICOT_PHASE5_CONFIG, MIXED_BLOCKS, MIXED_TXS, keys)
    # reps decode fresh Block objects from wire so every run pays full
    # sender recovery — same methodology as the other workloads
    wire = [b.encode() for b in blocks]
    want_root = blocks[-1].root
    txs = sum(len(b.transactions) for b in blocks)
    del blocks
    py_runs = []
    for _ in range(REPS):
        fresh = [Block.decode(w) for w in wire]
        chain = MX.host_chain(genesis, MIXED_BLOCKS, keys[0])
        t0 = time.monotonic()
        chain.insert_chain(fresh)
        py_runs.append(txs / (time.monotonic() - t0))
        if _deadline_tight():
            break
    tpu_runs, stats = [], None
    from coreth_tpu.evm import hostexec as _hx
    for _ in range(REPS):
        fresh = [Block.decode(w) for w in wire]
        eng, _g = MX.replay_engine(genesis, MIXED_BLOCKS, keys[0],
                                   window=int(os.environ.get(
                                       "BENCH_WINDOW", "128")))
        _hx.reset_counters()
        t0 = time.monotonic()
        eng.replay(fresh)
        dt = time.monotonic() - t0
        assert eng.root == want_root
        tpu_runs.append(txs / dt)
        stats = eng.stats.row()
        # which executor served the host-fallback blocks' txs
        # (evm/hostexec bridge counters for this rep)
        stats["host_exec"] = _hx.counters()
        if _deadline_tight():
            break
    if os.environ.get("BENCH_VERBOSE"):
        print("[mixed] py-host", [round(x) for x in py_runs], "txs/s",
              file=sys.stderr)
        print("[mixed] tpu", [round(x) for x in tpu_runs], "txs/s",
              stats, file=sys.stderr)
    return py_runs, tpu_runs, stats


def run_streaming():
    """Streaming-ingestion section: the transfer chain through the
    serve pipeline (feed -> prefetch -> execute -> commit), reporting
    p50/p99/max enqueue->committed block latency and sustained txs/s —
    once in backlog mode (feed released as fast as consumed: pipeline
    capacity) and once paced at ~70% of that rate (service latency
    under sustained arrival, the SLO-honest number)."""
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.types import Block
    genesis, blocks = build_or_load_chain("transfer")
    n = min(len(blocks),
            int(os.environ.get("BENCH_STREAM_BLOCKS", "512")))
    wire = [b.encode() for b in blocks[:n]]
    window = int(os.environ.get("BENCH_STREAM_WINDOW", "32"))
    out = {"blocks": n, "window": window}
    from coreth_tpu import obs

    def one_run(rate=None):
        fresh = [Block.decode(w) for w in wire]
        engine = _fresh_engine(genesis, TXS_PER_BLOCK)
        engine.window = window
        pipe = StreamingPipeline(engine, ChainFeed(fresh, rate=rate),
                                 window_wait=0.005)
        rep = pipe.run()
        assert engine.root == fresh[-1].header.root
        assert engine.stats.blocks_fallback == 0, engine.stats.row()
        return rep

    # the section owns the tracer state: a CORETH_TRACE=1 env must not
    # silently arm the backlog (capacity) rep through arm_from_env
    prev_env = os.environ.pop("CORETH_TRACE", None)
    try:
        obs.uninstall()
        rep = one_run()
        out["backlog"] = rep.row()
        if not _deadline_tight(margin=45.0):
            bps = rep.blocks / max(rep.wall_s, 1e-9)
            rate = round(0.7 * bps, 2)
            out["paced_rate_blocks_s"] = rate
            # the paced (SLO-honest) run carries the tracer so its row
            # records stage_breakdown — where the p50 actually goes at
            # a sustained arrival rate (the tracing section owns the
            # overhead A/B; gated >= 0.95, so attributing here is safe)
            obs.install()
            try:
                out["paced"] = one_run(rate=rate).row()
            finally:
                obs.uninstall()
    finally:
        if prev_env is not None:
            os.environ["CORETH_TRACE"] = prev_env
    return out


def run_tracing():
    """Tracing section (coreth_tpu/obs): per-stage latency attribution
    for a paced streaming run — the tracer's ``stage_breakdown``
    (shares of enqueue->committed time; sums to ~1.0) — plus the
    tracing OVERHEAD ratio: traced vs untraced sustained txs/s on the
    SAME backlog shape, interleaved reps so box drift hits both sides
    equally.  The ratio is the regression signal (the bench-drift
    rule) and must stay >= 0.95: tracing must never become the new
    bottleneck.  The Perfetto export is validated structurally (it
    must load) and its size recorded."""
    from coreth_tpu import obs
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.types import Block
    genesis, blocks = build_or_load_chain("transfer")
    n = min(len(blocks),
            int(os.environ.get("BENCH_TRACE_BLOCKS", "96")))
    wire = [b.encode() for b in blocks[:n]]
    out = {"blocks": n}

    def one_run(traced, rate=None):
        fresh = [Block.decode(w) for w in wire]
        # CORETH_TRACE=1 in the caller's env would silently arm the
        # "untraced" side through the engine/pipeline constructors'
        # arm_from_env and make the A/B vacuous (traced/traced ~ 1.0):
        # the A/B owns the tracer state for both sides
        prev_env = os.environ.pop("CORETH_TRACE", None)
        tracer = None
        try:
            if traced:
                tracer = obs.install()
            else:
                obs.uninstall()
            engine = _fresh_engine(genesis, TXS_PER_BLOCK)
            pipe = StreamingPipeline(engine, ChainFeed(fresh, rate=rate),
                                     window_wait=0.005)
            rep = pipe.run()
        finally:
            if traced:
                obs.uninstall()
            if prev_env is not None:
                os.environ["CORETH_TRACE"] = prev_env
        assert engine.root == fresh[-1].header.root
        return rep, tracer

    one_run(False)  # warm-up: XLA compiles must not skew the A/B
    plain, traced = [], []
    rep_t = tracer = None
    for _ in range(3):
        rep_p, _none = one_run(False)
        plain.append(rep_p.sustained_txs_s)
        rep_t, tracer = one_run(True)
        traced.append(rep_t.sustained_txs_s)
        if _deadline_tight():
            break
    out["stage_breakdown"] = rep_t.stage_breakdown
    # best-of each side: the gate asks whether tracing lowers the
    # path's CAPACITY, so one straggler rep (GC, a background compile)
    # must not fake a regression on this 1-core box
    out["untraced_txs_s"] = round(max(plain), 1)
    out["traced_txs_s"] = round(max(traced), 1)
    ratio = round(max(traced) / max(max(plain), 1e-9), 3)
    # the acceptance gate: tracing-enabled throughput >= 0.95x
    out["trace_overhead"] = ratio
    out["overhead_ok"] = ratio >= 0.95
    doc = tracer.export()
    out["trace_events"] = len(doc["traceEvents"])
    out["ring_dropped"] = tracer.dropped
    # shares must cover the latency (a breakdown that doesn't sum to
    # ~1.0 means a stage went unattributed)
    share_sum = sum(v for k, v in rep_t.stage_breakdown.items()
                    if not k.startswith("_"))
    out["breakdown_sum"] = round(share_sum, 4)
    return out


def run_forensics():
    """Forensics section (obs/recorder): the divergence flight
    recorder armed vs unarmed on the SAME backlog streaming shape,
    interleaved reps so box drift hits both sides equally.  The
    ``recorder_overhead`` RATIO is the regression signal (bench-drift
    rule) and must stay >= 0.95 — the witness ring must never become
    the new bottleneck.  Plus one INJECTED trip: a poison block
    quarantines, freezes a bundle, and the section records the
    bundle's on-disk size and drain-thread write latency."""
    import shutil
    import tempfile
    from coreth_tpu.obs import recorder as _rec
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.serve.pipeline import _corrupt_block
    from coreth_tpu.types import Block
    genesis, blocks = build_or_load_chain("transfer")
    n = min(len(blocks),
            int(os.environ.get("BENCH_FORENSICS_BLOCKS", "96")))
    wire = [b.encode() for b in blocks[:n]]
    out = {"blocks": n}
    tmp = tempfile.mkdtemp(prefix="bench_forensics_")

    def one_run(armed, feed_wire=wire, expect_root=True):
        fresh = [Block.decode(w) for w in feed_wire]
        # a CORETH_FORENSICS=1 env must not silently arm the
        # "unarmed" side through arm_from_env (the tracing-A/B rule)
        prev_env = os.environ.pop("CORETH_FORENSICS", None)
        try:
            if armed:
                rec = _rec.install(out_dir=tmp)
            else:
                rec = None
                _rec.uninstall()
            engine = _fresh_engine(genesis, TXS_PER_BLOCK)
            pipe = StreamingPipeline(engine, ChainFeed(fresh),
                                     window_wait=0.005)
            rep = pipe.run()
        finally:
            _rec.uninstall()
            if prev_env is not None:
                os.environ["CORETH_FORENSICS"] = prev_env
        if expect_root:
            assert engine.root == fresh[-1].header.root
        return rep, rec

    try:
        one_run(False)  # warm-up: XLA compiles must not skew the A/B
        plain, armed = [], []
        for r in range(4):
            # alternate which side goes first: on this 1-core box the
            # second run of a pair measures systematically slower
            # (scheduler/GC debt from the first), which read as a fake
            # ~5% recorder overhead when armed always went second
            order = (False, True) if r % 2 == 0 else (True, False)
            for is_armed in order:
                rep_x, _rec0 = one_run(is_armed)
                (armed if is_armed else plain).append(
                    rep_x.sustained_txs_s)
            if _deadline_tight():
                break
        out["unarmed_txs_s"] = round(max(plain), 1)
        out["armed_txs_s"] = round(max(armed), 1)
        ratio = round(max(armed) / max(max(plain), 1e-9), 3)
        # the acceptance gate: recorder-armed throughput >= 0.95x
        out["recorder_overhead"] = ratio
        out["overhead_ok"] = ratio >= 0.95
        # ---- one injected trip -> bundle size / write latency
        if not _deadline_tight():
            trip_wire = list(wire[:8])
            bad = _corrupt_block(Block.decode(trip_wire[-1]))
            trip_wire[-1] = bad.encode()
            rep_t, rec = one_run(True, feed_wire=trip_wire,
                                 expect_root=False)
            snap = rep_t.forensics
            out["trip"] = {
                "quarantined": len(rep_t.quarantined),
                "bundle_writes": snap.get("bundle_writes", 0),
                "bundle_failures": snap.get("bundle_failures", 0),
                "write_ms": snap.get("write_ms", 0.0),
            }
            paths = [b["path"] for b in snap.get("bundles", [])]
            if paths:
                size = sum(
                    os.path.getsize(os.path.join(dp, f))
                    for dp, _dn, fns in os.walk(paths[-1])
                    for f in fns)
                out["trip"]["bundle_bytes"] = size
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def run_flat_state():
    """Flat-state section (state/flat): the cold-read microbench —
    the SAME key population resolved through the flat store vs the
    trie-walk path it replaced — plus checkpoint durability cost ON vs
    OFF the execute thread (background stamp vs synchronous write,
    both recorded) and the layer's hit/miss counters.  All regression
    signals are RATIOS (speedup, stamp-vs-export), never absolute
    txs/s (the bench-drift rule: boxes differ, ratios travel)."""
    from coreth_tpu.replay.checkpoint import CheckpointManager
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.types import Block
    genesis, blocks = build_or_load_chain("erc20")
    n = min(len(blocks), int(os.environ.get("BENCH_FLAT_BLOCKS", "48")))
    wire = [b.encode() for b in blocks[:n]]
    out = {"blocks": n}

    # ---- replay once with the layer on: counters + key population
    fresh = [Block.decode(w) for w in wire]
    engine = _fresh_engine(genesis, ERC20_TXS)
    if engine.flat is None:
        return {"skipped": "CORETH_FLAT=0"}
    engine.replay_block(fresh[0])
    engine.replay(fresh[1:])
    assert engine.root == fresh[-1].header.root
    engine.commit_pipe.flush()
    flat = engine.flat
    out["counters"] = flat.snapshot()

    # ---- cold-read microbench: flat dict vs the trie-walk path
    # (engine.trie / storage tries — native C++ when built, so the
    # denominator is the FAST pre-flat path, not a strawman)
    addrs = sorted(flat.accounts)[:512]
    slots = sorted((a, k) for a, sub in flat.storage.items()
                   for k in sub)[:512]
    reads = len(addrs) + len(slots)
    reps = max(1, 100_000 // max(1, reads))
    t0 = time.monotonic()
    for _ in range(reps):
        for a in addrs:
            flat.account(a)
        for c, k in slots:
            flat.storage_value(c, k)
    t_flat = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(reps):
        for a in addrs:
            engine.trie.get(a)
        for c, k in slots:
            engine._storage_trie(c).get(k)
    t_trie = time.monotonic() - t0
    out["cold_read"] = {
        "reads": reads * reps,
        "flat_us_per_read": round(1e6 * t_flat / (reads * reps), 3),
        "trie_us_per_read": round(1e6 * t_trie / (reads * reps), 3),
        # the acceptance ratio: >= 3x over the replaced trie-walk path
        "speedup": round(t_trie / max(t_flat, 1e-9), 2),
        "trie_backend": "native" if engine._native else "py",
    }

    # ---- checkpoint durability: background stamp vs sync write, on
    # a real disk-backed store (tempdir FileDB + PersistentNodeDict)
    import shutil
    import tempfile
    from coreth_tpu.rawdb.kv import FileDB
    from coreth_tpu.rawdb.state_manager import (
        PersistentCodeDict, PersistentNodeDict)
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database

    def ckpt_run(sync: bool):
        td = tempfile.mkdtemp(prefix="bench_flat_")
        try:
            kv = FileDB(os.path.join(td, "chain.db"))
            db = Database(node_db=PersistentNodeDict(kv),
                          code_db=PersistentCodeDict(kv))
            gblock = genesis.to_block(db)
            eng = ReplayEngine(genesis.config, db, gblock.root,
                               parent_header=gblock.header,
                               batch_pad=ERC20_TXS,
                               window=int(os.environ.get(
                                   "BENCH_STREAM_WINDOW", "32")))
            if sync:
                os.environ["CORETH_CHECKPOINT_SYNC"] = "1"
            try:
                pipe = StreamingPipeline(
                    eng, ChainFeed([Block.decode(w) for w in wire]),
                    window_wait=0.005, checkpoint_every=8)
                t0 = time.monotonic()
                rep = pipe.run()
                wall = time.monotonic() - t0
            finally:
                os.environ.pop("CORETH_CHECKPOINT_SYNC", None)
            assert eng.root == fresh[-1].header.root
            kv.close()
            return rep, wall
        finally:
            shutil.rmtree(td, ignore_errors=True)

    rep_bg, wall_bg = ckpt_run(sync=False)
    ck = rep_bg.checkpoint
    out["checkpoint_background"] = {
        "wall_s": round(wall_bg, 3),
        "records": ck["written"],
        # the execute thread only pays the stamps...
        "stamp_us": ck["stamp_us"],
        # ...while the exporter thread pays the Merkleization + fsync
        "export_ms": ck["exporter"]["export_ms"],
        "entries": ck["exporter"]["entries_written"],
    }
    if not _deadline_tight():
        rep_sy, wall_sy = ckpt_run(sync=True)
        cks = rep_sy.checkpoint
        out["checkpoint_sync"] = {
            "wall_s": round(wall_sy, 3),
            "records": cks["written"],
            "write_ms": cks["write_ms"],   # on the execute thread
        }
        # the tentpole ratio: execute-thread durability cost,
        # background stamps vs synchronous exports
        stamp_ms = max(ck["stamp_us"] / 1000.0, 1e-3)
        out["execute_thread_cost_ratio"] = round(
            cks["write_ms"] / stamp_ms, 1)
    return out


def run_faults():
    """Fault-tolerance section: canned fault plans over a small
    transfer chain, reporting what the supervisor DID about them —
    demotion counts, retry counts, the demote latency (wall seconds
    from first strike to routing around the dead backend), the
    recovery wall (completing the whole chain on the host ladder), and
    the quarantine path's behavior on a poison block."""
    from coreth_tpu import faults as F
    from coreth_tpu.serve import ChainFeed, StreamingPipeline
    from coreth_tpu.types import Block
    genesis, blocks = build_or_load_chain("transfer")
    n = min(len(blocks), int(os.environ.get("BENCH_FAULT_BLOCKS", "64")))
    wire = [b.encode() for b in blocks[:n]]
    out = {"blocks": n}

    def one_run(plan, **pipe_kw):
        fresh = [Block.decode(w) for w in wire]
        engine = _fresh_engine(genesis, TXS_PER_BLOCK)
        with F.armed(plan):
            pipe = StreamingPipeline(engine, ChainFeed(fresh),
                                     window_wait=0.005, **pipe_kw)
            t0 = time.monotonic()
            rep = pipe.run()
            wall = time.monotonic() - t0
        assert engine.root == fresh[-1].header.root, "faulted run root"
        return engine, rep, wall

    # persistent device-dispatch failure: demote, finish on the host
    eng, rep, wall = one_run(F.FaultPlan(
        {"device/dispatch": F.FaultSpec()}))
    sup = rep.supervisor
    out["persistent_device"] = {
        "wall_s": round(wall, 3),
        "demotions": sup["demotions"],
        "retries": sup["retries"],
        "demote_latency_s": sup["demote_latency_s"].get("device"),
        "blocks_fallback": eng.stats.blocks_fallback,
        "sustained_txs_s": rep.sustained_txs_s,
    }

    # transient fault: retries absorb it, no demotion, device path kept
    eng, rep, wall = one_run(F.FaultPlan(
        {"device/dispatch": F.FaultSpec(times=2, transient=True)}))
    out["transient_device"] = {
        "wall_s": round(wall, 3),
        "retries": rep.supervisor["retries"],
        "demotions": rep.supervisor["demotions"],
        "blocks_device": eng.stats.blocks_device,
    }

    # poison block: quarantined + the stream keeps moving
    eng, rep, wall = one_run(F.FaultPlan(
        {"serve/malformed_block": F.FaultSpec(after=n // 2, times=1)}))
    out["poison_block"] = {
        "wall_s": round(wall, 3),
        "quarantined": len(rep.quarantined),
        "halted": rep.halted,
        "blocks": rep.blocks,
    }
    return out


def run_multichip_section(env_extra=None, out_name="multichip_bench"):
    """Fold the virtual-mesh scaling curve (tools/mesh_scaling.py)
    into the same deadline budget: a truncated shape in a subprocess
    (the virtual device count must be set before jax initializes, so
    it cannot run in-process), parsed from its stdout JSON."""
    import subprocess
    budget = max(20.0, min(_section_left(), _remaining() - 12.0))
    env = dict(os.environ)
    env.setdefault("SCALE_BLOCKS", "4")
    env.setdefault("SCALE_TXS", "128")
    env.setdefault("SCALE_REPS", "1")
    env.update(env_extra or {})
    # the truncated in-bench shape must not clobber the standalone
    # harness's committed artifact
    env["SCALE_OUT"] = os.path.join(_DIR, ".bench_cache",
                                    f"{out_name}.json")
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(_DIR, "tools",
                                          "mesh_scaling.py")],
            capture_output=True, text=True, timeout=budget, env=env)
    except subprocess.TimeoutExpired:
        return {"error": f"deadline: mesh_scaling exceeded {budget:.0f}s"}
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}",
                "tail": (r.stderr or r.stdout)[-300:]}
    try:
        return json.loads(r.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError) as exc:
        return {"error": f"parse: {exc}"}


def run_hot_contract():
    """Single-hot-contract section (ISSUE 14): ONE ERC-20-shaped
    contract takes 100% of txs with Zipf sender/recipient skew, forced
    through the general machine path (the key-range placement shape).
    Per the bench-drift rule the section reports sustained txs/s plus
    RATIOS only: vs_native (compiled C++ EVM replay of the same chain)
    and vs_1dev (2-device / 1-device sustained txs/s from the
    mesh-scaling subprocess — the flat-curve acceptance number),
    plus the load_imbalance placement counter."""
    from coreth_tpu import rlp
    from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    from coreth_tpu.types import Block
    from coreth_tpu.workloads import hot_contract as HC
    n_blocks = int(os.environ.get("BENCH_HOT_BLOCKS", "64"))
    txs = int(os.environ.get("BENCH_HOT_TXS", "128"))
    if _section_left() < 120:
        n_blocks = min(n_blocks, 16)
    n_keys = min(256, N_KEYS)
    seed, alpha = 20260804, 1.1
    # genesis comes from the workload module (one key-derivation
    # site), and the cache name carries every chain parameter so a
    # workload-default change can never replay a stale cached chain
    # against a fresh genesis
    genesis, _keys, _addrs = HC.hot_genesis(CFG, n_keys)
    cache = os.path.join(
        _DIR, ".bench_cache",
        f"hot_{n_blocks}x{txs}k{n_keys}s{seed}a{alpha}.bin")
    if os.path.exists(cache):
        blocks = [Block.decode(b)
                  for b in rlp.decode(open(cache, "rb").read())]
    else:
        _g, blocks = HC.build_hot_chain(CFG, n_blocks, txs,
                                        n_keys=n_keys, alpha=alpha,
                                        seed=seed)
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        with open(cache, "wb") as f:
            f.write(rlp.encode([b.encode() for b in blocks]))
    wire = [b.encode() for b in blocks]
    out = {"blocks": len(blocks), "txs_per_block": txs}

    saved = os.environ.get("CORETH_NO_TOKEN_FASTPATH")
    os.environ["CORETH_NO_TOKEN_FASTPATH"] = "1"
    try:
        def one_rep():
            fresh = [Block.decode(w) for w in wire]
            db = Database()
            gb = genesis.to_block(db)
            eng = ReplayEngine(CFG, db, gb.root,
                               parent_header=gb.header,
                               capacity=1 << 13,
                               slot_capacity=1 << 13,
                               batch_pad=txs, window=16)
            eng.replay_block(fresh[0])
            t0 = time.monotonic()
            eng.replay(fresh[1:])
            dt = time.monotonic() - t0
            assert eng.root == fresh[-1].header.root
            assert eng.stats.blocks_fallback == 0, eng.stats.row()
            n_txs = sum(len(b.transactions) for b in fresh[1:])
            return n_txs / dt, eng

        one_rep()  # compile warm-up, untimed
        # native denominator reps interleaved with the device reps
        # (device-first on even rounds, native-first on odd — the
        # PR-15 alternation): vs_native then compares two samples of
        # the SAME load window instead of a device phase followed by
        # a native phase
        nat_runs = []
        nat_rep = _native_evm_rep(genesis, blocks, nat_runs)
        tps_runs = []
        eng = None
        for r in range(REPS):
            if nat_rep is not None and r % 2 == 1:
                nat_rep()
            tps, eng = one_rep()
            tps_runs.append(tps)
            if nat_rep is not None and r % 2 == 0 \
                    and not _deadline_tight():
                nat_rep()
            if _deadline_tight():
                break
        mc = eng._machine.machine_counters()
        out.update({
            "txs_s": round(_median(tps_runs), 1),
            "spread_txs_s": _spread(tps_runs),
            # single-device in-process reps: key-range placement only
            # exists on a mesh, so kr_lanes/load_imbalance here would
            # read as a structural 0 — the placement surface comes
            # from the multichip subprocess below
            "machine": {
                "kernel_retraces": mc["kernel_retraces"],
                "premap_hit_rate": round(
                    mc["premap_hits"]
                    / max(1, mc["premap_predicted"]), 3),
                "lanes_specialized": mc["lanes_specialized"],
            },
        })
        if nat_runs:
            out["vs_native"] = round(
                _median(tps_runs) / _median(nat_runs), 3)
    finally:
        if saved is None:
            os.environ.pop("CORETH_NO_TOKEN_FASTPATH", None)
        else:
            os.environ["CORETH_NO_TOKEN_FASTPATH"] = saved

    # the flat-curve acceptance ratio: 2-device vs 1-device sustained
    # txs/s on the SAME hot shape (machine path, key-range placement),
    # measured by the mesh-scaling subprocess on the virtual mesh
    if not _deadline_tight(45.0):
        curve = run_multichip_section(
            env_extra={"SCALE_WORKLOAD": "hot_contract",
                       "SCALE_POINTS": "1,2",
                       "SCALE_BLOCKS": "4",
                       "SCALE_TXS": str(min(txs, 128)),
                       "SCALE_REPS": "2"},
            out_name="hot_multichip_bench")
        pts = {p["n_devices"]: p for p in curve.get("points", [])}
        if 1 in pts and 2 in pts:
            out["vs_1dev"] = round(
                pts[2]["txs_s_median"] / pts[1]["txs_s_median"], 3)
            # max/mean per-shard lane occupancy at 2 devices (the
            # key-range placement surface; n == collapse)
            out["load_imbalance_2dev"] = pts[2].get("load_imbalance")
        elif "error" in curve:
            out["multichip_error"] = curve["error"]
    return out


def run_cluster():
    """Distributed-serving section (serve/cluster): the transfer
    chain's head range-partitioned across subprocess workers over the
    length-prefixed control protocol, every boundary root verified by
    the aggregator.  Per the bench-drift rule the section leads with
    RATIOS: scale_2w_vs_1w compares cluster sustained txs/s at two
    worker widths (serve span from the federated lane reports —
    sequential lanes SUM their pipeline walls, concurrent lanes take
    the MAX), next to p99 block latency at both widths and a recovery
    probe (injected SIGKILL mid-stream; the outage window is read off
    the coordinator's event log).  Workers run host-platform jax (an
    accelerator is single-owner; N processes cannot share it), so on
    an N-core host the ratio measures real lane parallelism — on ONE
    core it honestly reads ~1x and scaling_evaluable marks the >=1.5x
    gate unratable rather than failed."""
    import shutil
    import tempfile
    from dataclasses import replace as _dc_replace
    from coreth_tpu import rlp
    from coreth_tpu.serve.cluster import (
        ClusterCoordinator, bootstrap_stores, partition_ranges,
    )
    n_blocks = int(os.environ.get("BENCH_CLUSTER_BLOCKS", "64"))
    genesis, blocks = build_or_load_chain("transfer")
    blocks = blocks[:n_blocks]
    cpus = os.cpu_count() or 1
    out = {"blocks": len(blocks), "txs_per_block": TXS_PER_BLOCK,
           "host_cpus": cpus, "scaling_evaluable": cpus >= 2}
    need = N_KEYS + len(blocks) * TXS_PER_BLOCK // 2 + 1024
    ekw = dict(capacity=1 << max(13, (need - 1).bit_length()),
               batch_pad=TXS_PER_BLOCK, window=8)
    base = tempfile.mkdtemp(prefix="coreth_cluster_bench_")
    try:
        chain_path = os.path.join(base, "chain.rlp")
        with open(chain_path, "wb") as f:
            f.write(rlp.encode([b.encode() for b in blocks]))
        # ONE bootstrap replay (untimed — the warm-start a real
        # cluster gets from state sync); every run below gets fresh
        # COPIES of the seeded lane stores so a finished run can
        # never leak tip state into the next one's resume
        seeds = bootstrap_stores(genesis.config, genesis, blocks,
                                 partition_ranges(len(blocks), 2),
                                 base, engine_kw=ekw)
        env = {
            # the parent holds the chip (one process per chip):
            # workers are host-platform, always
            "JAX_PLATFORMS": "cpu",
            "CORETH_CHECKPOINT_SYNC": "1",  # deterministic records
            "CORETH_TELEMETRY_PORT": "",    # no per-worker server
            "CORETH_TRACE": "1",            # federated stage rows
        }

        def fresh_seeds(tag):
            copies = []
            for s in seeds:
                dst = os.path.join(base, tag, s.lane)
                os.makedirs(dst, exist_ok=True)
                shutil.copyfile(os.path.join(s.db_dir, "chain.db"),
                                os.path.join(dst, "chain.db"))
                copies.append(_dc_replace(s, db_dir=dst))
            return copies

        def one_run(tag, n_workers, victim_env=None):
            coord = ClusterCoordinator(
                fresh_seeds(tag), chain_path, config="test",
                expected_tip=blocks[-1].header.root, engine_kw=ekw,
                checkpoint_every=4,
                # grace covers subprocess startup (imports + compile
                # cache load); the timeout POLICY itself is pinned by
                # the stepped-clock units in tests/test_cluster.py
                heartbeat_timeout=90.0,
                worker_env={"*": env, **({"w0": victim_env}
                                         if victim_env else {})})
            coord.start(n_workers)
            return coord.run(deadline_s=max(
                60.0, min(240.0, _section_left() - 5.0)))

        def width_row(summary):
            lanes = [l for l in summary["lanes"] if l["report"]]
            walls = [l["report"].get("wall_s") or 0.0 for l in lanes]
            served_by = {(l["history"] or [None])[-1] for l in lanes}
            # one worker serves lanes back-to-back (walls add up);
            # distinct workers overlap (the longest lane bounds)
            serve_s = (max(walls) if len(served_by) > 1
                       else sum(walls)) or None
            return {
                "txs": summary["txs"],
                "wall_s": round(summary["wall_s"], 2),
                "serve_s": round(serve_s, 2) if serve_s else None,
                "txs_s": (round(summary["txs"] / serve_s, 1)
                          if serve_s else None),
                "p99_ms": max((l["report"]["latency_ms"]["p99"]
                               for l in lanes), default=None),
                "verified": summary["verified"],
                "lanes": [{
                    "lane": l["lane"],
                    "worker": (l["history"] or [None])[-1],
                    "sustained_txs_s":
                        l["report"].get("sustained_txs_s"),
                    "wall_s": l["report"].get("wall_s"),
                    "p99_ms": l["report"]["latency_ms"]["p99"],
                    "stage_breakdown":
                        l["report"].get("stage_breakdown"),
                } for l in lanes],
            }

        # 1-worker first: it pays the workers' compile-cache
        # population the 2-worker and recovery runs then reload
        for n in (1, 2):
            key = f"{n}w"
            if n > 1 and _deadline_tight(45.0):
                out.setdefault("deadline_skipped", []).append(key)
                break
            try:
                out[key] = width_row(one_run(key, n))
            except Exception as exc:  # noqa: BLE001 — a failed width must not sink the section (partial emission keeps the rest)
                out[key] = {"error": f"{type(exc).__name__}: {exc}"}
        r1, r2 = out.get("1w", {}), out.get("2w", {})
        if r1.get("txs_s") and r2.get("txs_s"):
            ratio = round(r2["txs_s"] / r1["txs_s"], 3)
            out["scale_2w_vs_1w"] = ratio
            # the >=1.5x gate needs real cores to scale onto; a
            # 1-core host reports the honest ~1x wall-clock ratio
            # and marks itself core-bound instead of failing
            out["scale_2w_vs_1w_ok"] = (
                ratio >= 1.5 if out["scaling_evaluable"] else None)
            if not out["scaling_evaluable"]:
                out["core_bound"] = True

        # recovery probe: the victim carries an armed SIGKILL on its
        # 9th committed block — one full window PAST the first
        # durable record (window=8, every=4, sync writes), the same
        # timing argument as tests/test_cluster_handoff.py.  That
        # timing needs the victim lane to outlive its first full
        # window: serve/crash fires before the checkpoint cadence
        # inside a commit batch, so on a lane of <= window blocks the
        # kill either never fires or lands with nothing durable past
        # the seed — report that honestly instead of a no-op "crash"
        s0, e0 = partition_ranges(len(blocks), 2)[0]
        if e0 - s0 <= ekw["window"]:
            out["recovery"] = {
                "skipped": "victim lane has <= window blocks; the "
                           "injected kill cannot land past a durable "
                           "record (raise BENCH_CLUSTER_BLOCKS)"}
        elif not _deadline_tight(45.0):
            victim = {"CORETH_FAULT_PLAN": json.dumps(
                {"serve/crash": {"action": "sigkill",
                                 "after": ekw["window"]}})}
            try:
                summary = one_run("recovery", 2, victim_env=victim)

                def first_t(name):
                    for e in summary["events"]:
                        if e["event"] == name:
                            return e.get("t")
                    return None

                t_crash = first_t("worker_crash")
                t_assign = first_t("reassigned")
                t_first = first_t("first_commit_after_recovery")
                lane0 = summary["lanes"][0]
                out["recovery"] = {
                    "verified": summary["verified"],
                    "resumed_from": lane0["resumed_from"],
                    "failures": lane0["failures"],
                    # outage = crash detection to the lane's first
                    # post-handoff commit; resume_s isolates the
                    # handoff itself (assign -> first commit)
                    "recovery_s": (round(t_first - t_crash, 2)
                                   if t_crash is not None
                                   and t_first is not None else None),
                    "resume_s": (round(t_first - t_assign, 2)
                                 if t_assign is not None
                                 and t_first is not None else None),
                }
            except Exception as exc:  # noqa: BLE001 — same partial-emission argument as the width runs
                out["recovery"] = {
                    "error": f"{type(exc).__name__}: {exc}"}
        else:
            out.setdefault("deadline_skipped", []).append("recovery")
    finally:
        shutil.rmtree(base, ignore_errors=True)
    return out


def _begin_section(frac_end):
    """Advance the section budget slice; its rep loops and chain build
    stop when the slice (T0 + frac_end * DEADLINE) is spent."""
    global SECTION_END
    SECTION_END = T0 + DEADLINE * frac_end


def _device_identity():
    """What jax runs on, as jax reports it — every figure in the JSON
    line is a reading on THIS platform."""
    import jax
    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def main():
    # every section is deadline-guarded; whatever finished by the
    # budget is what the JSON line reports (missing sections -> null);
    # the watchdog guarantees the line prints even if a section wedges
    RESULT.update({
        "metric": "transfer_replay_throughput",
        "value": None,
        "unit": "txs/s",
        "reps": REPS,
        "deadline_s": DEADLINE,
        "host": {"cpus": os.cpu_count(),
                 "loadavg": [round(x, 2) for x in os.getloadavg()]},
    })
    _WATCHDOG.start()
    _spawn_watchdog_child()
    # after the watchdogs: a backend that hangs at start-up must still
    # end in a JSON line
    RESULT["device"] = _device_identity()
    _maybe_wedge()  # BENCH_WEDGE: watchdog regression harness
    result = RESULT
    skipped = []
    try:
        _begin_section(0.30)
        commit_stats = {}
        py_runs, tpu_runs, native_runs = run_workload(
            "transfer", BASELINE_BLOCKS, commit_stats=commit_stats)
        py_tps, tpu_tps = _median(py_runs), _median(tpu_runs)
        native_tps = _median(native_runs) if native_runs else None
        if _remaining() > 60:
            # native-vs-python trie backend on the same chain: the
            # commit-path ratio the window-batched fold is judged by
            commit_stats.update(run_trie_backend_compare("transfer"))
        result.update({
            "commit": commit_stats,
            "value": round(tpu_tps, 1),
            # primary ratio: median TPU / median compiled sequential
            # C++ replay (the Go-proxy baseline, BASELINE.md) — the
            # honest denominator; falls back to the Python host path
            # where the native build is unavailable
            "vs_baseline": round(tpu_tps / (native_tps or py_tps), 2),
            "tpu_spread_txs_s": _spread(tpu_runs),
            "native_baseline_txs_s":
                round(native_tps, 1) if native_tps else None,
            "native_spread_txs_s":
                _spread(native_runs) if native_runs else None,
            "vs_py_host": round(tpu_tps / py_tps, 2),
        })
        _section_done("transfer")

        erc20_native_tps = None
        _begin_section(0.48)
        if _remaining() > 45:
            e20_commit = {}
            erc20_py, erc20_tpu, erc20_native = run_workload(
                "erc20", ERC20_BASELINE_BLOCKS, commit_stats=e20_commit)
            erc20_native_tps = _median(erc20_native) if erc20_native \
                else None
            if _remaining() > 60:
                e20_commit.update(run_trie_backend_compare("erc20"))
            result.update({
                "erc20_commit": e20_commit,
                "erc20_txs_s": round(_median(erc20_tpu), 1),
                "erc20_spread_txs_s": _spread(erc20_tpu),
                "erc20_vs_native": (
                    round(_median(erc20_tpu) / erc20_native_tps, 3)
                    if erc20_native_tps else None),
                "erc20_native_txs_s": (round(erc20_native_tps, 1)
                                       if erc20_native_tps else None),
                "erc20_vs_py_host": round(
                    _median(erc20_tpu) / _median(erc20_py), 2),
            })
            _section_done("erc20")
        else:
            skipped.append("erc20")

        _begin_section(0.63)
        if _remaining() > 45:
            # the SAME erc20 chain forced through the general step
            # machine (no fast-path classification): config[1] through
            # SURVEY 7.4 + the fused device-resident OCC windows
            os.environ["CORETH_NO_TOKEN_FASTPATH"] = "1"
            mstats = {}
            # the native denominator reps run INTERLEAVED with the
            # machine-path device reps (the A/B/A/B pattern): the
            # earlier-section erc20_native_tps was measured minutes
            # before on a possibly different machine-load window,
            # which made this section's headline ratio drift run to
            # run; it survives only as the fallback when the native
            # build is absent
            em_genesis, em_blocks = build_or_load_chain("erc20")
            em_native_runs = []
            em_rep = _native_evm_rep(em_genesis,
                                     em_blocks[:MACHINE_BLOCKS],
                                     em_native_runs)
            _, erc20m_tpu, _ = run_workload(
                "erc20", ERC20_BASELINE_BLOCKS,
                tpu_blocks=MACHINE_BLOCKS,
                machine_stats=mstats, skip_baselines=True,
                interleave=em_rep)
            del os.environ["CORETH_NO_TOKEN_FASTPATH"]
            em_native_tps = (_median(em_native_runs)
                             if em_native_runs else erc20_native_tps)
            emv = (round(_median(erc20m_tpu) / em_native_tps, 3)
                   if em_native_tps else None)
            result.update({
                "erc20_machine_txs_s": round(_median(erc20m_tpu), 1),
                "erc20_machine_native_txs_s": (
                    round(em_native_tps, 1) if em_native_tps else None),
                "erc20_machine_vs_native": emv,
                # THE tentpole acceptance gate (ISSUE 13 / ROADMAP
                # direction 1): the fused OCC path with per-contract
                # specialization must be at least the native
                # sequential engine on the same chain (a RATIO per
                # the bench-drift rule)
                "erc20_machine_vs_native_ok": (
                    emv is not None and emv >= 1.0),
                "erc20_machine_stats": mstats,
            })
            _section_done("erc20_machine")
            if not _deadline_tight(margin=60.0):
                # specialization A/B with traced dispatch/fetch/fold
                # attribution (the CORETH_SPECIALIZE=0|1 before/after)
                result["specialize"] = run_specialize()
                _section_done("specialize")
        else:
            skipped.append("erc20_machine")

        _begin_section(0.74)
        if _remaining() > 45:
            # contention workload (config[3]): fully serial conflict
            # chains — the OCC rounds now run INSIDE one dispatch per
            # window of blocks; swap_stats.dispatches_per_block is the
            # before/after tentpole metric (round 5: O(txs) ~ one
            # dispatch per round; now O(1))
            sstats = {}
            swap_py, swap_tpu, swap_native = run_workload(
                "swap", min(16, SWAP_BLOCKS), machine_stats=sstats)
            swap_native_tps = _median(swap_native) if swap_native \
                else None
            result.update({
                "swap_txs_s": round(_median(swap_tpu), 1),
                "swap_vs_native": (
                    round(_median(swap_tpu) / swap_native_tps, 3)
                    if swap_native_tps else None),
                "swap_native_txs_s": (round(swap_native_tps, 1)
                                      if swap_native_tps else None),
                "swap_vs_py_host": round(
                    _median(swap_tpu) / _median(swap_py), 2),
                "swap_stats": sstats,
            })
            _section_done("swap")
        else:
            skipped.append("swap")

        _begin_section(0.82)
        if _remaining() > 45:
            # Avalanche-semantics segment (config[4]): atomic ExtData +
            # nativeAssetCall blocks fall back to the exact host path;
            # fallback_fraction records how much of the segment that is
            mixed_py, mixed_tpu, mixed_stats = run_mixed()
            result.update({
                "mixed_txs_s": round(_median(mixed_tpu), 1),
                "mixed_host_exec": mixed_stats.pop("host_exec", {}),
                "mixed_vs_py_host": round(
                    _median(mixed_tpu) / _median(mixed_py), 2),
                "mixed_fallback_fraction": round(
                    mixed_stats["blocks_fallback"]
                    / max(1, mixed_stats["blocks_fallback"]
                          + mixed_stats["blocks_device"]), 3),
                "mixed_phase_split": {
                    k: round(mixed_stats[k], 2)
                    for k in ("t_classify", "t_sender", "t_device",
                              "t_trie", "t_fallback")},
            })
            _section_done("mixed")
        else:
            skipped.append("mixed")

        _begin_section(0.84)
        if _remaining() > 45:
            # streaming ingestion (serve/): sustained-rate p50/p99
            # block latency through the bounded-queue pipeline — the
            # SLO surface, next to the one-shot throughput above
            result["streaming"] = run_streaming()
            _section_done("streaming")
        else:
            skipped.append("streaming")

        _begin_section(0.91)
        if _remaining() > 60:
            # distributed serving (serve/cluster): the 2w-vs-1w
            # scaling ratio, federated per-lane p99 + stage rows, and
            # the injected-kill recovery probe
            result["cluster"] = run_cluster()
            _section_done("cluster")
        else:
            skipped.append("cluster")

        _begin_section(0.93)
        if _remaining() > 30:
            # fault tolerance: demotion counts + recovery latency
            # under canned fault plans (supervisor + quarantine)
            result["faults"] = run_faults()
            _section_done("faults")
        else:
            skipped.append("faults")

        _begin_section(0.945)
        if _remaining() > 30:
            # span tracing: per-stage latency attribution + the
            # traced-vs-untraced overhead ratio (coreth_tpu/obs)
            result["tracing"] = run_tracing()
            _section_done("tracing")
        else:
            skipped.append("tracing")

        _begin_section(0.955)
        if _remaining() > 30:
            # divergence forensics: recorder-armed vs unarmed A/B
            # (>= 0.95 gated) + an injected trip's bundle size/write
            result["forensics"] = run_forensics()
            _section_done("forensics")
        else:
            skipped.append("forensics")

        _begin_section(0.965)
        if _remaining() > 30:
            # flat-state layer: cold-read speedup ratio + checkpoint
            # stamp-vs-export attribution (state/flat)
            result["flat_state"] = run_flat_state()
            _section_done("flat_state")
        else:
            skipped.append("flat_state")

        _begin_section(0.985)
        if _remaining() > 40:
            # single-hot-contract (ISSUE 14): sustained txs/s +
            # vs_native/vs_1dev ratios + load_imbalance — the
            # key-range flat-curve acceptance surface
            result["hot_contract"] = run_hot_contract()
            _section_done("hot_contract")
        else:
            skipped.append("hot_contract")

        _begin_section(0.99)
        if _remaining() > 40:
            result["multichip"] = run_multichip_section()
            _section_done("multichip")
        else:
            skipped.append("multichip")
    except Exception as exc:  # noqa: BLE001 — the JSON line must emit
        result["error"] = f"{type(exc).__name__}: {exc}"
    if skipped:
        result["deadline_skipped"] = skipped
    _WATCHDOG.cancel()
    _emit()
    if "error" in result:
        # the JSON line above carries the reason; the exit code must
        # not read as a healthy run
        sys.exit(1)


if __name__ == "__main__":
    main()

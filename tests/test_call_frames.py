"""Call frames on the device step machine: a seeded call-tree corpus.

Trees of value-0 CALL / STATICCALL frames, depth 1-3, with
RETURNDATASIZE / RETURNDATACOPY reads in and out of bounds, calldata
handed down through memory, EXTCODESIZE, logs from several addresses,
writes into several contracts' storage, writes under a static context,
callees first cold then warm, and child gas at and around the EIP-150
63/64 edge.  Each case runs on three legs against identical pre-state:

- the host interpreter (``evm/interpreter.py``), the oracle;
- the native C++ engine (``HostExecBackend``; skipped without the
  built library);
- the device step machine (``MachineRunner``), every case in one
  batched run.

Status, ``gas_left``, the refund counter, the storage each leg leaves
changed (every contract) and the logs with their addresses must agree
on every case a leg answers.  A device ``HOST`` answer is a deferral
(the host interpreter decides the transaction) and is not compared —
but each R_FRAME sub-reason must be hit at least once, by the case
built for it, and ``test_frame_escapes_bit_exact_through_host`` then
replays those transactions through ``ReplayEngine`` and holds every
block to the root the host processor wrote.
"""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu.evm import hostexec, vmerrs
from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device.adapter import BlockEnv, MachineRunner, TxSpec
from coreth_tpu.evm.evm import EVM, BlockContext, Config, TxContext
from coreth_tpu.mpt import EMPTY_ROOT
from coreth_tpu.params.config import _phases
from coreth_tpu.state import Database, StateDB
from coreth_tpu.state.statedb import normalize_state_key

FORK = "cancun"
CFG = _phases(11, cancun_time=0)
SENDER = b"\x11" * 20
COINBASE = bytes.fromhex("0100000000000000000000000000000000000000")
EOA = b"\xee" * 20
IDENTITY = (4).to_bytes(20, "big")          # a precompile
NUMBER, TIME = 5, 3_000
GAS_PRICE = 30 * 10**9
BASE_FEE = 25 * 10**9
CALLDATA = bytes(range(1, 37))
ENV = BlockEnv(coinbase=COINBASE, timestamp=TIME, number=NUMBER,
               gas_limit=8_000_000, chain_id=43111, base_fee=BASE_FEE)
PRE_SLOT = (1).to_bytes(32, "big")          # every contract: slot 1 = 5


def _push(v: int) -> bytes:
    raw = v.to_bytes((max(v.bit_length(), 1) + 7) // 8, "big")
    return bytes([0x5F + len(raw)]) + raw


def _sstore(slot: int) -> bytes:           # [v] -> storage[slot] = v
    return _push(slot) + b"\x55"


def assemble(actions, addrs, static=False) -> bytes:
    """One contract's bytecode from its action list.  Call targets are
    indices into `addrs` or literal 20-byte addresses.  A contract
    reached under a static context drops what it observes (POP) instead
    of storing it, so that only the actions meant to write do."""
    out = bytearray()

    def _sstore(slot):
        return b"\x50" if static else _push(slot) + b"\x55"

    # a word in memory at 0 so frames hand real bytes down as calldata
    out += _push(0xC0FFEE) + _push(0) + b"\x52"
    for k, act in enumerate(actions):
        kind = act[0]
        if kind in ("return", "stop", "revert"):
            break
        if kind == "sstore":
            out += _push(act[2]) + _sstore(act[1])
        elif kind == "sload":                      # slot -> slot + 20
            out += _push(act[1]) + b"\x54" + _sstore(act[1] + 20)
        elif kind == "log":
            n = act[1]
            out += b"".join(_push(0xA0 + t) for t in range(n))
            out += _push(32) + _push(0) + bytes([0xA0 + n])
        elif kind == "cd":                         # what came down
            out += _push(0) + b"\x35" + _sstore(150)
            out += b"\x36" + _sstore(151)
            out += _push(32) + _push(0) + _push(0x300) + b"\x37"
            out += _push(0x300) + b"\x51" + _sstore(152)
        elif kind == "xcs":
            tgt = act[1] if isinstance(act[1], bytes) else addrs[act[1]]
            out += _push(int.from_bytes(tgt, "big")) + b"\x3b" \
                + _sstore(140 + k)
        elif kind == "call":
            _k, op, tgt, gas, in_len, out_len = act[:6]
            value = act[6] if len(act) > 6 else 0
            tgt = tgt if isinstance(tgt, bytes) else addrs[tgt]
            out += _push(out_len) + _push(0x100) + _push(in_len) \
                + _push(0)
            if op == "call":
                out += _push(value)
            out += _push(int.from_bytes(tgt, "big"))
            out += (b"\x5a" if gas == "all" else _push(gas))
            out += b"\xf1" if op == "call" else b"\xfa"
            out += _sstore(100 + k)
            out += b"\x3d" + _sstore(110 + k)
            out += _push(0x100) + b"\x51" + _sstore(120 + k)
        elif kind == "rdcopy":                     # maybe out of bounds
            out += _push(act[2]) + _push(act[1]) + _push(0x200) + b"\x3e"
            out += _push(0x200) + b"\x51" + _sstore(130)
        else:
            raise ValueError(kind)
    end = actions[-1] if actions and actions[-1][0] in (
        "return", "stop", "revert") else ("stop",)
    if end[0] == "return":
        out += _push(end[1]) + _push(0) + b"\xf3"
    elif end[0] == "revert":
        out += _push(end[1]) + _push(0) + b"\xfd"
    else:
        out += b"\x00"
    return bytes(out)


class Case:
    def __init__(self, label, codes, root, gas, want_why=None):
        self.label = label
        self.codes = codes          # address -> bytecode
        self.root = root
        self.gas = gas
        self.want_why = want_why    # R_FRAME sub-reason the device owes


def _addrs(i: int):
    return [bytes([0xA0 + j]) + i.to_bytes(19, "big") for j in range(7)]


def _tree(rng, depth, max_depth, static):
    """A random contract action list; returns (actions, children) where
    each child is (index, subtree)."""
    acts, kids = [], []
    for _ in range(rng.randint(1, 3)):
        r = rng.random()
        if r < 0.25 and not static:
            acts.append(("sstore", rng.randint(2, 9), rng.randint(0, 3)))
        elif r < 0.4:
            acts.append(("sload", 1))
        elif r < 0.5 and not static:
            acts.append(("log", rng.randint(0, 3)))
        elif r < 0.6 and depth > 0:
            acts.append(("cd",))
        elif static and r < 0.65:
            acts.append(("sstore", 3, 1))      # a write under STATICCALL
    if depth < max_depth:
        for _ in range(rng.randint(1, 2)):
            op = "static" if rng.random() < 0.35 else "call"
            gas = rng.choice(["all"] * 5 + [200_000, 8_000, 1 << 64])
            acts.append(("call", op, None, gas, rng.choice([0, 4, 32]),
                         rng.choice([0, 32, 64])))
            kids.append((len(acts) - 1, op == "static" or static))
    if rng.random() < 0.5 and depth < max_depth and kids:
        acts.append(("rdcopy", rng.choice([0, 16, 32]),
                     rng.choice([0, 16, 32])))
    r = rng.random()
    if depth == 0 and r < 0.1:
        acts.append(("revert", 32))        # a top-frame REVERT
    elif r < 0.6:
        acts.append(("return", rng.choice([0, 32, 64])))
    return acts, kids


def random_case(i: int, rng) -> Case:
    addrs = _addrs(i)
    nxt = [1]
    codes = {}
    done = []          # finished subtrees: reusing one makes no cycle

    def build(slot_addr, depth, max_depth, static):
        acts, kids = _tree(rng, depth, max_depth, static)
        for ai, kid_static in kids:
            # reuse an earlier callee now and then: cold, then warm
            if done and rng.random() < 0.3:
                tgt = rng.choice(done)
            elif nxt[0] < len(addrs) - 1:
                tgt = nxt[0]
                nxt[0] += 1
                build(tgt, depth + 1, max_depth, kid_static)
            else:
                tgt = len(addrs) - 1     # the leaf: SLOAD, RETURN
            a = list(acts[ai])
            a[2] = tgt
            acts[ai] = tuple(a)
        if rng.random() < 0.2:
            acts.insert(0, ("xcs", rng.randint(0, len(addrs) - 1)))
        codes[addrs[slot_addr]] = assemble(acts, addrs, static)
        done.append(slot_addr)

    build(0, 0, rng.randint(1, 3), False)
    for a in addrs:
        codes.setdefault(a, assemble([("sload", 1), ("return", 32)],
                                     addrs))
    gas = rng.choice([1_000_000, 1_000_000, 300_000, 120_000])
    return Case(f"tree{i}", codes, addrs[0], gas)


def escape_cases(base: int):
    """One case per R_FRAME sub-reason, each of which the device must
    defer."""
    out = []

    def one(label, root_acts, callee_acts=None, why=None, value=0):
        addrs = _addrs(base + len(out))
        codes = {addrs[0]: assemble(root_acts, addrs)}
        codes[addrs[1]] = assemble(callee_acts or [("return", 32)], addrs)
        out.append(Case(label, codes, addrs[0], 500_000, why))

    one("value", [("call", "call", 1, "all", 0, 32, 1)], why="value")
    one("precompile", [("call", "static", IDENTITY, "all", 32, 32)],
        why="precompile")
    one("no_code", [("call", "call", EOA, "all", 0, 0)], why="no_code")
    one("callee_revert", [("call", "call", 1, "all", 0, 32)],
        [("sstore", 3, 1), ("revert", 32)], why="callee_fail")
    one("static_write", [("call", "static", 1, "all", 0, 32)],
        [("sstore", 3, 1), ("return", 32)], why="callee_fail")
    one("return_pool", [("call", "call", 1, "all", 0, 32)],
        [("return", 1024)], why="pool")
    # depth 5: a chain of frames one deeper than the machine stacks
    addrs = _addrs(base + len(out))
    codes = {addrs[j]: assemble([("call", "call", j + 1, "all", 0, 0)],
                                addrs) for j in range(5)}
    codes[addrs[5]] = assemble([("return", 0)], addrs)
    out.append(Case("depth", codes, addrs[0], 2_000_000, "depth"))
    return out


def edge_cases(base: int):
    """Child gas at the 63/64 edge: the tx's gas is chosen so that the
    requested gas lands just under, on and just over the cap."""
    out = []
    for j, gas in enumerate((40_000, 45_000, 52_000, 60_000)):
        addrs = _addrs(base + j)
        codes = {addrs[0]: assemble(
            [("call", "call", 1, 30_000, 0, 32), ("call", "call", 1,
                                                   "all", 0, 0)], addrs),
                 addrs[1]: assemble([("sstore", 4, 9), ("return", 32)],
                                    addrs)}
        out.append(Case(f"edge{gas}", codes, addrs[0], gas))
    return out


def corpus():
    rng = random.Random(0xCA11)
    cases = [random_case(i, rng) for i in range(64)]
    cases += edge_cases(100)
    cases += escape_cases(200)
    return cases


# ------------------------------------------------------------- legs

def _pre(case):
    return {(a, normalize_state_key(PRE_SLOT)): 5 for a in case.codes}


def _effect(writes, pre):
    """(contract, key) -> value for every slot a leg left changed."""
    return {k: v for k, v in writes.items() if v != pre.get(k, 0)}


def interp_run(case):
    rules = CFG.rules(NUMBER, TIME)
    db = Database()
    sdb = StateDB(EMPTY_ROOT, db)
    for a, code in case.codes.items():
        sdb.set_code(a, code)
        sdb.set_state(a, PRE_SLOT, (5).to_bytes(32, "big"))
        sdb.add_balance(a, 10)
    sdb.add_balance(SENDER, 10**18)
    sdb = StateDB(sdb.commit(False), db)
    ctx = BlockContext(coinbase=COINBASE, number=NUMBER, time=TIME,
                       gas_limit=ENV.gas_limit, base_fee=BASE_FEE)
    evm = EVM(ctx, TxContext(origin=SENDER, gas_price=GAS_PRICE), sdb,
              CFG, Config())
    sdb.prepare(rules, SENDER, COINBASE, case.root,
                list(rules.active_precompiles), [])
    _ret, gas_left, err = evm.call(SENDER, case.root, CALLDATA,
                                   case.gas, 0)
    if err is None:
        status = M.STOP
    elif isinstance(err, vmerrs.ErrExecutionReverted):
        status = M.REVERT
    else:
        status = M.ERR
    writes, logs = {}, []
    if status == M.STOP:
        for a, obj in sdb._objects.items():
            for key in obj.dirty_storage:
                writes[(a, key)] = int.from_bytes(
                    sdb.get_state(a, key, _normalize=False), "big")
        logs = [(bytes(lg.address), [bytes(t) for t in lg.topics],
                 bytes(lg.data)) for lg in sdb.logs]
    return status, gas_left, sdb.refund, writes, logs


def native_run_all(cases):
    from coreth_tpu.evm.hostexec.backend import HostExecBackend
    out = []
    for c in cases:
        pre = {(a, k): v.to_bytes(32, "big") for (a, k), v in
               _pre(c).items()}

        def slots(addr, key, pre=pre):
            return pre.get((addr, key), b"\x00" * 32)

        def code(addr, c=c):
            if addr == IDENTITY:
                return None
            return c.codes.get(addr, b"")

        be = HostExecBackend(FORK, ENV.chain_id, slots, code)
        try:
            be.set_env(COINBASE, TIME, NUMBER, ENV.gas_limit, BASE_FEE)
            be.set_code(c.root, c.codes[c.root])
            res = be.call(SENDER, c.root, 0, GAS_PRICE, CALLDATA, c.gas,
                          warm_addrs=[SENDER, c.root, COINBASE])
        finally:
            be.close()
        writes = {k: int.from_bytes(v, "big")
                  for k, v in res.writes.items()}
        out.append((res.status, res.gas_left, res.refund, writes,
                    [(a, list(t), d) for a, t, d in res.logs]))
    return out


def device_run_all(cases):
    committed = {}
    codes = {}
    for c in cases:
        committed.update(_pre(c))
        codes.update(c.codes)
    runner = MachineRunner(
        FORK, ENV, lambda a, k: committed.get((a, k), 0),
        code_resolver=lambda a: None if a == IDENTITY
        else codes.get(a, b""))
    # every case's contracts known up front: the rounds go to storage
    for a in codes:
        runner.callees.learn(a)
    specs = [TxSpec(code=c.codes[c.root], calldata=CALLDATA, gas=c.gas,
                    value=0, caller=SENDER, address=c.root, origin=SENDER,
                    gas_price=GAS_PRICE) for c in cases]
    out = []
    for c, r in zip(cases, runner.run(specs)):
        writes = {(c.root, k): v for k, v in r.writes.items()}
        writes.update(r.xwrites)
        addrs = r.log_addrs or [c.root] * len(r.logs)
        logs = [(a, list(t), d) for a, (t, d) in zip(addrs, r.logs)]
        out.append(((r.status, r.gas_left, r.refund, writes, logs), r))
    return out


def _agree(got, want, pre):
    if got[0] != want[0] or got[1] != want[1]:
        return False
    if want[0] != M.STOP:
        return True
    return (got[2] == want[2]
            and _effect(got[3], pre) == _effect(want[3], pre)
            and got[4] == want[4])


@pytest.fixture(scope="module")
def results():
    cases = corpus()
    oracle = [interp_run(c) for c in cases]
    device = device_run_all(cases)
    native = native_run_all(cases) if hostexec.available() else None
    return cases, oracle, device, native


def test_device_frames_match_the_interpreter(results):
    cases, oracle, device, _native = results
    bad, compared = [], 0
    for c, want, (got, _r) in zip(cases, oracle, device):
        if got[0] == M.HOST:
            continue
        compared += 1
        if not _agree(got, want, _pre(c)):
            bad.append(f"{c.label}: device {got[:3]} {got[3]} {got[4]}"
                       f" != interpreter {want[:3]} {want[3]} {want[4]}")
    assert not bad, "\n".join(bad)
    # the corpus is mostly device-answered, frames included
    assert compared >= 30
    assert sum(r.frames for _g, r in device) >= 40


def test_native_frames_match_the_interpreter(results):
    cases, oracle, _device, native = results
    if native is None:
        pytest.skip("native library not built")
    bad = []
    for c, want, got in zip(cases, oracle, native):
        if got[0] == M.HOST:
            continue
        if not _agree(got, want, _pre(c)):
            bad.append(f"{c.label}: native {got[:3]} != {want[:3]}")
    assert not bad, "\n".join(bad)


def test_every_frame_escape_reason_is_hit(results):
    cases, _oracle, device, _native = results
    seen = {}
    for c, (got, r) in zip(cases, device):
        if c.want_why is None:
            continue
        assert got[0] == M.HOST, c.label
        assert r.host_reason == M.R_FRAME, (c.label, r.host_reason)
        assert r.frame_why == c.want_why, (c.label, r.frame_why)
        seen[c.want_why] = c.label
    from coreth_tpu.evm.device.adapter import FRAME_REASONS
    assert set(seen) == set(FRAME_REASONS)


def test_corpus_covers_the_frame_shapes(results):
    """The corpus reaches what it claims: depth 3, static frames,
    return-data copies out of bounds, cold and warm callees."""
    cases, oracle, device, _native = results
    codes = b"".join(b"".join(c.codes.values()) for c in cases)
    assert b"\xfa" in codes and b"\x3e" in codes and b"\x3b" in codes
    assert max(r.frames for _g, r in device) >= 3
    assert any(w[0] == M.ERR for w in oracle)      # e.g. an OOB copy


# ------------------------------------------------ the static gate

def _window_params(code: bytes, calldata: bytes):
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    runner = MachineWindowRunner(FORK, lambda a, k: 0,
                                 code_resolver=lambda a: b"")
    spec = TxSpec(code=code, calldata=calldata, gas=100_000, value=0,
                  caller=SENDER, address=b"\xcc" * 20, origin=SENDER,
                  gas_price=GAS_PRICE)
    items = [(ENV, [spec])]
    premaps, _pred = runner._premaps(items, [[{}]])
    return runner._occ_params(items, premaps)


def test_token_window_keeps_its_graph():
    """Code without call ops compiles exactly as before the feature:
    no "call" feature, no frame capacities in the kernel key, and no
    frame ops in its graph."""
    import jax
    from coreth_tpu.evm.device import tables as DT
    from coreth_tpu.workloads.erc20 import TOKEN_RUNTIME, transfer_calldata
    assert "call" not in DT.scan_code(TOKEN_RUNTIME, FORK).features
    p, occ = _window_params(TOKEN_RUNTIME,
                            transfer_calldata(b"\x22" * 20, 5))
    assert "call" not in p.features
    assert (p.frame_cap, p.callee_cap, p.code_rows) == (0, 0, 0)
    assert p == M.MachineParams(
        fork=FORK, batch=p.batch, code_cap=p.code_cap,
        data_cap=p.data_cap, scache_cap=p.scache_cap,
        features=p.features)
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    args = MachineWindowRunner(FORK, lambda a, k: 0)._warm_args(p, occ)
    text = str(jax.make_jaxpr(M.build_occ_machine(p, occ))(*args))
    assert "call_frame" not in text
    assert "code_tab" not in args[2] and "hidx" not in args[2]


def test_call_window_names_its_frames():
    """The frame push / pop carries its scope name into the lowered
    kernel (found in a device trace by it)."""
    root = assemble([("call", "call", 1, "all", 0, 32)], _addrs(900))
    p, occ = _window_params(root, CALLDATA)
    assert "call" in p.features and p.frame_cap == 4
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    args = MachineWindowRunner(FORK, lambda a, k: 0)._warm_args(p, occ)
    text = M.get_occ_machine(p, occ, ()).lower(*args).as_text(
        debug_info=True)
    assert "coreth/call_frame" in text


def test_push_rows_hold_each_push_value():
    """The call kernel's PUSH values come from rows decoded on the host
    (adapter.push_row): PUSH1..PUSH32 big-endian, a push cut short by
    the end of the code padded with zero bytes as the EVM reads it,
    PUSH0 and every other op zero."""
    from coreth_tpu.evm.device.adapter import code_rows, push_row
    rng = random.Random(46)
    for n in range(40):
        body = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 90)))
        cb, _jd, ln = code_rows({}, FORK, body, 256)
        rows = push_row(cb)
        for pc in range(len(cb)):
            op = int(cb[pc])
            k = op - 0x5F if 0x5F <= op <= 0x7F else 0
            want = int.from_bytes(
                (body + b"\x00" * 33)[pc + 1:pc + 1 + k], "big") \
                if pc < ln else 0
            got = sum(int(x) << (16 * i) for i, x in enumerate(rows[pc]))
            assert got == want, (n, pc)


def test_slot_hash_twins_and_spreads():
    """adapter.slot_hash is machine.slot_hash bit for bit, and the index
    the window runner builds holds every key of a deployment's shape
    within HPROBE probes: 32 contracts' consecutive constant slots 0-15
    beside 6,000 keccak-derived keys, at M.HSPAN buckets a row."""
    import numpy as np
    import jax.numpy as jnp
    from coreth_tpu.crypto import keccak256
    from coreth_tpu.evm.device import adapter as A
    keys = [(a, i.to_bytes(32, "big")) for a in range(1, 33)
            for i in range(16)]
    keys += [(33 + n % 16, keccak256(n.to_bytes(32, "big")))
             for n in range(6000)]
    lo = np.array([k[31] | (k[30] << 8) for _a, k in keys], np.int32)
    hi = np.array([k[29] | (k[28] << 8) for _a, k in keys], np.int32)
    aid = np.array([a for a, _k in keys], np.int32)
    dev = np.asarray(M.slot_hash(jnp.asarray(lo), jnp.asarray(hi),
                                 jnp.asarray(aid)))
    host = np.array([A.slot_hash(k, a) for a, k in keys])
    assert (dev == host).all()
    H = 1 << (len(keys) * M.HSPAN - 1).bit_length()
    index = np.full((H,), -1)
    for g, h in enumerate(host.tolist()):
        b = next((h + k) & (H - 1) for k in range(M.HPROBE)
                 if index[(h + k) & (H - 1)] < 0)
        index[b] = g


# ------------------------------------- escapes decided by the host

def test_frame_escapes_bit_exact_through_host():
    """Each R_FRAME case as a transaction of a chain the host processor
    wrote, replayed through ReplayEngine: the device hands each to the
    host (counted by reason) and every block commits at its header's
    root; a plain call tree beside them commits on the device."""
    from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.types import Block, DynamicFeeTx, sign_tx
    from coreth_tpu.evm.device.adapter import FRAME_REASONS
    key = 0xC0FFEE
    sender = priv_to_address(key)
    cases = escape_cases(300)
    addrs = _addrs(400)
    plain = Case("plain", {
        addrs[0]: assemble([("call", "call", 1, "all", 4, 32),
                            ("call", "static", 2, "all", 0, 32),
                            ("return", 32)], addrs),
        addrs[1]: assemble([("sload", 1), ("log", 1), ("return", 32)],
                           addrs),
        addrs[2]: assemble([("sload", 1), ("return", 32)], addrs,
                           static=True)}, addrs[0], 500_000)
    alloc = {sender: GenesisAccount(balance=10**24)}
    for c in cases + [plain]:
        for a, code in c.codes.items():
            alloc[a] = GenesisAccount(balance=10, code=code, nonce=1,
                                      storage={PRE_SLOT: (5).to_bytes(
                                          32, "big")})
    genesis = Genesis(config=TEST_CHAIN_CONFIG, gas_limit=15_000_000,
                      alloc=alloc)
    cid = TEST_CHAIN_CONFIG.chain_id
    order = [plain] + cases + [plain]

    def gen(i, bg):
        bg.add_tx(sign_tx(DynamicFeeTx(
            chain_id_=cid, nonce=i, gas_tip_cap_=10**9,
            gas_fee_cap_=10**12, gas=order[i].gas + 60_000,
            to=order[i].root, value=0, data=CALLDATA), key, cid))

    db = Database()
    gblock = genesis.to_block(db)
    blocks, _receipts = generate_chain(TEST_CHAIN_CONFIG, gblock, db,
                                       len(order), gen)
    blocks = [Block.decode(b.encode()) for b in blocks]
    eng_db = Database()
    g2 = genesis.to_block(eng_db)
    engine = ReplayEngine(TEST_CHAIN_CONFIG, eng_db, g2.root,
                          parent_header=g2.header)
    engine.replay_block(blocks[0])
    engine.replay(blocks[1:])                  # raises off any header
    assert engine.root == blocks[-1].header.root
    st = engine.stats
    want = {}
    for c in cases:
        want[c.want_why] = want.get(c.want_why, 0) + 1
    assert set(want) == set(FRAME_REASONS)
    assert st.machine_frame_host_txs == want
    assert st.blocks_fallback == len(cases)
    assert st.machine_frames >= 2 * 2           # the plain blocks
    # every committed lane step ran on one of the two paths
    assert st.machine_fused_lane_steps + st.machine_xla_lane_steps \
        == st.machine_lane_steps
    assert st.machine_fused_lane_steps > st.machine_xla_lane_steps > 0
    assert st.machine_fused_exits > 0


# ------------------------------------ the fused step against the XLA step

def _op(name):
    return bytes([{"ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "LT": 0x10,
                   "GT": 0x11, "SLT": 0x12, "SGT": 0x13, "EQ": 0x14,
                   "ISZERO": 0x15, "AND": 0x16, "OR": 0x17, "XOR": 0x18,
                   "NOT": 0x19, "SHL": 0x1B, "SHR": 0x1C}[name]])


def _pushn(v: int, n: int) -> bytes:
    """PUSHn of v (n bytes, big endian; PUSH0 for n = 0)."""
    return bytes([0x5F + n]) + v.to_bytes(n, "big") if n else b"\x5f"


_EDGE = (0, 1, 2, 0xFFFF, 0x10000, 1 << 128, (1 << 255) - 1, 1 << 255,
         (1 << 256) - 1, 255, 256, 257, 15, 16, 17, 300, 1 << 200)


def _alu(rng) -> bytes:
    """Binary and unary light ALU ops on edge and random operands of
    every push length, each result stored."""
    out = bytearray()
    for r in range(10):
        a, b = (rng.choice(_EDGE) if rng.random() < 0.6
                else rng.getrandbits(rng.choice((8, 64, 200, 256)))
                for _ in range(2))
        for v in (b, a):
            n = max((v.bit_length() + 7) // 8, rng.choice((0, 1, 32)))
            out += _pushn(v, n) if v or n else b"\x5f"
        op = rng.choice(("ADD", "MUL", "SUB", "LT", "GT", "SLT", "SGT", "EQ",
                         "AND", "OR", "XOR", "SHL", "SHR", "ISZERO",
                         "NOT"))
        out += _op(op)
        if op in ("ISZERO", "NOT"):
            out += b"\x90\x50"                  # SWAP1 POP: drop b
        out += _sstore(10 + r)
    return bytes(out) + b"\x00"


def _memory(rng) -> bytes:
    """MSTORE / MSTORE8 / MLOAD at aligned and unaligned offsets,
    CALLDATALOAD across and past the calldata's end, MSIZE."""
    out = bytearray()
    for _ in range(4):
        off = rng.choice((0, 1, 2, 3, 5, 30, 33, 64 + 7, 96))
        out += _push(rng.getrandbits(256)) + _push(off) + b"\x52"
        out += _push(rng.getrandbits(8)) + _push(rng.randrange(140)) \
            + b"\x53"
    for r, off in enumerate(rng.sample((0, 1, 2, 3, 7, 30, 31, 60, 99), 5)):
        out += _push(off) + b"\x51" + _sstore(30 + r)
    for r, off in enumerate(rng.sample((0, 1, 3, 4, 5, 30, 33, 35, 36,
                                        40, 1 << 40), 5)):
        out += _push(off) + b"\x35" + _sstore(40 + r)
    out += b"\x59" + _sstore(50) + b"\x36" + _sstore(51)
    return bytes(out) + b"\x00"


def _context() -> bytes:
    """Every context read of the fused step, deep stack DUP16 / SWAP16,
    and a counted JUMPI loop with a never-taken bad destination."""
    out = bytearray()
    for k, op in enumerate((0x30, 0x32, 0x33, 0x34, 0x36, 0x38, 0x3D, 0x42,
                            0x43, 0x58, 0x59, 0x5A)):
        out += bytes([op]) + _sstore(60 + k)
    out += b"".join(_push(v + 1) for v in range(17))
    out += b"\x8f\x9f" + _sstore(80) + _sstore(81)   # DUP16 SWAP16
    out += b"\x50" * 15
    out += _push(0) + _push(3) + b"\x57"             # JUMPI not taken
    loop = len(out) + 2                               # after PUSH1 0
    out += _push(0) + b"\x5b" + _push(1) + b"\x01" + b"\x80" + _push(10) \
        + b"\x11" + _push(loop) + b"\x57" + _sstore(82)
    return bytes(out) + b"\x00"


def _fused_cases(base: int):
    """Lanes for the fused step's differential: the light opcode
    families on their edges, lanes that park on a failing light op
    (bad jump, out of gas, a stack or memory limit), a lane that parks
    at its first op, one of light ops alone, a frame that returns into
    its parent mid-stream.  Labels name the test case that reads them."""
    rng = random.Random(0xF05ED)
    out = []

    def one(label, code, gas=300_000, callee=None):
        addrs = _addrs(base + len(out))
        codes = {addrs[0]: code}
        codes[addrs[1]] = callee or assemble([("return", 32)], addrs)
        out.append(Case(label, codes, addrs[0], gas))

    for j in range(6):
        one(f"light_ops/alu{j}", _alu(rng))
    for j in range(4):
        one(f"light_ops/mem{j}", _memory(rng))
    one("light_ops/context", _context())
    # EIP-2200 / 3529's ladder on a cached slot (1, pre-state 5) and a
    # fresh one (40): clear, restore, dirty, no-op, set then clear
    one("light_ops/storage", b"".join(
        _push(v) + _sstore(k) for k, v in ((1, 0), (1, 5), (1, 7), (1, 7),
                                           (40, 3), (40, 0), (40, 0)))
        + _push(40) + b"\x54" + _sstore(41)
        + _push(1) + b"\x54" + _sstore(42) + b"\x00")
    loop = b"\x5b" + _push(1) + b"\x50" + _push(0) + b"\x56"
    one("mixed_chunk/bad_jump", _push(3) + b"\x56\x00")
    one("mixed_chunk/oog", loop, gas=3_000)
    one("mixed_chunk/stack_cap", _push(1) * 70 + b"\x00")
    one("mixed_chunk/mem_cap", _push(1) + _push(4090) + b"\x52\x00")
    one("mixed_chunk/mem_oog", _push(1) + _push(1 << 30) + b"\x52\x00")
    one("mixed_chunk/sloads", b"".join(
        _push(1) + b"\x54" + _push(k) + b"\x01" + _sstore(90 + k)
        for k in range(6)) + b"\x00")
    one("mixed_chunk/sentry", _push(1) + _sstore(1) + b"\x00", gas=2_300)
    one("mixed_chunk/keccak", b"".join(
        _push(32) + _push(0) + b"\x20" + _sstore(60 + k)
        for k in range(3)) + b"\x00")
    one("park_first", b"\x46" + _sstore(91) + _alu(rng))   # CHAINID
    one("light_only", _push(7) + _push(9) + b"\x01" + _push(64) + b"\x52"
        + _push(64) + b"\x51" + b"\x50" + b"\x00")
    addrs = _addrs(base + len(out))
    child = (_push(0) + b"\x35" + _push(2) + b"\x01" + _push(0) + b"\x52"
             + _push(0x77) + _push(32) + b"\x52"
             + _push(64) + _push(0) + b"\xf3")
    root = (_push(0xABC) + _push(0) + b"\x52"
            + _push(64) + _push(0x100) + _push(32) + _push(0) + _push(0)
            + _push(int.from_bytes(addrs[1], "big")) + b"\x5a\xf1"
            + b"\x3d" + _push(0x100) + b"\x51" + b"\x01"
            + _push(0x120) + b"\x51" + b"\x01" + _sstore(95) + b"\x00")
    out.append(Case("return_mid_stream", {addrs[0]: root, addrs[1]: child},
                    addrs[0], 300_000))
    return out


def occ_run_all(cases, fused=True, max_steps=None):
    """Every case a lane of ONE block through the call OCC kernel (the
    window runner's path), with the fused step or the XLA step alone."""
    from dataclasses import replace as _replace
    import jax
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    committed, codes = {}, {}
    for c in cases:
        committed.update(_pre(c))
        codes.update(c.codes)
    runner = MachineWindowRunner(
        FORK, lambda a, k: committed.get((a, k), 0),
        code_resolver=lambda a: None if a == IDENTITY
        else codes.get(a, b""))
    for a in codes:
        runner.callees.learn(a)
    if max_steps is not None:
        bucket = runner._occ_params
        runner._occ_params = lambda items, pre: (lambda p, o: (
            _replace(p, max_steps=max_steps), o))(*bucket(items, pre))
    if not fused:
        built = {}

        def kernel(p, occ, sk=None):
            if (p, occ) not in built:
                built[(p, occ)] = jax.jit(
                    M.build_occ_machine(p, occ, (), fused=False),
                    donate_argnums=(0,))
            return built[(p, occ)]
        runner._kernel = kernel
    specs = [TxSpec(code=c.codes[c.root], calldata=CALLDATA, gas=c.gas,
                    value=0, caller=SENDER, address=c.root, origin=SENDER,
                    gas_price=GAS_PRICE) for c in cases]
    w = runner.complete(runner.issue([(ENV, specs)]))
    return w.results[0], w


def _lane_view(r):
    return (r.status, r.gas_left, r.refund, r.host_reason, r.reads,
            r.writes, dict(r.xreads), dict(r.xwrites), r.logs, r.log_addrs,
            r.frames, r.steps, r.frame_why)


@pytest.fixture(scope="module")
def fused_legs():
    cases = corpus() + _fused_cases(500)
    fused, wf = occ_run_all(cases)
    xla, wx = occ_run_all(cases, fused=False)
    oracle = [interp_run(c) for c in cases]
    return cases, fused, xla, oracle, (wf, wx)


@pytest.fixture(scope="module")
def step_bound_legs():
    """Lanes that loop forever under a 60-step bound: one of light ops
    alone, one that leaves the fused step every few ops (CHAINID); and
    a call tree beside them, which makes the window a call window."""
    addrs = _addrs(700)
    light = b"\x5b" + _push(1) + b"\x50" + _push(0) + b"\x56"
    mixed = b"\x5b\x46\x50" + _push(0) + b"\x56"
    cases = [Case("light_loop", {addrs[0]: light}, addrs[0], 300_000),
             Case("mixed_loop", {addrs[2]: mixed}, addrs[2], 300_000),
             Case("call_tree", {
                 addrs[3]: assemble([("call", "call", 4, "all", 0, 32)],
                                    addrs),
                 addrs[4]: assemble([("return", 32)], addrs)},
                 addrs[3], 300_000)]
    fused, wf = occ_run_all(cases, max_steps=60)
    xla, _wx = occ_run_all(cases, fused=False, max_steps=60)
    return cases, fused, xla, wf


def _device_view(c, r):
    writes = {(c.root, k): v for k, v in r.writes.items()}
    writes.update(r.xwrites)
    addrs = r.log_addrs or [c.root] * len(r.logs)
    logs = [(a, list(t), d) for a, (t, d) in zip(addrs, r.logs)]
    return (r.status, r.gas_left, r.refund, writes, logs)


@pytest.mark.parametrize("which", [
    "corpus", "light_ops", "mixed_chunk", "park_first", "step_bound",
    "return_mid_stream"])
def test_fused_step_matches_the_xla_step(request, which):
    """The call OCC kernel with its light opcodes in the fused step
    (Pallas, in interpret mode off the chip) against the same kernel
    with the XLA step alone, lane by lane — status, gas, host_reason,
    storage entries read and written, logs, frames, steps — and, on
    every lane the device answers, against the host interpreter."""
    if which == "step_bound":
        cases, fused, xla, wf = request.getfixturevalue("step_bound_legs")
        for c, rf, rx in zip(cases, fused, xla):
            assert _lane_view(rf) == _lane_view(rx), c.label
        for c, rf in zip(cases[:2], fused):
            # the bound is per lane, across the kernel's returns
            assert (rf.status, rf.host_reason, rf.steps) == (
                M.HOST, M.R_STEPS, 60), c.label
        assert fused[0].fused_steps == 60            # light ops alone
        # CHAINID at steps 2, 7, ..., 57: twelve XLA steps, one for
        # each of the kernel's returns
        assert fused[1].fused_steps == 48 and wf.exits == [12]
        return
    cases, fused, xla, oracle, (wf, wx) = request.getfixturevalue(
        "fused_legs")
    picked = [k for k, c in enumerate(cases)
              if (which == "corpus" and "/" not in c.label and c.label
                  not in ("park_first", "light_only", "return_mid_stream"))
              or c.label.split("/")[0] == which]
    assert picked, which
    bad = []
    for k in picked:
        c, rf, rx = cases[k], fused[k], xla[k]
        if _lane_view(rf) != _lane_view(rx):
            bad.append(f"{c.label}: fused {_lane_view(rf)} != "
                       f"xla {_lane_view(rx)}")
        elif rf.status != M.HOST and not _agree(
                _device_view(c, rf), oracle[k], _pre(c)):
            bad.append(f"{c.label}: fused {_device_view(c, rf)} != "
                       f"interpreter {oracle[k]}")
        # every lane step ran on one of the two paths
        assert 0 <= rf.fused_steps <= rf.steps and rx.fused_steps == 0
    assert not bad, "\n".join(bad)
    assert wf.committed[0].tolist() == wx.committed[0].tolist()
    assert wf.rounds == wx.rounds and wx.exits == [0]
    views = [_lane_view(fused[k]) for k in picked]
    # the XLA step's share of each lane: what the fused step leaves
    xla_steps = [fused[k].steps - fused[k].fused_steps for k in picked]
    if which == "light_ops":
        # the cached SLOADs and SSTOREs included, nothing leaves
        assert all(v[0] == M.STOP for v in views)
        assert xla_steps == [0] * len(picked)
    elif which == "mixed_chunk":
        # errors the fused step parked on, decided by the XLA step (the
        # sentry's SSTORE among them), the KECCAK256s it leaves
        assert [v[0] for v in views] == [M.ERR, M.ERR, M.HOST, M.HOST,
                                          M.ERR, M.STOP, M.ERR, M.STOP]
        assert [v[3] for v in views][2:4] == [M.R_STACK, M.R_MEM]
        assert xla_steps == [1, 1, 1, 1, 1, 0, 1, 3]
    elif which == "park_first":
        # CHAINID alone
        assert views[0][0] == M.STOP and xla_steps == [1]
    elif which == "return_mid_stream":
        # the CALL and the callee's RETURN into its parent
        assert views[0][0] == M.STOP and views[0][10] == 1
        assert views[0][5] and xla_steps == [2]


def test_light_ops_alone_take_no_xla_step(fused_legs):
    """A lane of light opcodes alone, STOP included, never leaves the
    fused step; the token window's kernel builds no fused step."""
    cases, fused, _xla, _oracle, _w = fused_legs
    (r,) = [fused[k] for k, c in enumerate(cases) if c.label == "light_only"]
    assert r.status == M.STOP and r.steps == r.fused_steps == 9
    import jax
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    from coreth_tpu.workloads.erc20 import TOKEN_RUNTIME, transfer_calldata
    p, occ = _window_params(TOKEN_RUNTIME,
                            transfer_calldata(b"\x22" * 20, 5))
    args = MachineWindowRunner(FORK, lambda a, k: 0)._warm_args(p, occ)
    text = str(jax.make_jaxpr(M.build_occ_machine(p, occ))(*args))
    assert "pallas_call" not in text and "fused_step" not in text

"""Table-driven differential opcode micro-fuzzer (tier-1).

For every opcode a backend CLAIMS per fork — the claim sets are
extracted by the semconf lint pass (``tools.lint.semconf.tree_claims``)
from the live eligibility/device tables, never a hand list — this
module synthesizes short bytecode programs and replays them on up to
three legs against identical pre-state:

- the host interpreter (``evm/interpreter.py``), the oracle;
- the native C++ engine (``HostExecBackend``) — SKIPPED wholesale on
  boxes without the built ``libcoreth_native.so``;
- the device step machine (``MachineRunner``), one batched run per
  fork so the kernel compiles once.

Status taxonomy (STOP/REVERT/ERR), exact ``gas_left``, and (on STOP)
the refund counter must agree.  A leg answering HOST has legitimately
deferred to the host interpreter (value transfer, lane stack cap,
scache exhaustion) and is excluded from comparison — deferral is an
answer, disagreement is not.

Corpus shapes per claimed opcode: a small-operand tuple, edge-value
operands (0, 1, 2^255, 2^256-1, ...), a seeded random tuple, and — for
every net-push opcode the native engine claims — deep-stack variants
at 1023/1024 preamble pushes, pinning the stack-overflow boundary the
SEM004 guard audit hardened (interpreter errs at 1025; the native arm
must too, not scribble on).

Coverage is ASSERTED: the corpus must exercise 100% of the opcodes
each backend claims at each fork, and the compared (non-HOST) set must
match too.  Runs under pytest (full corpus at durango/cancun, lighter
at ap2/ap3) or standalone: ``python tests/fuzz_opcode_diff.py``.
"""

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu.evm import forks, hostexec, vmerrs
from coreth_tpu.evm import jump_table as JT
from coreth_tpu.evm.device import machine as M
from coreth_tpu.evm.device.adapter import BlockEnv, MachineRunner, TxSpec
from coreth_tpu.evm.evm import EVM, BlockContext, Config, TxContext
from coreth_tpu.mpt import EMPTY_ROOT
from coreth_tpu.params import protocol as P
from coreth_tpu.params.config import _phases
from coreth_tpu.state import Database, StateDB
from tools.lint.semconf import tree_claims

SENDER = b"\x11" * 20
CONTRACT = b"\xcc" * 20
EOA = b"\xee" * 20
# a contract in the pre-state of every leg, for the call family
# (CALL / STATICCALL / EXTCODESIZE): stores a word in memory and
# returns it — 32 bytes of return data
CALLEE = b"\xca" * 20
CALLEE_CODE = bytes([0x60, 0x2A, 0x60, 0x00, 0x52,
                     0x60, 0x20, 0x60, 0x00, 0xF3])
COINBASE = bytes.fromhex("0100000000000000000000000000000000000000")
NUMBER, TIME = 5, 3_000
GAS = 200_000
GAS_PRICE = 30 * 10**9
BASE_FEE = 25 * 10**9
CALLDATA = bytes(range(1, 33))
STORAGE = {(1).to_bytes(32, "big"): 5}   # committed slot 1 = 5, all legs
STACK_LIMIT = int(P.STACK_LIMIT)

CFGS = {"ap2": _phases(2), "ap3": _phases(3), "durango": _phases(11),
        "cancun": _phases(11, cancun_time=0)}
TABLES = {"ap2": JT.new_ap2_table, "ap3": JT.new_ap3_table,
          "durango": JT.new_durango_table, "cancun": JT.new_cancun_table}
HEAVY_FORKS = ("durango", "cancun")

ENV = BlockEnv(coinbase=COINBASE, timestamp=TIME, number=NUMBER,
               gas_limit=8_000_000, chain_id=43111, base_fee=BASE_FEE)

CLAIMS = tree_claims()

EDGES = (0, 1, (1 << 256) - 1, 1 << 255, (1 << 64) - 1, 255)

# operand tuples in POP ORDER (first element ends up on top of the
# stack) for opcodes whose operands must stay bounded (memory offsets)
# or hit interesting state (storage keys, refund transitions)
SPECIAL = {
    0x20: [(0, 32), (0, 0), (1, 64), (0, 1 << 64)],
    0x37: [(0, 0, 32), (1, 31, 7), (0, 0, 0)],
    0x39: [(0, 0, 16), (2, 1, 5), (0, 0, 0)],
    0x3B: [(int.from_bytes(CALLEE, "big"),), (int.from_bytes(EOA, "big"),)],
    0x3E: [(0, 0, 0), (0, 0, 1)],            # 2nd: out-of-bounds err
    0x51: [(0,), (32,), (1 << 64,)],          # huge offset: OOG
    0x52: [(0, 7), (64, 1 << 255), (1 << 64, 1)],
    0x53: [(0, 0xAB), (95, 1 << 200)],
    0x54: [(0,), (1,)],
    0x55: [(1, 0), (1, 5), (1, 6), (0, 7), (2, 0)],
    0x5C: [(0,), (1,)],
    0x5D: [(1, 7), (0, 0)],
    0x5E: [(0, 32, 32), (0, 0, 0), (4, 0, 8)],
    0xA0: [(0, 0), (0, 32)],
    0xA1: [(0, 32, 1)],
    0xA2: [(0, 32, 1, 2)],
    0xA3: [(0, 0, 1, 2, 3)],
    0xA4: [(0, 32, 1, 2, 3, 4)],
    0xF1: [(60_000, int.from_bytes(CALLEE, "big"), 0, 0, 0, 0, 32),
           (60_000, int.from_bytes(EOA, "big"), 0, 0, 0, 0, 0),
           (60_000, int.from_bytes(CALLEE, "big"), 0, 0, 4, 8, 16)],
    0xF3: [(0, 0), (0, 32)],
    0xFA: [(60_000, int.from_bytes(CALLEE, "big"), 0, 0, 0, 32),
           (60_000, int.from_bytes(EOA, "big"), 0, 0, 0, 0),
           ((1 << 256) - 1, int.from_bytes(CALLEE, "big"), 0, 4, 8, 64)],
    0xFD: [(0, 0), (0, 32)],
}


def _push(v: int) -> bytes:
    raw = v.to_bytes((max(v.bit_length(), 1) + 7) // 8, "big")
    return bytes([0x5F + len(raw)]) + raw


def _op_bytes(op: int) -> bytes:
    if 0x60 <= op <= 0x7F:          # PUSHn carries immediate data
        return bytes([op]) + b"\x00" * (op - 0x5F)
    return bytes([op])


def _arity(table, op):
    e = table[op]
    return e.min_stack, e.min_stack + STACK_LIMIT - e.max_stack


class Case:
    __slots__ = ("label", "op", "code", "deep")

    def __init__(self, label, op, code, deep=False):
        self.label = label
        self.op = op
        self.code = code
        self.deep = deep


def _generic(op, operands) -> bytes:
    body = b"".join(_push(v) for v in reversed(operands))
    return body + bytes([op]) + b"\x00"


def _op_cases(op, table, heavy):
    """Shallow corpus entries for one claimed opcode."""
    if op == 0x56:                   # JUMP: valid fwd, bad 0, bad huge
        out = [Case(f"jump-ok:{op:#04x}", op,
                    bytes([0x60, 4, 0x56, 0xFE, 0x5B, 0x00]))]
        if heavy:
            out.append(Case(f"jump-bad:{op:#04x}", op,
                            bytes([0x60, 0, 0x56, 0x5B, 0x00])))
            out.append(Case(f"jump-huge:{op:#04x}", op,
                            _push((1 << 256) - 1) + bytes([0x56])))
        return out
    if op == 0x57:                   # JUMPI over taken/not/bad-dest
        out = []
        for cond in ((0, 1, (1 << 256) - 1) if heavy else (1,)):
            pre = _push(cond)
            d = len(pre) + 4
            out.append(Case(f"jumpi-c{min(cond, 2)}:{op:#04x}", op,
                            pre + bytes([0x60, d, 0x57, 0x00,
                                         0x5B, 0x00])))
        if heavy:
            out.append(Case(f"jumpi-bad:{op:#04x}", op,
                            bytes([0x60, 1, 0x60, 0, 0x57])))
        return out
    if 0x60 <= op <= 0x7F:           # PUSHn: zero/ff/truncated data
        n = op - 0x5F
        out = [Case(f"push-zero:{op:#04x}", op,
                    bytes([op]) + b"\x00" * n + b"\x00")]
        if heavy:
            out.append(Case(f"push-ff:{op:#04x}", op,
                            bytes([op]) + b"\xFF" * n + b"\x00"))
            # data truncated by end-of-code: implicit zero padding
            out.append(Case(f"push-trunc:{op:#04x}", op, bytes([op])))
        return out
    pops, _pushes = _arity(table, op)
    if op in SPECIAL:
        tuples = SPECIAL[op] if heavy else SPECIAL[op][:1]
    elif pops == 0:
        tuples = [()]
    else:
        tuples = [tuple(range(1, pops + 1))]
        if heavy:
            tuples.append(tuple(EDGES[i % len(EDGES)]
                                for i in range(pops)))
            rng = random.Random(0xC0DE + op)
            tuples.append(tuple(rng.getrandbits(256)
                                for _ in range(pops)))
    return [Case(f"v{i}:{op:#04x}", op, _generic(op, t))
            for i, t in enumerate(tuples)]


def build_corpus(fork: str, heavy: bool):
    nat = CLAIMS["native"].get(fork, frozenset())
    dev = CLAIMS["device"].get(fork, frozenset())
    table = TABLES[fork]()
    cases = []
    for op in sorted(nat | dev):
        cases.extend(_op_cases(op, table, heavy))
    # deep-stack variants: every net-push opcode the native engine
    # claims must err at 1025 exactly like the interpreter (the SEM004
    # overflow-guard class) and still succeed at the 1024 boundary
    for op in sorted(nat):
        pops, pushes = _arity(table, op)
        if pushes <= pops:
            continue
        for k in ((1023, 1024) if heavy else (1024,)):
            code = b"\x60\x01" * k + _op_bytes(op) + b"\x00"
            cases.append(Case(f"deep{k}:{op:#04x}", op, code,
                              deep=True))
    return cases


# ------------------------------------------------------------- legs

def interp_run(fork: str, code: bytes):
    """The oracle: (status, gas_left, refund)."""
    cfg = CFGS[fork]
    rules = cfg.rules(NUMBER, TIME)
    db = Database()
    statedb = StateDB(EMPTY_ROOT, db)
    statedb.set_code(CONTRACT, code)
    statedb.set_code(CALLEE, CALLEE_CODE)
    for k, v in STORAGE.items():
        statedb.set_state(CONTRACT, k, v.to_bytes(32, "big"))
    statedb.add_balance(SENDER, 10**18)
    root = statedb.commit(False)
    statedb = StateDB(root, db)
    block_ctx = BlockContext(coinbase=COINBASE, number=NUMBER,
                             time=TIME, gas_limit=ENV.gas_limit,
                             base_fee=BASE_FEE)
    evm = EVM(block_ctx, TxContext(origin=SENDER, gas_price=GAS_PRICE),
              statedb, cfg, Config())
    statedb.prepare(rules, SENDER, COINBASE, CONTRACT,
                    list(rules.active_precompiles), [])
    _ret, gas_left, err = evm.call(SENDER, CONTRACT, b"" + CALLDATA,
                                   GAS, 0)
    if err is None:
        status = M.STOP
    elif isinstance(err, vmerrs.ErrExecutionReverted):
        status = M.REVERT
    else:
        status = M.ERR
    return status, gas_left, statedb.refund


def native_run_all(fork: str, cases):
    """One native session, one call per case; [(status, gas, refund)]."""
    from coreth_tpu.evm.hostexec.backend import HostExecBackend
    from coreth_tpu.state.statedb import normalize_state_key
    committed = {normalize_state_key(k): v.to_bytes(32, "big")
                 for k, v in STORAGE.items()}

    def slots(_addr, key):
        return committed.get(key, b"\x00" * 32)

    be = HostExecBackend(fork, ENV.chain_id, slots,
                         lambda a: CALLEE_CODE if a == CALLEE else b"")
    be.set_env(COINBASE, TIME, NUMBER, ENV.gas_limit, BASE_FEE)
    out = []
    try:
        for c in cases:
            be.set_code(CONTRACT, c.code)
            res = be.call(SENDER, CONTRACT, 0, GAS_PRICE, CALLDATA,
                          GAS, warm_addrs=[CONTRACT])
            out.append((res.status, res.gas_left, res.refund))
    finally:
        be.close()
    return out


def device_run_all(fork: str, cases):
    """One batched machine dispatch; [(status, gas, refund)]."""
    from coreth_tpu.state.statedb import normalize_state_key
    committed = {normalize_state_key(k): v
                 for k, v in STORAGE.items()}
    runner = MachineRunner(fork, ENV,
                           lambda _addr, key: committed.get(key, 0),
                           code_resolver=lambda a: CALLEE_CODE
                           if a == CALLEE else b"")
    specs = [TxSpec(code=c.code, calldata=CALLDATA, gas=GAS, value=0,
                    caller=SENDER, address=CONTRACT, origin=SENDER,
                    gas_price=GAS_PRICE) for c in cases]
    return [(r.status, r.gas_left, r.refund)
            for r in runner.run(specs)]


# ------------------------------------------------------- comparison

def _mismatch(leg, fork, case, got, want):
    return (f"{leg}@{fork} {case.label}: got status={got[0]} "
            f"gas_left={got[1]} refund={got[2]}, interpreter says "
            f"status={want[0]} gas_left={want[1]} refund={want[2]} "
            f"(code={case.code[:40].hex()}{'...' if len(case.code) > 40 else ''})")


def _compare(leg, fork, case, got, want, mismatches, compared_ops):
    if got[0] == M.HOST:
        return                      # legitimate defer-to-host
    compared_ops.add(case.op)
    ok = got[0] == want[0] and got[1] == want[1]
    if ok and want[0] == M.STOP:
        ok = got[2] == want[2]
    if not ok:
        mismatches.append(_mismatch(leg, fork, case, got, want))


def run_fork(fork: str, heavy: bool):
    """Returns (n_cases, mismatches, skipped_native)."""
    nat = CLAIMS["native"].get(fork, frozenset())
    dev = CLAIMS["device"].get(fork, frozenset())
    cases = build_corpus(fork, heavy)
    # coverage of the CORPUS, asserted from the extracted tables
    seen = {c.op for c in cases}
    assert not (nat | dev) - seen, \
        f"corpus misses claimed ops: {sorted(map(hex, (nat | dev) - seen))}"

    native_on = hostexec.available()
    nat_cases = [c for c in cases if c.op in nat] if native_on else []
    dev_cases = [c for c in cases if not c.deep and c.op in dev]

    oracle = {}
    for c in {id(c): c for c in nat_cases + dev_cases}.values():
        oracle[id(c)] = interp_run(fork, c.code)

    mismatches = []
    nat_compared, dev_compared = set(), set()
    if nat_cases:
        for c, got in zip(nat_cases, native_run_all(fork, nat_cases)):
            _compare("native", fork, c, got, oracle[id(c)],
                     mismatches, nat_compared)
    for c, got in zip(dev_cases, device_run_all(fork, dev_cases)):
        _compare("device", fork, c, got, oracle[id(c)],
                 mismatches, dev_compared)

    # every claimed opcode must have produced at least one COMPARED
    # (non-HOST) differential result
    if nat_cases:
        assert not nat - nat_compared, \
            f"native ops never compared: {sorted(map(hex, nat - nat_compared))}"
    missing_dev = dev - dev_compared
    assert not missing_dev, \
        f"device ops never compared: {sorted(map(hex, missing_dev))}"
    return len(oracle), mismatches, not native_on


# ------------------------------------------------------------ pytest

# tier-1 runs the lattice endpoints only: ap2 pins the oldest gate
# set, cancun claims the superset of every opcode, so the per-fork
# coverage asserts in run_fork still exercise 100% of the claimed
# surface.  The two intermediate forks each pay a fresh device-kernel
# compile (~2 min together on the 1-core box) for gate-boundary
# coverage only — slow-marked; `python tests/fuzz_opcode_diff.py`
# and -m slow still run all four.
_TIER1_FORKS = ("ap2", "cancun")


@pytest.mark.parametrize(
    "fork",
    [f if f in _TIER1_FORKS else pytest.param(f, marks=pytest.mark.slow)
     for f in forks.SUPPORTED])
def test_opcode_differential(fork):
    heavy = fork in HEAVY_FORKS
    n, mismatches, _skipped = run_fork(fork, heavy)
    assert n > 0
    assert not mismatches, "\n".join(mismatches)


def main(argv=None) -> int:
    total = 0
    bad = []
    for fork in forks.SUPPORTED:
        heavy = fork in HEAVY_FORKS
        n, mismatches, skipped = run_fork(fork, heavy)
        total += n
        bad.extend(mismatches)
        legs = "interp+device" + ("" if skipped else "+native")
        print(f"{fork}: {n} case(s), {len(mismatches)} mismatch(es) "
              f"[{legs}]")
    for m in bad:
        print(m)
    print(f"fuzz_opcode_diff: {total} case(s), {len(bad)} mismatch(es)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

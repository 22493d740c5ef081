"""Native-backend opcode coverage and per-fork dispatch tables.

The compiled interpreter (native/evm.cc run_frame) executes a fixed
opcode set; everything else that a fork DEFINES must make the native
call abort with a HOST status so the tx re-runs on the Python
interpreter.  This module owns that classification in one place:

- ``NATIVE_BASE`` / ``native_opcodes(fork)``: what the C++ engine
  executes (the census the coverage-assertion test pins);
- ``native_optable(fork)``: the 256-entry table handed to the session
  (0 undefined -> INVALID, 1 native, 2 defined-but-host-only -> HOST);
- ``native_eligible(code, fork)``: the static pre-check the bridge and
  the serial-block short-circuit run before attempting native
  execution (runtime escapes still cover dynamic cases: value-carrying
  subcalls, precompile targets, unknown callees).

Built on the SAME shared census walker as the device classifier
(evm/census.py), so the two backends cannot diverge on how bytecode is
read.
"""

from __future__ import annotations

from typing import Dict, Tuple

from coreth_tpu.evm import forks
from coreth_tpu.evm.census import opcode_census
from coreth_tpu.evm.device.tables import FORKS, op_tables

# Opcodes compiled into native/evm.cc's run_frame that every supported
# fork defines (keep in lockstep with build_replay_optable there;
# tests/test_hostexec.py pins the workload contracts against this set,
# and semconf SEM003 pins that each member is defined in EVERY fork's
# jump table — fork-introduced ops belong in NATIVE_GATED instead).
NATIVE_BASE = frozenset(
    list(range(0x00, 0x0C))        # STOP..SIGNEXTEND
    + list(range(0x10, 0x1E))      # LT..SAR
    + [0x20]                       # KECCAK256
    + [0x30, 0x32, 0x33, 0x34, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A]
    + [0x3B, 0x3D, 0x3E]           # EXTCODESIZE RETURNDATASIZE/COPY
    + [0x41, 0x42, 0x43, 0x44, 0x45, 0x46]  # COINBASE..CHAINID
    + list(range(0x50, 0x5C))      # POP..JUMPDEST
    + list(range(0x60, 0xA5))      # PUSHn DUPn SWAPn LOGn
    + [0xF1, 0xF3, 0xFA, 0xFD, 0xFE]  # CALL RETURN STATICCALL REVERT INVALID
)

# Fork-introduced opcodes the compiled engine implements; the lattice
# (evm/forks.py) decides which are live per fork — the PR-3 bug class
# (PUSH0 executing pre-durango) cannot be re-introduced by editing one
# set here.
NATIVE_GATED = frozenset({0x48, 0x5F})         # BASEFEE PUSH0

_FORK_EXTRA = {f: forks.extra_for(f, NATIVE_GATED)
               for f in forks.SUPPORTED}

# Derived fork-constant tuples (evm/forks.py feature flags; SEM005
# rejects hand-maintained literal redefinitions of these names).
REFUND_FORKS = forks.REFUND_FORKS
COINBASE_WARM_FORKS = forks.COINBASE_WARM_FORKS


def native_opcodes(fork: str) -> frozenset:
    return NATIVE_BASE | _FORK_EXTRA.get(fork, frozenset())


_OPTABLE_CACHE: Dict[str, bytes] = {}


def native_optable(fork: str) -> bytes:
    """256-entry dispatch classification for the C++ session."""
    cached = _OPTABLE_CACHE.get(fork)
    if cached is not None:
        return cached
    if fork not in FORKS:
        raise ValueError(f"unsupported native fork {fork!r}")
    defined = op_tables(fork).supported  # nonzero == defined per fork
    native = native_opcodes(fork)
    table = bytearray(256)
    for op in range(256):
        if defined[op] == 0:
            table[op] = 0
        elif op in native:
            table[op] = 1
        else:
            table[op] = 2
    out = bytes(table)
    _OPTABLE_CACHE[fork] = out
    return out


def native_eligible(code: bytes, fork: str,
                    code_cap: int = 24576) -> Tuple[bool, str]:
    """Static scan: can the native engine attempt this bytecode under
    `fork`?  (bool, reason).  Undefined opcodes stay eligible (INVALID
    at runtime, handled identically); defined-but-uncompiled ones make
    the attempt pointless — it would HOST-escape on first contact."""
    if fork not in FORKS:
        return False, f"unsupported fork {fork!r}"
    if len(code) > code_cap:
        return False, "code too large"
    table = native_optable(fork)
    for op in sorted(opcode_census(code)):
        if table[op] == 2:
            return False, f"host-only opcode 0x{op:02x}"
    return True, ""

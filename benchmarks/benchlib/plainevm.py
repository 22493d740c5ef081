"""The plain reference's own EVM, for one token contract.

``plainref.Book`` knows nonces and native balances.  A contract call's
fee is ``gas_used x price`` and the gas depends on the bytecode, so a
chain of token calls needs an interpreter of its own to be added up:
this one, in Python integers, for exactly the opcodes the benchmark's
token runtime uses (``OPCODES``) — any other opcode raises.  Like
``plainref`` it imports nothing of the program and takes nothing from
it: none of the four EVMs under test (the Python host interpreter that
writes the chain, the native host executor, the device step machine,
its specialized programs) decides what the right answer is.

The rules, each from its specification:

- intrinsic gas: 21,000 + 16 a nonzero and 4 a zero calldata byte
  (EIP-2028);
- EIP-2929: the first touch of a storage slot in a transaction costs
  2,100 (SLOAD) or 2,100 on top (SSTORE), later ones 100;
- EIP-2200 with EIP-3529's numbers for SSTORE: fails with 2,300 gas or
  less left; a write of the current value 100, a first change of a
  slot 20,000 from zero and 2,900 otherwise, a later change 100; the
  refund counter is kept as EIP-3529 sets it and NOT paid: Coreth pays
  no refunds from Apricot Phase 1 on;
- memory expansion 3 a word + words^2 / 512; KECCAK256 30 + 6 a word;
  LOG 375 + 375 a topic + 8 a byte;
- a REVERT gives back the gas that is left and undoes the call's
  storage writes and logs; any other failure takes all of it;
- Coreth keeps multi-coin balances in the storage trie of the same
  account, apart from contract storage by the lowest bit of the key's
  first byte: SLOAD and SSTORE clear that bit (``slot_key``).

``TokenBook`` puts the interpreter on top of ``plainref.Book``: a call
runs the bytecode over the contract's slots, charges ``gas_used x
price`` to the sender, credits the coinbase, and ``state_root`` folds
the accounts, the contract's storage root and code hash among them.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchlib import plainref

WORD = 1 << 256
TX_GAS = 21_000
DATA_NONZERO_GAS, DATA_ZERO_GAS = 16, 4
COLD_SLOAD, WARM_READ = 2_100, 100
SSTORE_SET, SSTORE_RESET = 20_000, 5_000 - COLD_SLOAD
SSTORE_SENTRY = 2_300
SSTORE_CLEARS_REFUND = SSTORE_RESET + 1_900          # EIP-3529: 4,800
KECCAK_GAS, KECCAK_WORD_GAS = 30, 6
LOG_GAS, LOG_TOPIC_GAS, LOG_DATA_GAS = 375, 375, 8

STOP_OK, REVERTED, FAILED = "ok", "reverted", "failed"


class UnknownOpcode(Exception):
    """The bytecode uses an opcode this reference has no rule for."""


class _Fail(Exception):
    """Out of gas, a bad jump, a stack under- or overflow: the call
    fails and keeps nothing."""


_KECCAK: Dict[bytes, bytes] = {}


def keccak(data: bytes) -> bytes:
    """``plainref.keccak256``, remembered: the same two mapping keys
    are hashed by every payment an account takes part in."""
    out = _KECCAK.get(data)
    if out is None:
        out = _KECCAK[data] = plainref.keccak256(data)
    return out


def slot_key(key: int) -> bytes:
    """The storage-trie key SLOAD/SSTORE reach for the word ``key``."""
    raw = key.to_bytes(32, "big")
    return bytes([raw[0] & 0xFE]) + raw[1:]


def mapping_slot(addr: bytes, slot: int = 0) -> bytes:
    """Solidity's rule for ``mapping(address => ...)`` at ``slot``."""
    return slot_key(int.from_bytes(
        keccak(b"\x00" * 12 + addr + slot.to_bytes(32, "big")), "big"))


def intrinsic_gas(data: bytes) -> int:
    zeros = data.count(0)
    return TX_GAS + DATA_ZERO_GAS * zeros \
        + DATA_NONZERO_GAS * (len(data) - zeros)


class Outcome:
    """What one call did: ``status``, the gas it used (intrinsic gas
    included), its refund counter, its logs as (topics, data), what it
    returned, and the slots it changed."""

    def __init__(self, status, gas_used, refund, logs, output, writes):
        self.status, self.gas_used, self.refund = status, gas_used, refund
        self.logs, self.output, self.writes = logs, output, writes


class _Frame:
    def __init__(self, code, caller, data, gas, storage):
        self.code, self.caller, self.data = code, caller, data
        self.gas = gas
        self.storage = storage              # committed slots, read only
        self.writes: Dict[bytes, int] = {}
        self.warm: set = set()
        self.stack: List[int] = []
        self.mem = bytearray()
        self.logs: List[Tuple[List[bytes], bytes]] = []
        self.refund = 0
        self.pc = 0
        self.jumpdests = _jumpdests(code)

    # ---- gas, stack, memory
    def use(self, n: int) -> None:
        if self.gas < n:
            raise _Fail("out of gas")
        self.gas -= n

    def pop(self) -> int:
        if not self.stack:
            raise _Fail("stack underflow")
        return self.stack.pop()

    def push(self, v: int) -> None:
        if len(self.stack) >= 1024:
            raise _Fail("stack overflow")
        self.stack.append(v % WORD)

    def expand(self, offset: int, size: int) -> None:
        if size == 0:
            return
        words = (offset + size + 31) // 32
        have = len(self.mem) // 32
        if words > have:
            self.use(_mem_gas(words) - _mem_gas(have))
            self.mem.extend(b"\x00" * (32 * (words - have)))

    def load(self, key: bytes) -> int:
        if key in self.writes:
            return self.writes[key]
        return self.storage.get(key, 0)


def _mem_gas(words: int) -> int:
    return 3 * words + words * words // 512


def _jumpdests(code: bytes) -> set:
    out, pc = set(), 0
    while pc < len(code):
        op = code[pc]
        if op == 0x5B:
            out.add(pc)
        pc += 1 + (op - 0x5F if 0x60 <= op <= 0x7F else 0)
    return out


# ------------------------------------------------------------- the opcodes
def _binary(gas, fn):
    def run(f: _Frame):
        f.use(gas)
        a, b = f.pop(), f.pop()
        f.push(fn(a, b))
    return run


def _iszero(f):
    f.use(3)
    f.push(int(f.pop() == 0))


def _keccak256(f):
    offset, size = f.pop(), f.pop()
    f.use(KECCAK_GAS + KECCAK_WORD_GAS * ((size + 31) // 32))
    f.expand(offset, size)
    f.push(int.from_bytes(keccak(bytes(f.mem[offset:offset + size])),
                          "big"))


def _caller(f):
    f.use(2)
    f.push(int.from_bytes(f.caller, "big"))


def _calldataload(f):
    f.use(3)
    i = f.pop()
    f.push(int.from_bytes(f.data[i:i + 32].ljust(32, b"\x00"), "big"))


def _mstore(f):
    f.use(3)
    offset, v = f.pop(), f.pop()
    f.expand(offset, 32)
    f.mem[offset:offset + 32] = v.to_bytes(32, "big")


def _sload(f):
    key = slot_key(f.pop())
    f.use(WARM_READ if key in f.warm else COLD_SLOAD)
    f.warm.add(key)
    f.push(f.load(key))


def _sstore(f):
    if f.gas <= SSTORE_SENTRY:
        raise _Fail("SSTORE with the stipend or less left")
    key, new = slot_key(f.pop()), f.pop()
    cold = 0 if key in f.warm else COLD_SLOAD
    f.warm.add(key)
    original, current = f.storage.get(key, 0), f.load(key)
    if current == new:
        cost = WARM_READ
    elif original == current:
        cost = SSTORE_SET if original == 0 else SSTORE_RESET
        if original != 0 and new == 0:
            f.refund += SSTORE_CLEARS_REFUND
    else:
        cost = WARM_READ
        if original != 0:
            if current == 0:
                f.refund -= SSTORE_CLEARS_REFUND
            elif new == 0:
                f.refund += SSTORE_CLEARS_REFUND
        if original == new:
            f.refund += (SSTORE_SET if original == 0
                         else SSTORE_RESET) - WARM_READ
    f.use(cost + cold)
    f.writes[key] = new


def _jumpi(f):
    f.use(10)
    dest, cond = f.pop(), f.pop()
    if cond:
        if dest not in f.jumpdests:
            raise _Fail("bad jump")
        f.pc = dest


def _jumpdest(f):
    f.use(1)


def _push(n):
    def run(f):
        f.use(3)
        f.push(int.from_bytes(f.code[f.pc:f.pc + n].ljust(n, b"\x00"),
                              "big"))
        f.pc += n
    return run


def _dup(n):
    def run(f):
        f.use(3)
        if len(f.stack) < n:
            raise _Fail("stack underflow")
        f.push(f.stack[-n])
    return run


def _swap1(f):
    f.use(3)
    if len(f.stack) < 2:
        raise _Fail("stack underflow")
    f.stack[-1], f.stack[-2] = f.stack[-2], f.stack[-1]


def _log3(f):
    offset, size = f.pop(), f.pop()
    topics = [f.pop().to_bytes(32, "big") for _ in range(3)]
    f.use(LOG_GAS + 3 * LOG_TOPIC_GAS + LOG_DATA_GAS * size)
    f.expand(offset, size)
    f.logs.append((topics, bytes(f.mem[offset:offset + size])))


class _Halt(Exception):
    def __init__(self, status, output):
        self.status, self.output = status, output


def _halt(status):
    def run(f):
        offset, size = f.pop(), f.pop()
        f.expand(offset, size)
        raise _Halt(status, bytes(f.mem[offset:offset + size]))
    return run


# exactly what the token's runtime uses; nothing else has a rule here
OPCODES = {
    0x01: _binary(3, lambda a, b: a + b),                   # ADD
    0x03: _binary(3, lambda a, b: a - b),                   # SUB
    0x10: _binary(3, lambda a, b: int(a < b)),              # LT
    0x14: _binary(3, lambda a, b: int(a == b)),             # EQ
    0x15: _iszero,
    0x1C: _binary(3, lambda s, v: v >> s if s < 256 else 0),  # SHR
    0x20: _keccak256,
    0x33: _caller,
    0x35: _calldataload,
    0x52: _mstore,
    0x54: _sload,
    0x55: _sstore,
    0x57: _jumpi,
    0x5B: _jumpdest,
    0x60: _push(1), 0x61: _push(2), 0x63: _push(4), 0x7F: _push(32),
    0x80: _dup(1), 0x81: _dup(2), 0x82: _dup(3),
    0x90: _swap1,
    0xA3: _log3,
    0xF3: _halt(STOP_OK),
    0xFD: _halt(REVERTED),
}


def call(code: bytes, caller: bytes, data: bytes, gas_limit: int,
         storage: Dict[bytes, int]) -> Outcome:
    """One transaction calling ``code`` with ``data`` and no value;
    ``storage`` (trie key -> value) is read, never written."""
    intrinsic = intrinsic_gas(data)
    if gas_limit < intrinsic:
        raise ValueError("gas limit below the intrinsic gas")
    f = _Frame(code, caller, data, gas_limit - intrinsic, storage)
    status, output = STOP_OK, b""
    try:
        while f.pc < len(code):
            op = code[f.pc]
            rule = OPCODES.get(op)
            if rule is None:
                raise UnknownOpcode(f"0x{op:02x} at {f.pc}")
            f.pc += 1
            rule(f)
    except _Halt as h:
        status, output = h.status, h.output
    except _Fail:
        status, f.gas = FAILED, 0
    if status != STOP_OK:
        f.writes, f.logs, f.refund = {}, [], 0
    return Outcome(status, gas_limit - f.gas, f.refund, f.logs, output,
                   f.writes)


# ------------------------------------------------------------------ the book
class TokenBook(plainref.Book):
    """``plainref.Book`` plus one contract: its code, its slots, and
    the calls into it."""

    def __init__(self, funded: Dict[bytes, int], token: bytes,
                 code: bytes, held: int):
        """``funded``: native wei by holder; every holder also holds
        ``held`` token units, in the mapping at slot 0."""
        super().__init__(funded)
        self.token, self.code = token, code
        self.holders = list(funded)
        self.slots = {mapping_slot(a): held for a in funded} if held \
            else {}
        self.nonce[token], self.balance[token] = 1, 0
        self.gas_used: List[int] = []       # a call, in chain order

    def call(self, src: bytes, data: bytes, gas_limit: int,
             price: int) -> Outcome:
        """``src`` calls the token: the bytecode runs over the slots,
        the sender pays ``gas_used x price`` to the coinbase whatever
        the outcome, and a call that succeeded keeps its writes."""
        out = call(self.code, src, data, gas_limit, self.slots)
        self.transfer(src, self.token, 0, out.gas_used, price)
        for key, v in out.writes.items():
            if v:
                self.slots[key] = v
            else:
                self.slots.pop(key, None)
        self.gas_used.append(out.gas_used)
        return out

    def storage_root(self) -> bytes:
        return plainref.trie_root({k: plainref.rlp_uint(v)
                                   for k, v in self.slots.items()})

    def state_root(self) -> bytes:
        leaves = {a: plainref.account_rlp(n, b)
                  for a, (n, b) in self.accounts().items()}
        leaves[self.token] = plainref.account_rlp(
            self.nonce[self.token], self.balance[self.token],
            self.storage_root(), plainref.keccak256(self.code))
        return plainref.trie_root(leaves)


def transfer_data(dst: bytes, amount: int) -> bytes:
    """ABI calldata of ``transfer(address,uint256)``."""
    return bytes.fromhex("a9059cbb") + b"\x00" * 12 + dst \
        + amount.to_bytes(32, "big")


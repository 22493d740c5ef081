"""The plain reference's own arithmetic: Keccak-256, secp256k1 public
keys, RLP, the hex-prefix Merkle-Patricia root, the fee rule, and the
account book a chain's transfers add up to.

Nothing here imports the program, and nothing here is taken from it:
no weights, tables, headers or hashes.  A chain builder
(``benchmarks/chains/<name>.py``) replays its own plan through
``Book`` — nonces and native balances as dictionary arithmetic, the
fee as ``gas x price`` credited to the coinbase, the price from
``base_fees`` — and ``state_root`` folds the book into the state root
the committed trie must have.  After the window the harness reads
every account of the book back from the state the timed engine
committed and compares the engine's root with this one.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

# ------------------------------------------------------------- Keccak-256
_RC = [0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
       0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
       0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
       0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
       0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
       0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
       0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
       0x8000000000008080, 0x0000000080000001, 0x8000000080008008]
_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
        [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]
_M = (1 << 64) - 1


def _f1600(a: List[List[int]]) -> None:
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4]
             for x in range(5)]
        for x in range(5):
            d = c[x - 1] ^ (((c[(x + 1) % 5] << 1)
                             | (c[(x + 1) % 5] >> 63)) & _M)
            for y in range(5):
                a[x][y] ^= d
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                r = _ROT[x][y]
                v = a[x][y]
                b[y][(2 * x + 3 * y) % 5] = \
                    ((v << r) | (v >> (64 - r))) & _M if r else v
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ (~b[(x + 1) % 5][y]
                                     & b[(x + 2) % 5][y] & _M)
        a[0][0] ^= rc


def keccak256(data: bytes) -> bytes:
    """Keccak-256 as Ethereum uses it (pad 0x01, not SHA-3's 0x06)."""
    rate = 136
    msg = bytearray(data)
    msg.append(0x01)
    msg += b"\x00" * (-len(msg) % rate)
    msg[-1] |= 0x80
    a = [[0] * 5 for _ in range(5)]
    for off in range(0, len(msg), rate):
        for i in range(rate // 8):
            a[i % 5][i // 5] ^= int.from_bytes(
                msg[off + 8 * i:off + 8 * i + 8], "little")
        _f1600(a)
    return b"".join(a[i % 5][i // 5].to_bytes(8, "little")
                    for i in range(4))


# ---------------------------------------------------------------- secp256k1
_P = 2**256 - 2**32 - 977
_G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
      0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)


def _add(p, q):
    if p is None:
        return q
    if q is None:
        return p
    if p[0] == q[0]:
        if (p[1] + q[1]) % _P == 0:
            return None
        lam = 3 * p[0] * p[0] * pow(2 * p[1], -1, _P) % _P
    else:
        lam = (q[1] - p[1]) * pow(q[0] - p[0], -1, _P) % _P
    x = (lam * lam - p[0] - q[0]) % _P
    return x, (lam * (p[0] - x) - p[1]) % _P


def _mul(k: int):
    out, add = None, _G
    while k:
        if k & 1:
            out = _add(out, add)
        add = _add(add, add)
        k >>= 1
    return out


def addresses(first_key: int, n: int) -> List[bytes]:
    """Addresses of the private keys first_key .. first_key+n-1 (one
    scalar multiplication, then one point addition a key)."""
    out, pt = [], _mul(first_key)
    for _ in range(n):
        out.append(keccak256(pt[0].to_bytes(32, "big")
                             + pt[1].to_bytes(32, "big"))[12:])
        pt = _add(pt, _G)
    return out


# ---------------------------------------------------------------------- RLP
def _length(n: int, offset: int) -> bytes:
    if n < 56:
        return bytes([offset + n])
    size = n.to_bytes((n.bit_length() + 7) // 8, "big")
    return bytes([offset + 55 + len(size)]) + size


def rlp_bytes(b: bytes) -> bytes:
    if len(b) == 1 and b[0] < 0x80:
        return b
    return _length(len(b), 0x80) + b


def rlp_uint(n: int) -> bytes:
    return rlp_bytes(n.to_bytes((n.bit_length() + 7) // 8, "big"))


def rlp_list(encoded_items: Iterable[bytes]) -> bytes:
    payload = b"".join(encoded_items)
    return _length(len(payload), 0xC0) + payload


# ------------------------------------------- hex-prefix Merkle-Patricia root
def _hex_prefix(nibbles: Tuple[int, ...], leaf: bool) -> bytes:
    flag = 2 if leaf else 0
    if len(nibbles) % 2:
        head, rest = bytes([(flag + 1) << 4 | nibbles[0]]), nibbles[1:]
    else:
        head, rest = bytes([flag << 4]), nibbles
    return head + bytes(rest[i] << 4 | rest[i + 1]
                        for i in range(0, len(rest), 2))


def _ref(node: bytes) -> bytes:
    """A child as its parent holds it: itself when shorter than a
    hash, its hash otherwise."""
    return node if len(node) < 32 else rlp_bytes(keccak256(node))


def _node(items: List[Tuple[Tuple[int, ...], bytes]], depth: int) -> bytes:
    """RLP of the node over ``items`` (sorted, distinct, equal-length
    nibble keys that agree on their first ``depth`` nibbles)."""
    first = items[0][0]
    if len(items) == 1:
        return rlp_list([rlp_bytes(_hex_prefix(first[depth:], True)),
                         rlp_bytes(items[0][1])])
    last, shared = items[-1][0], depth
    while first[shared] == last[shared]:
        shared += 1
    if shared > depth:
        return rlp_list([rlp_bytes(_hex_prefix(first[depth:shared], False)),
                         _ref(_node(items, shared))])
    slots, lo = [], 0
    for nib in range(16):
        hi = lo
        while hi < len(items) and items[hi][0][depth] == nib:
            hi += 1
        slots.append(_ref(_node(items[lo:hi], depth + 1)) if hi > lo
                     else b"\x80")
        lo = hi
    return rlp_list(slots + [b"\x80"])  # keys are equal-length: no value


EMPTY_ROOT = keccak256(b"\x80")
EMPTY_CODE_HASH = keccak256(b"")


def trie_root(pairs: Dict[bytes, bytes]) -> bytes:
    """Root of the secure trie holding ``pairs``: keys are hashed, then
    spelled in nibbles."""
    if not pairs:
        return EMPTY_ROOT
    items = sorted((tuple(n for b in keccak256(k) for n in (b >> 4, b & 15)),
                    v) for k, v in pairs.items())
    return keccak256(_node(items, 0))


def account_rlp(nonce: int, balance: int, storage_root: bytes = EMPTY_ROOT,
                code_hash: bytes = EMPTY_CODE_HASH) -> bytes:
    """Coreth's state account: the four Ethereum fields and the
    multi-coin flag (never set here)."""
    return rlp_list([rlp_uint(nonce), rlp_uint(balance),
                     rlp_bytes(storage_root), rlp_bytes(code_hash),
                     rlp_uint(0)])


# ------------------------------------------------------------- the fee rule
GWEI = 10**9
COINBASE = bytes.fromhex("01" + "00" * 19)  # coreth's blackhole address
INITIAL_BASE_FEE = 225 * GWEI
MIN_BASE_FEE = 25 * GWEI
BASE_FEE_CHANGE_DENOMINATOR = 36


def base_fees(n_blocks: int, block_gap_s: int) -> List[int]:
    """Base fee of blocks 1..n under coreth's dynamic fee rule from
    Apricot Phase 5 on, for a chain whose blocks lie ``block_gap_s``
    seconds apart.  The rule sums the gas of a rolling 10-second window;
    at a gap of 10 s or more every block finds that window empty, so
    whatever the blocks hold the fee falls by 1/36 a block (times
    ``gap // 10`` beyond 10 s) from 225 gwei to the floor of 25."""
    if block_gap_s < 10:
        raise ValueError("below a 10 s gap the fee depends on the gas "
                         "used; this closed form does not hold")
    fees, fee = [], INITIAL_BASE_FEE
    for _ in range(n_blocks):
        fees.append(fee)
        delta = max(fee // BASE_FEE_CHANGE_DENOMINATOR, 1)
        if block_gap_s > 10:
            delta *= block_gap_s // 10
        fee = max(fee - delta, MIN_BASE_FEE)
    return fees


# ------------------------------------------------------------------ the book
class Book:
    """Nonces and native balances, account by account, as the chain's
    plain transfers leave them.  An account exists once it is funded,
    has sent or has received."""

    def __init__(self, funded: Dict[bytes, int]):
        self.nonce: Dict[bytes, int] = {a: 0 for a in funded}
        self.balance: Dict[bytes, int] = dict(funded)

    def transfer(self, src: bytes, dst: bytes, value: int, gas: int,
                 price: int) -> None:
        """One value transfer that used ``gas`` at ``price``: coreth
        credits the whole fee to the block's coinbase."""
        fee = gas * price
        if self.balance[src] < value + fee:
            raise ValueError("the plan overdraws " + src.hex())
        self.nonce[src] += 1
        self.balance[src] -= value + fee
        for addr, amount in ((dst, value), (COINBASE, fee)):
            self.balance[addr] = self.balance.get(addr, 0) + amount
            self.nonce.setdefault(addr, 0)

    def accounts(self) -> Dict[bytes, Tuple[int, int]]:
        """{address: (nonce, balance)}; an account with neither is not
        in the state (coreth deletes empty accounts)."""
        return {a: (self.nonce[a], self.balance[a]) for a in self.nonce
                if self.nonce[a] or self.balance[a]}

    def state_root(self) -> bytes:
        """The state root of a state that holds these accounts and
        nothing else: no code, no storage."""
        return trie_root({a: account_rlp(n, b)
                          for a, (n, b) in self.accounts().items()})

"""The plain reference for Uniswap V2 swaps: an AMM book in integers.

Like ``plainref`` it imports nothing of the program and takes nothing
from it.  It does not interpret bytecode: a swap is added up by the
whitepaper's formulas ("Uniswap v2 Core", Adams, Zinsmeister,
Robinson, 2020; cited from memory), in Python integers:

- ``getAmountOut``: ``in*997*rOut / (rIn*1000 + in*997)``, floor;
- the pair's K check with the 0.3% fee on the input side;
- ``_update``: reserves <= 2^112 - 1; the time mod 2^32; when time has
  passed since the last update, each price accumulator grows by the
  UQ112x112 quotient (``(r1 << 112) // r0``, a uint224) times the
  seconds elapsed, wrapping at 2^256 as the source's ``uint`` does;
- ``transferFrom`` / ``transfer``: the balances; an allowance of
  ``2^256 - 1`` is never decremented.

Nonces and native balances come from ``plainref.Book``: a swap's fee is
``gas_used x price`` to the coinbase.  ``gas_used`` is the intrinsic gas
of the calldata (EIP-2028) plus the execution gas of the path the swap
takes, one of four numbers of the configuration (``exec_gas``: input
side token0 or token1, and whether time has passed since the pair's
last update).  Nothing else moves the gas: every slot a swap writes is
nonzero before and after, every contract it reaches is cold once.

Storage keys follow Solidity's mapping rule and Coreth's storage
partition (``plainevm.slot_key``): ``balanceOf`` at slot 1,
``allowance`` at slot 2, the pair's fields at 5-12.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from benchlib import plainevm, plainref

WORD = 1 << 256
M112 = (1 << 112) - 1
MAX = WORD - 1
TRANSFER_TOPIC = plainref.keccak256(b"Transfer(address,address,uint256)")
SYNC_TOPIC = plainref.keccak256(b"Sync(uint112,uint112)")
SWAP_TOPIC = plainref.keccak256(
    b"Swap(address,uint256,uint256,uint256,uint256,address)")
SEL_SWAP_EXACT = bytes.fromhex("38ed1739")
DEADLINE_S = 1200            # the Uniswap interface's default, 20 minutes
SLIPPAGE_PERMILLE = 5        # amountOutMin: the quote less 0.5%
# the pair's slots (UniswapV2Pair after UniswapV2ERC20's five)
FACTORY, TOKEN0, TOKEN1, RESERVES, PRICE0, PRICE1, UNLOCKED = \
    5, 6, 7, 8, 9, 10, 12


def slot(n: int) -> bytes:
    return plainevm.slot_key(n)


def balance_key(holder: bytes) -> bytes:
    return plainevm.mapping_slot(holder, 1)


def allowance_key(owner: bytes, spender: bytes) -> bytes:
    inner = plainevm.keccak(b"\x00" * 12 + owner + (2).to_bytes(32, "big"))
    return plainevm.slot_key(int.from_bytes(plainevm.keccak(
        b"\x00" * 12 + spender + inner), "big"))


def _name(text: str) -> bytes:
    return plainref.keccak256(text.encode())[12:]


class Pair:
    def __init__(self, index: int, token0: bytes, token1: bytes,
                 address: bytes):
        self.index, self.token0, self.token1 = index, token0, token1
        self.address = address


class World:
    """The deployment's addresses: ``n_tokens`` tokens, token i paired
    with token i + o (mod n) for each o in ``pair_offsets`` (offsets 1
    and 2 over 16 tokens: 32 pairs, every token in 4), each pair at the
    CREATE2 address ``pairFor`` computes."""

    def __init__(self, n_tokens: int, pair_offsets: List[int],
                 factory: bytes, init_hash: bytes):
        self.factory, self.init_hash = factory, init_hash
        self.router = _name("univ2-swap router")
        self.tokens = [_name(f"univ2-swap token {i}")
                       for i in range(n_tokens)]
        self.pairs: List[Pair] = []
        self.pair_of: Dict[Tuple[bytes, bytes], Pair] = {}
        for o in pair_offsets:
            for i in range(n_tokens):
                a, b = self.tokens[i], self.tokens[(i + o) % n_tokens]
                t0, t1 = (a, b) if a < b else (b, a)
                pa = Pair(len(self.pairs), t0, t1, self.pair_for(t0, t1))
                self.pairs.append(pa)
                self.pair_of[(t0, t1)] = pa

    def pair_for(self, t0: bytes, t1: bytes) -> bytes:
        salt = plainref.keccak256(t0 + t1)
        return plainref.keccak256(b"\xff" + self.factory + salt
                                  + self.init_hash)[12:]


def amount_out(amount_in: int, r_in: int, r_out: int) -> int:
    """UniswapV2Library.getAmountOut."""
    if amount_in <= 0 or r_in <= 0 or r_out <= 0:
        raise ValueError("getAmountOut: no input or no liquidity")
    fee_in = amount_in * 997
    return fee_in * r_out // (r_in * 1000 + fee_in)


def _word(v: int) -> bytes:
    return v.to_bytes(32, "big")


def intrinsic_gas(data: bytes) -> int:
    return plainevm.intrinsic_gas(data)


class AmmBook(plainref.Book):
    """Nonces and native balances (``plainref.Book``) plus every
    token's balances, every pair's reserves, time and accumulators."""

    def __init__(self, world: World, traders: List[bytes], chain: dict,
                 funded: int):
        super().__init__({a: funded for a in traders})
        self.w, self.traders, self.c = world, traders, chain
        for a in [world.router] + world.tokens + [p.address
                                                  for p in world.pairs]:
            self.nonce[a], self.balance[a] = 1, 0
        held, reserve = chain["trader_tokens"], chain["reserve"]
        self.bal: Dict[bytes, Dict[bytes, int]] = {
            t: {a: held for a in traders} for t in world.tokens}
        self.reserves: Dict[bytes, List[int]] = {}
        self.last: Dict[bytes, int] = {}
        self.price: Dict[bytes, List[int]] = {}
        for pa in world.pairs:
            self.bal[pa.token0][pa.address] = reserve
            self.bal[pa.token1][pa.address] = reserve
            self.reserves[pa.address] = [reserve, reserve]
            self.last[pa.address] = 0
            start = chain["price_cumulative_start"]
            self.price[pa.address] = [start, start]
        self.gas_used: List[int] = []

    # ------------------------------------------------------- the state
    def token_slots(self, token: bytes) -> Dict[bytes, int]:
        """Every slot of a token: totalSupply, balances, the traders'
        allowances of the router (2^256 - 1)."""
        out = {slot(0): sum(self.bal[token].values())}
        for holder, v in self.bal[token].items():
            out[balance_key(holder)] = v
        for a in self.traders:
            out[allowance_key(a, self.w.router)] = MAX
        return out

    def pair_slots(self, pa: Pair) -> Dict[bytes, int]:
        r0, r1 = self.reserves[pa.address]
        p0, p1 = self.price[pa.address]
        return {slot(FACTORY): int.from_bytes(self.w.factory, "big"),
                slot(TOKEN0): int.from_bytes(pa.token0, "big"),
                slot(TOKEN1): int.from_bytes(pa.token1, "big"),
                slot(RESERVES): r0 | (r1 << 112)
                | (self.last[pa.address] << 224),
                slot(PRICE0): p0, slot(PRICE1): p1, slot(UNLOCKED): 1}

    def storage(self) -> Dict[bytes, Dict[bytes, int]]:
        """What the read-back compares: every balance in every token,
        every pair's fields."""
        out: Dict[bytes, Dict[bytes, int]] = {}
        for t in self.w.tokens:
            out[t] = {balance_key(h): v for h, v in self.bal[t].items()}
        for pa in self.w.pairs:
            out[pa.address] = self.pair_slots(pa)
        return out

    # --------------------------------------------------------- a swap
    def swap_data(self, p: int, side: int, trader: bytes, amount: int,
                  now: int) -> bytes:
        """The calldata of swapExactTokensForTokens over pair ``p`` from
        its token0 (side 0) or token1 (side 1), quoted against the
        reserves as they stand."""
        pa = self.w.pairs[p]
        tin, tout = (pa.token0, pa.token1) if side == 0 \
            else (pa.token1, pa.token0)
        r0, r1 = self.reserves[pa.address]
        r_in, r_out = (r0, r1) if side == 0 else (r1, r0)
        quote = amount_out(amount, r_in, r_out)
        low = quote * (1000 - SLIPPAGE_PERMILLE) // 1000
        return (SEL_SWAP_EXACT + _word(amount) + _word(low)
                + _word(0xA0) + _word(int.from_bytes(trader, "big"))
                + _word(now + DEADLINE_S) + _word(2)
                + _word(int.from_bytes(tin, "big"))
                + _word(int.from_bytes(tout, "big")))

    def swap(self, trader: bytes, data: bytes, now: int, price: int,
             gas_limit: int) -> int:
        """One swap transaction at block time ``now``; returns the gas
        it used.  A swap that would revert raises: the plan has none."""
        amount = int.from_bytes(data[4:36], "big")
        low = int.from_bytes(data[36:68], "big")
        to = data[4 + 96 + 12:4 + 128]
        deadline = int.from_bytes(data[132:164], "big")
        tin, tout = data[196 + 12:228], data[228 + 12:260]
        if deadline < now:
            raise ValueError("expired")
        t0, t1 = (tin, tout) if tin < tout else (tout, tin)
        pa = self.w.pair_of[(t0, t1)]
        r0, r1 = self.reserves[pa.address]
        zero_for_one = tin == t0
        r_in, r_out = (r0, r1) if zero_for_one else (r1, r0)
        out = amount_out(amount, r_in, r_out)
        if out < low:
            raise ValueError("insufficient output amount")
        # transferFrom(trader -> pair) and transfer(pair -> to)
        self._move(tin, trader, pa.address, amount)
        a0_out, a1_out = (0, out) if zero_for_one else (out, 0)
        if not (a0_out < r0 and a1_out < r1):
            raise ValueError("insufficient liquidity")
        self._move(tout, pa.address, to, out)
        b0 = self.bal[pa.token0][pa.address]
        b1 = self.bal[pa.token1][pa.address]
        a0_in = b0 - (r0 - a0_out) if b0 > r0 - a0_out else 0
        a1_in = b1 - (r1 - a1_out) if b1 > r1 - a1_out else 0
        if not (a0_in or a1_in):
            raise ValueError("insufficient input amount")
        adj0, adj1 = b0 * 1000 - a0_in * 3, b1 * 1000 - a1_in * 3
        if adj0 * adj1 < r0 * r1 * 1000 ** 2:
            raise ValueError("K")
        # _update
        if b0 > M112 or b1 > M112:
            raise ValueError("overflow")
        ts = now % (1 << 32)
        elapsed = (ts - self.last[pa.address]) % (1 << 32)
        if elapsed > 0 and r0 and r1:
            acc = self.price[pa.address]
            acc[0] = (acc[0] + ((r1 << 112) // r0) * elapsed) % WORD
            acc[1] = (acc[1] + ((r0 << 112) // r1) * elapsed) % WORD
        self.reserves[pa.address] = [b0, b1]
        self.last[pa.address] = ts
        path = ("in0" if zero_for_one else "in1") \
            + ("_elapsed" if elapsed else "")
        used = intrinsic_gas(data) + self.c["exec_gas"][path]
        if used > gas_limit:
            raise ValueError("the swap needs more gas than its limit")
        self.transfer(trader, self.w.router, 0, used, price)
        self.gas_used.append(used)
        return used

    def _move(self, token: bytes, src: bytes, dst: bytes,
              amount: int) -> None:
        bal = self.bal[token]
        if bal.get(src, 0) < amount:
            raise ValueError("insufficient balance")
        bal[src] -= amount
        bal[dst] = bal.get(dst, 0) + amount

    def accounts(self) -> Dict[bytes, Tuple[int, int]]:
        """Every account with a nonce or balance, contracts included
        (nonce 1, balance 0)."""
        return {a: (self.nonce[a], self.balance[a]) for a in self.nonce
                if self.nonce[a] or self.balance[a]}

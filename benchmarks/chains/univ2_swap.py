"""Uniswap V2 swaps as the C-Chain's DEXes run them (Trader Joe v1 and
Pangolin are forks of it): one ``swapExactTokensForTokens`` of one hop
a transaction, router -> pair -> two tokens.

Sources, cited from memory (no network here): "Uniswap v2 Core"
(Adams, Zinsmeister, Robinson, 2020); ``v2-core`` ``UniswapV2Pair``
(``swap``, ``_update``, ``getReserves``, the ``lock`` modifier,
``_safeTransfer``) over ``UniswapV2ERC20``'s ``transfer`` /
``transferFrom`` / ``balanceOf``; ``v2-periphery``
``UniswapV2Router02.swapExactTokensForTokens`` with
``UniswapV2Library.sortTokens`` / ``pairFor`` (the CREATE2 rule) /
``getReserves`` / ``getAmountOut`` and ``TransferHelper``.

No compiler is available here, so the three contracts are
hand-assembled with the program's shared assembler
(``coreth_tpu.workloads.erc20._assemble``), keeping the sources'
semantics and storage layout:

- token (``UniswapV2ERC20``'s layout): ``totalSupply`` slot 0,
  ``balanceOf`` the mapping at slot 1, ``allowance`` the nested mapping
  at slot 2; ``transferFrom`` skips the allowance write at ``uint(-1)``;
  SafeMath's checks; ``Transfer`` (LOG3).
- pair: ``factory`` 5, ``token0`` 6, ``token1`` 7, ``reserve0`` /
  ``reserve1`` / ``blockTimestampLast`` packed in 8, the price
  accumulators 9 and 10, ``unlocked`` 12.  ``swap`` locks (1 -> 0 ...
  0 -> 1), reads the reserves, transfers out with ``_safeTransfer``
  (CALL, return data checked), reads both balances back (EXTCODESIZE,
  STATICCALL ``balanceOf``), checks K with the 0.3% fee, and
  ``_update`` writes the reserves, the time mod 2^32 and, when time has
  passed, the UQ112x112 price accumulators; ``Sync`` (LOG1), ``Swap``
  (LOG3, four data words).  A flash swap (``data`` not empty) reverts:
  the plan never sends one.
- router: ``ensure(deadline)``, ``sortTokens``, ``pairFor`` (two
  KECCAK256s), EXTCODESIZE then STATICCALL ``getReserves``,
  ``getAmountOut`` (997/1000), ``amountOutMin``, ``safeTransferFrom``
  (CALL, return data copied and checked), EXTCODESIZE then CALL
  ``pair.swap``; returns ``amounts``.  Paths of two tokens only (one
  hop): a longer path reverts.

Six call frames a swap (getReserves, transferFrom, swap, transfer, two
balanceOf), four EXTCODESIZEs, logs from three addresses, writes into
three contracts.  No factory is deployed: the pairs sit in the genesis
allocation at their CREATE2 addresses.

The plan (pair by Zipf, direction, trader) is fixed by the
configuration's ``plan_seed``; ``--seed`` moves the traders' keys and
the amounts.  ``ledger`` adds the chain up with ``benchlib.plainamm``.
"""

from __future__ import annotations

import bisect
import random
from typing import List

from benchlib import plainamm, plainref
from benchlib.chains import first_key

M112 = (1 << 112) - 1
MASK = (1 << 160) - 1

SEL_SWAP_EXACT = 0x38ED1739      # swapExactTokensForTokens(...)
SEL_GET_RESERVES = 0x0902F1AC
SEL_SWAP = 0x022C0D9F            # swap(uint,uint,address,bytes)
SEL_TOKEN0, SEL_TOKEN1 = 0x0DFE1681, 0xD21220A7
SEL_TRANSFER, SEL_TRANSFER_FROM = 0xA9059CBB, 0x23B872DD
SEL_BALANCE_OF = 0x70A08231

_OPS = {
    "STOP": 0x00, "ADD": 0x01, "MUL": 0x02, "SUB": 0x03, "DIV": 0x04,
    "MOD": 0x06, "LT": 0x10, "GT": 0x11, "EQ": 0x14, "ISZERO": 0x15,
    "AND": 0x16, "OR": 0x17, "NOT": 0x19, "SHL": 0x1B, "SHR": 0x1C,
    "SHA3": 0x20, "ADDRESS": 0x30, "CALLER": 0x33, "CALLDATALOAD": 0x35,
    "EXTCODESIZE": 0x3B, "RETURNDATASIZE": 0x3D,
    "RETURNDATACOPY": 0x3E, "TIMESTAMP": 0x42, "POP": 0x50,
    "MLOAD": 0x51, "MSTORE": 0x52, "MSTORE8": 0x53, "SLOAD": 0x54,
    "SSTORE": 0x55, "JUMP": 0x56, "JUMPI": 0x57, "GAS": 0x5A,
    "JUMPDEST": 0x5B, "LOG1": 0xA1, "LOG3": 0xA3, "CALL": 0xF1,
    "RETURN": 0xF3, "STATICCALL": 0xFA, "REVERT": 0xFD,
    "DUP1": 0x80, "DUP2": 0x81, "DUP3": 0x82, "DUP4": 0x83,
    "DUP5": 0x84, "DUP6": 0x85, "SWAP1": 0x90, "SWAP2": 0x91,
}


def _p(v: int):
    return ("PUSH", v.to_bytes(max(1, (v.bit_length() + 7) // 8), "big"))


def _ld(off):                     # -> [mem[off]]
    return [_p(off), "MLOAD"]


def _st(off):                     # [v] -> mem[off] = v
    return [_p(off), "MSTORE"]


def _cd(off):
    return [_p(off), "CALLDATALOAD"]


def _req():                       # [cond] -> revert unless cond != 0
    return ["ISZERO", ("PUSHL", "revert"), "JUMPI"]


class _Labels:
    def __init__(self):
        self.n = 0

    def new(self, stem):
        self.n += 1
        return f"{stem}{self.n}"


def _mulchk(lb):
    """[x, y] -> [x * y], SafeMath.mul (y == 0 or z / y == x)."""
    ok = lb.new("mul")
    return ["DUP2", "DUP2", "MUL",                    # [x, y, z]
            "DUP2", "ISZERO", ("PUSHL", ok), "JUMPI",
            "DUP2", "DUP2", "DIV", "DUP4", "EQ"] + _req() + [
            ("LABEL", ok), "SWAP2", "POP", "POP"]


def _subchk():
    """[x, y] -> [x - y], SafeMath.sub (y <= x)."""
    return ["DUP2", "DUP2", "GT", ("PUSHL", "revert"), "JUMPI",
            "SWAP1", "SUB"]


def _addchk():
    """[x, y] -> [x + y], SafeMath.add (no wrap)."""
    return ["DUP2", "ADD", "DUP1", "DUP3", "GT", ("PUSHL", "revert"),
            "JUMPI", "SWAP1", "POP"]


def _dispatch(table):
    out = [_p(0), "CALLDATALOAD", _p(224), "SHR"]
    for sel, label in table:
        out += ["DUP1", _p(sel), "EQ", ("PUSHL", label), "JUMPI"]
    return out + [("LABEL", "revert"), _p(0), _p(0), "REVERT"]


def _asm(program) -> bytes:
    from coreth_tpu.workloads.erc20 import _assemble
    return _assemble(program, _OPS)


def _key(owner_off, slot):         # -> [keccak(pad32(mem[owner]) . slot)]
    return _ld(owner_off) + _st(0) + [_p(slot)] + _st(0x20) + [
        _p(0x40), _p(0), "SHA3"]


def token_code() -> bytes:
    """UniswapV2ERC20's transfer / transferFrom / balanceOf.  Memory:
    0x80 from, 0xa0 to, 0xc0 value."""
    ret_true = [_p(1)] + _st(0) + [_p(0x20), _p(0), "RETURN"]
    return _asm(_dispatch([(SEL_TRANSFER, "transfer"),
                           (SEL_TRANSFER_FROM, "transferFrom"),
                           (SEL_BALANCE_OF, "balanceOf")]) + [
        ("LABEL", "balanceOf"),
        *_cd(4), _p(MASK), "AND", *_st(0x80), *_key(0x80, 1), "SLOAD",
        *_st(0), _p(0x20), _p(0), "RETURN",

        ("LABEL", "transfer"),
        "CALLER", *_st(0x80),
        *_cd(4), _p(MASK), "AND", *_st(0xA0),
        *_cd(0x24), *_st(0xC0),
        ("PUSHL", "t_done"), ("PUSHL", "_transfer"), "JUMP",
        ("LABEL", "t_done"), *ret_true,

        ("LABEL", "transferFrom"),
        *_cd(4), _p(MASK), "AND", *_st(0x80),
        *_cd(0x24), _p(MASK), "AND", *_st(0xA0),
        *_cd(0x44), *_st(0xC0),
        # allowance[from][msg.sender]
        *_key(0x80, 2), *_st(0x20), "CALLER", *_st(0),
        _p(0x40), _p(0), "SHA3",                       # [ka]
        "DUP1", "SLOAD",                               # [ka, al]
        "DUP1", _p(0), "NOT", "EQ", ("PUSHL", "tf_skip"), "JUMPI",
        *_ld(0xC0), *_subchk(),                        # [ka, al - v]
        "SWAP1", "SSTORE", ("PUSHL", "tf_go"), "JUMP",
        ("LABEL", "tf_skip"), "POP", "POP",
        ("LABEL", "tf_go"),
        ("PUSHL", "tf_done"), ("PUSHL", "_transfer"), "JUMP",
        ("LABEL", "tf_done"), *ret_true,

        # _transfer(from, to, value): [ret] -> []
        ("LABEL", "_transfer"),
        *_key(0x80, 1), "DUP1", "SLOAD",               # [ret, kf, bf]
        *_ld(0xC0), *_subchk(),                        # [ret, kf, bf-v]
        "SWAP1", "SSTORE",
        *_key(0xA0, 1), "DUP1", "SLOAD",               # [ret, kt, bt]
        *_ld(0xC0), *_addchk(),                        # [ret, kt, bt+v]
        "SWAP1", "SSTORE",
        *_ld(0xC0), *_st(0),
        *_ld(0xA0), *_ld(0x80), ("PUSH", plainamm.TRANSFER_TOPIC),
        _p(0x20), _p(0), "LOG3",
        "JUMP",
    ])


def pair_code() -> bytes:
    """UniswapV2Pair's getReserves / swap / token0 / token1.  Memory:
    0x80 amount0Out, 0xa0 amount1Out, 0xc0 to, 0xe0 reserve0, 0x100
    reserve1, 0x120 blockTimestampLast, 0x140 token0, 0x160 token1,
    0x180 balance0, 0x1a0 balance1, 0x1c0 amount0In, 0x1e0 amount1In,
    0x200.. call scratch, 0x2a0 / 0x2c0 adjusted balances, 0x2e0 the
    time mod 2^32, 0x300 timeElapsed, 0x320.. event data."""
    lb = _Labels()
    ret32 = [*_st(0), _p(0x20), _p(0), "RETURN"]
    amount_in = []
    for out_off, res_off, bal_off, dst, stem in (
            (0x80, 0xE0, 0x180, 0x1C0, "in0"),
            (0xA0, 0x100, 0x1A0, 0x1E0, "in1")):
        # amountIn = balance > reserve - out ? balance - (reserve - out) : 0
        amount_in += [
            *_ld(out_off), *_ld(res_off), "SUB",       # [x]
            "DUP1", *_ld(bal_off), "GT", ("PUSHL", stem), "JUMPI",
            "POP", _p(0), ("PUSHL", stem + "d"), "JUMP",
            ("LABEL", stem), *_ld(bal_off), "SUB",
            ("LABEL", stem + "d"), *_st(dst)]
    adjusted = []
    for bal_off, in_off, dst in ((0x180, 0x1C0, 0x2A0),
                                 (0x1A0, 0x1E0, 0x2C0)):
        adjusted += [*_ld(bal_off), _p(1000), *_mulchk(lb),
                     *_ld(in_off), _p(3), *_mulchk(lb), *_subchk(),
                     *_st(dst)]
    price = []
    for num_off, den_off, slot in ((0x100, 0xE0, 9), (0xE0, 0x100, 10)):
        # priceCumulativeLast += uint(UQ112x112.encode(num).uqdiv(den))
        #                        * timeElapsed   (no checks: wraps)
        price += [*_ld(den_off), *_ld(num_off), _p(112), "SHL", "DIV",
                  *_ld(0x300), "MUL", _p(slot), "SLOAD", "ADD",
                  _p(slot), "SSTORE"]
    return _asm(_dispatch([(SEL_GET_RESERVES, "getReserves"),
                           (SEL_SWAP, "swap"), (SEL_TOKEN0, "token0"),
                           (SEL_TOKEN1, "token1")]) + [
        ("LABEL", "getReserves"),
        _p(8), "SLOAD", "DUP1", _p(M112), "AND", *_st(0),
        "DUP1", _p(112), "SHR", _p(M112), "AND", *_st(0x20),
        _p(224), "SHR", *_st(0x40), _p(0x60), _p(0), "RETURN",
        ("LABEL", "token0"), _p(6), "SLOAD", *ret32,
        ("LABEL", "token1"), _p(7), "SLOAD", *ret32,

        ("LABEL", "swap"),
        _p(12), "SLOAD", _p(1), "EQ", *_req(),          # lock
        _p(0), _p(12), "SSTORE",
        *_cd(4), *_st(0x80), *_cd(0x24), *_st(0xA0),
        *_cd(0x44), _p(MASK), "AND", *_st(0xC0),
        *_cd(0x64), _p(4), "ADD", "CALLDATALOAD", "ISZERO", *_req(),
        *_ld(0x80), *_ld(0xA0), "OR", *_req(),
        _p(8), "SLOAD", "DUP1", _p(M112), "AND", *_st(0xE0),
        "DUP1", _p(112), "SHR", _p(M112), "AND", *_st(0x100),
        _p(224), "SHR", *_st(0x120),
        *_ld(0xE0), *_ld(0x80), "LT", *_req(),
        *_ld(0x100), *_ld(0xA0), "LT", *_req(),
        _p(6), "SLOAD", *_st(0x140), _p(7), "SLOAD", *_st(0x160),
        *_ld(0x140), *_ld(0xC0), "EQ", "ISZERO", *_req(),
        *_ld(0x160), *_ld(0xC0), "EQ", "ISZERO", *_req(),
        *_ld(0x80), "ISZERO", ("PUSHL", "sw_no0"), "JUMPI",
        ("PUSHL", "sw_no0"), *_ld(0x140), *_ld(0x80),
        ("PUSHL", "_safeT"), "JUMP",
        ("LABEL", "sw_no0"),
        *_ld(0xA0), "ISZERO", ("PUSHL", "sw_no1"), "JUMPI",
        ("PUSHL", "sw_no1"), *_ld(0x160), *_ld(0xA0),
        ("PUSHL", "_safeT"), "JUMP",
        ("LABEL", "sw_no1"),
        ("PUSHL", "b0d"), *_ld(0x140), ("PUSHL", "_bal"), "JUMP",
        ("LABEL", "b0d"), *_st(0x180),
        ("PUSHL", "b1d"), *_ld(0x160), ("PUSHL", "_bal"), "JUMP",
        ("LABEL", "b1d"), *_st(0x1A0),
        *amount_in,
        *_ld(0x1C0), *_ld(0x1E0), "OR", *_req(),
        *adjusted,
        # K: balance0Adjusted * balance1Adjusted >= r0 * r1 * 1000**2
        *_ld(0x2A0), *_ld(0x2C0), *_mulchk(lb),
        *_ld(0xE0), *_ld(0x100), *_mulchk(lb), _p(1000000),
        *_mulchk(lb), "SWAP1", "LT", ("PUSHL", "revert"), "JUMPI",
        # _update
        *_ld(0x180), _p(M112), "LT", ("PUSHL", "revert"), "JUMPI",
        *_ld(0x1A0), _p(M112), "LT", ("PUSHL", "revert"), "JUMPI",
        _p(1 << 32), "TIMESTAMP", "MOD", *_st(0x2E0),
        *_ld(0x120), *_ld(0x2E0), "SUB", _p(0xFFFFFFFF), "AND",
        *_st(0x300),
        *_ld(0x300), "ISZERO", ("PUSHL", "upd"), "JUMPI",
        *_ld(0xE0), "ISZERO", ("PUSHL", "upd"), "JUMPI",
        *_ld(0x100), "ISZERO", ("PUSHL", "upd"), "JUMPI",
        *price,
        ("LABEL", "upd"),
        *_ld(0x2E0), _p(224), "SHL", *_ld(0x1A0), _p(112), "SHL", "OR",
        *_ld(0x180), "OR", _p(8), "SSTORE",
        *_ld(0x180), *_st(0x320), *_ld(0x1A0), *_st(0x340),
        ("PUSH", plainamm.SYNC_TOPIC), _p(0x40), _p(0x320), "LOG1",
        *_ld(0x1C0), *_st(0x320), *_ld(0x1E0), *_st(0x340),
        *_ld(0x80), *_st(0x360), *_ld(0xA0), *_st(0x380),
        *_ld(0xC0), "CALLER", ("PUSH", plainamm.SWAP_TOPIC),
        _p(0x80), _p(0x320), "LOG3",
        _p(1), _p(12), "SSTORE",                        # unlock
        "STOP",

        # _safeTransfer(token, to = mem[0xc0], value): [ret, token, v]
        ("LABEL", "_safeT"),
        _p(SEL_TRANSFER << 224), *_st(0x200), *_ld(0xC0), *_st(0x204),
        *_st(0x224),                                    # [ret, token]
        _p(0), _p(0), _p(0x44), _p(0x200), _p(0), "DUP6", "GAS", "CALL",
        *_req(), "POP",                                 # [ret]
        "RETURNDATASIZE", "DUP1", "ISZERO", ("PUSHL", "st_ok"), "JUMPI",
        "DUP1", _p(0x20), "GT", ("PUSHL", "revert"), "JUMPI",
        "DUP1", _p(0), _p(0x240), "RETURNDATACOPY",
        _p(0x240), "MLOAD", *_req(),
        ("LABEL", "st_ok"), "POP", "JUMP",

        # IERC20(token).balanceOf(address(this)): [ret, token] -> [bal]
        ("LABEL", "_bal"),
        "DUP1", "EXTCODESIZE", *_req(),
        _p(SEL_BALANCE_OF << 224), *_st(0x200), "ADDRESS", *_st(0x204),
        _p(0x20), _p(0x200), _p(0x24), _p(0x200), "DUP5", "GAS",
        "STATICCALL", *_req(), "POP",
        _p(0x20), "RETURNDATASIZE", "LT", ("PUSHL", "revert"), "JUMPI",
        _p(0x200), "MLOAD", "SWAP1", "JUMP",
    ])


def router_code(factory: bytes, init_hash: bytes) -> bytes:
    """UniswapV2Router02.swapExactTokensForTokens, paths of two.
    Memory: 0x200 path[0], 0x220 path[1], 0x240 token0, 0x260 token1,
    0x280 pair, 0x2a0 amountIn, 0x2c0 amountOut, 0x2e0 reserveIn, 0x300
    reserveOut, 0x320.. return data."""
    lb = _Labels()
    return _asm(_dispatch([(SEL_SWAP_EXACT, "swapExact")]) + [
        ("LABEL", "swapExact"),
        "TIMESTAMP", *_cd(0x84), "LT", ("PUSHL", "revert"), "JUMPI",
        *_cd(0x44), _p(4), "ADD", "DUP1", "CALLDATALOAD", _p(2), "EQ",
        *_req(),
        "DUP1", _p(0x20), "ADD", "CALLDATALOAD", _p(MASK), "AND",
        *_st(0x200),
        _p(0x40), "ADD", "CALLDATALOAD", _p(MASK), "AND", *_st(0x220),
        # sortTokens
        *_ld(0x220), *_ld(0x200), "EQ", "ISZERO", *_req(),
        *_ld(0x220), *_ld(0x200), "LT", ("PUSHL", "ab"), "JUMPI",
        *_ld(0x220), *_st(0x240), *_ld(0x200), *_st(0x260),
        ("PUSHL", "srt"), "JUMP",
        ("LABEL", "ab"), *_ld(0x200), *_st(0x240), *_ld(0x220),
        *_st(0x260),
        ("LABEL", "srt"), *_ld(0x240), "ISZERO", "ISZERO", *_req(),
        # pairFor: keccak(0xff . factory . keccak(token0 . token1) .
        # init code hash)[12:]
        *_ld(0x240), _p(96), "SHL", *_st(0), *_ld(0x260), _p(96), "SHL",
        *_st(0x14), _p(0x28), _p(0), "SHA3",
        _p(0xFF), _p(0), "MSTORE8",
        ("PUSH", factory), _p(96), "SHL", *_st(0x01), *_st(0x15),
        ("PUSH", init_hash), *_st(0x35),
        _p(0x55), _p(0), "SHA3", _p(MASK), "AND", *_st(0x280),
        # getReserves
        *_ld(0x280), "EXTCODESIZE", *_req(),
        _p(SEL_GET_RESERVES << 224), *_st(0),
        _p(0x60), _p(0x80), _p(4), _p(0), *_ld(0x280), "GAS",
        "STATICCALL", *_req(),
        _p(0x60), "RETURNDATASIZE", "LT", ("PUSHL", "revert"), "JUMPI",
        *_ld(0x80), _p(M112), "AND", *_ld(0xA0), _p(M112), "AND",
        *_ld(0x240), *_ld(0x200), "EQ", ("PUSHL", "in0"), "JUMPI",
        "SWAP1",
        ("LABEL", "in0"), *_st(0x300), *_st(0x2E0),
        # getAmountOut
        *_cd(4), *_st(0x2A0),
        *_ld(0x2A0), "ISZERO", "ISZERO", *_req(),
        *_ld(0x2E0), "ISZERO", "ISZERO", *_req(),
        *_ld(0x300), "ISZERO", "ISZERO", *_req(),
        *_ld(0x2A0), _p(997), *_mulchk(lb),            # [aFee]
        "DUP1", *_ld(0x300), *_mulchk(lb),             # [aFee, num]
        "SWAP1", *_ld(0x2E0), _p(1000), *_mulchk(lb),  # [num, aFee, rIn*1000]
        *_addchk(),                                    # [num, den]
        "SWAP1", "DIV",                                # [out]
        "DUP1", *_st(0x2C0),
        *_cd(0x24), "GT", ("PUSHL", "revert"), "JUMPI",
        # TransferHelper.safeTransferFrom(path[0], msg.sender, pair, in)
        _p(SEL_TRANSFER_FROM << 224), *_st(0), "CALLER", *_st(4),
        *_ld(0x280), *_st(0x24), *_ld(0x2A0), *_st(0x44),
        _p(0), _p(0), _p(0x64), _p(0), _p(0), *_ld(0x200), "GAS", "CALL",
        *_req(),
        "RETURNDATASIZE", "DUP1", "ISZERO", ("PUSHL", "tfok"), "JUMPI",
        "DUP1", _p(0x20), "GT", ("PUSHL", "revert"), "JUMPI",
        "DUP1", _p(0), _p(0x320), "RETURNDATACOPY",
        _p(0x320), "MLOAD", *_req(),
        ("LABEL", "tfok"), "POP",
        # _swap: pair.swap(amount0Out, amount1Out, to, new bytes(0))
        _p(SEL_SWAP << 224), *_st(0),
        *_ld(0x240), *_ld(0x200), "EQ", ("PUSHL", "z0"), "JUMPI",
        *_ld(0x2C0), *_st(4), _p(0), *_st(0x24), ("PUSHL", "zd"), "JUMP",
        ("LABEL", "z0"), _p(0), *_st(4), *_ld(0x2C0), *_st(0x24),
        ("LABEL", "zd"), *_cd(0x64), _p(MASK), "AND", *_st(0x44),
        _p(0x80), *_st(0x64), _p(0), *_st(0x84),
        *_ld(0x280), "EXTCODESIZE", *_req(),
        _p(0), _p(0), _p(0xA4), _p(0), _p(0), *_ld(0x280), "GAS", "CALL",
        *_req(),
        # returns (uint[] amounts)
        _p(0x20), *_st(0x320), _p(2), *_st(0x340), *_ld(0x2A0),
        *_st(0x360), *_ld(0x2C0), *_st(0x380), _p(0x80), _p(0x320),
        "RETURN",
    ])


# ------------------------------------------------------------------ shape
def world(config) -> plainamm.World:
    """Addresses and the pair graph of the configuration."""
    c = config["chain"]
    return plainamm.World(
        n_tokens=c["tokens"], pair_offsets=c["pair_offsets"],
        factory=bytes.fromhex(c["factory"]),
        init_hash=bytes.fromhex(c["init_code_hash"]))


def _zipf_cdf(n: int, theta: float) -> List[float]:
    w = [1.0 / (k + 1) ** theta for k in range(n)]
    total, acc, out = sum(w), 0.0, []
    for x in w:
        acc += x
        out.append(acc / total)
    return out


def _plan(config, seed):
    """Block ``i``'s swaps as (pair index, input side, trader index,
    amountIn); the pair (Zipf over the pairs in configuration order),
    the side and the trader from ``plan_seed`` alone, the amounts from
    ``--seed``.  Blocks are asked for in order, each once."""
    c = config["chain"]
    shape = random.Random(c["plan_seed"])
    amounts = random.Random(seed)
    cdf = _zipf_cdf(len(world(config).pairs), c["zipf_theta"])

    def block(_i):
        rows = []
        for _ in range(config["txs_per_block"]):
            p = min(bisect.bisect_left(cdf, shape.random()), len(cdf) - 1)
            side = shape.randrange(2)
            trader = shape.randrange(c["accounts"])
            rows.append((p, side, trader,
                         amounts.randrange(1, c["max_amount_in"])))
        return rows

    return block


def genesis(config, traffic, seed):
    from coreth_tpu.chain import Genesis, GenesisAccount
    from coreth_tpu.crypto.secp256k1 import priv_to_address
    from coreth_tpu.params import TEST_CHAIN_CONFIG
    c = config["chain"]
    w = world(config)
    keys = [first_key(config, seed) + i for i in range(c["accounts"])]
    traders = [priv_to_address(k) for k in keys]
    book = plainamm.AmmBook(w, traders, c, c["funded"])
    alloc = {a: GenesisAccount(balance=c["funded"]) for a in traders}
    word = lambda v: v.to_bytes(32, "big")  # noqa: E731
    alloc[w.router] = GenesisAccount(
        code=router_code(w.factory, w.init_hash), nonce=1)
    tok = token_code()
    for t in w.tokens:
        alloc[t] = GenesisAccount(code=tok, nonce=1, storage={
            k: word(v) for k, v in book.token_slots(t).items()})
    pc = pair_code()
    for pa in w.pairs:
        alloc[pa.address] = GenesisAccount(code=pc, nonce=1, storage={
            k: word(v) for k, v in book.pair_slots(pa).items()})
    return Genesis(config=TEST_CHAIN_CONFIG, gas_limit=c["gas_limit"],
                   alloc=alloc), {"keys": keys, "traders": traders}


def gen(config, traffic, seed, genesis, state, alter=None):
    from coreth_tpu.types import DynamicFeeTx, sign_tx
    c, cid = config["chain"], genesis.config.chain_id
    keys, traders = state["keys"], state["traders"]
    w = world(config)
    # the builder's own quote: the book run ahead of the chain
    book = plainamm.AmmBook(w, traders, c, c["funded"])
    nonces = [0] * len(keys)
    plan = _plan(config, seed)
    fees = plainref.base_fees(config["chain_blocks"], c["block_gap_s"])

    def block(i, bg):
        t = (i + 1) * c["block_gap_s"]
        price = min(c["fee_cap"], fees[i] + c["tip_cap"])
        for j, (p, side, trader, amount) in enumerate(plan(i)):
            if alter == (i, j):
                amount += 1
            data = book.swap_data(p, side, traders[trader], amount, t)
            book.swap(traders[trader], data, t, price, c["tx_gas"])
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=cid, nonce=nonces[trader],
                gas_tip_cap_=c["tip_cap"], gas_fee_cap_=c["fee_cap"],
                gas=c["tx_gas"], to=w.router, value=0, data=data),
                keys[trader], cid))
            nonces[trader] += 1

    return block


def ledger(config, traffic, seed):
    c = config["chain"]
    traders = plainref.addresses(first_key(config, seed), c["accounts"])
    w = world(config)
    book = plainamm.AmmBook(w, traders, c, c["funded"])
    plan = _plan(config, seed)
    fees = plainref.base_fees(config["chain_blocks"], c["block_gap_s"])
    for i, base_fee in enumerate(fees):
        t = (i + 1) * c["block_gap_s"]
        price = min(c["fee_cap"], base_fee + c["tip_cap"])
        for p, side, trader, amount in plan(i):
            data = book.swap_data(p, side, traders[trader], amount, t)
            book.swap(traders[trader], data, t, price, c["tx_gas"])
    return book


def read_back(engine, book) -> dict:
    """Every number of the book under the root the engine committed:
    nonce and native balance of every account (traders, the router,
    tokens, pairs, the coinbase), every trader's and every pair's
    balance in every token, every pair's packed reserves and time, its
    two price accumulators, its token0 / token1 and its lock."""
    from coreth_tpu.state import StateDB
    engine.commit()
    sdb = StateDB(engine.root, engine.db)
    wrong: List[dict] = []
    compared = 0
    for addr, (nonce, balance) in book.accounts().items():
        got = (sdb.get_nonce(addr), sdb.get_balance(addr))
        compared += 1
        if got != (nonce, balance):
            wrong.append({"addr": addr.hex(), "got": got,
                          "want": (nonce, balance)})
    for contract, slots in book.storage().items():
        for key, want in slots.items():
            got = int.from_bytes(sdb.get_state(contract, key), "big")
            compared += 1
            if got != want:
                wrong.append({"contract": contract.hex(),
                              "key": key.hex(), "got": got,
                              "want": want})
    return {"compared": compared, "wrong": wrong, "root": None}

"""The arithmetic under the native sender batch's ladder, held to Python
integers mod p and mod n (native/secp256k1.cc).

Field elements are five 52-bit limbs with lazy reduction: a sum, a
negation or a small multiple carries nothing, and a value is any
representative of its residue up to its MAGNITUDE m (limbs 0-3 at most
2m(2^52 - 1), limb 4 at most 2m(2^48 - 1)).  Inversions mod p and mod n
are variable-time safegcd.  These tests pin:

- every field operation through the test-only ``coreth_test_fe_op``, on
  raw limbs: the residue it answers, and that its result stays inside
  the magnitude the point formulas count on — at the edges (0, 1, p - 1,
  p, p + 1, 2^256 - 1, every limb at its top) and on seeded random limbs
  at the largest magnitude each operation is given;
- the scalar inversion through ``coreth_test_sc_inv``;
- the batch entries at the sizes where their shape changes (one
  signature; the 16 a hardware thread where ``in_chunks`` starts
  threads), and on edge signatures: every valid lane answered by the
  fast path, ``ok == 1``, never the sequential fallback's 2.
"""

import ctypes
import itertools
import os
import random
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import pytest

from coreth_tpu.crypto import native
from coreth_tpu.crypto import secp256k1 as S
from coreth_tpu.crypto.keccak import keccak256_py
from coreth_tpu.types import DynamicFeeTx, sign_tx

pytestmark = pytest.mark.skipif(
    native.load() is None, reason="no native library")

P, N = S.P, S.N
M52, M48 = (1 << 52) - 1, (1 << 48) - 1
CID = 43112

OP = {"mul": 0, "sqr": 1, "add": 2, "negate": 3, "mul_int": 4,
      "normalize_weak": 5, "normalize": 6, "normalizes_to_zero": 7,
      "inv": 8, "sqrt": 9}


def limbs(x):
    """x (below 2^260) as five 52-bit limbs, the top one unmasked."""
    return [(x >> 52 * i) & M52 for i in range(4)] + [x >> 208]


def value(ls):
    return sum(v << 52 * i for i, v in enumerate(ls))


def top(m):
    """Every limb at its top for magnitude m."""
    return [2 * m * M52] * 4 + [2 * m * M48]


def within(ls, m):
    return all(v <= 2 * m * M52 for v in ls[:4]) and ls[4] <= 2 * m * M48


def rand_limbs(rng, m):
    return [rng.randint(0, 2 * m * M52) for _ in range(4)] \
        + [rng.randint(0, 2 * m * M48)]


def fe_op(op, a, b=(0,) * 5, k=0):
    Limbs = ctypes.c_uint64 * 5
    out = Limbs()
    native.load().coreth_test_fe_op(OP[op], Limbs(*a), Limbs(*b), k, out)
    return list(out)


EDGES = {
    "0": limbs(0), "1": limbs(1), "p-1": limbs(P - 1), "p": limbs(P),
    "p+1": limbs(P + 1), "2^256-1": limbs((1 << 256) - 1),
    "2^256-1 mod p": limbs(((1 << 256) - 1) % P), "top 1": top(1),
}


def _cases():
    """name -> (op, a, b, k): each operation at its edges and at the
    largest magnitude the point formulas give it."""
    rng = random.Random(0x5EC9)
    c = {}
    for (na, a), (nb, b) in itertools.combinations_with_replacement(
            EDGES.items(), 2):
        c[f"mul {na} x {nb}"] = ("mul", a, b, 0)
    for na, a in EDGES.items():
        c[f"sqr {na}"] = ("sqr", a, (0,) * 5, 0)
    c["mul top 8 x top 8"] = ("mul", top(8), top(8), 0)
    c["sqr top 8"] = ("sqr", top(8), (0,) * 5, 0)
    for i in range(6):
        c[f"mul random 8 #{i}"] = ("mul", rand_limbs(rng, 8),
                                   rand_limbs(rng, 8), 0)
        c[f"sqr random 8 #{i}"] = ("sqr", rand_limbs(rng, 8), (0,) * 5, 0)
    # pt_double's nx before its weak normalization: 1 + 5 + 5
    c["add top 1 + top 5"] = ("add", top(1), top(5), 0)
    c["add top 6 + top 5"] = ("add", top(6), top(5), 0)
    for m in (1, 2, 4, 8):
        c[f"negate top {m}"] = ("negate", top(m), (0,) * 5, m)
        c[f"negate 0 at {m}"] = ("negate", limbs(0), (0,) * 5, m)
        c[f"negate random {m}"] = ("negate", rand_limbs(rng, m), (0,) * 5, m)
    for k, m in ((2, 1), (3, 1), (4, 1), (8, 1), (2, 4), (3, 2)):
        c[f"mul_int {k} top {m}"] = ("mul_int", top(m), (0,) * 5, k)
    for name, a in [*EDGES.items(), ("top 11", top(11)),
                    ("top 32", top(32)),
                    ("random 32", rand_limbs(rng, 32)),
                    ("random 11", rand_limbs(rng, 11))]:
        c[f"normalize_weak {name}"] = ("normalize_weak", a, (0,) * 5, 0)
        c[f"normalize {name}"] = ("normalize", a, (0,) * 5, 0)
    zeros = {"0": limbs(0), "p": limbs(P), "2p": limbs(2 * P),
             "16p": limbs(16 * P)}
    # the low limb of p + 2^52 is p's: the quick exit cannot decide it
    nonzero = {"1": limbs(1), "p-1": limbs(P - 1), "p+1": limbs(P + 1),
               "2^52": limbs(1 << 52), "p + 2^52": limbs(P + (1 << 52)),
               "2^256-1": limbs((1 << 256) - 1), "top 1": top(1),
               "top 11": top(11)}
    for name, a in [*zeros.items(), *nonzero.items()]:
        c[f"normalizes_to_zero {name}"] = ("normalizes_to_zero", a,
                                           (0,) * 5, 0)
    for name, a in [*EDGES.items(), ("2", limbs(2)), ("top 8", top(8)),
                    ("random 8", rand_limbs(rng, 8)),
                    ("random 1", limbs(rng.randrange(P)))]:
        c[f"inv {name}"] = ("inv", a, (0,) * 5, 0)
    gx3 = (pow(S.Gx, 3, P) + 7) % P
    for name, a in [("0", limbs(0)), ("1", limbs(1)), ("4", limbs(4)),
                    ("Gx^3 + 7", limbs(gx3)),
                    ("Gx^3 + 7 as top-limb magnitude 2",
                     limbs(gx3 + P)),
                    ("random square", limbs(pow(rng.randrange(P), 2, P))),
                    ("random", limbs(rng.randrange(P))),
                    ("top 8", top(8))]:
        c[f"sqrt {name}"] = ("sqrt", a, (0,) * 5, 0)
    return c


FE_CASES = _cases()


@pytest.mark.parametrize("case", list(FE_CASES))
def test_field_op_matches_python_integers(case):
    op, a, b, k = FE_CASES[case]
    out = fe_op(op, a, b, k)
    va, vb, vo = value(a), value(b), value(out)
    if op == "mul":
        assert vo % P == va * vb % P and within(out, 1)
    elif op == "sqr":
        assert vo % P == va * va % P and within(out, 1)
    elif op == "add":
        assert out == [x + y for x, y in zip(a, b)]
    elif op == "negate":
        assert (vo + va) % P == 0 and within(out, k + 1)
    elif op == "mul_int":
        assert out == [k * x for x in a]
    elif op == "normalize_weak":
        assert vo % P == va % P and within(out, 1)
    elif op == "normalize":
        assert vo == va % P and out == limbs(vo) and out[4] <= M48
    elif op == "normalizes_to_zero":
        assert out[0] == (va % P == 0)
    elif op == "inv":
        want = pow(va % P, -1, P) if va % P else 0
        assert vo == want
    elif op == "sqrt":
        assert vo % P == pow(va % P, (P + 1) // 4, P) and within(out, 1)


SC_CASES = {
    "0": 0, "1": 1, "2": 2, "n-1": N - 1, "n/2": N // 2,
    "n/2 + 1": N // 2 + 1, "2^255": 1 << 255, "2^128 + 1": (1 << 128) + 1,
    **{f"random #{i}": random.Random(i).randrange(1, N) for i in range(6)},
}


@pytest.mark.parametrize("case", list(SC_CASES))
def test_scalar_inverse_matches_python_integers(case):
    a = SC_CASES[case]
    out = ctypes.create_string_buffer(32)
    native.load().coreth_test_sc_inv(a.to_bytes(32, "big"), out)
    assert int.from_bytes(out.raw, "big") == (pow(a, -1, N) if a else 0)


# ------------------------------------------------------------ the batch

THREADS = os.cpu_count() or 1
SIZES = sorted({1, 15, 16, 17, 16 * THREADS - 1, 16 * THREADS,
                16 * THREADS + 1})


@pytest.fixture(scope="module")
def signed():
    """max(SIZES) signed transactions of distinct keys: (wire, r, s,
    recid, address) each."""
    rows = []
    for i in range(max(SIZES)):
        key = 0x5EC0000 + 7919 * i
        tx = sign_tx(DynamicFeeTx(
            chain_id_=CID, nonce=i, gas_tip_cap_=1, gas_fee_cap_=10**11,
            gas=21_000, to=bytes([i % 251]) * 20, value=i), key, CID)
        r, s, recid = tx.inner.raw_signature()
        rows.append((tx.encode(), r, s, recid, S.priv_to_address(key)))
    return rows


def _batch(lanes):
    """coreth_ecrecover_batch over (hash, r, s, recid) lanes."""
    return native.recover_addresses_batch(
        b"".join(h for h, _, _, _ in lanes),
        b"".join(r.to_bytes(32, "big") for _, r, _, _ in lanes),
        b"".join(s.to_bytes(32, "big") for _, _, s, _ in lanes),
        bytes(v for _, _, _, v in lanes))


@pytest.mark.parametrize("entry", ["batch", "wire"])
@pytest.mark.parametrize("n", SIZES)
def test_batch_sizes_recover_every_lane_on_the_fast_path(signed, n, entry):
    """Each size the batch changes shape at: one signature, the 16 a
    hardware thread where it starts threads, one either side."""
    rows = signed[:n]
    if entry == "wire":
        wires = [w for w, *_ in rows]
        out, ok = native.recover_senders_wire(
            b"".join(wires),
            list(itertools.accumulate(map(len, wires), initial=0)), CID)
    else:
        lanes = [(keccak256_py(bytes([i % 256, i // 256])), r, s, v)
                 for i, (_, r, s, v, _) in enumerate(rows)]
        out, ok = _batch(lanes)
        want = [S.recover_address_py(*lane) for lane in lanes]
    assert ok == b"\x01" * n
    for i in range(n):
        expect = rows[i][4] if entry == "wire" else want[i]
        assert out[20 * i:20 * i + 20] == expect, i


def _x_past_n(recid):
    """A lane whose R has x = r + n (below p): not made by signing."""
    for t in itertools.count(1):
        x = N + t
        if x >= P:
            raise AssertionError("no x in [n, p) on the curve")
        ysq = (pow(x, 3, P) + 7) % P
        if pow(ysq, (P + 1) // 4, P) ** 2 % P == ysq:
            return (keccak256_py(b"past n"), t, 0x1234567 * t % N, recid)


def _non_residue():
    for r in itertools.count(2):
        ysq = (pow(r, 3, P) + 7) % P
        if pow(ysq, (P + 1) // 4, P) ** 2 % P != ysq:
            return (keccak256_py(b"no point"), r, 5, 0)


_H = keccak256_py(b"edge")
_R, _S, _V = S.sign(_H, 0xED6E)

EDGE_SIGS = {
    "recid 2, r + n < p": lambda: _x_past_n(2),
    "recid 3, r + n < p": lambda: _x_past_n(3),
    "recid 2, r + n past p": lambda: (_H, _R, _S, 2),
    "s = n / 2": lambda: (_H, _R, N // 2, _V),
    "s = n / 2 + 1": lambda: (_H, _R, N // 2 + 1, _V),
    "s = n - 1": lambda: (_H, _R, N - 1, _V),
    "r = n - 1": lambda: (_H, N - 1, _S, 0),
    "r = n - 1, odd y": lambda: (_H, N - 1, _S, 1),
    "hash of zeros": lambda: (bytes(32), _R, _S, _V),
    "hash = n": lambda: (N.to_bytes(32, "big"), _R, _S, _V),
    "hash = 2^256 - 1": lambda: (b"\xff" * 32, _R, _S, _V),
    "r = 0": lambda: (_H, 0, _S, _V),
    "s = 0": lambda: (_H, _R, 0, _V),
    "r = n": lambda: (_H, N, _S, _V),
    "recid 4": lambda: (_H, _R, _S, 4),
    "r no x on the curve": _non_residue,
}


@pytest.mark.parametrize("case", list(EDGE_SIGS))
def test_edge_signature_is_answered_as_python_answers(case):
    """An edge lane between two valid ones: ok 1 and Python's address
    where Python recovers, ok 0 where it refuses; its neighbours ok 1."""
    lane = EDGE_SIGS[case]()
    try:
        want = S.recover_address_py(*lane)
    except ValueError:
        want = None
    good = (_H, _R, _S, _V)
    out, ok = _batch([good, lane, good])
    assert ok == bytes([1, 1 if want else 0, 1])
    if want:
        assert out[20:40] == want
    assert out[:20] == out[40:] == S.recover_address_py(*good)

"""Crypto foundation tests: keccak (py / native / device) and secp256k1.

Anchored on well-known public vectors:
  - keccak256("")    = c5d246...5a470 (the EVM empty-code hash / empty trie leaf)
  - keccak256("abc") = 4e0365...d6c45
  - privkey 1 -> address 0x7E5F4552091A69125d5DfCb7b8C2659029395Bdf
"""

import os

import numpy as np
import pytest

from coreth_tpu.crypto import keccak as K
from coreth_tpu.crypto import secp256k1 as S

V_EMPTY = bytes.fromhex(
    "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")
V_ABC = bytes.fromhex(
    "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45")


def test_keccak_known_vectors():
    assert K.keccak256_py(b"") == V_EMPTY
    assert K.keccak256_py(b"abc") == V_ABC


def test_keccak_multiblock():
    # exercise rate-block boundaries; digests must be 32B and all distinct
    seen = set()
    for n in (0, 1, 55, 56, 135, 136, 137, 272, 300):
        d = K.keccak256_py(bytes([i % 256 for i in range(n)]))
        assert len(d) == 32
        seen.add(d)
    assert len(seen) == 9


def test_keccak_native_matches_python():
    from coreth_tpu.crypto import native
    if native.load() is None:
        pytest.skip("native lib unavailable")
    for n in (0, 1, 31, 32, 64, 135, 136, 137, 500):
        msg = bytes([(i * 7 + 3) % 256 for i in range(n)])
        assert native.keccak256_native(msg) == K.keccak256_py(msg)


def test_keccak_device_fixed():
    from coreth_tpu.ops import keccak as DK
    msgs = [bytes([(i + j) % 256 for i in range(64)]) for j in range(5)]
    words = DK.pack_fixed(msgs, 64)
    out = np.asarray(DK.keccak256_fixed(words, 64))
    got = DK.digest_words_to_bytes(out)
    for m, d in zip(msgs, got):
        assert d == K.keccak256_py(m)


def test_keccak_device_blocks_variable_length():
    from coreth_tpu.ops import keccak as DK
    msgs = [b"", b"abc", bytes(136), bytes([i % 256 for i in range(137)]),
            bytes([i % 251 for i in range(400)])]
    blocks, nblocks = DK.pack_blocks(msgs)
    out = np.asarray(DK.keccak256_blocks(blocks, nblocks))
    got = DK.digest_words_to_bytes(out)
    for m, d in zip(msgs, got):
        assert d == K.keccak256_py(m)
    assert got[0] == V_EMPTY


def test_secp256k1_known_address():
    assert S.priv_to_address(1).hex() == "7e5f4552091a69125d5dfcb7b8c2659029395bdf"


def test_secp256k1_curve_sanity():
    assert S._on_curve(S.Gx, S.Gy)
    # n*G == infinity
    assert S._jac_mul((S.Gx, S.Gy, 1), S.N) is None


def test_sign_recover_roundtrip():
    for priv in (1, 2, 0xDEADBEEF, S.N - 2):
        for msg in (b"\x01" * 32, K.keccak256_py(b"hello")):
            r, s, recid = S.sign(msg, priv)
            assert s <= S.N // 2
            addr = S.recover_address_py(msg, r, s, recid)
            assert addr == S.priv_to_address(priv)


def test_recover_rejects_invalid():
    with pytest.raises(ValueError):
        S.recover_pubkey(b"\x00" * 32, 0, 1, 0)
    with pytest.raises(ValueError):
        S.recover_pubkey(b"\x00" * 32, S.N, 1, 0)


def test_native_keccak_batch_matches_singles():
    """coreth_keccak256_batch (fixed-stride packed hashing) must agree
    with per-item keccak256 across ragged lengths incl. the 136-byte
    rate boundary."""
    from coreth_tpu.crypto import keccak, native
    if native.load() is None:
        pytest.skip("native lib unavailable")
    stride = 144
    lens = [0, 1, 55, 135, 136, 137, 144]
    data = bytearray()
    for i, ln in enumerate(lens):
        item = bytes((i + j) % 256 for j in range(ln))
        data += item + b"\x00" * (stride - ln)
    out = native.keccak256_batch(bytes(data), lens, stride)
    for i, ln in enumerate(lens):
        item = bytes(data[i * stride:i * stride + ln])
        assert out[32 * i:32 * i + 32] == keccak.keccak256_py(item), ln


def test_native_fe_mul_carry_band():
    """Regression: fe_mul's folds of the high columns can carry past bit
    256; the dropped 2^256 must come back as 2^256 - p (mod p).  Driven
    through the raw-limb test entry (tests/test_secp_field.py has the
    rest of the field arithmetic)."""
    import ctypes
    from coreth_tpu.crypto import native
    if native.load() is None:
        pytest.skip("native lib unavailable")
    lib = native.load()  # loader declares coreth_test_fe_op argtypes
    limbs = ctypes.c_uint64 * 5

    def fe(x):
        return limbs(*[(x >> 52 * i) & (2**52 - 1) for i in range(4)],
                     x >> 208)

    cases = [
        (0x200000000000000000000000000000000000000000000000000000003,
         0xDEBC32AB94B43FABCB3D33BEF15F01B6BB5DC8A5F93BB2A187AAE89CD3297E01),
        (S.P - 1, S.P - 1),
        (S.P - 1, 2),
        (2**255, 2**255),
        (0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF, 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFF),
    ]
    for a, b in cases:
        out = limbs()
        lib.coreth_test_fe_op(0, fe(a), fe(b), 0, out)
        got = sum(v << 52 * i for i, v in enumerate(out))
        assert got % S.P == (a * b) % S.P, (hex(a), hex(b))


def test_native_recover_matches_python():
    from coreth_tpu.crypto import native
    if native.load() is None:
        pytest.skip("native lib unavailable")
    for priv in (1, 2, 12345, 0xDEADBEEF):
        msg = K.keccak256_py(priv.to_bytes(32, "big"))
        r, s, recid = S.sign(msg, priv)
        assert native.recover_address_native(msg, r, s, recid) == \
            S.priv_to_address(priv)
    # batch path
    n = 8
    hashes = b"".join(K.keccak256_py(bytes([i])) for i in range(n))
    rs, ss, recids = b"", b"", b""
    privs = [i + 1 for i in range(n)]
    for i in range(n):
        h = hashes[32 * i:32 * i + 32]
        r, s, recid = S.sign(h, privs[i])
        rs += r.to_bytes(32, "big")
        ss += s.to_bytes(32, "big")
        recids += bytes([recid])
    addrs, ok = native.recover_addresses_batch(hashes, rs, ss, recids)
    assert ok == b"\x01" * n
    for i in range(n):
        assert addrs[20 * i:20 * i + 20] == S.priv_to_address(privs[i])


# ---------------------------------------------------------- RFC 9380 SSWU

def test_sswu_points_on_isogenous_curve():
    """Fresh-randomness re-run of the h2c import self-check: SSWU
    outputs satisfy E' (y^2 = x^3 + 240i*x + 1012(1+i)), isogeny
    images satisfy E2 (y^2 = x^3 + 4(1+i))."""
    import os as _os
    from coreth_tpu.crypto import h2c
    h2c._selfcheck(n=6, seed=_os.urandom(8))


def test_hash_to_g2_subgroup_and_determinism():
    from coreth_tpu.crypto import bls, h2c
    p1 = h2c.hash_to_g2(b"warp message")
    p2 = h2c.hash_to_g2(b"warp message")
    p3 = h2c.hash_to_g2(b"other message")
    assert p1 == p2
    assert p1 != p3
    # cofactor-cleared output lies in the r-torsion subgroup
    assert bls.g2_mul(p1, bls.R) is None
    # domain separation: same msg, different DST -> different point
    p4 = h2c.hash_to_g2(b"warp message", h2c.DST_POP)
    assert p4 != p1


def test_expand_message_xmd_shape_and_separation():
    from coreth_tpu.crypto.h2c import expand_message_xmd
    out = expand_message_xmd(b"abc", b"DST", 256)
    assert len(out) == 256
    assert expand_message_xmd(b"abc", b"DST", 256) == out
    assert expand_message_xmd(b"abc", b"DST2", 256) != out
    assert expand_message_xmd(b"abd", b"DST", 256) != out
    # prefix property does NOT hold across lengths (l_i_b is hashed in)
    assert expand_message_xmd(b"abc", b"DST", 128) != out[:128]


def test_sswu_exceptional_zero_input():
    """u = 0 hits the tv2 == 0 exceptional branch (x = B/(Z*A)) and
    must still produce a valid curve point."""
    from coreth_tpu.crypto import bls, h2c
    x, y = h2c.sswu(bls.Fq2(0, 0))
    assert y.sq() == h2c._g_iso(x)
    xi, yi = h2c.iso3((x, y))
    assert yi.sq() == xi.sq() * xi + bls.B2


def test_bls_sign_verify_aggregate_with_sswu():
    from coreth_tpu.crypto import bls
    sks = [bls.secret_from_bytes(bytes([i]) * 8) for i in range(1, 5)]
    pks = [bls.public_key(sk) for sk in sks]
    msg = b"sswu end to end"
    sigs = [bls.sign(sk, msg) for sk in sks]
    for pk, sig in zip(pks, sigs):
        assert bls.verify(pk, msg, sig)
    agg = bls.aggregate_signatures(sigs)
    assert bls.verify_aggregate(pks, msg, agg)
    assert not bls.verify_aggregate(pks, b"tampered", agg)


def test_rfc9380_known_answer_vectors():
    """RFC 9380 Appendix J.10.1 (BLS12381G2_XMD:SHA-256_SSWU_RO_),
    DST "QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_": the
    published hash_to_curve outputs for msg="" and msg="abc",
    byte-for-byte — wire compatibility with every conforming
    implementation (blst included) hangs on these."""
    from coreth_tpu.crypto import h2c
    dst = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"
    x, y = h2c.hash_to_g2(b"", dst)
    assert x[0] == 0x0141ebfbdca40eb85b87142e130ab689c673cf60f1a3e98d69335266f30d9b8d4ac44c1038e9dcdd5393faf5c41fb78a  # noqa: E501
    assert x[1] == 0x05cb8437535e20ecffaef7752baddf98034139c38452458baeefab379ba13dff5bf5dd71b72418717047f5b0f37da03d  # noqa: E501
    assert y[0] == 0x0503921d7f6a12805e72940b963c0cf3471c7b2a524950ca195d11062ee75ec076daf2d4bc358c4b190c0c98064fdd92  # noqa: E501
    assert y[1] == 0x12424ac32561493f3fe3c260708a12b7c620e7be00099a974e259ddc7d1f6395c3c811cdd19f1e8dbf3e9ecfdcbab8d6  # noqa: E501
    x, y = h2c.hash_to_g2(b"abc", dst)
    assert x[0] == 0x02c2d18e033b960562aae3cab37a27ce00d80ccd5ba4b7fe0e7a210245129dbec7780ccc7954725f4168aff2787776e6  # noqa: E501

"""ctypes bridge to the C++ host runtime (native/libcoreth_native.so).

The native library supplies the fast paths that the reference gets from
asm/cgo dependencies (SURVEY.md section 2.7): keccak-256 and batched
secp256k1 recovery.  Built lazily via ``coreth_tpu.nativebuild`` on
first load if g++ is available; every caller keeps working on the
pure-Python path when the build is unavailable.

``CORETH_NATIVE_SANITIZE=1`` loads the sanitizer-hardened build
(``libcoreth_native_asan.so``, ``make sanitize``) instead: same ABI,
but every heap overflow / use-after-free / UB at the boundary aborts
the process.  The ASan runtime must be preloaded for that to work —
drive it through a subprocess with ``nativebuild.asan_env()`` (see
tests/test_sanitize.py); the tier-1 sanitizer suite does exactly this.

``CORETH_NATIVE_TSAN=1`` likewise loads the ThreadSanitizer build
(``libcoreth_native_tsan.so``, ``make sanitize-thread``): data races
where GIL-releasing native calls overlap across threads are reported
instead of silently corrupting.  Drive it through a subprocess with
``nativebuild.tsan_env()`` (see tests/test_tsan.py).
"""

from __future__ import annotations

import ctypes
import os

from coreth_tpu import nativebuild

_lib = None


def load():
    """Load the native library, or return None.

    Builds when the .so is missing, and REBUILDS when any source file
    is newer than it (a prebuilt library must not mask source edits).
    If the rebuild fails (no C++ toolchain), the existing prebuilt .so
    still loads — callers probe per-symbol (hasattr) for ABI surfaces
    newer than the prebuilt, so features degrade one by one instead of
    all-or-nothing.  The ``CORETH_NATIVE_SANITIZE`` /
    ``CORETH_NATIVE_TSAN`` selection is read once, at first load (the
    handle is cached for the process)."""
    global _lib
    if _lib is not None:
        return _lib
    sanitize = os.environ.get("CORETH_NATIVE_SANITIZE", "") == "1"
    tsan = not sanitize \
        and os.environ.get("CORETH_NATIVE_TSAN", "") == "1"
    path = nativebuild.ensure_built(sanitize=sanitize, tsan=tsan)
    if path is None:
        return None
    lib = ctypes.CDLL(path)
    lib.coreth_keccak256.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p]
    lib.coreth_keccak256.restype = None
    lib.coreth_ecrecover.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_int, ctypes.c_char_p]
    lib.coreth_ecrecover.restype = ctypes.c_int
    lib.coreth_ecrecover_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p]
    lib.coreth_ecrecover_batch.restype = None
    lib.coreth_ecrecover_pubkeys.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p]
    lib.coreth_ecrecover_pubkeys.restype = None
    lib.coreth_recover_wire.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p]
    lib.coreth_recover_wire.restype = None
    lib.coreth_recover_prep.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p]
    lib.coreth_recover_prep.restype = None
    lib.coreth_recover_finish.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p]
    lib.coreth_recover_finish.restype = None
    lib.coreth_baseline_replay.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_double)]
    lib.coreth_baseline_replay.restype = ctypes.c_int
    lib.coreth_receipt_root.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_char_p]
    lib.coreth_receipt_root.restype = None
    lib.coreth_evm_replay.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64,
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.POINTER(ctypes.c_double)]
    lib.coreth_evm_replay.restype = ctypes.c_int
    lib.coreth_keccak256_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64),
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_char_p]
    lib.coreth_keccak256_batch.restype = None
    lib.coreth_test_fe_op.argtypes = [
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint64),
        ctypes.POINTER(ctypes.c_uint64), ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint64)]
    lib.coreth_test_fe_op.restype = None
    lib.coreth_test_sc_inv.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.coreth_test_sc_inv.restype = None
    # test-only symbol compiled ONLY into the sanitized build (`make
    # sanitize`) — proves the ASan trap actually fires
    if hasattr(lib, "coreth_sanitize_smoke"):
        lib.coreth_sanitize_smoke.argtypes = [ctypes.c_int64]
        lib.coreth_sanitize_smoke.restype = ctypes.c_int
    # test-only symbol compiled ONLY into the tsan build (`make
    # sanitize-thread`) — proves the TSan trap actually fires
    if hasattr(lib, "coreth_tsan_smoke"):
        lib.coreth_tsan_smoke.argtypes = [ctypes.c_int]
        lib.coreth_tsan_smoke.restype = ctypes.c_int
    _lib = lib
    return _lib


def _require() -> ctypes.CDLL:
    lib = load()
    if lib is None:
        raise RuntimeError(
            "coreth native library unavailable (build failed or g++ missing); "
            "use the pure-python entry points in coreth_tpu.crypto")
    return lib


def keccak256_native(data: bytes) -> bytes:
    out = ctypes.create_string_buffer(32)
    _require().coreth_keccak256(data, len(data), out)
    return out.raw


def recover_address_native(msg_hash: bytes, r: int, s: int, recid: int) -> bytes:
    out = ctypes.create_string_buffer(20)
    ok = _require().coreth_ecrecover(
        msg_hash, r.to_bytes(32, "big"), s.to_bytes(32, "big"), recid, out)
    if not ok:
        raise ValueError("invalid signature values")
    return out.raw


def recover_addresses_batch(hashes: bytes, rs: bytes, ss: bytes,
                            recids: bytes):
    """Batched recovery over packed buffers.  Returns (addresses, ok)
    bytes: ``ok[i]`` 1, or 2 where the batch's sequential fallback
    recovered what its fast path could not (ReplayStats.sigs_slow_path),
    0 for an invalid signature."""
    n = len(recids)
    out = ctypes.create_string_buffer(20 * n)
    ok = ctypes.create_string_buffer(n)
    _require().coreth_ecrecover_batch(hashes, rs, ss, recids, n, out, ok)
    return out.raw, ok.raw


def recover_pubkeys_batch(hashes: bytes, rs: bytes, ss: bytes,
                          recids: bytes):
    """recover_addresses_batch with the 64-byte public keys (x || y,
    big-endian) in place of the addresses.  Returns (keys, ok) bytes,
    ``ok`` as there."""
    n = len(recids)
    if not len(hashes) == len(rs) == len(ss) == 32 * n:
        raise ValueError("hashes, r and s must be 32 bytes a signature")
    out = ctypes.create_string_buffer(64 * n)
    ok = ctypes.create_string_buffer(n)
    _require().coreth_ecrecover_pubkeys(hashes, rs, ss, recids, n, out, ok)
    return out.raw, ok.raw


def recover_senders_wire(wire: bytes, offsets, chain_id: int):
    """Batched recovery from transactions' wire encodings: ``wire`` holds
    them end to end, transaction i is ``wire[offsets[i]:offsets[i + 1]]``
    (legacy RLP list, or type byte 1 / 2 and its list).  The native walk
    derives signing hash, r, s and recovery id by the rules of
    ``LatestSigner(chain_id)`` and feeds the batch above.  Returns
    (addresses, ok) bytes, ``ok`` as above; ``ok[i] == 0`` leaves
    transaction i to ``signer.sender``: malformed or truncated bytes, an
    offset outside ``wire``, a foreign chain id, high s, recovery id
    past 1, r or s out of range."""
    n = len(offsets) - 1
    if n < 0:
        raise ValueError("offsets must hold at least [0]")
    if not 0 <= chain_id < 1 << 64:
        raise ValueError(f"chain id {chain_id} does not fit 64 bits")
    off = (ctypes.c_uint64 * (n + 1))(*offsets)
    out = ctypes.create_string_buffer(20 * n)
    ok = ctypes.create_string_buffer(n)
    _require().coreth_recover_wire(wire, len(wire), off, n, chain_id,
                                   out, ok)
    return out.raw, ok.raw


def install() -> bool:
    """Activate native fast paths on the pure-python entry points."""
    if load() is None:
        return False
    from coreth_tpu.crypto import keccak as _k
    from coreth_tpu.crypto import secp256k1 as _s
    _k.set_impl(keccak256_native)
    _s.set_recover_impl(recover_address_native)
    return True


def baseline_replay(tx_records: bytes, block_offsets, roots: bytes,
                    coinbases: bytes, accounts: bytes, n_accounts: int):
    """Run the compiled sequential transfer processor (native/baseline.cc
    — the Go-proxy baseline; see BASELINE.md).  Returns (rc, phases)
    where rc==0 means every block's state root matched and phases is
    [t_sender, t_exec, t_trie] seconds.

    The decoder is bounds-checked, not trusted: the wrapper validates
    the fixed-stride blobs against the counts it passes, and the C
    side validates the offsets against the explicit tx-blob length
    (rc 5 = malformed; fuzzed under ASan in tests/test_sanitize.py)."""
    lib = _require()
    if not block_offsets:
        raise ValueError("block_offsets must hold at least [0]")
    n_blocks = len(block_offsets) - 1
    if len(roots) != 32 * n_blocks:
        raise ValueError(f"roots blob {len(roots)}B != 32*{n_blocks}")
    if len(coinbases) != 20 * n_blocks:
        raise ValueError(
            f"coinbases blob {len(coinbases)}B != 20*{n_blocks}")
    if len(accounts) != 60 * n_accounts:
        raise ValueError(
            f"accounts blob {len(accounts)}B != 60*{n_accounts}")
    if any(o < 0 for o in block_offsets):
        raise ValueError("negative block offset")
    off = (ctypes.c_uint64 * len(block_offsets))(*block_offsets)
    phases = (ctypes.c_double * 3)()
    rc = lib.coreth_baseline_replay(
        tx_records, len(tx_records), off, n_blocks, roots, coinbases,
        accounts, n_accounts, phases)
    return rc, list(phases)


def evm_replay(tx_records: bytes, block_offsets, block_env: bytes,
               accounts: bytes, n_accounts: int, contracts: bytes,
               n_contracts: int, chain_id: int):
    """Run the compiled sequential EVM processor (native/evm.cc — the
    contract-workload baseline; see BASELINE.md round 5).  Returns
    (rc, phases); rc==0 means every block's state root matched.

    Like baseline_replay, the packed-blob decode is bounds-checked:
    fixed-stride blobs validate here, and the variable-length tx and
    contract records (dlen/clen/nslots prefixes) validate in C against
    the explicit blob lengths (rc -10 = malformed)."""
    lib = _require()
    if not block_offsets:
        raise ValueError("block_offsets must hold at least [0]")
    n_blocks = len(block_offsets) - 1
    if len(block_env) != 116 * n_blocks:
        raise ValueError(
            f"block_env blob {len(block_env)}B != 116*{n_blocks}")
    if len(accounts) != 60 * n_accounts:
        raise ValueError(
            f"accounts blob {len(accounts)}B != 60*{n_accounts}")
    if any(o < 0 for o in block_offsets):
        raise ValueError("negative block offset")
    off = (ctypes.c_uint64 * len(block_offsets))(*block_offsets)
    phases = (ctypes.c_double * 3)()
    rc = lib.coreth_evm_replay(
        tx_records, len(tx_records), off, n_blocks, block_env,
        accounts, n_accounts, contracts, len(contracts), n_contracts,
        chain_id, phases)
    return rc, list(phases)


def receipt_root(cum_gas, tx_types: bytes, has_log: bytes,
                 log_blob: bytes):
    """Receipt-trie root + header bloom for a device-path block in one
    C++ call (DeriveSha/StackTrie + CreateBloom role — reference
    core/types/hashing.go:97, bloom9.go).  Receipts are status-1 with 0
    or 1 Transfer-shaped log (addr20 ++ 3*topic32 ++ data32 = 148B).
    Returns (root32, bloom256)."""
    lib = _require()
    n = len(tx_types)
    cg = (ctypes.c_uint64 * n)(*cum_gas)
    root = ctypes.create_string_buffer(32)
    bloom = ctypes.create_string_buffer(256)
    lib.coreth_receipt_root(cg, tx_types, has_log, log_blob, n, root,
                            bloom)
    return root.raw, bloom.raw


def recover_prep(hashes: bytes, rs: bytes, ss: bytes, recids: bytes):
    """C++ host prep for the device recovery kernel: range checks, x
    coordinate, and u1/u2 scalars via one Montgomery batch inversion.
    Returns (xs_le33, u1_le32, u2_le32, ok) packed bytes."""
    lib = _require()
    n = len(recids)
    xs = ctypes.create_string_buffer(33 * n)
    u1 = ctypes.create_string_buffer(32 * n)
    u2 = ctypes.create_string_buffer(32 * n)
    ok = ctypes.create_string_buffer(n)
    lib.coreth_recover_prep(hashes, rs, ss, recids, n, xs, u1, u2, ok)
    return xs.raw, u1.raw, u2.raw, ok.raw


def keccak256_batch(data: bytes, lens, stride: int) -> bytes:
    """Batched fixed-stride keccak-256: item i occupies
    ``data[i*stride : i*stride + lens[i]]``.  Returns the packed
    32-byte digests."""
    n = len(lens)
    arr = (ctypes.c_uint64 * n)(*lens)
    out = ctypes.create_string_buffer(32 * n)
    _require().coreth_keccak256_batch(data, arr, stride, n, out)
    return out.raw


def sanitize_smoke_available() -> bool:
    """True when the loaded library carries the test-only sanitizer
    smoke helper (i.e. it is the ``make sanitize`` build)."""
    lib = load()
    return lib is not None and hasattr(lib, "coreth_sanitize_smoke")


def sanitize_smoke(idx: int) -> int:
    """Drive the deliberately-bugged test-only helper: reads
    ``buf[idx]`` of an 8-byte heap allocation.  ``idx >= 8`` is a heap
    overflow the sanitized build must trap (abort), which is exactly
    what tests/test_sanitize.py proves in a subprocess."""
    return _require().coreth_sanitize_smoke(idx)


def tsan_smoke_available() -> bool:
    """True when the loaded library carries the test-only race smoke
    helper (i.e. it is the ``make sanitize-thread`` build)."""
    lib = load()
    return lib is not None and hasattr(lib, "coreth_tsan_smoke")


def tsan_smoke(racy: int) -> int:
    """Drive the deliberately-racy test-only helper: two threads
    hammer one counter, unsynchronized when ``racy`` is truthy (the
    TSan build must report a data race — with ``halt_on_error=1``
    the process dies with TSAN_OPTIONS' exitcode) and mutex-guarded
    otherwise (must stay silent).  tests/test_tsan.py proves both
    halves in subprocesses."""
    return _require().coreth_tsan_smoke(1 if racy else 0)


def recover_finish(rows: bytes, n: int, ok_in: bytes):
    """C++ finish for the device recovery kernel: batched Jacobian->
    affine conversion + keccak address derivation.  Returns (addrs, ok)
    where ok[i]==2 marks ladder-collision rows for host re-run."""
    lib = _require()
    out = ctypes.create_string_buffer(20 * n)
    ok = ctypes.create_string_buffer(n)
    lib.coreth_recover_finish(rows, n, ok_in, out, ok)
    return out.raw, ok.raw

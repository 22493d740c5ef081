"""Sanitizer-hardened native boundary (tier-1).

The nativeabi lint pass proves the *static shape* of the ctypes
boundary; this module proves its *dynamic memory behavior*: the
hostexec hand-derived vectors and the randomized py-vs-native trie
differential run against ``libcoreth_native_asan.so`` (``make
sanitize``: ``-fsanitize=address,undefined -fno-sanitize-recover``) in
a subprocess with the ASan runtime preloaded, so any heap overflow,
use-after-free, or UB crossing the boundary aborts the run instead of
silently corrupting memory.  A deliberately-bugged test-only helper
(``coreth_sanitize_smoke`` — heap overflow on demand, compiled ONLY
into the sanitized build) proves the trap is actually armed: a
mis-built library that loads but does not instrument would pass every
other test.

Skips without a C++ toolchain, like the existing rebuild path.
"""

import os
import re
import subprocess
import sys

import pytest

from coreth_tpu import nativebuild

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_env = nativebuild.asan_env()
_san_lib = nativebuild.ensure_built(sanitize=True) if _env else None

pytestmark = pytest.mark.skipif(
    _env is None or _san_lib is None,
    reason="no C++ toolchain / sanitized build unavailable")


def _run(args, timeout=420):
    env = dict(_env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable] + args, env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout)


def test_sanitized_library_is_selected():
    """CORETH_NATIVE_SANITIZE=1 must load the asan build — probed via
    the smoke symbol that only exists there."""
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "assert native.load() is not None\n"
              "assert native.sanitize_smoke_available(), 'production lib loaded'\n"
              "assert native.keccak256_native(b'abc').hex().startswith('4e03657a')\n"
              "print('OK')"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_smoke_helper_in_bounds_is_clean():
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "assert native.sanitize_smoke(0) == 0\n"
              "assert native.sanitize_smoke(7) == 0\n"
              "print('OK')"])
    assert r.returncode == 0, r.stdout + r.stderr


def test_smoke_helper_heap_overflow_traps():
    """The deliberately-bugged read one past the 8-byte allocation
    must ABORT the process (-fno-sanitize-recover), with a sanitizer
    report on stderr — the proof the instrumentation is live."""
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "native.sanitize_smoke(9)\n"
              "print('UNREACHABLE-SENTINEL')"])
    out = r.stdout + r.stderr
    assert r.returncode != 0, "overflow did not trap: " + out
    assert "UNREACHABLE-SENTINEL" not in out
    assert ("runtime error" in out or "AddressSanitizer" in out), out


def test_replay_decoder_length_prefix_fuzz_under_asan():
    """The packed-blob replay decoders (coreth_baseline_replay /
    coreth_evm_replay) against the seeded hostile corpus — truncated
    blobs, non-monotone offsets, lying dlen/clen/nslots length
    prefixes — with ASan armed: any read past a blob aborts the run.
    The script also asserts blatant truncations come back with the
    malformed rc (5 / -10), so a decoder that silently "succeeds" off
    a bad prefix fails even without a sanitizer hit."""
    r = _run(["tests/fuzz_native_replay.py"])
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "OK baseline_rejected=" in r.stdout, out[-3000:]


def test_sender_wire_walk_hostile_encodings_under_asan():
    """The native sender batch's wire walk (coreth_recover_wire)
    parses untrusted bytes on a worker thread: every prefix of a
    transaction as the last lane of its buffer, length prefixes that
    claim more than their offsets hold, offsets that run backwards or
    past the buffer, and seeded byte edits, with ASan armed — a read
    past the buffer aborts the run; the script's own assertions hold
    each refusal to its lane and each accepted mutant to the Python
    decoder and signer."""
    r = _run(["tests/fuzz_sender_wire.py"])
    out = r.stdout + r.stderr
    assert r.returncode == 0, out[-3000:]
    assert "OK refused=" in r.stdout, out[-3000:]


def test_receipt_check_under_asan():
    """The device path's receipt check (coreth_receipt_check) with ASan
    armed: the StackTrie parity sizes from 0 to 4,096 receipts, every
    receipt type and log share, a gas change, and the malformed
    columns it must refuse before reading past them."""
    r = _run(["-m", "pytest", "tests/test_receipt_check.py", "-q",
              "-k", "receipt_check_", "-p", "no:cacheprovider",
              "-p", "no:randomly"])
    tail = r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    m = re.search(r"(\d+) passed", r.stdout)
    assert m and int(m.group(1)) >= 60, tail


def test_hostexec_vectors_and_trie_differential_under_asan():
    """The real boundary drives: 13 hand-derived hostexec vectors
    (gas/refund/returndata/static-protection) + the randomized
    py-vs-native trie differential + the oracle-armed replays, all
    against the sanitized library.  Any boundary memory bug aborts
    the inner pytest run."""
    r = _run(["-m", "pytest", "tests/test_hostexec_vectors.py",
              "tests/test_native_trie.py", "-q",
              "-p", "no:cacheprovider", "-p", "no:randomly"])
    tail = r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode == 0, tail
    # the suites must actually run (not silently skip): both backends
    # are available in the sanitized build by construction
    import re
    m = re.search(r"(\d+) passed", r.stdout)
    assert m and int(m.group(1)) >= 20, tail

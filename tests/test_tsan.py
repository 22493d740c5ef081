"""ThreadSanitizer-hardened native boundary (tier-1).

The threadsafety lint pass proves the *static* thread discipline of
the Python side; this module proves the *dynamic* half at the native
boundary: the streaming pipeline seams where GIL-releasing native
calls overlap across threads — the prefetch thread's batch ECDSA
against the execute thread's trie folds against the flat exporter's
shadow tries, and the hostexec session under cross-tx cache reuse —
replay against ``libcoreth_native_tsan.so`` (``make sanitize-thread``:
``-fsanitize=thread``) in a subprocess with the TSan runtime
preloaded, so any data race crossing the boundary is reported (and,
with ``halt_on_error=1:exitcode=66``, kills the run) instead of
silently corrupting state.  A deliberately-racy test-only helper
(``coreth_tsan_smoke`` — two unsynchronized writer threads on demand,
compiled ONLY into the TSan build) proves the detector is actually
armed before the clean runs are trusted: a mis-built library that
loads but does not instrument would pass every other test.

Skips without a C++ toolchain, like the ASan module next door.
"""

import os
import re
import subprocess
import sys

import pytest

from coreth_tpu import nativebuild

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_env = nativebuild.tsan_env()
_tsan_lib = nativebuild.ensure_built(tsan=True) if _env else None

pytestmark = pytest.mark.skipif(
    _env is None or _tsan_lib is None,
    reason="no C++ toolchain / TSan build unavailable")


def _run(args, timeout=420):
    env = dict(_env)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable] + args, env=env, cwd=REPO,
        capture_output=True, text=True, timeout=timeout)


def test_tsan_library_is_selected():
    """CORETH_NATIVE_TSAN=1 must load the tsan build — probed via the
    smoke symbol that only exists there; the ordinary boundary symbols
    must still work through the instrumented library."""
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "assert native.load() is not None\n"
              "assert native.tsan_smoke_available(), 'production lib loaded'\n"
              "assert native.keccak256_native(b'abc').hex().startswith('4e03657a')\n"
              "print('OK')"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_smoke_helper_race_trips_the_detector():
    """Two unsynchronized writer threads on a plain int: TSan must
    report a data race and halt_on_error=1:exitcode=66 must kill the
    process with rc 66 — the proof the instrumentation is live.  The
    report lands on stderr (or, under some runtimes, is swallowed with
    only the exit code surviving), so the rc is the primary signal."""
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "native.load()\n"
              "native.tsan_smoke(True)\n"
              "print('UNREACHABLE-SENTINEL')"])
    out = r.stdout + r.stderr
    assert r.returncode == 66, f"race did not trap (rc {r.returncode}): " + out
    assert "UNREACHABLE-SENTINEL" not in out


def test_smoke_helper_locked_is_clean():
    """The same hammering under a mutex must stay silent and return
    the exact count — no lost updates, no report, rc 0."""
    r = _run(["-c",
              "from coreth_tpu.crypto import native\n"
              "native.load()\n"
              "print(native.tsan_smoke(False))"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.strip() == "100000", r.stdout + r.stderr


def test_two_threads_in_the_sender_wire_batch_are_clean():
    """The native sender batch from the wire bytes
    (coreth_recover_wire) entered by two threads at once, as the tip's
    prefetcher (warm_senders on its own thread) and the replay
    thread's recovery worker can be: each call's scratch is its own,
    the comb table is built once under call_once — any shared write
    reports and exits 66; rc 0 with both threads' answers right is
    the clean bill."""
    r = _run(["tests/fuzz_sender_wire.py", "threads"])
    assert r.returncode == 0, \
        f"rc {r.returncode}: " + r.stdout[-2000:] + r.stderr[-2000:]
    assert "OK calls=" in r.stdout, r.stdout + r.stderr


def test_streaming_and_hostexec_seams_replay_clean():
    """The real concurrency seams against the instrumented library:

    - a streaming run with the ECDSA prefetch thread overlapping the
      execute thread's native trie folds
      (``test_stream_prefetch_overlap_counters``),
    - the flat exporter's shadow tries folding on the export thread
      while the main thread keeps executing
      (``test_exporter_shadow_trie_backend``),
    - a hostexec session reusing cross-tx storage/existence caches
      (``test_bridge_cross_tx_storage_cache_reuse`` + the EOA redrive
      variant).

    Any data race where those native calls overlap exits 66 via
    halt_on_error; rc 0 with the expected pass count is the clean
    bill.  One inner pytest amortizes the jax import across all four
    drives."""
    r = _run(["-m", "pytest", "-q",
              "tests/test_serve.py::test_stream_prefetch_overlap_counters",
              "tests/test_flat_state.py::test_exporter_shadow_trie_backend",
              "tests/test_hostexec.py::test_bridge_cross_tx_storage_cache_reuse",
              "tests/test_hostexec.py::"
              "test_bridge_cache_reuse_redrives_eoa_existence",
              "-p", "no:cacheprovider", "-p", "no:randomly"])
    tail = r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode == 0, f"rc {r.returncode}: " + tail
    m = re.search(r"(\d+) passed", r.stdout)
    assert m and int(m.group(1)) >= 4, tail

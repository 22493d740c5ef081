"""Build machinery for the native C++ runtime (``make -C native``).

Moved out of ``coreth_tpu.crypto.native`` (PR 3 follow-up) so the
``crypto`` package carries only the ctypes *boundary* — loaders and
per-symbol degradation — while subprocess invocation, source-staleness
mtime checks, and build-artifact paths live here at the package root.
That split is what lets the corethlint ``[determinism]`` scope cover
``crypto``: build orchestration is inherently wall-clock/filesystem
flavored and never belongs in a consensus-scoped package.

Three build flavors of the same sources:

- ``libcoreth_native.so`` — the production library (``make``).  The
  .so itself is a build artifact (gitignored, NOT in the repo); the
  per-symbol degradation below is for a library built EARLIER on the
  same machine whose sources have since moved on — when the rebuild
  fails (toolchain gone), the old .so keeps its features alive one by
  one instead of all-or-nothing.  A truly fresh box with no compiler
  gets the pure-Python paths everywhere.
- ``libcoreth_native_asan.so`` — the sanitizer-hardened library
  (``make sanitize``): ``-fsanitize=address,undefined
  -fno-sanitize-recover`` so any heap overflow, use-after-free, or UB
  at the ctypes boundary aborts the process instead of silently
  corrupting state.  Never shipped prebuilt (it is a test/debug
  artifact and needs the matching libasan runtime preloaded —
  ``asan_env()`` below); selected by ``CORETH_NATIVE_SANITIZE=1`` in
  ``crypto.native.load()``.
- ``libcoreth_native_tsan.so`` — the ThreadSanitizer library (``make
  sanitize-thread``): ``-fsanitize=thread`` so data races where
  GIL-releasing native calls overlap across threads (prefetch-thread
  batch ECDSA against execute-thread trie folds against the flat
  exporter's shadow tries) are *reported* instead of silently
  corrupting.  Same preload contract via ``tsan_env()``; selected by
  ``CORETH_NATIVE_TSAN=1`` in ``crypto.native.load()``.
"""

from __future__ import annotations

import os
import subprocess
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_DIR = os.path.join(REPO_ROOT, "native")
LIB_NAME = "libcoreth_native.so"
SANITIZE_LIB_NAME = "libcoreth_native_asan.so"
TSAN_LIB_NAME = "libcoreth_native_tsan.so"

# flavor -> (library file, make target, test-only sources the OTHER
# flavors must not see as staleness triggers)
_FLAVORS = {
    "prod": (LIB_NAME, None),
    "asan": (SANITIZE_LIB_NAME, "sanitize"),
    "tsan": (TSAN_LIB_NAME, "sanitize-thread"),
}

# test-only sources compiled ONLY into their sanitizer's library; they
# must not mark the other flavors stale (make would no-op on them)
_FLAVOR_ONLY_SRCS = {
    "sanitize_smoke.cc": "asan",
    "tsan_smoke.cc": "tsan",
}


def _flavor(sanitize: bool, tsan: bool) -> str:
    if sanitize and tsan:
        raise ValueError("ASan and TSan builds are mutually exclusive")
    return "asan" if sanitize else "tsan" if tsan else "prod"


def lib_path(sanitize: bool = False, tsan: bool = False) -> str:
    return os.path.join(NATIVE_DIR,
                        _FLAVORS[_flavor(sanitize, tsan)][0])


def build(sanitize: bool = False, tsan: bool = False,
          timeout: int = 180) -> bool:
    """Run the make target; True iff the library exists afterwards."""
    cmd = ["make", "-C", NATIVE_DIR]
    target = _FLAVORS[_flavor(sanitize, tsan)][1]
    if target:
        cmd.append(target)
    try:
        subprocess.run(cmd, check=True, capture_output=True,
                       timeout=timeout)
    except Exception:  # noqa: BLE001 — any build failure leaves the caller's fallback path active
        return False
    return os.path.exists(lib_path(sanitize, tsan))


def rebuild(timeout: int = 600) -> str:
    """Rebuild the production library from ``native/*.cc``
    unconditionally (``make -B``) and return its path; raises with the
    compiler's output when the build fails.  For runs that must not
    load whatever ``.so`` happens to sit on disk — a library copied in
    from another machine, or a stale one kept by :func:`ensure_built`
    after a failed rebuild — nor carry on without one on the
    pure-Python paths.  Call before the first ``crypto.native.load()``:
    a loaded handle is cached for the process."""
    proc = subprocess.run(["make", "-B", "-C", NATIVE_DIR],
                          capture_output=True, text=True,
                          timeout=timeout)
    path = lib_path()
    if proc.returncode != 0 or not os.path.exists(path):
        raise RuntimeError(
            f"native build failed (rc={proc.returncode}):\n"
            + (proc.stderr or proc.stdout)[-2000:])
    return path


def stale(path: str, sanitize: bool = False, tsan: bool = False) -> bool:
    """True when any C++ source or the Makefile is newer than the
    built library at ``path``."""
    flavor = _flavor(sanitize, tsan)
    try:
        lib_mtime = os.path.getmtime(path)
        for fn in os.listdir(NATIVE_DIR):
            if not (fn.endswith(".cc") or fn == "Makefile"):
                continue
            owner = _FLAVOR_ONLY_SRCS.get(fn)
            if owner is not None and owner != flavor:
                continue
            if os.path.getmtime(
                    os.path.join(NATIVE_DIR, fn)) > lib_mtime:
                return True
    except OSError:
        return False
    return False


def ensure_built(sanitize: bool = False,
                 tsan: bool = False) -> Optional[str]:
    """The library path to load, building or rebuilding as needed.

    Missing library: build it (None when the build fails — no
    toolchain).  Present but STALE (a .cc newer than the .so): rebuild
    best-effort, and on failure still return the existing library —
    that is the per-symbol degradation contract: a prebuilt .so keeps
    old features alive while callers probe (hasattr) for newer ABI
    surfaces."""
    path = lib_path(sanitize, tsan)
    if not os.path.exists(path):
        return path if build(sanitize, tsan) else None
    if stale(path, sanitize, tsan):
        # best effort: fall back to the prebuilt on failure
        build(sanitize, tsan)
    return path


def _compiler_lib(name: str) -> Optional[str]:
    """Absolute path of a compiler-bundled runtime library, or None."""
    try:
        out = subprocess.run(
            ["g++", f"-print-file-name={name}"], check=True,
            capture_output=True, text=True, timeout=30).stdout.strip()
    except Exception:  # noqa: BLE001 — no toolchain means no sanitizer runs at all
        return None
    return out if out and os.path.isabs(out) and os.path.exists(out) \
        else None


def asan_runtime() -> Optional[str]:
    """Path to the compiler's libasan.so (to LD_PRELOAD), or None."""
    return _compiler_lib("libasan.so")


def tsan_runtime() -> Optional[str]:
    """Path to the compiler's libtsan.so (to LD_PRELOAD), or None."""
    return _compiler_lib("libtsan.so")


def _preload_env(runtime: str, base: Optional[dict]) -> dict:
    """LD_PRELOAD the sanitizer runtime + libstdc++ ahead of anything
    the caller already preloads.  libstdc++ rides along because python
    links no C++ runtime: without it the sanitizer's ``__cxa_throw``
    interceptor never resolves the real symbol and the first C++
    exception thrown from ANY extension module (jaxlib's MLIR
    iterators throw StopIteration this way) hard-kills the process
    with an interceptor CHECK."""
    preload = [runtime]
    stdcpp = _compiler_lib("libstdc++.so")
    if stdcpp:
        preload.append(stdcpp)
    env = dict(os.environ if base is None else base)
    env["LD_PRELOAD"] = " ".join(
        preload + ([env["LD_PRELOAD"]] if env.get("LD_PRELOAD") else []))
    return env


def asan_env(base: Optional[dict] = None) -> Optional[dict]:
    """Environment for a SUBPROCESS that loads the ASan library:
    libasan must be first in the link order (LD_PRELOAD — a plain
    python binary is not ASan-linked), leak checking off (the Python
    interpreter itself never frees everything at exit), and
    ``CORETH_NATIVE_SANITIZE=1`` so the loader picks the asan build.
    None when there is no toolchain."""
    rt = asan_runtime()
    if rt is None:
        return None
    env = _preload_env(rt, base)
    env["ASAN_OPTIONS"] = ("detect_leaks=0:abort_on_error=0:"
                           + env.get("ASAN_OPTIONS", ""))
    env["CORETH_NATIVE_SANITIZE"] = "1"
    return env


def tsan_env(base: Optional[dict] = None) -> Optional[dict]:
    """Environment for a SUBPROCESS that loads the TSan library:
    libtsan LD_PRELOADed (same reasoning as ``asan_env``),
    ``halt_on_error=1:exitcode=66`` so the first detected race kills
    the process with an unmistakable exit status (66 cannot be
    confused with a python exception's 1 or a signal death),
    ``die_after_fork=0`` so jax/xla process pools that fork without
    exec keep running, and ``CORETH_NATIVE_TSAN=1`` so the loader
    picks the tsan build.  ``native/tsan.supp`` rides along as the
    suppressions file: jaxlib's ``xla_extension.so`` is not
    instrumented, so its JIT thread pool's cross-thread allocations
    look like races to the interceptors (``called_from_lib`` drops
    exactly those — our instrumented library still reports for real).
    None when there is no toolchain."""
    rt = tsan_runtime()
    if rt is None:
        return None
    env = _preload_env(rt, base)
    supp = os.path.join(NATIVE_DIR, "tsan.supp")
    # report_mutex_bugs=0 / detect_deadlocks=0: mutex-misuse checking
    # and lock-order prediction (NOT race detection) trip on mutexes
    # that live inside uninstrumented runtime code — Eigen's
    # thread-pool condvars look destroyed-while-waited and libgcc's
    # unwinder frame registration inverts against XLA internals from
    # the interceptors' limited view; data-race reports are unaffected
    env["TSAN_OPTIONS"] = (f"halt_on_error=1:exitcode=66:"
                           f"die_after_fork=0:report_mutex_bugs=0:"
                           f"detect_deadlocks=0:"
                           f"suppressions={supp}:"
                           + env.get("TSAN_OPTIONS", ""))
    env["CORETH_NATIVE_TSAN"] = "1"
    return env

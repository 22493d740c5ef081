"""corethlint (tools/lint) — tier-1 gate plus per-pass unit fixtures.

The gate test keeps the tree permanently clean: layer boundaries,
determinism in consensus packages, jit purity, rationalized broad
excepts, native-ABI conformance, thread discipline, and the env-knob
census (run_all includes the nativeabi/threadsafety/envknobs passes;
their own fixtures live in tests/test_nativeabi.py and
tests/test_threadsafety.py).  Pure static analysis — no jax, no
device, no network.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from tools.lint import run_all
from tools.lint.baseline import load_baseline, split_findings
from tools.lint.core import Finding, Source, is_suppressed, package_of
from tools.lint.determinism import check_determinism
from tools.lint.excepts import check_excepts
from tools.lint.jitpurity import check_jit_purity
from tools.lint.layers import (
    DEFAULT_TOML, _parse_minitoml, check_layers, load_config,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = load_config()


def src(snippet: str, path: str = "coreth_tpu/mpt/x.py") -> Source:
    return Source(path, textwrap.dedent(snippet))


def codes(findings):
    return [f.code for f in findings]


# ------------------------------------------------------------- the gate

def test_tree_is_clean():
    """Zero non-baselined findings over the real tree (tier-1)."""
    baseline = load_baseline(os.path.join(REPO, "tools", "lint", "baseline.txt"))
    new, _baselined, stale = run_all(
        [os.path.join(REPO, "coreth_tpu")], CONFIG, baseline)
    assert not new, "\n" + "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries: {stale}"


def test_cli_exit_zero_on_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "coreth_tpu"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_flags_synthetic_violations(tmp_path):
    bad = tmp_path / "coreth_tpu" / "mpt" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("from coreth_tpu.state import StateDB\n"
                   "GAS = float(3) + 1.5\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(tmp_path / "coreth_tpu")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "LAY001" in proc.stdout and "DET001" in proc.stdout
    assert "bad.py:1" in proc.stdout  # file:line diagnostics


# ------------------------------------------------------------ layer map

def test_every_package_is_mapped():
    pkgs = set()
    root = os.path.join(REPO, "coreth_tpu")
    for entry in os.listdir(root):
        if entry == "__pycache__":
            continue
        full = os.path.join(root, entry)
        if os.path.isdir(full):
            pkgs.add(entry)
        elif entry.endswith(".py") and entry != "__init__.py":
            pkgs.add(entry[:-3])
    unmapped = pkgs - set(CONFIG.levels)
    assert not unmapped, f"add to tools/lint/layers.toml: {sorted(unmapped)}"


def test_layer_upward_import_flagged():
    s = src("from coreth_tpu.state import StateDB\n")  # mpt -> state
    assert codes(check_layers([s], CONFIG)) == ["LAY001"]


def test_ctypes_outside_native_boundary_flagged():
    """replay binding ctypes directly bypasses the crypto/mpt/evm
    native-runtime wrappers (LAY004)."""
    s = src("import ctypes\n", path="coreth_tpu/replay/x.py")
    assert codes(check_layers([s], CONFIG)) == ["LAY004"]
    s = src("from ctypes import CDLL\n", path="coreth_tpu/state/x.py")
    assert codes(check_layers([s], CONFIG)) == ["LAY004"]


@pytest.mark.parametrize("snippet", [
    "from coreth_tpu.crypto.secp_device import issue_recover\n",
    "from coreth_tpu.crypto import native, secp_device\n",
    "import coreth_tpu.ops.secp as S\n",
    "def f():\n    from ..ops.secp import recover_kernel\n",
])
@pytest.mark.parametrize("path", [
    "coreth_tpu/replay/x.py", "coreth_tpu/serve/x.py",
    "coreth_tpu/serve/cluster/x.py"])
def test_serving_path_may_not_import_the_device_ladder(snippet, path):
    """LAY005: replay/ and serve/ recover senders on the native batch
    alone — the ladder's modules are closed to them by every spelling,
    lazy imports included, while their neighbours and other packages
    stay free to import them."""
    if "cluster" in path:
        snippet = snippet.replace("..ops", "...ops")
    assert codes(check_layers([src(snippet, path=path)], CONFIG)) \
        == ["LAY005"]
    ok = src("from coreth_tpu.crypto import native\n"
             "from coreth_tpu.ops import u256\n", path=path)
    assert check_layers([ok], CONFIG) == []
    lib = src(snippet.replace("...ops", "..ops"),
              path="coreth_tpu/parallel/x.py")
    assert check_layers([lib], CONFIG) == []


def test_ctypes_inside_native_boundary_allowed():
    for path in ("coreth_tpu/mpt/native_trie2.py",
                 "coreth_tpu/crypto/x.py",
                 "coreth_tpu/evm/hostexec/y.py"):
        s = src("import ctypes\n", path=path)
        assert check_layers([s], CONFIG) == [], path


def test_layer_lazy_import_also_flagged():
    s = src("""
        def f():
            from coreth_tpu.state import StateDB
            return StateDB
    """)
    assert codes(check_layers([s], CONFIG)) == ["LAY001"]


def test_layer_relative_upward_import_flagged():
    # from ..state import X inside mpt/ resolves to coreth_tpu.state
    s = src("from ..state import StateDB\n")
    assert codes(check_layers([s], CONFIG)) == ["LAY001"]
    # from .. import state at package root designates packages by name
    s2 = src("from .. import state\n")
    assert codes(check_layers([s2], CONFIG)) == ["LAY001"]


def test_layer_relative_same_package_ok():
    s = src("from . import node\nfrom .node import X\n",
            path="coreth_tpu/mpt/trie.py")
    assert check_layers([s], CONFIG) == []
    # a top-level module importing a lower-layer sibling via `from .`
    s2 = src("from . import rlp\nfrom .crypto import keccak256\n",
             path="coreth_tpu/wire.py")
    assert check_layers([s2], CONFIG) == []


def test_layer_downward_and_same_layer_ok():
    s = src("from coreth_tpu.crypto import keccak256\n"
            "from coreth_tpu import rlp\n"
            "from coreth_tpu.mpt import trie\n")
    assert check_layers([s], CONFIG) == []


def test_layer_root_symbol_import_not_mistaken_for_package():
    # `from coreth_tpu import <symbol>` where <symbol> is a re-export,
    # not a package: no LAY002 unless it names a mapped/scanned package
    s = src("from coreth_tpu import keccak256\n")
    assert check_layers([s], CONFIG) == []
    s2 = src("from coreth_tpu import state\n")  # real package: still caught
    assert codes(check_layers([s2], CONFIG)) == ["LAY001"]


def test_layer_bare_root_import_flagged():
    s = src("import coreth_tpu\n")
    assert codes(check_layers([s], CONFIG)) == ["LAY003"]


def test_layer_unmapped_package_flagged():
    s = src("import coreth_tpu.shinynewpkg.core\n")
    assert codes(check_layers([s], CONFIG)) == ["LAY002"]
    s2 = src("x = 1\n", path="coreth_tpu/shinynewpkg/core.py")
    assert codes(check_layers([s2], CONFIG)) == ["LAY002"]


def test_layer_nested_package_own_level():
    """state/flat has its OWN level below state: a state/flat source
    importing upward into state is LAY001, while state (and replay)
    importing down into state/flat is fine — nested names resolve
    most-specific-first against the configured levels."""
    assert CONFIG.levels["state/flat"] < CONFIG.levels["state"]
    up = src("from coreth_tpu.state import StateDB\n",
             path="coreth_tpu/state/flat/store.py")
    assert codes(check_layers([up], CONFIG)) == ["LAY001"]
    down = src("from coreth_tpu.state.flat import FlatStore\n",
               path="coreth_tpu/state/statedb.py")
    assert check_layers([down], CONFIG) == []
    down2 = src("from coreth_tpu.state.flat.store import FlatStore\n",
                path="coreth_tpu/replay/engine.py")
    assert check_layers([down2], CONFIG) == []


def test_layer_nested_package_internal_and_fallback():
    """Imports WITHIN a configured nested package are same-package;
    an unconfigured nested directory still resolves to its top-level
    package (evm/device inherits evm's level)."""
    inner = src("from .store import FlatStore\n"
                "from coreth_tpu.state.flat import DELETED\n",
                path="coreth_tpu/state/flat/exporter.py")
    assert check_layers([inner], CONFIG) == []
    # evm/device is NOT in layers.toml: resolves to evm, so importing
    # state (one level down from evm) stays legal
    dev = src("from coreth_tpu.state import StateDB\n",
              path="coreth_tpu/evm/device/adapter2.py")
    assert check_layers([dev], CONFIG) == []
    # ...and state/flat importing mpt/rawdb (below it) is legal
    ok = src("from coreth_tpu.mpt import EMPTY_ROOT\n"
             "from coreth_tpu.rawdb import schema\n",
             path="coreth_tpu/state/flat/exporter.py")
    assert check_layers([ok], CONFIG) == []


def test_package_of():
    assert package_of("coreth_tpu/mpt/trie.py") == "mpt"
    assert package_of("coreth_tpu/rlp.py") == "rlp"
    assert package_of("coreth_tpu/__init__.py") == "coreth_tpu"
    assert package_of("/tmp/x/coreth_tpu/evm/device/machine.py") == "evm"
    assert package_of("tests/test_lint.py") is None


def test_minitoml_parser():
    data = _parse_minitoml(
        '# comment\n[[layer]]\nlevel = 3\npackages = ["a", "b"]\n'
        '[[layer]]\nlevel = 4\npackages = [\n  "c",\n]\n'
        '[other]\nname = "x # not a comment"\n')
    assert data["layer"] == [{"level": 3, "packages": ["a", "b"]},
                             {"level": 4, "packages": ["c"]}]
    assert data["other"]["name"] == "x # not a comment"


# ---------------------------------------------------------- determinism

@pytest.mark.parametrize("snippet,expect", [
    ("X = 1.5\n", ["DET001"]),
    ("X = 1 + 2j\n", ["DET001"]),
    ("def f(x):\n    return float(x)\n", ["DET002"]),
    ("import time\n", ["DET003"]),
    ("import random as rnd\nX = rnd.random()\n", ["DET003", "DET003"]),
    ("from os import urandom\n", ["DET003"]),
    ("import datetime\nT = datetime.datetime.now()\n", ["DET003"]),
    ("from datetime import datetime\n", ["DET003"]),
    ("import os\nX = os.urandom(8)\n", ["DET003"]),
    ("K = {hash(b'k'): 1}\n", ["DET004"]),
    ("def f(xs):\n    return sorted(xs, key=id)\n", []),  # id ref, not call
    ("def f(xs):\n    for x in set(xs):\n        pass\n", ["DET005"]),
    ("def f(xs):\n    return [y for y in {1, 2}]\n", ["DET005"]),
    ("def f(d, enc):\n    return enc.encode(d.keys())\n", ["DET006"]),
    ("def f(xs):\n    return keccak256(set(xs))\n", ["DET006"]),
    ("def f(xs):\n    return sha256(set(xs))\n", ["DET006"]),
    # DET007: true division of provably-int operands
    ("X = 3 / 2\n", ["DET007"]),
    ("def f(xs):\n    return len(xs) / 4\n", ["DET007"]),
    ("def f(xs):\n    n = len(xs)\n    return n / 2\n", ["DET007"]),
    ("def f(x):\n    return int(x) / (1 + len(x))\n", ["DET007"]),
    # augmented /= evicts the name from the int trace (it rebinds to a
    # float) BEFORE judgment — conservatively exempt, not flagged
    ("def f(x):\n    y = 5\n    y /= 2\n    return y\n", []),
    ("def f(x):\n    y = 5\n    y /= x.field\n    return y\n", []),
    # DET007 negatives: type-unknown operands stay exempt (the
    # Fq/bn256 field classes overload / legitimately)
    ("def f(a, b):\n    return a / b\n", []),          # params unknown
    ("def g1(x1, y1):\n    m = (x1 * x1 * 3) / (y1 * 2)\n", []),
    ("def f(tx):\n    return tx.burned() / max(tx.gas, 1)\n", []),
    # a nested function's int binding must NOT leak into the enclosing
    # scope's same-named (unknown) parameter
    ("def outer(n):\n    def helper(q):\n        n = len(q)\n"
     "        return n\n    return n / 2\n", []),
    # sum/abs/pow over unknown elements are not provably int (a sum of
    # Fq field values is exactly the carve-out)
    ("def mean(xs):\n    return sum(xs) / 4\n", []),
    ("def f(x):\n    return abs(x) / 2\n", []),
    ("def f(xs):\n    n = len(xs)\n    n = xs.w\n    return n / 2\n",
     []),                                             # rebound: evicted
    ("X = 3 // 2\n", []),
    ("def f(xs):\n    return Fraction(len(xs), 4)\n", []),
    # negatives
    ("def f(x):\n    return shard_map(set(x))\n", []),  # sha* != hashing
    ("def f(x):\n    return shape({1, 2})\n", []),
    ("X = 15\ns = 'a 1.5 string'\n", []),
    ("def f(xs):\n    for x in sorted(set(xs)):\n        pass\n", []),
    ("def f(d):\n    return encode(sorted(d.keys()))\n", []),
    ("import os\nX = os.path.join('a', 'b')\n", []),
])
def test_determinism_fixtures(snippet, expect):
    assert codes(check_determinism([src(snippet)], CONFIG)) == expect


def test_determinism_only_in_consensus_packages():
    s = src("X = 1.5\nimport time\n", path="coreth_tpu/rpc/x.py")
    assert check_determinism([s], CONFIG) == []


# ----------------------------------------------------------- jit purity

def test_jit_decorated_print_flagged():
    s = src("""
        import jax
        @jax.jit
        def f(x):
            print(x)
            return x
    """)
    assert codes(check_jit_purity([s])) == ["JIT001"]


def test_jit_partial_decorator_and_host_ops():
    s = src("""
        from functools import partial
        import jax
        import numpy as np
        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            y = np.asarray(x)
            return y.item()
    """)
    assert sorted(codes(check_jit_purity([s]))) == ["JIT002", "JIT005"]


def test_jit_wrapped_by_name_closure_mutation():
    s = src("""
        import jax
        acc = []
        def step(x):
            acc.append(x)
            return x
        fast = jax.jit(step)
    """)
    assert codes(check_jit_purity([s])) == ["JIT004"]


def test_jit_io_and_global():
    s = src("""
        import jax
        @jax.jit
        def f(x):
            global COUNT
            open("/tmp/log").read()
            return x
    """)
    assert sorted(codes(check_jit_purity([s]))) == ["JIT003", "JIT004"]


def test_jit_clean_and_unjitted_ignored():
    s = src("""
        import jax
        import jax.numpy as jnp
        from coreth_tpu.ops import u256
        @jax.jit
        def f(x):
            y = jnp.add(x, 1)
            return u256.add(y, y)        # module fn call, not mutation
        def host(x):
            print(x)                      # not jitted: fine
            return [float(v) for v in x]
    """, path="coreth_tpu/parallel/x.py")
    assert check_jit_purity([s]) == []


def test_jit_factory_call_result_traced():
    """jax.jit(build(...)) — the closure the factory returns is checked
    like a decorated kernel (machine.py build_machine shape)."""
    s = src("""
        import jax
        def build(params):
            def run(x):
                print(x)
                return x
            return run
        fn = jax.jit(build(3))
    """)
    assert codes(check_jit_purity([s])) == ["JIT001"]


def test_jit_factory_transitive_returns_traced():
    """A factory returning another factory's call result is followed
    through the call graph."""
    s = src("""
        import jax
        import numpy as np
        def inner(p):
            def kernel(x):
                return np.sum(x)
            return kernel
        def outer(p):
            return inner(p)
        fn = jax.jit(outer(1))
    """)
    assert codes(check_jit_purity([s])) == ["JIT002"]


def test_jit_factory_marker_opt_in():
    """# corethlint: jit-factory marks a factory whose closure is
    jitted elsewhere (the _build_exec shape)."""
    s = src("""
        # corethlint: jit-factory
        def build_exec(p):
            def lanes(x):
                return x.tolist()
            return lanes
    """)
    assert codes(check_jit_purity([s])) == ["JIT005"]


def test_jit_factory_tuple_return_and_decorated_marker():
    """Tuple returns (`return init_fn, step_fn`) are traced, and the
    marker is found above a decorator stack (FunctionDef.lineno is the
    def line, not the first decorator's)."""
    s = src("""
        import functools
        # corethlint: jit-factory
        @functools.cache
        def build_pair(p):
            def init_fn(x):
                return x
            def step_fn(x):
                print(x)
                return x
            return init_fn, step_fn
    """)
    assert codes(check_jit_purity([s])) == ["JIT001"]


def test_jit_factory_listcomp_program_set_traced():
    """Program-SET factories (the specialize.py shape) returning a
    comprehension of per-item factory calls are followed into each
    element factory's closures."""
    s = src("""
        import jax
        # corethlint: jit-factory
        def build_programs(codes):
            return [build_one(c) for c in codes]
        def build_one(code):
            def prog(x):
                print(x)
                return x
            return prog
    """)
    assert codes(check_jit_purity([s])) == ["JIT001"]


def test_jit_factory_tuple_genexp_traced_and_clean_ok():
    """``return tuple(build_one(c) for c in cs)`` is traced too; a
    clean program set produces no findings."""
    s = src("""
        import jax
        import numpy as np
        # corethlint: jit-factory
        def build_programs(codes):
            return tuple(build_one(c) for c in codes)
        def build_one(code):
            def prog(x):
                return np.sum(x)
            return prog
        # clean variant never jitted nor marked: ignored
        def host_set(codes):
            return [host_one(c) for c in codes]
        def host_one(code):
            def probe(x):
                print(x)
                return x
            return probe
    """)
    assert codes(check_jit_purity([s])) == ["JIT002"]


def test_jit_factory_clean_and_untraced_factory_ignored():
    """Factories whose results are never jitted (and carry no marker)
    stay unchecked; clean factory closures produce no findings."""
    s = src("""
        import jax
        import jax.numpy as jnp
        def build(p):
            def run(x):
                return jnp.add(x, p)
            return run
        def host_builder(p):
            def probe(x):
                print(x)              # never jitted: fine
                return x
            return probe
        fn = jax.jit(build(2))
        probe = host_builder(2)
    """)
    assert check_jit_purity([s]) == []


# ---------------------------------------------------------- bare except

def test_broad_except_needs_rationale():
    s = src("""
        try:
            x = 1
        except Exception:
            pass
    """)
    assert codes(check_excepts([s])) == ["EXC001"]


def test_bare_and_base_exception_flagged():
    s = src("""
        try:
            x = 1
        except:
            pass
        try:
            y = 2
        except (ValueError, BaseException) as e:
            raise
    """)
    assert sorted(codes(check_excepts([s]))) == ["EXC001", "EXC002"]


def test_annotated_except_ok():
    s = src("try:\n    x = 1\n"
            "except Exception:  # noqa: BLE001 — warming is best-effort\n"
            "    pass\n"
            "try:\n    y = 2\n"
            "except Exception:  # noqa: BLE001 - hyphen style works too\n"
            "    pass\n")
    assert check_excepts([s]) == []


def test_noqa_without_reason_rejected():
    s = src("try:\n    x = 1\n"
            "except Exception:  # noqa: BLE001\n"
            "    pass\n")
    assert codes(check_excepts([s])) == ["EXC001"]


def test_narrow_except_ok():
    s = src("try:\n    x = 1\nexcept ValueError:\n    pass\n")
    assert check_excepts([s]) == []


# ------------------------------------------------- suppression/baseline

def test_inline_noqa_suppresses_with_reason_only():
    s = src("X = 1.5  # noqa: DET001 — fixture constant, not consensus\n"
            "Y = 2.5  # noqa: DET001\n")
    findings = check_determinism([s], CONFIG)
    kept = [f for f in findings if not is_suppressed(f, {s.path: s})]
    assert codes(findings) == ["DET001", "DET001"]
    assert [f.line for f in kept] == [2]  # reasonless noqa does not count


def test_baseline_matching_and_stale(tmp_path):
    f1 = Finding("coreth_tpu/mpt/x.py", 10, "DET001", "m", "literal:1.5")
    bl = tmp_path / "baseline.txt"
    bl.write_text("# header\n"
                  "coreth_tpu/mpt/x.py::DET001::literal:1.5  # accepted\n"
                  "coreth_tpu/gone.py::LAY001::a->b  # was real once\n")
    baseline = load_baseline(str(bl))
    new, baselined, stale = split_findings([f1], baseline)
    assert new == [] and baselined == [f1]
    assert stale == ["coreth_tpu/gone.py::LAY001::a->b"]


def test_partial_run_ignores_out_of_scope_baseline_entries():
    baseline = frozenset(["coreth_tpu/state/x.py::DET001::literal:1.5",
                          "coreth_tpu/mpt/gone.py::DET001::literal:2.5"])
    new, baselined, stale = split_findings(
        [], baseline, scope_roots=["coreth_tpu/mpt"])
    assert new == [] and baselined == []
    # the state/ entry is out of scope; the mpt/ one is genuinely stale
    assert stale == ["coreth_tpu/mpt/gone.py::DET001::literal:2.5"]


def test_write_baseline_still_exits_nonzero(tmp_path):
    bad = tmp_path / "coreth_tpu" / "mpt" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("X = 1.5\n")
    bl = tmp_path / "baseline.txt"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(tmp_path / "coreth_tpu"),
         "--baseline", str(bl), "--write-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    # findings were written but not yet justified: the run is not green
    assert proc.returncode == 1
    assert "TODO justify" in bl.read_text()
    # and the unedited stub is rejected outright on the next run
    proc2 = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(tmp_path / "coreth_tpu"),
         "--baseline", str(bl)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc2.returncode == 2
    assert "justification" in proc2.stderr


@pytest.mark.parametrize("entry", [
    "coreth_tpu/mpt/x.py::DET001::literal:1.5\n",              # no reason
    "coreth_tpu/mpt/x.py::DET001::literal:1.5  # TODO justify\n",
    "coreth_tpu/mpt/x.py::DET001::literal:1.5  # todo later\n",
])
def test_baseline_rejects_unjustified_entries(tmp_path, entry):
    bl = tmp_path / "baseline.txt"
    bl.write_text(entry)
    with pytest.raises(ValueError, match="justification"):
        load_baseline(str(bl))


def test_multiline_statement_noqa_on_closing_line_suppresses():
    s = src("from coreth_tpu.state import (\n"
            "    StateDB,\n"
            ")  # noqa: LAY001 — fixture exercising closing-line noqa\n")
    findings = check_layers([s], CONFIG)
    assert codes(findings) == ["LAY001"]
    assert all(is_suppressed(f, {s.path: s}) for f in findings)


def test_noqa_in_compound_body_does_not_leak_to_header():
    # ast.For's end_lineno is its body's last line — a noqa there must
    # not suppress the DET005 on the `for ... in set(...)` header
    s = src("def f(xs):\n"
            "    for x in set(xs):\n"
            "        a = 1\n"
            "        b = 2  # noqa: DET005, DET001 — unrelated line\n")
    findings = check_determinism([s], CONFIG)
    assert codes(findings) == ["DET005"]
    assert not any(is_suppressed(f, {s.path: s}) for f in findings)


def test_baseline_counts_occurrences_per_key():
    key = "coreth_tpu/mpt/x.py::DET001::literal:0.5"
    f = lambda line: Finding("coreth_tpu/mpt/x.py", line, "DET001",  # noqa: E731
                             "m", "literal:0.5")
    two_accepted = {key: 2}
    new, baselined, stale = split_findings([f(1), f(2), f(3)], two_accepted)
    assert len(baselined) == 2 and [x.line for x in new] == [3]
    new2, baselined2, stale2 = split_findings([f(1)], two_accepted)
    assert new2 == [] and len(baselined2) == 1 and stale2 == [key]

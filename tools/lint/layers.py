"""Layer-boundary pass — the Python twin of the reference's
``scripts/lint_allowed_geth_imports.sh`` + ``geth-allowed-packages.txt``.

``layers.toml`` declares a total order of package layers (mirroring
SURVEY §1, L0 storage → top API).  A package may import packages at its
own layer or below; an upward import is LAY001, a package missing from
the map (source or target) is LAY002, and a bare ``import coreth_tpu``
(which executes the root __init__ and thus the whole upper tree) is
LAY003, a raw ``ctypes`` import outside the binder packages is LAY004,
and a module that ``[[forbid]]`` closes to a package is LAY005 (a legal
downward import the architecture has decided against).  *All* imports
count, including function-local lazy ones — laziness changes import
time, not the architecture.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from tools.lint.core import (
    Finding, ROOT_PACKAGE, Source, nested_package_of,
)

DEFAULT_TOML = os.path.join(os.path.dirname(__file__), "layers.toml")


@dataclass
class Config:
    levels: Dict[str, int] = field(default_factory=dict)
    determinism_packages: List[str] = field(default_factory=list)
    # packages allowed to bind the C++ runtime directly via ctypes
    # ([native] ctypes_packages); an import elsewhere is LAY004
    ctypes_packages: List[str] = field(default_factory=list)
    # [[forbid]] tables: {"packages": [...], "modules": [...]} — the
    # listed packages (and their subpackages) may not import the
    # listed modules ("crypto.secp_device"), LAY005
    forbidden: List[dict] = field(default_factory=list)


def _parse_minitoml(text: str) -> dict:
    """Parse the subset of TOML layers.toml uses (py3.10 has no
    tomllib): ``[section]`` / ``[[array-of-tables]]``, int, string, and
    string-list values; ``#`` comments."""
    root: dict = {}
    current = root
    buf_key = None
    buf_items: List[str] = []

    def strip_comment(line: str) -> str:
        out, in_str = [], False
        for ch in line:
            if ch == '"':
                in_str = not in_str
            if ch == "#" and not in_str:
                break
            out.append(ch)
        return "".join(out).strip()

    def parse_scalar(tok: str):
        tok = tok.strip()
        if tok.startswith('"') and tok.endswith('"'):
            return tok[1:-1]
        return int(tok)

    for raw in text.splitlines():
        line = strip_comment(raw)
        if not line:
            continue
        if buf_key is not None:  # inside a multi-line list
            buf_items.append(line)
            if line.endswith("]"):
                joined = " ".join(buf_items)
                current[buf_key] = [parse_scalar(t) for t in
                                    re.split(r"\s*,\s*", joined.strip("[] ")) if t]
                buf_key, buf_items = None, []
            continue
        m = re.fullmatch(r"\[\[(\w+)\]\]", line)
        if m:
            current = {}
            root.setdefault(m.group(1), []).append(current)
            continue
        m = re.fullmatch(r"\[(\w+)\]", line)
        if m:
            current = root.setdefault(m.group(1), {})
            continue
        key, _, val = line.partition("=")
        key, val = key.strip(), val.strip()
        if val.startswith("[") and not val.endswith("]"):
            buf_key, buf_items = key, [val]
        elif val.startswith("["):
            current[key] = [parse_scalar(t) for t in
                            re.split(r"\s*,\s*", val.strip("[] ")) if t]
        else:
            current[key] = parse_scalar(val)
    return root


def load_config(toml_path: str = DEFAULT_TOML) -> Config:
    with open(toml_path, encoding="utf-8") as fh:
        data = _parse_minitoml(fh.read())
    cfg = Config()
    for layer in data.get("layer", []):
        for pkg in layer.get("packages", []):
            cfg.levels[pkg] = layer["level"]
    cfg.determinism_packages = data.get("determinism", {}).get("packages", [])
    cfg.ctypes_packages = data.get("native", {}).get("ctypes_packages", [])
    cfg.forbidden = data.get("forbid", [])
    return cfg


def _resolve_nested(mod_tail: List[str], levels: Dict[str, int]) -> str:
    """Most specific configured package name for an import path tail
    (the parts after ``coreth_tpu``): ``["state", "flat", "store"]``
    resolves to ``state/flat`` when layers.toml assigns that nested
    package its own layer, else to the top-level ``state``."""
    for k in range(len(mod_tail), 1, -1):
        cand = "/".join(mod_tail[:k])
        if cand in levels:
            return cand
    return mod_tail[0]


def _source_package(src: Source, levels: Dict[str, int]) -> Optional[str]:
    """The source file's package at configured granularity: the nested
    name when layers.toml maps it, else the top-level package."""
    nested = nested_package_of(src.path)
    if nested is not None:
        for cand in _prefixes_desc(nested):
            if cand in levels:
                return cand
    return src.package


def _prefixes_desc(nested: str) -> List[str]:
    parts = nested.split("/")
    return ["/".join(parts[:k]) for k in range(len(parts), 1, -1)]


def _import_paths(src: Source):
    """Yield (node, tail, names) for every coreth_tpu import,
    module-level or nested: ``tail`` is the module path after the root
    (``[]`` for the root itself), ``names`` the names a from-import
    binds (None for ``import a.b``).  Relative imports are resolved
    against the source file's own package — ``from ..state import X``
    inside ``coreth_tpu/mpt/`` targets ``state`` exactly like the
    absolute form, so the standard relative idiom cannot dodge the
    gate."""
    parts = src.path.split("/")
    pkg_parts = None  # the file's containing package, e.g. [root, "mpt"]
    if ROOT_PACKAGE in parts:
        idx = len(parts) - 1 - parts[::-1].index(ROOT_PACKAGE)
        pkg_parts = parts[idx:-1] or [ROOT_PACKAGE]
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                mod = alias.name.split(".")
                if mod[0] == ROOT_PACKAGE:
                    yield node, mod[1:], None
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                if pkg_parts is None or node.level > len(pkg_parts):
                    continue  # resolves above coreth_tpu — not ours
                base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                mod = base + (node.module.split(".") if node.module else [])
            else:
                mod = (node.module or "").split(".")
            if mod[0] == ROOT_PACKAGE:
                yield node, mod[1:], [a.name for a in node.names]


def _import_targets(src: Source, levels: Optional[Dict[str, int]] = None):
    """Yield (node, target_package, name_form) for every coreth_tpu
    import.  ``name_form`` marks ``from coreth_tpu import X`` aliases,
    where X may be a plain re-exported symbol rather than a package.
    With ``levels``, dotted targets resolve to the most specific
    configured nested package (``coreth_tpu.state.flat.store`` ->
    ``state/flat``)."""
    levels = levels or {}
    for node, tail, names in _import_paths(src):
        if tail:
            yield node, _resolve_nested(tail, levels), False
        elif names is None:
            # bare root import — target is the root itself
            # (check_layers turns it into LAY003)
            yield node, ROOT_PACKAGE, False
        else:  # from coreth_tpu import rlp, wire  /  from .. import rlp
            for name in names:
                yield node, name, True


def _forbidden_imports(src: Source, pkg: str, config: Config):
    """Yield (node, module) for every import by ``pkg`` of a module a
    ``[[forbid]]`` table closes to it — by either spelling: ``from
    coreth_tpu.crypto import secp_device`` and ``import
    coreth_tpu.crypto.secp_device`` name the same module."""
    closed = [m for rule in config.forbidden
              if pkg.split("/")[0] in rule.get("packages", [])
              for m in rule.get("modules", [])]
    if not closed:
        return
    for node, tail, names in _import_paths(src):
        base = ".".join(tail)
        seen = [base] + [f"{base}.{n}" if base else n
                         for n in names or []]
        for mod in closed:
            if any(c == mod or c.startswith(mod + ".") for c in seen):
                yield node, mod


def check_layers(sources: List[Source], config: Config) -> List[Finding]:
    findings = []
    # packages actually scanned (configured granularity)
    present = {_source_package(s, config.levels) for s in sources}
    for src in sources:
        pkg = _source_package(src, config.levels)
        if pkg is None or pkg == ROOT_PACKAGE:
            continue  # outside the tree / root __init__ re-exports
        if pkg not in config.levels:
            findings.append(Finding(
                src.path, 1, "LAY002",
                f"package '{pkg}' is not in tools/lint/layers.toml — "
                f"assign it a layer", f"package:{pkg}"))
            continue
        level = config.levels[pkg]
        # LAY004 — the native-runtime boundary: a raw ctypes import
        # outside the designated binder packages bypasses the loader,
        # the ABI declarations, and the per-symbol degradation policy
        if config.ctypes_packages \
                and pkg.split("/")[0] not in config.ctypes_packages:
            for node in ast.walk(src.tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name.split(".")[0] for a in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    mods = [(node.module or "").split(".")[0]]
                if "ctypes" in mods:
                    findings.append(Finding(
                        src.path, node.lineno, "LAY004",
                        f"direct ctypes import in '{pkg}' — only "
                        f"{sorted(config.ctypes_packages)} bind the "
                        f"native runtime; go through their wrappers",
                        "ctypes-outside-boundary"))
        # LAY005 — a downward import the architecture closed
        for node, mod in _forbidden_imports(src, pkg, config):
            findings.append(Finding(
                src.path, node.lineno, "LAY005",
                f"'{pkg}' may not import {ROOT_PACKAGE}.{mod} "
                f"(tools/lint/layers.toml [[forbid]])",
                f"forbidden:{pkg}->{mod}"))
        seen = set()
        for node, target, name_form in _import_targets(src,
                                                       config.levels):
            if target == pkg:
                continue
            if target == ROOT_PACKAGE:
                findings.append(Finding(
                    src.path, node.lineno, "LAY003",
                    f"bare 'import {ROOT_PACKAGE}' executes the root "
                    f"__init__ (the whole upper tree) — import the "
                    f"needed subpackage directly", "bare-root-import"))
                continue
            if name_form and target not in config.levels and target not in present:
                continue  # plain re-exported symbol, not a package
            if target not in config.levels:
                key = (node.lineno, "?", target)
                if key in seen:
                    continue
                seen.add(key)
                findings.append(Finding(
                    src.path, node.lineno, "LAY002",
                    f"import of package '{target}' which is not in "
                    f"tools/lint/layers.toml", f"unmapped:{target}"))
            elif config.levels[target] > level:
                key = (node.lineno, target)
                if key in seen:
                    continue
                seen.add(key)
                findings.append(Finding(
                    src.path, node.lineno, "LAY001",
                    f"upward import: {pkg} (L{level}) -> {target} "
                    f"(L{config.levels[target]})", f"{pkg}->{target}"))
    return findings

"""Chains from ``--seed``: builders found by name, and the wire cache.

A configuration names its chain builder (``"chain": {"builder": ...}``,
a file under ``benchmarks/chains/``).  The builder file owns everything
that depends on the kind of transaction (``benchmarks/README.md``):

- ``genesis(config, traffic, seed) -> (Genesis, state)``
- ``gen(config, traffic, seed, genesis, state, alter=None)``: the
  ``gen(i, block_gen)`` callback that signs and adds block ``i``'s
  transactions; ``alter=(block, tx)`` moves one wei more in that one
  transaction (the control's altered chain, never a benchmark run);
- ``ledger(config, traffic, seed)``: what the chain adds up to, from
  ``benchlib.plainref`` alone;
- ``read_back(engine, book)``: the committed state against that book.

The blocks themselves are produced by ``coreth_tpu.chain.generate_chain``
— the program's Python host processor, which executes every transaction
on the host EVM and writes state root, receipt root and gas into each
header.  The timed engine is held to those headers block by block; the
harness holds the root it ends on to the plain reference's own
(``plainref.Book.state_root``) as well.

The seed moves identities and amounts (keys, addresses, values), never
the shapes, so every seed is the same amount of work.

The chain is written by a child process into ``.bench_cache/``
(gitignored); the timed process only ever reads it, and the time it
waits for the child or reads the file is not part of ``setup_s``.  A
rerun of the same seed in the same checkout finds the file and starts
no child.  No run depends on a file being there, and the name carries a
digest of everything the chain was built from.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time
from typing import List, Optional, Tuple

from benchlib.names import BENCH_DIR, REPO, load_named

CACHE_DIR = os.path.join(REPO, ".bench_cache")


def generate(genesis, n_blocks: int, gen, gap: int) -> list:
    """``chip_smoke._generate``: the host processor writes the chain,
    one block every ``gap`` seconds."""
    from coreth_tpu.chain import generate_chain
    from coreth_tpu.state import Database
    db = Database()
    gblock = genesis.to_block(db)
    blocks, _ = generate_chain(genesis.config, gblock, db, n_blocks, gen,
                               gap=gap)
    return blocks


def _digest(config: dict, traffic: dict, builder_path: str) -> str:
    h = hashlib.sha256()
    h.update(json.dumps(config, sort_keys=True).encode())
    h.update(json.dumps(traffic.get("chain", {}), sort_keys=True).encode())
    with open(builder_path, "rb") as f:
        h.update(f.read())
    return h.hexdigest()[:12]


def cache_path(config: dict, traffic: dict, seed: int) -> str:
    _builder, path = load_named("chains", config["chain"]["builder"])
    return os.path.join(CACHE_DIR, (
        f"{config['name']}_{config['chain_blocks']}_s{seed}_"
        f"{_digest(config, traffic, path)}.bin"))


def build_wire(config: dict, traffic: dict, seed: int,
               alter: Optional[Tuple[int, int]] = None):
    """(genesis, the chain as wire bytes), built here and now."""
    builder, _ = load_named("chains", config["chain"]["builder"])
    genesis, state = builder.genesis(config, traffic, seed)
    gen = builder.gen(config, traffic, seed, genesis, state, alter)
    blocks = generate(genesis, config["chain_blocks"], gen,
                      config["chain"]["block_gap_s"])
    return genesis, [b.encode() for b in blocks]


def build_to_cache(config: dict, traffic: dict, seed: int) -> str:
    """What the builder child does (``benchmarks/build_chain.py``)."""
    from coreth_tpu import rlp
    path = cache_path(config, traffic, seed)
    if not os.path.exists(path):
        _genesis, wire = build_wire(config, traffic, seed)
        os.makedirs(CACHE_DIR, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(rlp.encode(wire))
        os.replace(tmp, path)
    return path


def start_build(config_file: str, traffic_file: str, config: dict,
                traffic: dict, seed: int):
    """Start the chain builder in a child process, unless this seed's
    chain is in the cache already; returns the child or None.

    The chain is never built in the timed process: a run whose process
    had just executed 33 thousand transactions on the Python EVM
    measured ~3% fewer txs/s in its window than the same seed read
    from the cache (my chip runs, PR 28) — a heap that large is slower
    to allocate from and to collect.  The child is pinned to the host
    platform (one process to a chip) and runs while this process
    imports jax and reaches the chip."""
    if os.path.exists(cache_path(config, traffic, seed)):
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "build_chain.py"),
         "--config", config_file, "--traffic", traffic_file,
         "--seed", str(seed)], env=env, stdout=subprocess.DEVNULL)


def chain_for(config: dict, traffic: dict, seed: int, child=None
              ) -> Tuple[object, List[bytes], dict]:
    """(genesis, wire blocks, how it was made) for one cell and seed.
    ``child`` is what ``start_build`` returned.  ``wait_s`` is the time
    this process stood still for the chain — waiting for the child,
    reading the file — which the harness takes out of ``setup_s``."""
    from coreth_tpu import rlp
    builder, _ = load_named("chains", config["chain"]["builder"])
    genesis, _state = builder.genesis(config, traffic, seed)
    t0 = time.monotonic()
    if child is not None and child.wait() != 0:
        raise SystemExit(f"chain builder exited {child.returncode}")
    with open(cache_path(config, traffic, seed), "rb") as f:
        wire = [bytes(w) for w in rlp.decode(f.read())]
    info = {"chain": "cache" if child is None else "child",
            "wait_s": time.monotonic() - t0,
            "wire_bytes": sum(len(w) for w in wire)}
    return genesis, wire, info


# ------------------------------------------------ shared builder helpers
def first_key(config: dict, seed: int) -> int:
    """The first private key of a seed's range; ranges lie 4,096 keys
    apart, so no two seeds share a sender.  ``plainref.addresses``
    gives the same keys' addresses without the program."""
    return config["chain"]["key_base"] + (seed << 12)


def read_accounts(engine, book) -> dict:
    """Every account of a ``plainref.Book`` read back from the state
    the engine committed, through the program's own read path
    (``engine.commit()``, ``StateDB``), nonce and balance each.  The
    book's own state root goes along for the harness to hold the
    engine's root to."""
    from coreth_tpu.state import StateDB
    engine.commit()
    sdb = StateDB(engine.root, engine.db)
    wrong = []
    want = book.accounts()
    for addr, (nonce, balance) in want.items():
        got = (sdb.get_nonce(addr), sdb.get_balance(addr))
        if got != (nonce, balance):
            wrong.append({"addr": addr.hex(), "got": got,
                          "want": (nonce, balance)})
    return {"compared": len(want), "wrong": wrong,
            "root": book.state_root()}

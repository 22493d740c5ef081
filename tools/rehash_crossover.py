#!/usr/bin/env python
"""Measure the device_rehash crossover point on this machine.

Builds tries with N dirty leaves (fresh keccak-keyed accounts), then
times (a) the host path (native C++ keccak, trie.hash()) vs (b) the
batched device keccak path (mpt/rehash.device_rehash with min_batch=0)
for each N.  Prints a table and the measured crossover, which is the
evidence a CORETH_REHASH_MIN_BATCH default has to rest on.

Run on the chip (through the chip tool; one process holds the chip):
python tools/rehash_crossover.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from coreth_tpu import compile_cache  # noqa: E402

compile_cache.configure()

from coreth_tpu.crypto import keccak256  # noqa: E402
from coreth_tpu.mpt.rehash import collect_dirty, device_rehash  # noqa: E402
from coreth_tpu.mpt.trie import Trie  # noqa: E402


def build_dirty_trie(n: int, seed: int = 0) -> Trie:
    t = Trie()
    for i in range(n):
        k = keccak256(seed.to_bytes(4, "big") + i.to_bytes(8, "big"))
        t.update(k, b"\x84" + i.to_bytes(4, "big") + b"\x01" * 9)
    return t


def time_host(n: int, reps: int = 3) -> float:
    best = float("inf")
    for r in range(reps):
        t = build_dirty_trie(n, seed=r)
        t0 = time.monotonic()
        t.hash()
        best = min(best, time.monotonic() - t0)
    return best


def time_device(n: int, reps: int = 3) -> float:
    # warm compile once
    device_rehash(build_dirty_trie(n, seed=99), min_batch=0)
    best = float("inf")
    for r in range(reps):
        t = build_dirty_trie(n, seed=r)
        t0 = time.monotonic()
        device_rehash(t, min_batch=0)
        best = min(best, time.monotonic() - t0)
    return best


def main():
    sizes = [256, 1024, 4096, 16384, 65536, 262144]
    print(f"backend: {jax.default_backend()}")
    print(f"{'dirty':>8} {'host_s':>9} {'device_s':>9} {'winner':>7}")
    crossover = None
    for n in sizes:
        th = time_host(n)
        td = time_device(n)
        winner = "device" if td < th else "host"
        if winner == "device" and crossover is None:
            crossover = n
        print(f"{n:>8} {th:>9.4f} {td:>9.4f} {winner:>7}")
    if crossover is None:
        print("crossover: none up to 262144 — host path wins at every "
              "measured size on this transport")
    else:
        print(f"crossover: ~{crossover} dirty nodes")


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Quick transfer-workload TPU pass for perf iteration (no baselines).

Usage: python tools/bench_transfer_only.py [reps]
Honors BENCH_WINDOW.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (places the compile cache)


def main():
    reps = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    workload = sys.argv[2] if len(sys.argv) > 2 else "transfer"
    genesis, blocks = bench.build_or_load_chain(workload)
    wire = [b.encode() for b in blocks]
    txs_per_block = bench._txs_per_block(workload)
    from coreth_tpu.types import Block
    warm_blocks = [Block.decode(w) for w in wire]
    warm = bench._fresh_engine(genesis, txs_per_block)
    warm.replay_block(warm_blocks[0])
    warm.replay(warm_blocks[1:])
    assert warm.root == warm_blocks[-1].header.root
    for _ in range(reps):
        blocks = [Block.decode(w) for w in wire]
        engine = bench._fresh_engine(genesis, txs_per_block)
        engine.replay_block(blocks[0])
        t0 = time.monotonic()
        engine.replay(blocks[1:])
        dt = time.monotonic() - t0
        txs = sum(len(b.transactions) for b in blocks[1:])
        assert engine.root == blocks[-1].header.root
        assert engine.stats.blocks_fallback == 0
        row = {k: round(v, 2) if isinstance(v, float) else v
               for k, v in engine.stats.row().items()}
        print(f"{txs / dt:.0f} txs/s wall={dt:.2f}s {row}",
              file=sys.stderr)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The builder child: the reference writes one chain into the cache.

    python3 benchmarks/build_chain.py --config <file> --traffic <file> \
        --seed <n>

Started by ``benchlib.chains.start_build`` with ``JAX_PLATFORMS=cpu``;
it never needs the chip.
"""

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(_HERE))

from benchlib import chains  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/build_chain.py")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    print(chains.build_to_cache(config, traffic, args.seed))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

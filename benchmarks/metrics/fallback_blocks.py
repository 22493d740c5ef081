"""Blocks the engine gave up on and executed on the exact host path
(``ReplayStats.blocks_fallback``); none are expected."""


def read(run):
    return sum(r["blocks_fallback"] for r in run["passes"])

"""Programs jax compiled, or loaded from its cache, inside the timed
window (``CompileMeter``); none are expected."""


def read(run):
    return run["compile"]["compiles"]

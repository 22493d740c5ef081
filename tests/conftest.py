"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The suite never runs on the chip: the driver runs it with several
xdist workers and a chip belongs to one process.  Sharding correctness
is exercised on the host platform with
xla_force_host_platform_device_count, exactly as the driver's
dryrun_multichip harness does; what the chip's compiler makes of the
kernels is checked by tests/test_tpu_compile.py against a described
topology, and the chip itself by ``chip_smoke.py`` through the chip
tool.
"""

import fcntl
import os
import sys
import tempfile

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    import jax
    from coreth_tpu import compile_cache
    jax.config.update("jax_platforms", "cpu")
    # persistent XLA compilation cache: the keccak/replay kernels
    # compile once per machine instead of once per pytest run
    compile_cache.configure()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """``@pytest.mark.alone``: under xdist the case runs with every
    other worker waiting between two cases, so a gate on wall time (the
    two-device smoke's ratio) reads the box and not its neighbours.

    Two locks in the run's temp directory, as a fair readers-writer
    pair: every case takes ``box`` shared for its whole protocol
    (fixtures included), an ``alone`` case takes it exclusive, and each
    asks while holding ``gate`` — so an ``alone`` case that waits for
    the cases in progress keeps new ones from starting.  One process,
    or a pytest that a case spawns (no ``workerinput``), takes
    neither."""
    worker = getattr(item.config, "workerinput", None)
    if worker is None:
        yield
        return
    base = os.path.join(tempfile.gettempdir(),
                        "coreth-tests-%s" % worker["testrunuid"])
    alone = item.get_closest_marker("alone") is not None
    with open(base + ".gate", "w") as gate, \
            open(base + ".box", "w") as box:
        fcntl.flock(gate, fcntl.LOCK_EX)
        fcntl.flock(box, fcntl.LOCK_EX if alone else fcntl.LOCK_SH)
        fcntl.flock(gate, fcntl.LOCK_UN)
        yield

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

import os
import sys

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

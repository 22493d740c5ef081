"""Multi-chip scaling: meshes, shardings, collective replay.

Reference analog (SURVEY.md section 2.9): the reference's distributed
backend is gRPC + AppRequest/Gossip on the host; compute-side scaling in
the TPU build rides jax.sharding over ICI — the replay batch shards over
the ``dp`` mesh axis, account state shards over the same devices, and
per-account reductions cross shards with psum_scatter.
"""

from coreth_tpu.parallel.mesh import (  # noqa: F401
    collective_reduce,
    make_mesh,
    sharded_recover,
    sharded_slot_step,
    sharded_transfer_step,
)
from coreth_tpu.parallel.shard import (  # noqa: F401
    account_bucket,
    contract_bucket,
    exchange_mode,
    remap_rows,
    slot_bucket,
)

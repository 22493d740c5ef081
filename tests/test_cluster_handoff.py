"""Two-process cluster handoff: SIGKILL a worker mid-stream, watch the
aggregator re-assign its range from the victim's last checkpoint
record, and demand bit-identical final roots.

The matrix cell: a deterministic chain (tests/ckpt_child.py builders)
is range-partitioned into two lanes with seeded stores
(bootstrap_stores), two subprocess workers dial the coordinator, and
the victim (w0, always assigned the earliest lane) carries an armed
``serve/crash`` SIGKILL plan plus ``CORETH_CHECKPOINT_SYNC=1`` — sync
records land on the execute thread, so by the injected kill the lane
provably holds a durable record PAST its seed.  The survivor finishes
its own lane, inherits the dead lane, resumes from the victim's
record (``resumed_from`` proves it), and the cluster's final root
must equal the single-engine batch-replay truth
(``blocks[-1].header.root``) — across transfer/erc20 and both trie
backends (``CORETH_TRIE=native|py``).

The mismatch cell: the victim instead arms ``cluster/
boundary_mismatch`` (it lies about its boundary root while its store
stays correct) with forensics on.  The aggregator must refuse the
root, demand and receive the worker's bundle (paths that exist on
disk), and only then re-assign — converging to the same verified
roots because re-execution from the untouched store is honest.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu import rlp
from coreth_tpu.serve.cluster import (
    ClusterCoordinator, bootstrap_stores, partition_ranges,
)

from tests.ckpt_child import build_chain

# small engine geometry, matched to the ckpt subprocess tests: the
# point is protocol + recovery, not throughput
EKW = dict(capacity=256, batch_pad=64, window=4)

# env every worker needs: host-platform jax.  The workers place the
# suite's shared compile cache themselves (coreth_tpu.compile_cache:
# first cell pays the trace, the rest reuse it)


def _base_env():
    return {
        "JAX_PLATFORMS": "cpu",
        "CORETH_CHECKPOINT_SYNC": "1",
        "CORETH_TELEMETRY_PORT": "",  # no per-worker server in tests
    }


def _run_cluster(tmp_path, workload, victim_env, trie=None,
                 checkpoint_every=2):
    genesis, blocks = build_chain(workload)
    chain_path = os.path.join(str(tmp_path), "chain.rlp")
    with open(chain_path, "wb") as f:
        f.write(rlp.encode([b.encode() for b in blocks]))
    seeds = bootstrap_stores(genesis.config, genesis, blocks,
                             partition_ranges(len(blocks), 2),
                             str(tmp_path), engine_kw=EKW)
    env = _base_env()
    if trie is not None:
        env["CORETH_TRIE"] = trie
    coord = ClusterCoordinator(
        seeds, chain_path, config="test",
        expected_tip=blocks[-1].header.root, engine_kw=EKW,
        checkpoint_every=checkpoint_every,
        # generous: worker startup (imports + engine build) precedes
        # the first heartbeat; timeout policy is unit-tested with a
        # stepped clock in tests/test_cluster.py
        heartbeat_timeout=120.0,
        worker_env={"*": env, "w0": victim_env})
    coord.start(2)
    summary = coord.run(deadline_s=240.0)
    return summary, blocks, seeds


@pytest.mark.parametrize("trie", ["native", "py"])
@pytest.mark.parametrize("workload", ["transfer", "erc20"])
def test_cluster_handoff_matrix(tmp_path, workload, trie):
    victim = {
        # SIGKILL on the 5th commit hit: serve/crash fires BEFORE the
        # checkpoint cadence inside the same commit batch, so the kill
        # must land in the window AFTER the first full one (window=4)
        # for its sync record (every=2 -> tip 4) to be durable
        "CORETH_FAULT_PLAN": json.dumps(
            {"serve/crash": {"action": "sigkill", "after": 4}}),
    }
    summary, blocks, seeds = _run_cluster(tmp_path, workload, victim,
                                          trie=trie)
    assert summary["verified"], summary["events"]
    assert summary["final_root"] == blocks[-1].header.root.hex()
    lanes = summary["lanes"]
    # every lane's boundary root is the single-engine truth
    for lane, seed in zip(lanes, sorted(seeds, key=lambda s: s.start)):
        want = blocks[seed.end - 1].header.root.hex()
        assert lane["root"] == want, (lane["lane"], lane["root"], want)
    # the victim's lane changed hands exactly once, to the survivor
    lane0 = lanes[0]
    assert lane0["history"][0] == "w0" and len(lane0["history"]) == 2
    assert lane0["failures"] == 1
    # the replacement resumed from the victim's record, NOT the seed:
    # the record-implies-closure protocol as a handoff
    assert lane0["resumed_from"] is not None
    assert lane0["resumed_from"] > lane0["start"]
    counters = summary["counters"]
    assert counters["cluster/worker_crash"]["count"] == 1
    assert counters["cluster/reassigned"]["count"] == 1
    assert counters["cluster/boundary_mismatch"]["count"] == 0
    events = [e["event"] for e in summary["events"]]
    assert "worker_crash" in events and "reassigned" in events


def test_boundary_mismatch_demands_bundle(tmp_path):
    fdir = os.path.join(str(tmp_path), "forensics")
    victim = {
        "CORETH_FAULT_PLAN": json.dumps(
            {"cluster/boundary_mismatch": {"times": 1}}),
        "CORETH_FORENSICS": "1",
        "CORETH_FORENSICS_DIR": fdir,
    }
    summary, blocks, _seeds = _run_cluster(tmp_path, "transfer",
                                           victim)
    # the lie was caught, evidence escrowed, and recovery converged
    assert summary["verified"], summary["events"]
    assert summary["final_root"] == blocks[-1].header.root.hex()
    lane0 = summary["lanes"][0]
    assert lane0["failures"] == 1
    assert lane0["history"][0] == "w0" and len(lane0["history"]) == 2
    assert lane0["bundles"], "mismatch must surrender a bundle"
    for path in lane0["bundles"]:
        assert os.path.isdir(path), path
        manifest = os.path.join(path, "manifest.json")
        assert os.path.exists(manifest)
        with open(manifest) as f:
            data = json.load(f)
        assert any("cluster/boundary_mismatch" in str(t)
                   for t in data.get("triggers", [data])), data
    counters = summary["counters"]
    assert counters["cluster/boundary_mismatch"]["count"] == 1
    assert counters["cluster/reassigned"]["count"] == 1
    events = [e["event"] for e in summary["events"]]
    assert "boundary_mismatch" in events
    assert "bundle_received" in events
    # evidence strictly precedes the re-assignment
    assert events.index("bundle_received") < events.index("reassigned")

"""Fault injection, backend supervision, and quarantine.

Three layers under test:

1. the registry itself (coreth_tpu/faults): seeded determinism,
   after/times/prob arming, env arming, and the COMPLETENESS GATE —
   every declared injection point must appear in COVERAGE below, so a
   new point cannot land without a test that arms it;
2. the supervisor (replay/supervisor.py): bounded-backoff retries for
   transient faults, strike-counted demotion down the execution ladder
   (device OCC -> native -> interpreter), cooldown probes and
   re-promotion — with bit-identical roots throughout, because the
   ladder only ever trades speed;
3. the streaming pipeline's fault surface (serve/pipeline.py): feed
   stall/drop/malform injection, poison-block quarantine that does not
   stall later blocks, and the sequence-gap halt.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu import faults
from coreth_tpu.faults import FaultInjected, FaultPlan, FaultSpec
from coreth_tpu.metrics import default_registry
from coreth_tpu.replay.supervisor import BackendFault, BackendSupervisor
from coreth_tpu.serve import ChainFeed, StreamingPipeline

from tests.test_serve import (  # noqa: E501 — deterministic chain builders shared with the serve suite
    build_swap_chain, build_token_chain, build_transfer_chain,
    _fresh_engine,
)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """No fault state may leak out of this module: disarm any plan and
    drop the bridge's supervisor observer (a demoted native scope left
    behind would silently reroute later suites' hostexec tests)."""
    yield
    faults.disarm()
    from coreth_tpu.evm.hostexec import bridge
    bridge.set_fault_observer(None)


# ------------------------------------------------------------------ registry

def test_unarmed_points_are_noops():
    assert faults.check("device/dispatch") is None
    assert faults.fire("device/dispatch") is None
    assert faults.fired() == {}


def test_plan_after_times_and_determinism():
    plan = FaultPlan({"p": FaultSpec(after=2, times=2)}, seed=7)
    with faults.armed(plan):
        fires = [faults.check("p") is not None for _ in range(6)]
    # hits 0-1 skipped (after), 2-3 fire (times=2), 4-5 exhausted
    assert fires == [False, False, True, True, False, False]
    assert plan.fired() == {"p": 2}

    # seeded probability replays identically
    def draw(seed):
        p = FaultPlan({"q": FaultSpec(prob=0.5)}, seed=seed)
        with faults.armed(p):
            return [faults.check("q") is not None for _ in range(32)]
    assert draw(3) == draw(3)
    assert draw(3) != draw(4)  # and the seed actually matters


def test_fire_raises_with_transience():
    with faults.armed(FaultPlan({"p": FaultSpec(transient=True)})):
        with pytest.raises(FaultInjected) as ei:
            faults.fire("p")
        assert ei.value.transient and ei.value.point == "p"


def test_arm_from_env(monkeypatch, tmp_path):
    faults.disarm()
    monkeypatch.setenv(
        "CORETH_FAULT_PLAN",
        '{"seed": 3, "points": {"x/y": {"times": 1}}}')
    try:
        plan = faults.arm_from_env()
        assert plan is not None and "x/y" in plan.points
        assert plan.seed == 3
        # idempotent: a second arm (engine + pipeline both call it)
        # keeps the first plan
        assert faults.arm_from_env() is plan
    finally:
        faults.disarm()
    # @path form
    f = tmp_path / "plan.json"
    f.write_text('{"p": {"after": 1}}')
    monkeypatch.setenv("CORETH_FAULT_PLAN", "@" + str(f))
    try:
        plan = faults.arm_from_env()
        assert plan.points["p"].after == 1
    finally:
        faults.disarm()


def test_declared_points_all_covered():
    """The completeness gate: every DECLARED injection point must be
    armed by a test somewhere in the suite (entries below name it).  A
    new fault point fails this until its scenario exists."""
    # import every module that declares points
    import coreth_tpu.evm.device.adapter  # noqa: F401
    import coreth_tpu.evm.device.shard  # noqa: F401
    import coreth_tpu.evm.hostexec.backend  # noqa: F401
    import coreth_tpu.evm.hostexec.bridge  # noqa: F401
    import coreth_tpu.obs.recorder  # noqa: F401
    import coreth_tpu.obs.trace  # noqa: F401
    import coreth_tpu.replay.checkpoint  # noqa: F401
    import coreth_tpu.replay.commit  # noqa: F401
    import coreth_tpu.replay.engine  # noqa: F401
    import coreth_tpu.serve.cluster.coordinator  # noqa: F401
    import coreth_tpu.serve.cluster.worker  # noqa: F401
    import coreth_tpu.serve.pipeline  # noqa: F401
    import coreth_tpu.state.flat.exporter  # noqa: F401
    COVERAGE = {
        "device/dispatch":
            "test_faults::test_persistent_device_fault_demotes",
        "device/shard_exchange":
            "test_faults::test_shard_exchange_fault_demotes",
        "device/key_exchange":
            "test_faults::test_key_exchange_fault_demotes",
        "native/error_rc": "test_faults::test_native_error_rc",
        "native/session_loss": "test_faults::test_native_session_loss",
        "native/oracle_divergence":
            "test_faults::test_oracle_divergence_hard_demotes",
        "commit/flush_fail":
            "test_faults::test_commit_flush_transient_retries",
        "recover/fault": "test_faults::test_recover_fault_degrades",
        "serve/feed_stall": "test_faults::test_stream_feed_stall",
        "serve/feed_drop": "test_faults::test_stream_feed_drop_halts",
        "serve/malformed_block":
            "test_faults::test_stream_poison_block_quarantines",
        "serve/crash":
            "test_checkpoint_resume::test_sigkill_resume_matrix",
        "checkpoint/crash_gap":
            "test_checkpoint_resume::test_torn_checkpoint_keeps_previous",
        "flat/torn_write":
            "test_flat_state::test_torn_flat_write_retries (+ the "
            "persistent shape in "
            "test_torn_flat_write_persistent_keeps_previous)",
        "flat/stale_generation":
            "test_flat_state::test_stale_generation_handout_skipped",
        "obs/export_fail":
            "test_obs::test_export_fail_fault_counted_pipeline_unharmed",
        "obs/bundle_fail":
            "test_forensics::test_bundle_fail_fault_counted_atomic "
            "(+ the serialization shape in "
            "test_bundle_fail_partial_write_cleaned)",
        "cluster/worker_crash":
            "test_cluster_handoff::test_cluster_handoff_matrix (+ the "
            "detection unit in test_cluster::test_dead_worker_detected)",
        "cluster/heartbeat_loss":
            "test_cluster::test_heartbeat_loss_fault_drops_sends (+ "
            "timeout policy in test_heartbeat_timeout_reassigns)",
        "cluster/boundary_mismatch":
            "test_cluster_handoff::test_boundary_mismatch_demands_bundle "
            "(+ the corruption unit in "
            "test_cluster::test_boundary_mismatch_corrupts_report)",
        "cluster/reassign_race":
            "test_cluster::test_reassign_race_repicks_next_pass",
    }
    declared = set(faults.declared())
    covered = set(COVERAGE)
    assert declared == covered, (
        f"uncovered injection points: {sorted(declared - covered)}; "
        f"stale coverage entries: {sorted(covered - declared)}")


# ---------------------------------------------------------------- supervisor

def _fast_supervisor_env(monkeypatch, strikes="1", cooldown="60"):
    monkeypatch.setenv("CORETH_SUPERVISOR_RETRIES", "1")
    monkeypatch.setenv("CORETH_SUPERVISOR_BACKOFF", "0.001")
    monkeypatch.setenv("CORETH_SUPERVISOR_STRIKES", strikes)
    monkeypatch.setenv("CORETH_SUPERVISOR_COOLDOWN", cooldown)


def test_supervisor_demote_probe_promote_cycle():
    """Pure ladder arithmetic with an injected clock: strikes demote,
    the cooldown gates the probe, a probe success promotes, a probe
    failure re-demotes with a doubled cooldown."""
    now = [100.0]
    sup = BackendSupervisor(clock=lambda: now[0], sleep=lambda s: None)
    sup.strikes_to_demote = 2
    sup.cooldown = 10.0
    exc = RuntimeError("boom")
    sup.strike("device", exc)
    assert sup.allows("device")  # one strike: still healthy
    sup.strike("device", exc)
    assert sup.demoted("device") and not sup.allows("device")
    assert sup.demotions == 1
    now[0] += 5
    assert not sup.allows("device")  # cooling
    now[0] += 6
    assert sup.allows("device")      # probe window open
    sup.strike("device", exc)        # failed probe
    assert not sup.allows("device")
    assert sup.demotions == 2
    now[0] += 15
    assert not sup.allows("device")  # doubled cooldown (20s)
    now[0] += 10
    assert sup.allows("device")
    sup.note_ok("device")            # probe success
    assert not sup.demoted("device")
    assert sup.promotions == 1
    assert sup.snapshot()["demote_latency_s"]["device"] >= 0


def test_supervisor_transient_retry_then_success():
    sup = BackendSupervisor(sleep=lambda s: None)
    sup.max_retries = 3
    calls = []
    plan = FaultPlan({"p": FaultSpec(times=2, transient=True)})
    with faults.armed(plan):
        out = sup.run("device", "p", lambda: calls.append(1) or 42)
    assert out == 42
    assert sup.retries == 2 and sup.strikes == 0


def test_supervisor_persistent_fault_raises_backend_fault():
    sup = BackendSupervisor(sleep=lambda s: None)
    sup.strikes_to_demote = 1
    with faults.armed(FaultPlan({"p": FaultSpec()})):
        with pytest.raises(BackendFault):
            sup.run("device", "p", lambda: 42)
    assert sup.demoted("device")


# ------------------------------------------------- engine ladder integration

def test_transient_device_fault_retries_bit_identical(monkeypatch):
    _fast_supervisor_env(monkeypatch, strikes="3")
    genesis, blocks = build_transfer_chain()
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"device/dispatch":
                      FaultSpec(times=1, transient=True)})
    with faults.armed(plan):
        root = eng.replay(list(blocks))
    assert root == blocks[-1].header.root
    assert eng.supervisor.retries >= 1
    assert eng.supervisor.demotions == 0
    assert eng.stats.blocks_device > 0  # the retry kept the device path


def test_persistent_device_fault_demotes(monkeypatch):
    """The acceptance scenario: persistent device-dispatch failure ->
    the supervisor demotes, the whole chain completes on the host
    ladder with identical roots, and the demotion is visible in the
    metrics registry."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain()
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"device/dispatch": FaultSpec()})):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)))
        report = pipe.run()
    assert eng.root == blocks[-1].header.root
    assert report.blocks == len(blocks)
    assert eng.stats.blocks_fallback == len(blocks)
    assert eng.stats.blocks_device == 0
    assert report.supervisor["demotions"] >= 1
    assert "device" in report.supervisor["demoted_scopes"]
    assert report.faults["device/dispatch"] >= 1
    g = default_registry.get("supervisor/demotions")
    assert g is not None and g.value >= 1


def test_demoted_device_repromotes_after_cooldown(monkeypatch):
    """A fault that clears: demote on the first window, then (cooldown
    forced open) the probe succeeds, the scope re-promotes, and later
    blocks ride the device path again."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain(n_blocks=10)
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"device/dispatch":
                                 FaultSpec(times=1)})):
        half = list(blocks[:5])
        eng.replay(half)
        assert eng.supervisor.demoted("device")
        fell_back = eng.stats.blocks_fallback
        assert fell_back > 0
        # cooldown lapse (deterministic: open the probe window)
        eng.supervisor._state["device"]["until"] = 0.0
        eng.replay(list(blocks[5:]))
    assert eng.root == blocks[-1].header.root
    assert eng.supervisor.promotions >= 1
    assert not eng.supervisor.demoted("device")
    assert eng.stats.blocks_device > 0  # device path resumed


def test_machine_occ_device_fault_demotes(monkeypatch):
    """The fused-OCC dispatch path (adapter.issue) under a persistent
    fault: contained, struck, demoted; the swap chain completes on the
    host path with exact roots."""
    _fast_supervisor_env(monkeypatch)
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    genesis, blocks = build_swap_chain()
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"device/dispatch": FaultSpec()})):
        root = eng.replay(list(blocks))
    assert root == blocks[-1].header.root
    assert eng.supervisor.demotions >= 1
    assert eng.stats.blocks_fallback == len(blocks)


def test_retry_after_failed_donated_dispatch(monkeypatch):
    """The OCC slot table is DONATED into every window dispatch.  A
    dispatch that fails after the device consumed it leaves the runner
    holding a deleted buffer; the supervisor's retry must rebuild the
    table from the host mirror — not hand the kernel the dead handle
    and turn one transient fault into a demotion."""
    from coreth_tpu.evm.device.adapter import MachineWindowRunner
    _fast_supervisor_env(monkeypatch, strikes="5")
    monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
    real = MachineWindowRunner._get_kernel
    failed = []

    def flaky(self, p, occ):
        fn = real(self, p, occ)
        if failed:
            return fn

        def lost_after_launch(table, *rest):
            fn(table, *rest)
            assert table.is_deleted()  # donation is live on this backend
            failed.append(1)
            raise RuntimeError("device lost after launch")
        return lost_after_launch

    monkeypatch.setattr(MachineWindowRunner, "_get_kernel", flaky)
    genesis, blocks = build_token_chain()
    eng, _ = _fresh_engine(genesis)
    assert eng.replay(list(blocks)) == blocks[-1].header.root
    assert failed == [1]
    sup = eng.supervisor.snapshot()
    assert (sup["retries"], sup["strikes"], sup["demotions"]) == (1, 0, 0)
    assert eng.stats.blocks_fallback == 0


def test_shard_exchange_fault_demotes(monkeypatch):
    """The cross-shard collective exchange seam on a 2-device mesh."""
    import jax
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    from coreth_tpu.parallel import make_mesh
    from coreth_tpu.state import Database
    from coreth_tpu.replay import ReplayEngine
    _fast_supervisor_env(monkeypatch)
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    genesis, blocks = build_swap_chain()
    db = Database()
    gblock = genesis.to_block(db)
    eng = ReplayEngine(genesis.config, db, gblock.root,
                       parent_header=gblock.header, capacity=256,
                       batch_pad=64, window=4,
                       mesh=make_mesh(devs[:2]))
    with faults.armed(FaultPlan({"device/shard_exchange":
                                 FaultSpec()})) as plan:
        root = eng.replay(list(blocks))
        fired = plan.fired().get("device/shard_exchange", 0)
    assert root == blocks[-1].header.root
    assert fired >= 1
    assert eng.supervisor.strikes >= 1


def test_key_exchange_fault_demotes(monkeypatch):
    """The INTRA-contract key-range exchange seam (ISSUE 14): a
    persistent fault at the replica-sync collective on a 2-device mesh
    with a hot contract — contained, struck toward device demotion,
    and the chain still completes with the exact root on the host
    ladder."""
    import jax
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    from coreth_tpu.parallel import make_mesh
    from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
    from coreth_tpu.replay import ReplayEngine
    from coreth_tpu.state import Database
    from coreth_tpu.workloads.hot_contract import build_hot_chain
    _fast_supervisor_env(monkeypatch)
    monkeypatch.setenv("CORETH_NO_TOKEN_FASTPATH", "1")
    monkeypatch.setenv("CORETH_SERIAL_SHORTCIRCUIT", "0")
    monkeypatch.setenv("CORETH_MACHINE_WINDOW", "2")
    monkeypatch.setenv("CORETH_KEYRANGE_THRESHOLD", "3")
    genesis, blocks = build_hot_chain(CFG, 4, 6, n_keys=8)
    db = Database()
    gblock = genesis.to_block(db)
    eng = ReplayEngine(genesis.config, db, gblock.root,
                       parent_header=gblock.header, capacity=256,
                       batch_pad=64, window=4,
                       mesh=make_mesh(devs[:2]))
    with faults.armed(FaultPlan({"device/key_exchange":
                                 FaultSpec()})) as plan:
        root = eng.replay(list(blocks))
        fired = plan.fired().get("device/key_exchange", 0)
    assert root == blocks[-1].header.root
    assert fired >= 1
    assert eng.supervisor.strikes >= 1
    assert eng.supervisor.demotions >= 1
    assert eng.stats.blocks_fallback > 0  # host ladder finished it


def test_recover_fault_degrades(monkeypatch):
    """Sender-recovery faults degrade to the lazy per-tx python path:
    slower, never wrong."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain()
    from coreth_tpu.types import Block
    fresh = [Block.decode(b.encode()) for b in blocks]  # cold senders
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"recover/fault": FaultSpec()})) as pl:
        root = eng.replay(fresh)
        assert pl.fired().get("recover/fault", 0) >= 1
    assert root == blocks[-1].header.root
    assert eng.stats.sigs_device == 0 and eng.stats.sigs_host == 0


def test_commit_flush_transient_retries(monkeypatch):
    _fast_supervisor_env(monkeypatch, strikes="5")
    monkeypatch.setenv("CORETH_SUPERVISOR_RETRIES", "3")
    genesis, blocks = build_transfer_chain()
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"commit/flush_fail":
                      FaultSpec(times=2, transient=True)})
    with faults.armed(plan):
        root = eng.replay(list(blocks))
    assert root == blocks[-1].header.root
    assert eng.supervisor.retries >= 2
    # and a PERSISTENT flush failure is fatal (no alternative backend)
    eng2, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"commit/flush_fail": FaultSpec()})):
        with pytest.raises(FaultInjected):
            eng2.replay(list(blocks))


# ------------------------------------------------------------ native boundary

def _hostexec_available():
    from coreth_tpu.evm.hostexec.backend import load_hostexec
    return load_hostexec() is not None


def test_native_session_loss(monkeypatch):
    """Session loss at bridge setup: the interpreter serves every tx;
    roots unchanged.  (Fires before the library probe, so this runs
    on toolchain-less boxes too.)"""
    _fast_supervisor_env(monkeypatch)
    monkeypatch.setenv("CORETH_MACHINE", "0")  # host path -> bridge
    genesis, blocks = build_swap_chain()
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"native/session_loss":
                                 FaultSpec()})) as plan:
        root = eng.replay(list(blocks))
        fired = plan.fired().get("native/session_loss", 0)
    assert root == blocks[-1].header.root
    assert fired >= 1
    from coreth_tpu.evm.hostexec import bridge
    assert bridge.counters().get("session_faults", 0) >= 1


def test_native_error_rc(monkeypatch):
    """Error rc from the native session: per-tx interpreter fallback +
    native-scope strikes -> demotion; the chain completes with exact
    roots on the interpreter."""
    if not _hostexec_available():
        pytest.skip("hostexec native ABI unavailable")
    _fast_supervisor_env(monkeypatch, strikes="2")
    monkeypatch.setenv("CORETH_MACHINE", "0")
    genesis, blocks = build_swap_chain()
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"native/error_rc":
                                 FaultSpec()})) as plan:
        root = eng.replay(list(blocks))
        fired = plan.fired().get("native/error_rc", 0)
    assert root == blocks[-1].header.root
    assert fired >= 1
    assert eng.supervisor.strikes >= 1
    assert eng.supervisor.demoted("native")


def test_oracle_divergence_hard_demotes(monkeypatch):
    """An armed-oracle divergence hard-demotes the native scope
    IMMEDIATELY (a wrong backend, not a slow one); the interpreter's
    result is authoritative and the replay proceeds bit-identical."""
    if not _hostexec_available():
        pytest.skip("hostexec native ABI unavailable")
    _fast_supervisor_env(monkeypatch, strikes="99")  # hard path only
    monkeypatch.setenv("CORETH_MACHINE", "0")
    monkeypatch.setenv("CORETH_HOST_EXEC_CHECK", "1")
    genesis, blocks = build_swap_chain()
    eng, _ = _fresh_engine(genesis)
    with faults.armed(FaultPlan({"native/oracle_divergence":
                                 FaultSpec(times=1)})) as plan:
        root = eng.replay(list(blocks))
        fired = plan.fired().get("native/oracle_divergence", 0)
    assert root == blocks[-1].header.root
    assert fired == 1
    assert eng.supervisor.demotions >= 1  # one divergence was enough
    from coreth_tpu.evm.hostexec import bridge
    assert bridge.counters().get("oracle_divergences", 0) >= 1


# ------------------------------------------------------------- serve faults

def test_stream_feed_stall(monkeypatch):
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain()
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"serve/feed_stall":
                      FaultSpec(action="stall", delay=0.002, times=5)})
    with faults.armed(plan):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)))
        report = pipe.run()
    assert eng.root == blocks[-1].header.root
    assert report.feed_stalls >= 5
    assert report.halted is None


def test_stream_feed_drop_halts(monkeypatch):
    """A silently dropped block surfaces as a NAMED sequence-gap halt
    (not a baffling root mismatch downstream); the committed prefix is
    intact, and a second stream over the missing tail completes to the
    exact final root — the operator's refetch story."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain(n_blocks=8)
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"serve/feed_drop": FaultSpec(after=3, times=1)})
    with faults.armed(plan):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)))
        report = pipe.run()
    assert report.feed_drops == 1
    assert report.halted is not None and "sequence gap" in report.halted
    n = report.blocks
    assert n == 3  # the prefix before the dropped block
    assert eng.root == blocks[n - 1].header.root
    # refetch: stream the tail (including the dropped block) to the end
    pipe2 = StreamingPipeline(eng, ChainFeed(list(blocks[n:])))
    pipe2.run()
    assert eng.root == blocks[-1].header.root


def test_stream_poison_block_quarantines(monkeypatch):
    """The acceptance scenario's second half: a malformed block — it
    executes fine but its header lies — fails validation on EVERY
    backend, quarantines (state applied, block parked + reported), and
    later blocks commit normally with bit-identical final roots."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain(n_blocks=10)
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"serve/malformed_block":
                      FaultSpec(after=4, times=1)})
    with faults.armed(plan):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)))
        report = pipe.run()
    assert report.halted is None  # later blocks were NOT stalled
    assert len(report.quarantined) == 1
    q = report.quarantined[0]
    assert q["number"] == blocks[4].number
    assert any("receipt root mismatch" in r for r in q["reasons"])
    assert report.blocks == len(blocks)  # quarantined one included
    assert eng.stats.blocks_quarantined == 1
    # the corrupted copy only lied about receipts: state transitions
    # are unchanged, so the final root matches the true chain exactly
    assert eng.root == blocks[-1].header.root
    assert default_registry.get("serve/quarantined").value >= 1


def test_stream_strict_mode_raises_on_poison(monkeypatch):
    from coreth_tpu.replay.engine import ReplayError
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_transfer_chain()
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"serve/malformed_block":
                      FaultSpec(after=2, times=1)})
    with faults.armed(plan):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)),
                                 quarantine=False)
        with pytest.raises(ReplayError):
            pipe.run()


def test_stream_token_poison_quarantines(monkeypatch):
    """Quarantine on the token fast path (storage slots + logs in
    play) — the rewind + host retry + tolerant apply must hold there
    too."""
    _fast_supervisor_env(monkeypatch)
    genesis, blocks = build_token_chain()
    eng, _ = _fresh_engine(genesis)
    plan = FaultPlan({"serve/malformed_block":
                      FaultSpec(after=1, times=1)})
    with faults.armed(plan):
        pipe = StreamingPipeline(eng, ChainFeed(list(blocks)))
        report = pipe.run()
    assert len(report.quarantined) == 1
    assert eng.root == blocks[-1].header.root


# -------------------------------------------------------------- warp metric

def test_warp_peer_faults_counted():
    """Satellite: the aggregator's silent peer-fault skip is now a
    counted metric (warp/peer_faults) + a per-aggregator counter."""
    from tests.test_warp import (
        CALLER, N_VALIDATORS, NETWORK_ID, SKS, SOURCE_CHAIN, VSET)
    from coreth_tpu.warp import (
        AddressedCall, Aggregator, UnsignedMessage, WarpBackend)

    msg = UnsignedMessage(NETWORK_ID, SOURCE_CHAIN,
                          AddressedCall(CALLER, b"faulty peers").encode())
    backends = {bytes([i]) * 20: WarpBackend(NETWORK_ID, SOURCE_CHAIN,
                                             SKS[i])
                for i in range(N_VALIDATORS)}
    for b in backends.values():
        b.add_message(msg)
    wedged = {bytes([0]) * 20}  # 3/4 healthy still clears 67% quorum

    def fetch(node_id, m):
        if node_id in wedged:
            raise ConnectionError("peer wedged")
        return backends[node_id].get_message_signature(m.id())

    before = default_registry.get("warp/peer_faults")
    before_n = before.value if before is not None else 0
    agg = Aggregator(VSET, fetch)
    signed = agg.aggregate(msg)
    assert signed is not None
    assert agg.peer_faults == 1
    assert default_registry.get("warp/peer_faults").value == before_n + 1

"""chip_smoke.py's phases at a toy size on the CPU.

The script itself only runs on a TPU (it refuses anything else, and the
driver runs it there); these cases call its phase FUNCTIONS so a change
to an engine counter, a chain builder or the pipeline report breaks a
tier-1 test instead of the next chip run.  Senders are recovered by
the native batch, as on the chip; the device ladder's proof is the
smoke's ``recover`` phase, here at its smallest bucket.
"""

import json

import jax
import pytest

import chip_smoke as cs
from coreth_tpu import nativebuild

TOY = cs.Sizes(n_keys=16, txs=8, window=2, capacity=256,
               slot_capacity=64, erc20_txs=8, machine_window=2,
               hot_keys=8, hot_txs=8, hot_capacity=256, hot_window=2,
               stream_window=2, hot_blocks=5)


@pytest.fixture(scope="module")
def meter():
    return cs.CompileMeter()


@pytest.fixture
def toy(monkeypatch):
    # chains are rebuilt, never written into the checkout's cache
    monkeypatch.setattr(cs, "_cached_chain", lambda name, build: build())
    return TOY


@pytest.mark.parametrize("phase", [
    "transfer", "erc20", "erc20_machine", "conflicts", "streaming"])
def test_phase_passes_at_toy_size(phase, toy, meter):
    row = getattr(cs, "phase_" + phase)(meter, toy)
    assert row["failures"] == [], row
    assert row["root_ok"] and row["blocks_fallback"] == 0
    assert row["sigs_host"] > 0 and row["sigs_device"] == 0
    assert row["recover_degraded"] == 0
    assert row["compile"]["compiles"] >= 0 and "reduced" in row
    if phase in ("erc20_machine", "conflicts"):
        m = row["machine"]
        assert m["machine_blocks"] == row["blocks"]
        assert m["kernel_retraces"] == 0 and m["host_txs"] == 0
    if phase == "conflicts":
        # the Zipf hot-contract chain conflicts on computed keys and
        # stays on device OCC (no serial short-circuit)
        assert row["machine"]["occ_rounds"] > 0
        assert row["machine"]["serial_blocks"] == 0
    if phase == "transfer":
        assert row["reduced"]["chain_blocks"]["smoke"] == TOY.chain_blocks
    json.dumps({k: v for k, v in row.items() if not k.startswith("_")})


def test_recover_phase_proves_the_ladder_bucket_by_bucket(toy, meter):
    """The toy chain fills one bucket: one probe, equal addresses, and
    the report's two columns read from its runs."""
    row = cs.phase_recover(meter, toy)
    assert row["failures"] == [], row
    have = TOY.chain_blocks * TOY.txs
    assert [(p["n"], p["pad"]) for p in row["probes"]] == [(have, 64)]
    probe = row["probes"][0]
    assert probe["equal"] and len(probe["device_s"]) == 3
    assert set(row["table"]["launch_s"]) == {64}
    assert set(row["table"]["host_s"]) == {have}
    assert set(cs.PHASES) == {"transfer", "recover", "erc20",
                              "erc20_machine", "conflicts", "streaming"}
    json.dumps(row)


def test_mesh_phase_on_four_virtual_devices(toy, meter):
    """The --chips 4 phase on the conftest's virtual CPU mesh: mesh and
    single-device roots equal the headers, tables quartered."""
    row = cs.phase_mesh(meter, toy, devices=jax.devices("cpu"))
    assert row["failures"] == [], row
    for tag in ("transfer", "hot_contract"):
        assert row[tag]["single"]["root_ok"] and row[tag]["mesh"]["root_ok"]
    bal = row["transfer"]["tables"]["balances"]
    assert sorted(s["device"] for s in bal) == [0, 1, 2, 3]
    assert {s["rows"] for s in bal} == {TOY.capacity // 4}
    occ = row["hot_contract"]["tables"]["occ_table"]
    assert len({s["device"] for s in occ}) == 4


def test_failures_name_a_run_that_only_looked_healthy():
    """A matching root is not enough: host fallbacks, degraded
    recoveries, supervisor activity and senders the native batch did
    not recover each fail the phase."""
    row = {"blocks": 3, "root_ok": True, "blocks_fallback": 1,
           "blocks_device": 2, "sigs_device": 0, "sigs_host": 0,
           "recover_degraded": 2,
           "supervisor": {"retries": 1, "strikes": 0, "demotions": 0},
           "dispatches": 0}
    bad = cs.replay_failures(row, machine=True)
    assert not any("native batch" in b for b in cs.replay_failures(
        dict(row, sigs_host=24), machine=True))
    for needle in ("blocks_fallback=1", "blocks_device=2",
                   "not recovered by the native batch",
                   "recover_degraded=2", "supervisor.retries=1",
                   "machine path never ran"):
        assert any(needle in b for b in bad), (needle, bad)


def test_main_refuses_without_a_tpu(capsys):
    """JAX_PLATFORMS=cpu (the suite's platform): non-zero exit before
    any work, and no result line on stdout."""
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refusing" in out.err


def test_build_phase_requires_the_native_seams(monkeypatch):
    # the rebuild itself is not re-run here: xdist workers share the .so
    monkeypatch.setattr(nativebuild, "rebuild", nativebuild.lib_path)
    row = cs.phase_build()
    assert row["failures"] == [], row
    assert row["native_lib"].endswith("libcoreth_native.so")


def test_broken_native_build_raises(monkeypatch):
    """CXX=false: the forced rebuild fails loudly (make stops at the
    first command, the .so on disk is untouched) instead of leaving a
    stale or missing library to the pure-Python fallbacks."""
    monkeypatch.setenv("CXX", "false")
    with pytest.raises(RuntimeError, match="native build failed"):
        nativebuild.rebuild()

"""The benchmark's harness at toy size on the CPU (tier-1).

The benchmark itself only runs on a TPU.  These cases keep its pieces
from rotting between chip runs: a whole run of each cell with the look
for a chip skipped — the chain written by the builder child into the
cache from a temporary configuration file, as a real run takes it — is
correct, every account of the plain reference's book read back and the
engine's root equal to the reference's own; it is NOT correct with a
control or any fault planted under the timed path, and the control that
commits silently at the engine's own root is failed by the plain
reference alone; a run that leaves the device path is not correct
either; ``run.py`` refuses to run without a TPU; the plain reference's
trie root on hand-made tries; the trace reduction on hand-made
intervals (overlap, gap, empty); every name in BENCHMARK.json resolves
to a file.  No host-clock assertion anywhere.
"""

import argparse
import copy
import json
import os
import re
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from benchlib import faults, harness, names, plainref, replay_pass  # noqa: E402
from benchlib.trace_reduce import label_gaps, reduce_intervals  # noqa: E402

SPEC = names.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 2028  # the driver's seeds are large
TOY_BLOCKS = 5


@pytest.fixture
def toy_cell(request, monkeypatch, tmp_path):
    """The cell's configuration with every size cut to a toy, written
    to a temporary file that the builder child reads."""
    cell, entry, config, traffic = names.resolve_cell(SPEC, request.param)
    config = copy.deepcopy(config)
    config["name"] = "toy-" + config["name"]
    config["chain_blocks"] = TOY_BLOCKS
    if "accounts" in config["chain"]:
        config["chain"]["accounts"] = 16
        config["txs_per_block"] = 8
    config["engine"] = dict(batch_pad=8, window=2, capacity=256,
                            slot_capacity=64)
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(config))
    monkeypatch.setattr(
        names, "resolve_cell",
        lambda spec, wl: (cell, dict(entry, file=str(path)), config,
                          traffic))
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    return cell, config, traffic


def run_toy(workload, trace=0):
    args = argparse.Namespace(workload=workload, seed=SEED, seconds=0.01,
                              trace=trace)
    result = harness.run_cell(args, time.monotonic(), require_tpu=False)
    assert list(result)[:5] == list(harness.RESULT_KEYS)
    assert list(result)[-1] == "compared"
    json.dumps(result)
    return result


@pytest.mark.parametrize("toy_cell", CELLS, indirect=True)
def test_whole_run_is_correct(toy_cell):
    """The rest of a run with the look for a chip skipped; the chain
    comes from the builder child through the cache."""
    cell, config, traffic = toy_cell
    result = run_toy(cell["name"], trace=1)
    compared = result["compared"]
    assert result["correct"] is True and result["failed"] == 0, compared
    assert result["attempted"] >= TOY_BLOCKS
    assert all(n["value"] == 0 for n in compared.values())
    # every account of the plain reference's book was read back, and
    # the book folds to a root of its own
    txs = config["txs_per_block"]
    assert compared["accounts_off_ledger"]["of"] >= 3
    for name in ("passes_off_ledger_root", "blocks_fallback",
                 "blocks_off_device"):
        assert name in compared
    metrics = result["metrics"]
    wanted = {m["name"] for m in names.cell_metrics(
        SPEC, "per_layer", cell["name"])}
    # the CPU has no device plane: the idle share is left out, never 0
    assert set(metrics) == wanted - {"device_idle_share"}
    assert metrics["fallback_blocks"]["value"] == 0
    assert metrics["compiles_in_window"]["value"] == 0
    shares = [k for k in metrics if k.endswith("_share")
              and k != "sigs_device_share"]
    assert abs(sum(metrics[k]["value"] for k in shares) - 100.0) < 1e-6
    # another seed: other identities, the same shapes
    from benchlib import chains
    from coreth_tpu.types import Block
    wires = [chains.build_wire(config, traffic, s)[1]
             for s in (SEED, SEED + 1)]
    assert wires[0] != wires[1]
    assert [[len(Block.decode(w).transactions) for w in wire]
            for wire in wires] == [[txs] * TOY_BLOCKS] * 2


@pytest.mark.parametrize("toy_cell", ["p2p-1k.catchup"], indirect=True)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_whole_run_is_not_correct_with_the_path_broken(toy_cell, fault):
    """With a control or a fault under the timed path ``correct`` comes
    out false.  ``silent_alter`` commits at the engine's own root with
    no complaint from the engine: the plain reference alone fails it."""
    with faults.planted(fault):
        result = run_toy("p2p-1k.catchup")
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    if fault == "silent_alter":
        assert over == {"accounts_off_ledger", "passes_off_ledger_root"}
        assert compared["accounts_off_ledger"]["value"] == 2
    else:
        assert over


@pytest.mark.parametrize("toy_cell", ["valuetx.catchup"], indirect=True)
def test_a_run_off_the_device_path_is_not_correct(toy_cell, monkeypatch):
    """Right roots by the host fallback are not a run of this cell."""
    def all_fallback(engine, blocks):
        for b in blocks:
            engine._fallback(b)
    monkeypatch.setattr(replay_pass, "run_engine", all_fallback)
    result = run_toy("valuetx.catchup")
    compared = result["compared"]
    assert result["correct"] is False
    assert compared["blocks_fallback"]["value"] >= TOY_BLOCKS
    assert compared["passes_off_ledger_root"]["value"] == 0
    assert compared["accounts_off_ledger"]["value"] == 0


def test_plain_reference_trie_root():
    """The reference's own RLP + hex-prefix trie against the empty
    root every Ethereum client knows, and against the program's trie on
    random accounts: two implementations that share no code have to
    agree."""
    from coreth_tpu.mpt import EMPTY_ROOT
    from coreth_tpu.state import Database
    import random
    assert plainref.trie_root({}).hex() == (
        "56e81f171bcc55a6ff8345e692c0f86e5b48e01b996cadc001622fb5e363b421")
    rng = random.Random(7)
    for n in (1, 2, 17, 300):
        pairs = {rng.randbytes(20): plainref.account_rlp(
            rng.randrange(5), rng.randrange(1, 10**30)) for _ in range(n)}
        trie = Database().open_trie(EMPTY_ROOT)
        for k, v in pairs.items():
            trie.update(k, v)
        assert plainref.trie_root(pairs) == trie.hash(), n


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "refusing to run" in proc.stderr


@pytest.mark.parametrize("intervals,span,busy,idle,gaps", [
    # overlap: the union, not the sum
    ([(0.0, 1.0, "a"), (0.5, 1.0, "b")], None, 1.5, 0.0, []),
    # a gap inside, and the span's own ends count as idle
    ([(1.0, 1.0, "a"), (3.0, 1.0, "a")], (0.0, 5.0), 2.0, 0.6,
     [(0.0, 1.0), (2.0, 1.0), (4.0, 1.0)]),
])
def test_trace_reduce_on_hand_made_intervals(intervals, span, busy, idle,
                                             gaps):
    # nothing ran: nothing to read, not "100% idle"
    assert reduce_intervals([], (0.0, 1.0)) is None
    assert reduce_intervals([(0.0, 0.0, "a")]) is None
    red = reduce_intervals(intervals, span)
    assert red["busy_s"] == pytest.approx(busy)
    assert red["idle_share"] == pytest.approx(idle)
    assert sorted(red["gaps"]) == gaps
    assert red["ops"][0][0] == "a"
    labelled = label_gaps(red["gaps"], [(0.0, 10.0, "bench/pass"),
                                        (1.9, 1.2, "bench/replay")])
    assert all(n in ("bench/pass", "bench/replay") for n, _d in labelled)


def test_every_name_in_benchmark_json_resolves():
    """A later PR adds a cell by adding files and one entry: the
    harness finds each by its name and lists none of them."""
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_re = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for cell in SPEC["workloads"]:
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        assert name_re.match(cell["name"]) and name_re.match(cell["traffic"])
        _c, entry, config, traffic = names.resolve_cell(SPEC, cell["name"])
        assert entry["file"].startswith("benchmarks/configs/")
        assert config["name"] == cell["config"] and config["chips"] == 1
        assert set(entry["reduced"]) == set(config["reduced"])
        for key in ("source", "assumed", "reduced", "guarantees", "engine",
                    "env", "expect", "program_defaults"):
            assert key in config, (cell["name"], key)
        assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
        assert all(k.startswith("CORETH_") for k in config["env"])
        builder, _ = names.load_named("chains", config["chain"]["builder"])
        for fn in ("genesis", "gen", "ledger", "read_back"):
            assert callable(getattr(builder, fn)), fn
        driver, _ = names.load_named("drivers", traffic["driver"])
        assert callable(driver.drive)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert name_re.match(m["name"]) and unit_re.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        reader, _ = names.load_named("metrics", m["name"])
        assert callable(reader.read)
        # a reader that finds nothing to read returns nothing
        assert reader.read({"passes": [], "window_s": 0.0, "trace": None,
                            "compile": {"compiles": 0}, "spec": SPEC,
                            "cell": SPEC["workloads"][0]}) in (None, 0)

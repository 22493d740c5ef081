"""The token cell's own controls and faults, at toy size on the CPU
(tier-1).  ``test_harness.py`` runs every cell clean and pins its fault
cases to the cells it was written with; the same cases for
``p2p-token-1k.catchup`` are here: every fault in ``faults.FAULTS``
makes ``correct`` false, ``silent_alter`` (one token unit more, in the
chain itself) is failed by the plain reference alone, and a run that
is right but left the fused device path — every block through the
engine's host fallback — is not a run of this cell.
"""

import pytest

from test_harness import (  # noqa: F401 — toy_cell is a fixture
    TOY_BLOCKS, faults, replay_pass, run_toy, toy_cell)

CELL = "p2p-token-1k.catchup"


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_token_run_is_not_correct_with_the_path_broken(toy_cell, fault):
    with faults.planted(fault):
        result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    if fault == "silent_alter":
        # the host processor and the engine agree on every header of
        # the altered chain; the book does not: two holders' slots are
        # off by one unit (and the sender's fee, where the unit more
        # changed a calldata byte from zero)
        assert over == {"accounts_off_ledger", "passes_off_ledger_root"}
        assert 2 <= compared["accounts_off_ledger"]["value"] <= 4
        assert compared["passes_off_header_root"]["value"] == 0
    else:
        assert over


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_a_token_run_off_the_machine_is_not_correct(toy_cell,
                                                    monkeypatch):
    """Right roots and a right ledger by the host fallback: every
    compared number of the answer is 0, the path's are not."""
    from coreth_tpu.replay.machine_block import MachineBlockExecutor
    monkeypatch.setattr(MachineBlockExecutor, "classify",
                        lambda self, block: None)
    result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False
    for name in ("blocks_fallback", "blocks_off_device",
                 "machine.blocks_off_machine"):
        assert compared[name]["value"] >= TOY_BLOCKS, name
    for name in ("passes_off_header_root", "passes_off_ledger_root",
                 "accounts_off_ledger", "machine.host_txs"):
        assert compared[name]["value"] == 0, name

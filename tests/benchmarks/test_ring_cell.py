"""The ring cell's own controls and faults, at toy size on the CPU
(tier-1).  ``test_harness.py`` runs every cell clean and pins its fault
cases to the cells it was written with; the same cases for
``ring1k.catchup`` are here: every fault in ``faults.FAULTS`` makes
``correct`` false, ``silent_alter`` (every value one wei LESS from one
transaction on, in the chain itself: one wei more is insolvent in a
ring) is failed by the plain reference alone, and a run that is right
but sent every block to the host — what the program did with this
chain before the transfer window's solvency check went in block order
— is not a run of this cell.  The builder's fill is upstream's loop
arithmetic, and the ring leaves the money on one account.
"""

import pytest

from test_harness import (  # noqa: F401 — toy_cell is a fixture
    SEED, SPEC, TOY_BLOCKS, faults, names, plainref, replay_pass, run_toy,
    toy_cell)

CELL = "ring1k.catchup"


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_ring_run_is_not_correct_with_the_path_broken(toy_cell, fault):
    with faults.planted(fault):
        result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    if fault == "silent_alter":
        # the host processor and the engine agree on every header of
        # the altered chain and it stays on the device path; the book
        # does not: the account the wei stayed on, and the one the
        # money ended on
        assert over == {"accounts_off_ledger", "passes_off_ledger_root"}
        assert compared["accounts_off_ledger"]["value"] == 2
        assert compared["passes_off_header_root"]["value"] == 0
        assert compared["blocks_fallback"]["value"] == 0
    else:
        assert over


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_a_ring_run_on_the_host_path_is_not_correct(toy_cell, monkeypatch):
    """Right roots and a right ledger by the host fallback, block after
    block: every compared number of the answer is 0, the path's are
    not."""
    def all_fallback(engine, blocks):
        for b in blocks:
            engine._fallback(b)
    monkeypatch.setattr(replay_pass, "run_engine", all_fallback)
    result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False
    for name in ("blocks_fallback", "blocks_off_device"):
        assert compared[name]["value"] >= TOY_BLOCKS, name
    for name in ("passes_off_header_root", "passes_off_ledger_root",
                 "accounts_off_ledger"):
        assert compared[name]["value"] == 0, name


@pytest.mark.parametrize("parent_gas_limit,txs", [(15_000_000, 713),
                                                  (8_000_000, 379)])
def test_full_size_fill_is_upstreams_loop(parent_gas_limit, txs):
    """At the FULL configuration, without building the chain: upstream's
    ``gas -= TxGas; if gas < TxGas {break}`` over the parent's gas limit
    and the configuration's ``txs_per_block`` agree on 713 at Cortina's
    15,000,000; the default genesis limit of 8,000,000 would hold 379."""
    _cell, _entry, config, _traffic = names.resolve_cell(SPEC, CELL)
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    c = config["chain"]
    assert builder.fill(parent_gas_limit, c["tx_gas"],
                        config["txs_per_block"]) == txs
    assert builder.fill(parent_gas_limit, c["tx_gas"], 8) == 8
    assert config["txs_per_block"] == 713
    assert c["gas_limit"] == builder.CORTINA_GAS_LIMIT == 15_000_000
    assert config["reduced"] == {} and c["accounts"] == 1000


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_the_ring_leaves_the_money_on_one_account(toy_cell):
    """The ledger's book after the toy chain: every ring account the
    money passed through is at exactly 0 with its nonces spent, ONE
    holds what is left, the coinbase holds the fees, and nothing was
    made or lost."""
    _cell, config, traffic = toy_cell
    builder, _ = names.load_named("chains", config["chain"]["builder"])
    book = builder.ledger(config, traffic, SEED)
    accounts = book.accounts()
    rich = [a for a, (_n, bal) in accounts.items()
            if bal and a != plainref.COINBASE]
    assert len(rich) == 1
    txs = TOY_BLOCKS * config["txs_per_block"]
    assert sum(n for n, _b in accounts.values()) == txs
    assert len(accounts) == config["chain"]["accounts"] + 1
    assert sum(b for _n, b in accounts.values()) \
        == config["chain"]["root_funds"]

"""The bootstrap cell at toy size on the CPU (tier-1).

``test_harness.py`` runs every cell clean — ``valuetx.bootstrap`` among
them, picked up from BENCHMARK.json — and pins its fault cases to the
cells it was written with.  Here: the cell's own numbers (the sibling's
counters, exact; what is counted as accepted), every control and fault
of ``faults.FAULTS`` planted under the VM's pass through the same
``replay_pass.run_engine`` the other cells use, the cell's own fault
(the sibling accepted in the chain's block's place), the five readers
of layer "consensus plugin", the configuration against its source's,
and ``plainsnow`` against a script written out by hand.
"""

import json

import pytest

from test_harness import (  # noqa: F401 — toy_cell is a fixture
    SPEC, TOY_BLOCKS, faults, harness, names, run_toy, toy_cell)

from benchlib import plainref, plainsnow

CELL = "valuetx.bootstrap"
READERS = ("vm_parse_us_per_block", "vm_verify_us_per_block",
           "vm_accept_us_per_block", "engine_block_us",
           "vm_rollbacks_per_pass")


@pytest.fixture
def pass_rows(monkeypatch):
    """The rows of the timed passes, as the harness compared them."""
    seen = []
    real = harness.compare

    def compare(rows, *args):
        seen.extend(rows)
        return real(rows, *args)

    monkeypatch.setattr(harness, "compare", compare)
    return seen


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_bootstrap_run_counts_what_consensus_accepted(toy_cell, pass_rows):
    result = run_toy(CELL, trace=1)
    compared = result["compared"]
    assert result["correct"] is True and result["failed"] == 0, compared
    assert all(n["value"] == 0 for n in compared.values())
    for name in ("vm.accepted_off_engine", "vm.status_off_reference",
                 "vm.rollbacks_off_plan", "vm.engine_off_accepted",
                 "blocks_off_device"):
        assert name in compared
    assert compared["accounts_off_ledger"]["of"] == 3
    assert pass_rows
    for row in pass_rows:
        # every block but the last on the engine's tip, and the
        # sibling; the last on the host path, then once more on the
        # engine; one rollback of one block
        assert row["vm"] == {
            "blocks_verified_device": TOY_BLOCKS, "blocks_verified_host": 1,
            "blocks_accepted": TOY_BLOCKS, "blocks_rejected": 1,
            "engine_rollbacks": 1, "blocks_reapplied": 1,
            "accepted_off_engine": 0, "rollbacks_off_plan": 0,
            "engine_off_accepted": 0, "status_off_reference": 0}
        assert row["blocks"] == row["txs_committed"] == TOY_BLOCKS
        assert row["blocks_device"] == TOY_BLOCKS + 1
        assert row["window_uploads"] == TOY_BLOCKS + 1
        assert row["decode_s"] == 0.0 and row["error"] is None
        assert len(row["_script"]) == 2 * TOY_BLOCKS + 2
    metrics = result["metrics"]
    for name in READERS:
        assert metrics[name]["value"] > 0, name
    assert metrics["vm_rollbacks_per_pass"]["value"] == 1
    assert metrics["fallback_blocks"]["value"] == 0


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_bootstrap_run_is_not_correct_with_the_path_broken(toy_cell,
                                                           fault):
    """``benchlib/faults.py`` plants its five under
    ``replay_pass.run_engine``, which this cell's pass calls with the
    consensus client in the engine's place."""
    with faults.planted(fault):
        result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    if fault == "silent_alter":
        # the VM and the engine agree with every header of the altered
        # chain; the plain reference's book does not
        assert over == {"accounts_off_ledger", "passes_off_ledger_root"}
        assert compared["accounts_off_ledger"]["value"] == 2
    else:
        assert "passes_off_header_root" in over


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_bootstrap_run_with_the_sibling_accepted_is_not_correct(
        toy_cell, monkeypatch):
    """The cell's own fault: consensus's client accepts the sibling and
    rejects the chain's block.  The VM does as it is told (statuses and
    counters agree with the contract); the answer is off the last
    header's root and off the book by the sibling's extra wei."""
    driver, _ = names.load_named("drivers", "snowman_bootstrap")
    monkeypatch.setattr(driver, "FAULT", "sibling_accepted")
    result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    # the engine held the sibling, so its accept rolls nothing back:
    # the path's own counter says so too
    assert over == {"passes_off_header_root", "passes_off_ledger_root",
                    "accounts_off_ledger", "vm.rollbacks_off_plan"}
    assert compared["accounts_off_ledger"]["value"] == 2
    assert compared["vm.status_off_reference"]["value"] == 0


def test_engine_off_accepted_reads_what_the_engine_holds():
    """The harness's read-back goes under the last accepted header's
    root; this number reads the ENGINE: its root, its device rows, its
    flat layer.  A block verified and not accepted is on the engine and
    not in the accepted state (root, sender, recipient, coinbase); the
    sibling undone and the accepted block run again leave nothing."""
    from benchlib import chains
    from benchlib.genesis_bytes import genesis_to_json
    driver, _ = names.load_named("drivers", "snowman_bootstrap")
    _c, _e, config, traffic = names.resolve_cell(SPEC, CELL)
    genesis, wire = chains.build_wire(dict(config, chain_blocks=2),
                                      traffic, 77)
    now = [0]
    vm = driver._boot(genesis_to_json(genesis).encode(),
                      dict(window=2, capacity=256, slot_capacity=64), now)
    engine = vm.chain.state_processor.engine

    def off():
        return driver.engine_off_accepted(engine, vm.chain)

    assert off() == 0
    first, last = (vm.parse_block(w) for w in wire)
    now[0] = last.timestamp
    first.verify()
    assert off() == 4              # the root and the block's 3 accounts
    first.accept()
    assert off() == 0
    sibling = vm.parse_block(
        genesis.sibling([first.block]).encode())
    sibling.verify()               # on the engine
    last.verify()                  # beside it: the host path
    assert off() == 4
    last.accept()                  # the sibling undone, `last` run again
    assert off() == 0
    sibling.reject()
    assert off() == 0 and engine is vm.chain.state_processor.engine
    vm.shutdown()


def test_configuration_is_insertchain_valuetx_behind_the_vm():
    """The chain is ``insertchain-valuetx``'s key for key but the
    builder's name, and the builder's chain is ``value_tx``'s byte for
    byte; only the chain's length is reduced, and says why."""
    _c, entry, config, traffic = names.resolve_cell(SPEC, CELL)
    _c, _e, source, _t = names.resolve_cell(SPEC, "valuetx.catchup")
    assert entry["reduced"] == ["chain_blocks"] == list(config["reduced"])
    assert config["chain_blocks"] == 2500 < source["chain_blocks"]
    assert {**config["chain"], "builder": "value_tx"} == source["chain"]
    for key in ("txs_per_block", "engine", "env"):
        assert config[key] == source[key], key
    assert set(source["expect"]["zero"]) < set(config["expect"]["zero"])
    assert source["guarantees"] == config["guarantees"][:4]
    assert traffic["siblings_per_pass"] == 1 and traffic["accept_lag"] == 0
    from benchlib import chains
    toy = dict(config, chain_blocks=3)
    assert chains.build_wire(toy, traffic, 77)[1] == chains.build_wire(
        dict(toy, chain=source["chain"]), traffic, 77)[1]


def test_plainsnow_on_a_script_written_out_by_hand():
    """A(1) accepted; B and C on A, D on C, all verified; C accepted, B
    rejected, D accepted: statuses, the last accepted id and the
    accepted book after every call, and the calls the contract does not
    allow."""
    me, you = b"\x01" * 20, b"\x02" * 20
    ids = {k: k.encode() * 32 for k in "GABCD"}
    snow = plainsnow.Snow(ids["G"], plainref.Book({me: 10**18}))

    def pay(wei):
        return lambda book: book.transfer(me, you, wei, 21_000, 10**9)

    fee = 21_000 * 10**9
    snow.verify(ids["A"], ids["G"], 1, pay(1))
    assert snow.status(ids["A"]) == plainsnow.PROCESSING
    assert snow.last_accepted == ids["G"]           # verified, not accepted
    assert snow.accepted_book().accounts() == {me: (0, 10**18)}
    snow.accept(ids["A"])
    assert snow.accepted_book().accounts()[you] == (0, 1)
    snow.verify(ids["B"], ids["A"], 2, pay(10))
    snow.verify(ids["C"], ids["A"], 2, pay(100))
    snow.verify(ids["D"], ids["C"], 3, pay(1000))
    assert snow.depth(ids["D"]) == 2 and snow.viable(ids["D"])
    with pytest.raises(plainsnow.ContractError):
        snow.accept(ids["D"])                       # its parent is not last
    with pytest.raises(plainsnow.ContractError):
        snow.verify(b"E" * 32, ids["A"], 3)         # height
    with pytest.raises(plainsnow.ContractError):
        snow.verify(b"E" * 32, b"?" * 32, 1)        # unknown parent
    assert ("accept", ids["B"]) in snow.legal() \
        and ("accept", ids["D"]) not in snow.legal()
    snow.accept(ids["C"])
    assert not snow.viable(ids["B"])                # doomed
    with pytest.raises(plainsnow.ContractError):
        snow.verify(b"F" * 32, ids["B"], 3)
    with pytest.raises(plainsnow.ContractError):
        snow.accept(ids["B"])
    snow.reject(ids["B"])
    snow.verify(ids["B"], ids["A"], 2, pay(10))     # decided: no change
    snow.accept(ids["D"])
    with pytest.raises(plainsnow.ContractError):
        snow.reject(ids["D"])
    assert snow.statuses() == {
        ids["G"]: plainsnow.ACCEPTED, ids["A"]: plainsnow.ACCEPTED,
        ids["B"]: plainsnow.REJECTED, ids["C"]: plainsnow.ACCEPTED,
        ids["D"]: plainsnow.ACCEPTED}
    assert snow.last_accepted == ids["D"]
    # B's 10 wei left no trace: A, C and D alone add up
    assert snow.accepted_book().accounts() == {
        me: (3, 10**18 - 1101 - 3 * fee), you: (0, 1101),
        plainref.COINBASE: (0, 3 * fee)}
    assert json.dumps(sorted(s for s in snow.statuses().values()))

"""The atomic bootstrap cell at toy size on the CPU (tier-1).

``test_harness.py`` runs every cell clean, ``atomic.bootstrap`` among
them.  Here: the cell's own numbers (the engine's atomic counters, what
is counted as committed, the UTXOs the passes left against
``plainatomic``), every control and fault of ``faults.FAULTS`` planted
under the VM's pass, the cell's own fault (the conflicting sibling
accepted in the last block's place), its four readers of layer
"consensus plugin", the configuration against ``snowman-valuetx``'s,
and the plain reference's codec, fees and signatures against the
program's.
"""

import pytest

from test_harness import (  # noqa: F401 — toy_cell is a fixture
    SEED, SPEC, TOY_BLOCKS, faults, harness, names, run_toy, toy_cell)

from benchlib import chains, plainatomic

CELL = "atomic.bootstrap"
READERS = ("atomic_verify_us_per_block", "atomic_classify_us_per_block",
           "atomic_accept_us_per_block", "atomic_txs_device_per_pass")


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
def test_atomic_run_counts_what_consensus_accepted(toy_cell, pass_rows):
    result = run_toy(CELL, trace=1)
    compared = result["compared"]
    assert result["correct"] is True and result["failed"] == 0, compared
    assert all(n["value"] == 0 for n in compared.values())
    for name in ("atomic.blocks_host", "atomic.utxos_off_reference",
                 "vm.engine_off_accepted", "blocks_fallback"):
        assert name in compared
    assert pass_rows
    n_atomic = TOY_BLOCKS   # one a block
    for row in pass_rows:
        # every chain block but the last, and the sibling, on the
        # engine; the last on the host path, then once more on the
        # engine after the rollback
        assert row["atomic"] == {
            "blocks_device": TOY_BLOCKS + 1, "txs_device": n_atomic + 1,
            "blocks_host": 0, "utxos_off_reference": 0}
        assert row["vm"]["engine_rollbacks"] == 1
        assert (row["evm_txs_committed"], row["atomic_txs_committed"]) \
            == (TOY_BLOCKS, n_atomic)
        assert row["txs_committed"] == TOY_BLOCKS + n_atomic
    metrics = result["metrics"]
    for name in READERS:
        assert metrics[name]["value"] > 0, name
    assert metrics["atomic_txs_device_per_pass"]["value"] == n_atomic + 1


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
@pytest.mark.parametrize("fault", faults.FAULTS)
def test_atomic_run_is_not_correct_with_the_path_broken(toy_cell, fault):
    with faults.planted(fault):
        result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    if fault == "silent_alter":
        # the VM and the engine agree with every header of the altered
        # chain; the plain reference's book does not
        assert over == {"accounts_off_ledger", "passes_off_ledger_root"}
    else:
        assert "passes_off_header_root" in over


@pytest.mark.parametrize("toy_cell", [CELL], indirect=True)
def test_atomic_run_with_the_sibling_accepted_is_not_correct(
        toy_cell, monkeypatch):
    """Consensus's client accepts the sibling, whose atomic tx spends
    the last block's input otherwise, and rejects the last block: the
    VM does as it is told; the answer is off the header's root, off the
    book, and the UTXOs the export left are not the reference's."""
    base, _ = names.load_named("drivers", "snowman_bootstrap")
    monkeypatch.setattr(base, "FAULT", "sibling_accepted")
    result = run_toy(CELL)
    compared = result["compared"]
    assert result["correct"] is False and result["failed"] > 0
    over = {k for k, n in compared.items() if n["value"] > n["limit"]}
    assert {"passes_off_header_root", "passes_off_ledger_root",
            "accounts_off_ledger", "vm.rollbacks_off_plan"} <= over
    assert compared["vm.status_off_reference"]["value"] == 0
    if TOY_BLOCKS % 2:   # the last block exports: another UTXO
        assert "atomic.utxos_off_reference" in over


def test_readers_find_nothing_where_the_program_has_no_atomic_path():
    """A run of a program without the phases and counters (the parent
    of the change that added them), or of another cell, reads None."""
    for name in READERS:
        reader, _ = names.load_named("metrics", name)
        assert reader.read({"passes": [{"blocks": 5, "t_start": 0.0,
                                        "t_end": 0.0}],
                            "window_s": 1.0}) is None, name


def test_configuration_is_snowman_valuetx_with_atomic_txs():
    """The chain is ``snowman-valuetx``'s key for key with the atomic
    txs' keys beside them; nothing is reduced; the expectations and the
    guarantees extend the bootstrap cell's.  Every block holds the one
    transfer and one atomic tx, an import at even heights and an export
    at odd ones, at the gas the reference computes."""
    _c, entry, config, traffic = names.resolve_cell(SPEC, CELL)
    _c, _e, source, _t = names.resolve_cell(SPEC, "valuetx.bootstrap")
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert config["chain_blocks"] == source["chain_blocks"] == 2500
    same = {k: v for k, v in source["chain"].items() if k != "builder"}
    assert {k: config["chain"][k] for k in same} == same
    assert set(source["expect"]["zero"]) < set(config["expect"]["zero"])
    assert config["guarantees"][:len(source["guarantees"])] \
        == source["guarantees"]
    assert traffic["siblings_per_pass"] == 1 and traffic["accept_lag"] == 0
    toy = dict(config, chain_blocks=4)
    genesis, wire = chains.build_wire(toy, traffic, SEED)
    from coreth_tpu.atomic import (
        UnsignedImportTx, decode_ext_data)
    from coreth_tpu.types import Block
    _fees, import_gas, export_gas = plainatomic.fees(toy)
    for height, w in enumerate(wire, start=1):
        block = Block.decode(w)
        (tx,) = decode_ext_data(block.ext_data())
        imported = isinstance(tx.unsigned, UnsignedImportTx)
        assert imported == (height % 2 == 0)
        assert len(block.transactions) == 1
        assert len(tx.encode()) == (plainatomic.IMPORT_BYTES if imported
                                    else plainatomic.EXPORT_BYTES)
        assert block.header.ext_data_gas_used == (
            import_gas if imported else export_gas)
    assert plainatomic.ledger(toy, SEED).state_root() \
        == Block.decode(wire[-1]).header.root


def test_plain_reference_signs_and_addresses_as_the_program_does():
    """The reference's RFC 6979 signatures and X-Chain addresses,
    written with nothing of the program, against the program's own."""
    import hashlib
    from coreth_tpu.crypto import secp256k1 as secp
    from coreth_tpu.workloads.atomic import short_address
    for k in (1, 7, 2**200 + 3, secp.N - 2):
        digest = hashlib.sha256(str(k).encode()).digest()
        r, s, v = secp.sign(digest, k)
        assert plainatomic.sign(digest, k) == (
            r.to_bytes(32, "big") + s.to_bytes(32, "big") + bytes([v]))
        assert plainatomic.short_id(k) == short_address(k)

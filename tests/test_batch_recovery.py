"""On-device sender recovery in BATCH replay (not just serve prefetch).

The replay loop's _SenderPipeline now routes segments through the
device ECDSA ladder — mesh-sharded under CORETH_SHARD_RECOVER=1 — so a
window's senders recover on device while the previous window executes.
These tests pin:

- parity: a mesh-driven batch replay with CORETH_SHARD_RECOVER=1
  recovers every sender on the sharded ladder inside the replay loop
  (ReplayStats.sigs_device) and lands roots bit-identical to the
  host-recovered replay;
- fault isolation: a malformed-signature lane routed through the
  device ladder is rejected WITHOUT poisoning the batch — every valid
  lane's sender is cached, and the malformed tx falls back to the host
  per-tx path (signer.sender), which raises the canonical rejection.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest
import jax

from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto import secp256k1
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
from coreth_tpu.parallel import make_mesh
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.replay.engine import _SenderPipeline
from coreth_tpu.state import Database
from coreth_tpu.types import Block, DynamicFeeTx, sign_tx

GWEI = 10**9
KEYS = [0x7A00 + i for i in range(8)]
ADDRS = [priv_to_address(k) for k in KEYS]


def _alloc():
    return {a: GenesisAccount(balance=10**24) for a in ADDRS}


def _build_chain(n_blocks):
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=_alloc())
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def gen(i, bg):
        for k in range(len(KEYS)):
            t = sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=21_000,
                to=bytes([0x41 + i]) * 20, value=1000 + k),
                KEYS[k], CFG.chain_id)
            nonces[k] += 1
            bg.add_tx(t)

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=2)
    return blocks


def _engine(mesh=None):
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=_alloc())
    db = Database()
    g = genesis.to_block(db)
    return ReplayEngine(CFG, db, g.root, parent_header=g.header,
                        capacity=256, batch_pad=64, window=4, mesh=mesh)


def _fresh(blocks):
    # decode from wire so no sender caches leak between paths
    return [Block.decode(b.encode()) for b in blocks]


def test_batch_replay_shard_recover_parity(monkeypatch):
    """CORETH_SHARD_RECOVER=1 + a dp mesh: batch replay recovers its
    senders on the mesh-sharded ladder INSIDE the replay loop
    (sigs_device > 0), bit-identical roots vs host recovery."""
    blocks = _build_chain(3)

    monkeypatch.delenv("CORETH_SHARD_RECOVER", raising=False)
    host_eng = _engine()
    host_root = host_eng.replay(_fresh(blocks))
    assert host_root == blocks[-1].root
    assert host_eng.stats.sigs_device == 0

    monkeypatch.setenv("CORETH_SHARD_RECOVER", "1")
    mesh_eng = _engine(mesh=make_mesh(jax.devices("cpu")[:2]))
    mesh_root = mesh_eng.replay(_fresh(blocks))
    assert mesh_root == host_root == blocks[-1].root
    # the sharded ladder served the whole batch in the replay loop
    assert mesh_eng.stats.sigs_device == sum(
        len(b.transactions) for b in blocks)
    assert mesh_eng.stats.blocks_fallback == 0


def test_batch_replay_shard_recover_default_off(monkeypatch):
    """Default (env unset): even with a mesh, replay's sender pipeline
    stays on the measured host/device split (no sharded forcing)."""
    monkeypatch.delenv("CORETH_SHARD_RECOVER", raising=False)
    blocks = _build_chain(1)
    eng = _engine(mesh=make_mesh(jax.devices("cpu")[:2]))
    assert eng.replay(_fresh(blocks)) == blocks[-1].root
    assert eng.stats.sigs_device == 0  # CPU backend: host batch


def test_device_recover_malformed_lane_no_poison(monkeypatch):
    """One corrupted signature in a device-routed segment: the device
    prep flags the lane invalid, every OTHER lane's sender lands in
    the cache, and the malformed tx falls back to the host per-tx path
    — signer.sender raises the canonical rejection instead of the
    batch aborting or mis-recovering neighbors."""
    monkeypatch.setenv("CORETH_RECOVER_FORCE_DEVICE", "1")
    monkeypatch.setenv("CORETH_RECOVER_SPLIT", "1.0")
    monkeypatch.setattr(ReplayEngine, "DEVICE_RECOVER_MIN", 1)
    blocks = _fresh(_build_chain(2))
    bad = blocks[0].transactions[2]
    bad.inner.s = secp256k1.N  # out of range: never a valid signature

    eng = _engine()
    pipe = _SenderPipeline(eng, blocks)
    pipe.ensure(len(blocks) - 1)
    assert pipe.dev_sigs > 0
    assert eng.stats.sigs_device == pipe.dev_sigs

    for b in blocks:
        for tx in b.transactions:
            if tx is bad:
                continue
            assert tx.cached_sender() in ADDRS
    assert bad.cached_sender() is None
    with pytest.raises(ValueError, match="invalid signature"):
        eng.signer.sender(bad)


@pytest.mark.parametrize("seam", ["issue_recover", "complete_recover"])
def test_failed_device_recovery_is_not_counted_as_device_work(
        monkeypatch, seam):
    """A device recovery that RAISES (at dispatch or at the result
    read) must not read as device work: sigs_device counts only what
    complete_recover returned, the failed batch shows in
    recover_degraded, and the txs still recover per-tx on the host —
    slower, never wrong, and never silently."""
    from coreth_tpu.crypto import secp_device
    monkeypatch.setenv("CORETH_RECOVER_FORCE_DEVICE", "1")
    monkeypatch.setenv("CORETH_RECOVER_SPLIT", "1.0")
    monkeypatch.setattr(ReplayEngine, "DEVICE_RECOVER_MIN", 1)

    def boom(*_a, **_k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(secp_device, seam, boom)
    blocks = _fresh(_build_chain(3))
    eng = _engine()
    assert eng.replay(blocks) == blocks[-1].root
    assert eng.stats.sigs_device == 0
    assert eng.stats.recover_degraded >= 1
    assert eng.stats.blocks_fallback == 0

    # the synchronous form (serve prefetch, replay_block) counts the same
    eng2 = _engine()
    eng2.warm_senders(_fresh(blocks))
    assert eng2.stats.sigs_device == 0
    assert eng2.stats.recover_degraded == 1


def test_accelerator_probe_does_not_swallow_a_broken_backend(monkeypatch):
    """A backend probe that raises must propagate: returning False
    would route every signature to the host and look healthy."""
    from coreth_tpu.replay import engine as E
    monkeypatch.delenv("CORETH_RECOVER_FORCE_DEVICE", raising=False)

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(E.jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        E._has_accelerator()

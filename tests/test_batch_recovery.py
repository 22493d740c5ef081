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
    stays on its routing rule (no sharded forcing)."""
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


# ------------------------------------------- routing by earliest finish
# A stub cost table (the ledger's numbers before the chip was asked:
# 0.14-0.29 s a launch at every bucket, 0.009 ms a signature on 13
# cores) and stub engines: pure host Python, nothing compiles.
STUB_LAUNCH_S = {64: 0.14, 128: 0.14, 256: 0.267, 512: 0.169,
                 1024: 0.14, 2048: 0.142, 4096: 0.285}


def _stub_cost(cores):
    from coreth_tpu.replay.recover_cost import RecoverCost
    return RecoverCost(launch_s=STUB_LAUNCH_S, host_fixed_s=0.001,
                       host_sig_core_s=0.000117, cores=cores)


def _stub_engines(monkeypatch, eng):
    """Both batch engines and the packing replaced by counters: a
    "block" is any object with a ``transactions`` list.  Returns the
    chunk contexts each ladder issue made."""
    from coreth_tpu.crypto import native, secp_device
    from coreth_tpu.replay import engine as E
    issues = []

    def pack(blocks):
        n = sum(len(b.transactions) for b in blocks)
        return [None] * n, bytes(32 * n), bytes(32 * n), bytes(32 * n), \
            bytes(n)

    def issue_chunk(hashes, rs, ss, recids, kernel=None):
        return dict(n=len(recids), out=None)

    real_issue = secp_device.issue_recover

    def issue(*a, **k):
        ctxs = real_issue(*a, **k)     # the chunk loop is the real one
        issues.append(ctxs)
        return ctxs

    monkeypatch.setattr(E, "_has_accelerator", lambda: True)
    monkeypatch.setattr(eng, "_pack_sigs", pack)
    monkeypatch.setattr(eng, "_apply_recovered", lambda *a: None)
    monkeypatch.setattr(native, "recover_addresses_batch",
                        lambda h, r, s, v: (bytes(20 * len(v)),
                                            b"\x01" * len(v)))
    monkeypatch.setattr(secp_device, "_issue_chunk", issue_chunk)
    monkeypatch.setattr(secp_device, "issue_recover", issue)
    monkeypatch.setattr(secp_device, "fetch_recover", lambda ctxs: None)
    monkeypatch.setattr(
        secp_device, "complete_recover",
        lambda ctxs: (bytes(20 * sum(c["n"] for c in ctxs)),
                      b"\x01" * sum(c["n"] for c in ctxs)))
    return issues


ROUTING = {
    # the three cells' chains after the lead block, on the chip's host:
    # segment 0 and every later one to the native batch
    "p2p-1k on 13 cores": dict(cores=13, blocks=[714] * 32,
                               kinds="h" * 7),
    "p2p-token-1k on 13 cores": dict(cores=13, blocks=[445] * 32,
                                     kinds="h" * 4),
    "valuetx on 13 cores": dict(cores=13, blocks=[1] * 9999,
                                kinds="h" * 3),
    # one core does 4,096 signatures in 0.48 s, a launch in 0.285 s
    "a full launch on 1 core": dict(cores=1, blocks=[4096], kinds="d"),
    # ... and the book then sends the next segment to the idle engine:
    # the first four issue before any completes, by the model alone
    "the book alternates on 1 core": dict(cores=1, blocks=[714] * 32,
                                          kinds="dhdh", first=4),
    "a block larger than a launch": dict(cores=1, blocks=[5000],
                                         kinds="d", chunks=2),
    "CORETH_RECOVER_FORCE_DEVICE=1": dict(cores=13, blocks=[714] * 32,
                                          kinds="d" * 7, force=True),
    "_recover_packed on 13 cores": dict(cores=13, packed=4284, n_dev=0),
    "_recover_packed on 1 core": dict(cores=1, packed=4284, n_dev=2048),
    "_recover_packed forced": dict(cores=13, packed=4284, n_dev=4284,
                                   force=True),
}


@pytest.mark.parametrize("case", list(ROUTING), ids=list(ROUTING))
def test_recovery_goes_to_the_engine_that_finishes_first(monkeypatch,
                                                         case):
    """One rule, read from the input and the machine: a segment (or a
    synchronous batch's share) goes to the ladder only where the cost
    model has it done strictly earlier there.  No segment of whole
    blocks exceeds one launch, a ladder segment is ONE chunk context,
    and the per-engine counters add up to what was issued."""
    from types import SimpleNamespace
    from coreth_tpu import obs
    from coreth_tpu.crypto.secp_device import MAX_CHUNK
    c = ROUTING[case]
    monkeypatch.delenv("CORETH_SHARD_RECOVER", raising=False)
    monkeypatch.delenv("CORETH_RECOVER_FORCE_DEVICE", raising=False)
    if c.get("force"):
        monkeypatch.setenv("CORETH_RECOVER_FORCE_DEVICE", "1")
    eng = _engine()
    monkeypatch.setattr(eng, "recover_cost", _stub_cost(c["cores"]))
    issues = _stub_engines(monkeypatch, eng)
    st = eng.stats

    if "packed" in c:
        n = c["packed"]
        out, ok = eng._recover_packed(bytes(32 * n), bytes(32 * n),
                                      bytes(32 * n), bytes(n),
                                      obs.NULL_ACCOUNT)
        assert (len(out), len(ok)) == (20 * n, n)
        assert (st.sigs_device, st.sigs_host) == (c["n_dev"],
                                                  n - c["n_dev"])
        assert st.segs_device == (c["n_dev"] > 0)
        assert st.segs_host == (c["n_dev"] < n)
        assert eng.recover_cost.split(n) == (
            c["n_dev"] if not c.get("force") else 0)
        return

    blocks = [SimpleNamespace(transactions=[None] * k)
              for k in c["blocks"]]
    pipe = _SenderPipeline(eng, blocks)
    sizes = [sum(len(b.transactions) for b in seg)
             for seg in pipe.segments]
    assert sum(sizes) == sum(c["blocks"])
    assert all(k <= MAX_CHUNK or len(seg) == 1
               for k, seg in zip(sizes, pipe.segments))
    for i in range(len(blocks)):       # block by block, as replay()
        pipe.ensure(i)
    kinds = "".join(h["kind"][0] for h in pipe.issued)
    first = c.get("first", len(kinds))
    assert kinds[:first] == c["kinds"], kinds
    # a routed segment is one launch (one chunk context) unless a
    # single block alone is larger
    assert len(issues) == kinds.count("d")
    assert all(len(ctxs) == c.get("chunks", 1) for ctxs in issues)
    # the counters add up to the segments issued, engine by engine
    assert st.segs_device == kinds.count("d")
    assert st.segs_host == kinds.count("h")
    assert st.segs_device + st.segs_host == len(pipe.segments)
    assert st.sigs_device + st.sigs_host == sum(c["blocks"])
    assert st.sigs_device == pipe.dev_sigs
    assert st.recover_degraded == 0
    for kind, n_segs in (("device", st.segs_device),
                         ("host", st.segs_host)):
        model = getattr(st, f"t_recover_{kind}_model")
        seen = getattr(st, f"t_recover_{kind}_seen")
        assert (model > 0) == (n_segs > 0) and seen >= 0


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

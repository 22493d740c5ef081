"""Sender recovery in batch replay: ONE batch engine.

``ReplayEngine.replay`` recovers senders in look-ahead segments of
whole blocks (``_SenderPipeline``), each handed over by the replay
thread as one buffer of wire bytes and recovered from it by the native
C++ batch on one worker thread;
``warm_senders`` (``replay_block``, the serve prefetcher) is the same
batch on the calling thread.  Where the native library is missing or a
batch raises, ``signer.sender`` recovers per transaction.  These tests
pin:

- the segmenting the benchmark's rows were measured with, the
  look-ahead, and the counters (``sigs_host`` = signatures whose batch
  completed, ``sigs_device`` constant 0, ``recover_degraded`` = batches
  that raised);
- fault isolation: a malformed signature is rejected WITHOUT poisoning
  its segment, and a batch that raises at any of its three seams falls
  to per-tx recovery — slower, never wrong, never silently;
- that nothing selects another engine: not a mesh, not the retired
  knobs, and neither ``crypto.secp_device`` nor ``ops.secp`` is even
  imported by a process that replays.
"""

import dataclasses
import os
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import pytest
import jax

from coreth_tpu import obs
from coreth_tpu.chain import Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto import native, secp256k1
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.params import TEST_CHAIN_CONFIG as CFG
from coreth_tpu.parallel import make_mesh
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.replay.engine import _SenderPipeline
from coreth_tpu.state import Database
from coreth_tpu.types import Block, DynamicFeeTx, Transaction, sign_tx

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


@pytest.fixture(scope="module")
def chain3():
    return _build_chain(3)


def _engine(mesh=None):
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=_alloc())
    db = Database()
    g = genesis.to_block(db)
    return ReplayEngine(CFG, db, g.root, parent_header=g.header,
                        capacity=256, batch_pad=64, window=4, mesh=mesh)


def _fresh(blocks):
    # decode from wire so no sender caches leak between paths
    return [Block.decode(b.encode()) for b in blocks]


def _n_txs(blocks):
    return sum(len(b.transactions) for b in blocks)


def test_mesh_engine_recovers_on_the_native_batch(chain3):
    """A dp mesh shards execution, not sender recovery: the mesh engine
    sends every signature to the native batch like any other."""
    blocks = _fresh(chain3[:1])
    eng = _engine(mesh=make_mesh(jax.devices("cpu")[:2]))
    assert eng.replay(blocks) == blocks[-1].root
    assert eng.stats.sigs_host == _n_txs(blocks)
    assert eng.stats.sigs_device == 0
    assert eng.stats.recover_degraded == 0


def test_malformed_lane_does_not_poison_its_segment(chain3):
    """One corrupted signature in a pipelined segment: the native batch
    flags the lane invalid, every OTHER lane's sender lands in the
    cache, and the malformed tx falls to the per-tx path — signer.sender
    raises the canonical rejection instead of the batch aborting or
    mis-recovering neighbours."""
    blocks = _fresh(chain3[:2])
    # out of range: never a valid signature.  Built in process (no wire
    # bytes kept), so the segment's buffer holds its encode(): a decoded
    # transaction IS its wire bytes, editing its fields changes nothing
    good = blocks[0].transactions[2]
    bad = Transaction(dataclasses.replace(good.inner, s=secp256k1.N))
    blocks[0].transactions[2] = bad

    eng = _engine()
    pipe = _SenderPipeline(eng, blocks)
    pipe.ensure(len(blocks) - 1)
    assert len(pipe.segments) == 1
    assert eng.stats.sigs_host == _n_txs(blocks)
    assert eng.stats.sigs_left_to_signer == 1
    assert eng.stats.recover_degraded == 0

    for b in blocks:
        for tx in b.transactions:
            if tx is not bad:
                assert tx.cached_sender() in ADDRS
    assert bad.cached_sender() is None
    with pytest.raises(ValueError, match="invalid signature"):
        eng.signer.sender(bad)


@pytest.mark.parametrize("form", ["replay", "warm_senders"])
def test_a_built_chain_recovers_on_the_fast_path_alone(chain3, form):
    """Every lane of a built chain is answered by the batch's fast path:
    no lane falls to its sequential fallback (ok = 2), whose count rides
    the stats row beside sigs_left_to_signer."""
    blocks = _fresh(chain3)
    eng = _engine()
    if form == "replay":
        assert eng.replay(blocks) == blocks[-1].root
    else:
        eng.warm_senders(blocks)
    assert eng.stats.sigs_host == _n_txs(blocks)
    assert eng.stats.sigs_slow_path == 0
    assert eng.stats.sigs_left_to_signer == 0
    assert eng.stats.row()["sigs_slow_path"] == 0


def test_fallback_lanes_are_primed_and_counted(monkeypatch, chain3):
    """A lane the batch answered ok = 2 (its sequential fallback
    recovered it) primes the sender cache like ok = 1, and counts in
    sigs_slow_path; ok = 0 still goes to sigs_left_to_signer."""
    blocks = _fresh(chain3[:1])
    n = _n_txs(blocks)
    real = native.recover_senders_wire

    def batch(wire, offsets, chain_id):
        out, ok = real(wire, offsets, chain_id)
        return out, bytes([2, 0]) + ok[2:]

    monkeypatch.setattr(native, "recover_senders_wire", batch)
    eng = _engine()
    eng.warm_senders(blocks)
    txs = blocks[0].transactions
    assert eng.stats.sigs_slow_path == 1
    assert eng.stats.sigs_left_to_signer == 1
    assert txs[0].cached_sender() == ADDRS[0]
    assert txs[1].cached_sender() is None
    assert all(tx.cached_sender() in ADDRS for tx in txs[2:n])


def _boom(*_a, **_k):
    raise RuntimeError("batch lost")


class _LostResultPool:
    """A recovery pool whose futures raise at ``result()``."""

    def submit(self, fn, *args):
        return SimpleNamespace(result=_boom)


# seam -> (what to break, batches the synchronous form loses: it has no
# Future, so a pool that loses results costs it nothing)
SEAMS = {
    "packing raises": (
        lambda mp: mp.setattr(ReplayEngine, "_pack_sigs", _boom), 1),
    "the worker's batch raises": (
        lambda mp: mp.setattr(native, "recover_senders_wire", _boom),
        1),
    "Future.result() raises": (
        lambda mp: mp.setattr(ReplayEngine, "_recover_pool_get",
                              lambda self: _LostResultPool()), 0),
}


@pytest.mark.parametrize("seam", list(SEAMS))
def test_failed_batch_is_not_counted_as_recovered(monkeypatch, chain3,
                                                  seam):
    """A batch that RAISES — while packing, in the worker, or when its
    result is read — must not read as recovered: sigs_host counts only
    batches that completed, the failed one shows in recover_degraded,
    and its txs recover per-tx in signer.sender: the root still lands,
    on the device path."""
    arm, sync_lost = SEAMS[seam]
    arm(monkeypatch)
    blocks = _fresh(chain3)
    eng = _engine()
    assert eng.replay(blocks) == blocks[-1].root
    assert eng.stats.sigs_host == 0 and eng.stats.sigs_device == 0
    assert eng.stats.recover_degraded >= 1
    assert eng.stats.blocks_fallback == 0

    # the synchronous form (serve prefetch, replay_block) counts the same
    again = _fresh(chain3)
    eng2 = _engine()
    eng2.warm_senders(again)
    assert eng2.stats.recover_degraded == sync_lost
    assert eng2.stats.sigs_host == (0 if sync_lost else _n_txs(again))


# ------------------------------------------------- segments, look-ahead
def _stub_batch(monkeypatch, eng):
    """The native batch and the packing replaced by counters — pure host
    Python, nothing compiles; a "block" is any object with a
    ``transactions`` list.  Returns the (signatures, thread) of every
    batch run, in the order run."""
    batches = []

    def pack(blocks):
        n = _n_txs(blocks)
        return [None] * n, bytes(n), list(range(n + 1))

    def batch(wire, offsets, chain_id):
        n = len(offsets) - 1
        assert len(wire) == offsets[-1] and chain_id == CFG.chain_id
        batches.append((n, threading.get_ident()))
        return bytes(20 * n), b"\x01" * n

    monkeypatch.setattr(eng, "_pack_sigs", pack)
    monkeypatch.setattr(eng, "_apply_recovered", lambda *a: None)
    monkeypatch.setattr(native, "recover_senders_wire", batch)
    return batches


def _stub_blocks(sizes):
    return [SimpleNamespace(transactions=[None] * k) for k in sizes]


SEGMENTING = {
    # the three cells' chains after the lead block: the segment sizes
    # the benchmark's rows were measured with
    "p2p-1k": ([714] * 32, [3570] * 6 + [1428]),
    "p2p-token-1k": ([445] * 32, [4005] * 3 + [2225]),
    "valuetx": ([1] * 9999, [4096, 4096, 1807]),
    "a block larger than a segment": ([5000], [5000]),
    "empty blocks between full ones": ([0, 2000, 0, 2000, 0, 2000, 0, 0],
                                       [4000, 2000]),
    "an empty chain": ([], []),
}


@pytest.mark.parametrize("case", list(SEGMENTING))
def test_segments_are_whole_blocks_of_at_most_4096_signatures(
        monkeypatch, case):
    """A segment closes BEFORE the block that would take it past
    SEGMENT_SIGS; a single larger block is a segment, and a batch, of
    its own.  Every signature completes on the native batch."""
    sizes, want = SEGMENTING[case]
    eng = _engine()
    batches = _stub_batch(monkeypatch, eng)
    blocks = _stub_blocks(sizes)
    pipe = _SenderPipeline(eng, blocks)
    assert [b for seg in pipe.segments for b in seg] == blocks
    assert [_n_txs(seg) for seg in pipe.segments] == want
    assert all(n <= pipe.SEGMENT_SIGS == 4096 or len(seg) == 1
               for n, seg in zip(want, pipe.segments))
    for i in range(len(blocks)):       # block by block, as replay()
        pipe.ensure(i)
        assert pipe.done == pipe.block_seg[i] + 1
    assert [n for n, _ in batches] == want
    st = eng.stats
    assert st.sigs_host == sum(sizes) and st.sigs_device == 0
    assert st.recover_degraded == 0
    assert st.t_sender_device == 0.0


def test_lookahead_issues_ahead_and_completes_in_order(monkeypatch):
    """ensure(0) applies segment 0 and leaves exactly AHEAD more
    issued; the one worker runs them in the order issued, off the
    replay thread."""
    eng = _engine()
    batches = _stub_batch(monkeypatch, eng)
    sizes = [4000, 3000, 2500, 4001, 3500, 2600]  # one block a segment
    pipe = _SenderPipeline(eng, _stub_blocks(sizes))
    assert len(pipe.segments) == len(sizes)
    pipe.ensure(0)
    assert len(pipe.issued) == pipe.AHEAD + 1 == 4
    assert pipe.done == 1 and eng.stats.sigs_host == sizes[0]
    pipe.ensure(1)
    assert len(pipe.issued) == 5 and pipe.done == 2
    pipe.ensure(len(sizes) - 1)
    assert len(pipe.issued) == len(sizes) == pipe.done
    assert [n for n, _ in batches] == sizes
    workers = {t for _, t in batches}
    assert len(workers) == 1 and threading.get_ident() not in workers
    n = eng.account.row()["n"]
    assert n["sender/pack"] == n["sender/wait_host"] == len(sizes)
    # the batches are the WORKER's phase, one entry a segment, on an
    # account the worker opened itself inside this call
    assert "sender/native" not in n
    (worker,) = [a for a in obs.accounts_between(
        eng.account.t_open, time.monotonic(), role="recover")]
    row = worker.row()
    assert row["n"]["sender/native"] == len(sizes)
    assert row["thread"].startswith("coreth-recover")
    assert worker.t_open > eng.account.t_open
    assert worker not in obs.accounts_between(eng.account.t_open,
                                              time.monotonic())


def test_warm_senders_runs_the_batch_on_the_calling_thread(monkeypatch):
    """The synchronous form has no worker and no wait: the batch is
    work of the thread that called, phase ``sender/native`` between
    its packing and its applying."""
    eng = _engine()
    batches = _stub_batch(monkeypatch, eng)
    eng.warm_senders(_stub_blocks([5, 7]))
    assert batches == [(12, threading.get_ident())]
    assert eng.stats.sigs_host == 12 and eng.stats.sigs_device == 0
    n = eng.account.row()["n"]
    assert n.get("sender/pack") == 1 and n.get("sender/native") == 1
    assert n.get("sender/apply") == 1
    assert not n.get("sender/wait_host")


def test_warm_senders_from_another_thread_lands_on_that_threads_account(
        monkeypatch):
    """While the replay thread holds the engine's account, a call from
    a thread with an account of its own is that account's; from a
    thread with none it is nobody's."""
    eng = _engine()
    _stub_batch(monkeypatch, eng)
    tok = eng.account.begin()
    before = dict(eng.account.row()["n"])
    seen = {}

    def with_account():
        acct = obs.thread_account("prefetch")
        eng.warm_senders(_stub_blocks([3]))
        seen["own"] = acct.row()
        seen["current"] = obs.current() is acct

    def without():
        eng.warm_senders(_stub_blocks([4]))
        seen["none"] = obs.current()

    for fn in (with_account, without):
        th = threading.Thread(target=fn, name="other-" + fn.__name__)
        th.start()
        th.join(30)
        assert not th.is_alive()
    eng.account.end(tok)
    own = seen["own"]
    assert own["role"] == "prefetch" and own["thread"] == "other-with_account"
    assert [own["n"].get(p) for p in ("sender/pack", "sender/native",
                                      "sender/apply")] == [1, 1, 1]
    assert seen["current"] and seen["none"] is None
    after = eng.account.row()["n"]
    assert {k: after[k] for k in before} == before
    assert not any(k.startswith("sender/") for k in after)
    assert eng.stats.sigs_host == 7    # both batches ran


def test_without_the_native_library_senders_recover_per_tx(monkeypatch,
                                                           chain3):
    """No native library: no batch engine at all.  Every segment stays
    lazy and signer.sender recovers per tx; the header's root lands and
    nothing reads as a batch, done or degraded."""
    real_load = native.load

    def load():
        # the engine alone finds no library: keccak and the tries of
        # this process were bound to it at import
        asks = sys._getframe(1).f_globals["__name__"]
        return None if asks == "coreth_tpu.replay.engine" else real_load()

    monkeypatch.setattr(native, "load", load)
    monkeypatch.setattr(native, "recover_senders_wire", _boom)
    blocks = _fresh(chain3)
    eng = _engine()
    assert eng.replay(blocks) == blocks[-1].root
    eng.warm_senders(_fresh(chain3))
    st = eng.stats
    assert st.sigs_host == 0 and st.sigs_device == 0
    assert st.recover_degraded == 0 and st.blocks_fallback == 0


@pytest.mark.parametrize("knob,mesh", [
    ("CORETH_RECOVER_FORCE_DEVICE", False),
    ("CORETH_SHARD_RECOVER", True)])
def test_retired_recover_knobs_select_nothing(monkeypatch, chain3, knob,
                                              mesh):
    """The variables that used to send recovery to the device ladder
    are read by nothing: same root, every signature on the native
    batch."""
    monkeypatch.setenv(knob, "1")
    blocks = _fresh(chain3)
    eng = _engine(mesh=make_mesh(jax.devices("cpu")[:2]) if mesh else None)
    assert eng.replay(blocks) == blocks[-1].root
    assert eng.stats.sigs_device == 0
    assert eng.stats.sigs_host == _n_txs(blocks)
    eng.warm_senders(_fresh(chain3))
    assert eng.stats.sigs_device == 0
    assert eng.stats.sigs_host == 2 * _n_txs(blocks)


_FRESH_REPLAY = """
import sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import coreth_tpu.replay.engine
import coreth_tpu.serve
from coreth_tpu.serve.prefetch import Prefetcher
import test_batch_recovery as T
blocks = T._build_chain(2)
eng = T._engine()
Prefetcher(eng).warm(T._fresh(blocks)[:1])
assert eng.replay(T._fresh(blocks)) == blocks[-1].root
assert eng.stats.sigs_host > 0 and eng.stats.recover_degraded == 0
ladder = [m for m in ("coreth_tpu.crypto.secp_device",
                      "coreth_tpu.ops.secp") if m in sys.modules]
assert not ladder, ladder
print("replayed without", "the ladder")
"""


def test_a_replaying_process_never_imports_the_ladder():
    """A fresh interpreter imports the engine and the serve package,
    prefetches and replays a toy chain: the device ladder's modules are
    not even loaded (tools/lint LAY005 holds the same line statically)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH_REPLAY.format(
            repo=REPO, tests=os.path.join(REPO, "tests"))],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "replayed without the ladder" in proc.stdout

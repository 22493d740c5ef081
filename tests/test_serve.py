"""Streaming block-ingestion service (coreth_tpu/serve).

Equivalence: every workload shape streamed through the bounded-queue
pipeline must land on bit-identical state roots to batch
``ReplayEngine.replay`` — across both trie backends.  Fault injection:
a stalled feed, a slow commit stage (backpressure engages, queues stay
bounded), and mid-stream shutdown draining cleanly.  Plus the
mempool-fed mode: blocks built live by the txpool/miner machinery
replay on a replica engine to the builder's exact roots.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest

from coreth_tpu.chain import BlockChain, Genesis, GenesisAccount, generate_chain
from coreth_tpu.crypto.secp256k1 import priv_to_address
from coreth_tpu.mpt import native_trie
from coreth_tpu.params import TEST_CHAIN_CONFIG
from coreth_tpu.replay import ReplayEngine
from coreth_tpu.serve import (
    BlockFeed, ChainFeed, FeedExhausted, MempoolFeed, StreamingPipeline,
)
from coreth_tpu.state import Database
from coreth_tpu.types import Block, DynamicFeeTx, sign_tx

GWEI = 10**9
KEYS = [0x7A00 + i for i in range(8)]
ADDRS = [priv_to_address(k) for k in KEYS]
CFG = TEST_CHAIN_CONFIG
TOKEN = bytes([0x77]) * 20
POOL = bytes([0x70]) * 20

BACKENDS = ["py"] + (["native"] if native_trie.available() else [])


# ------------------------------------------------------------- chain builders

def build_transfer_chain(n_blocks=6, txs_per_block=8):
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={a: GenesisAccount(balance=10**24)
                             for a in ADDRS})
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def gen(i, bg):
        for j in range(txs_per_block):
            k = (i * txs_per_block + j) % len(KEYS)
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=21_000,
                to=bytes([0x40 + k]) * 20, value=1000 + j,
            ), KEYS[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=2)
    return genesis, blocks


def build_token_chain(n_blocks=4, txs_per_block=6):
    from coreth_tpu.workloads.erc20 import (
        token_genesis_account, transfer_calldata)
    alloc = {a: GenesisAccount(balance=10**24) for a in ADDRS}
    alloc[TOKEN] = token_genesis_account({a: 10**18 for a in ADDRS})
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=alloc)
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(KEYS)

    def gen(i, bg):
        for j in range(txs_per_block):
            k = (i * txs_per_block + j) % len(KEYS)
            to = ADDRS[(k + 1) % len(KEYS)] if j % 3 == 0 \
                else bytes([0x50 + (j % 40)]) * 20
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI, gas=100_000,
                to=TOKEN, value=0, data=transfer_calldata(to, 10 + j),
            ), KEYS[k], CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=2)
    return genesis, blocks


def build_swap_chain(n_blocks=3, txs_per_block=4):
    from coreth_tpu.workloads.swap import (
        pool_genesis_account, swap_calldata)
    keys = [0x6200 + i for i in range(txs_per_block)]
    addrs = [priv_to_address(k) for k in keys]
    alloc = {a: GenesisAccount(balance=10**24) for a in addrs}
    alloc[POOL] = pool_genesis_account(10**15, 10**15)
    genesis = Genesis(config=CFG, gas_limit=8_000_000, alloc=alloc)
    db = Database()
    gblock = genesis.to_block(db)
    nonces = [0] * len(keys)

    def gen(i, bg):
        for k in range(txs_per_block):
            bg.add_tx(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=300 * GWEI,
                gas=200_000, to=POOL, value=0,
                data=swap_calldata(1000 + 13 * i + k)), keys[k],
                CFG.chain_id))
            nonces[k] += 1

    blocks, _ = generate_chain(CFG, gblock, db, n_blocks, gen, gap=2)
    return genesis, blocks


def _fresh_engine(genesis, window=4, **kw):
    db = Database()
    gblock = genesis.to_block(db)
    return ReplayEngine(genesis.config, db, gblock.root,
                        parent_header=gblock.header, capacity=256,
                        batch_pad=64, window=window, **kw), gblock


def _stream_vs_batch(genesis, blocks, **pipe_kw):
    """Replay ``blocks`` batch and streamed; assert identical roots."""
    batch_eng, _ = _fresh_engine(genesis)
    root_batch = batch_eng.replay(list(blocks))
    stream_eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(stream_eng, ChainFeed(list(blocks)),
                             **pipe_kw)
    report = pipe.run()
    assert stream_eng.root == root_batch
    assert stream_eng.root == blocks[-1].header.root
    assert report.blocks == len(blocks)
    assert report.txs == sum(len(b.transactions) for b in blocks)
    return report


# --------------------------------------------------------------- equivalence

@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_transfer_equivalence(monkeypatch, backend):
    monkeypatch.setenv("CORETH_TRIE", backend)
    genesis, blocks = build_transfer_chain()
    _stream_vs_batch(genesis, blocks)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_erc20_equivalence(monkeypatch, backend):
    """Token fast-path blocks (storage slots + logs) streamed."""
    monkeypatch.setenv("CORETH_TRIE", backend)
    genesis, blocks = build_token_chain()
    _stream_vs_batch(genesis, blocks)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_swap_equivalence(monkeypatch, backend):
    """Machine-path blocks (device OCC / serial short-circuit)."""
    monkeypatch.setenv("CORETH_TRIE", backend)
    genesis, blocks = build_swap_chain()
    _stream_vs_batch(genesis, blocks)


def test_stream_mixed_equivalence():
    """Avalanche-semantics segment: atomic ExtData blocks ride the
    exact host fallback inside the stream; roots stay bit-identical
    to batch replay of the same chain."""
    from coreth_tpu.params import TEST_APRICOT_PHASE5_CONFIG
    from coreth_tpu.workloads import mixed as MX
    keys = [0xB0B + i for i in range(8)]
    genesis, blocks = MX.build_mixed_chain(
        TEST_APRICOT_PHASE5_CONFIG, 6, 4, keys)
    batch_eng, _ = MX.replay_engine(genesis, 6, keys[0])
    root_batch = batch_eng.replay([Block.decode(b.encode())
                                   for b in blocks])
    stream_eng, _ = MX.replay_engine(genesis, 6, keys[0], window=4)
    pipe = StreamingPipeline(
        stream_eng,
        ChainFeed([Block.decode(b.encode()) for b in blocks]))
    pipe.run()
    assert stream_eng.root == root_batch
    assert stream_eng.root == blocks[-1].header.root
    assert stream_eng.stats.blocks_fallback > 0  # atomic blocks


def test_stream_prefetch_overlap_counters():
    """The acceptance counters: sender recovery happens on the
    prefetch stage (hits at classify time) and the windowed
    fetch-tensor read is issued asynchronously at dispatch."""
    genesis, blocks = build_transfer_chain()
    wire = [b.encode() for b in blocks]
    fresh = [Block.decode(w) for w in wire]  # no cached senders
    stream_eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(stream_eng, ChainFeed(fresh))
    report = pipe.run()
    assert stream_eng.root == blocks[-1].header.root
    assert report.prefetch["sigs"] > 0
    assert report.prefetch["hits"] > 0
    assert report.prefetch["reads_prefetched"] > 0
    # the windows' lane fill rides the report (and /report) too
    assert report.lanes["real"] == sum(len(b.transactions) for b in blocks)
    assert report.lanes["padded"] == stream_eng.stats.lanes_padded \
        >= report.lanes["real"]
    assert report.lanes["window_uploads"] \
        == stream_eng.stats.window_uploads \
        == stream_eng.stats.reads_prefetched
    assert report.lanes["window_upload_bytes"] \
        == stream_eng.stats.window_upload_bytes > 0
    assert pipe._live_report()["lanes"] == report.lanes
    # and the prefetcher's sender recovery went to the native batch,
    # every signature
    assert stream_eng.stats.sigs_host == report.prefetch["sigs"]
    assert stream_eng.stats.sigs_device == 0
    assert report.latency_ms["p99"] >= report.latency_ms["p50"] > 0
    assert report.sustained_txs_s > 0


# ------------------------------------------------------------ fault injection

class _StutteringFeed(BlockFeed):
    """Stalls two polls out of three — the wedged-peer shape."""

    def __init__(self, blocks):
        self.blocks = blocks
        self._i = 0
        self._calls = 0

    def next_block(self, timeout):
        self._calls += 1
        if self._i >= len(self.blocks):
            raise FeedExhausted
        if self._calls % 3:
            time.sleep(min(timeout, 0.002))
            return None
        b = self.blocks[self._i]
        self._i += 1
        return b


def test_stream_stalled_feed_degrades_not_deadlocks():
    genesis, blocks = build_transfer_chain()
    stream_eng, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(stream_eng, _StutteringFeed(list(blocks)))
    report = pipe.run()
    assert stream_eng.root == blocks[-1].header.root
    assert report.blocks == len(blocks)
    assert report.feed_stalls > 0  # the stall was observed, not hidden


def test_stream_slow_commit_backpressure_bounds_queues():
    """A slow commit stage must engage backpressure: the feed blocks
    on the bounded queues, total in-flight work stays capped, and the
    run still completes with exact roots."""
    genesis, blocks = build_transfer_chain(n_blocks=24, txs_per_block=4)
    stream_eng, _ = _fresh_engine(genesis, window=2)
    pipe = StreamingPipeline(stream_eng, ChainFeed(list(blocks)),
                             depth=4, commit_delay=0.05)
    report = pipe.run()
    assert stream_eng.root == blocks[-1].header.root
    assert report.blocks == 24
    # bound: both queues (depth each) + execute buffer + the pending
    # speculative window (window each), plus the item in hand
    bound = 2 * 4 + 2 * 2 + 2
    assert report.queues["max_inflight"] <= bound, report.queues
    assert report.queues["max_inflight"] < 24  # backpressure engaged
    assert report.backpressure["feed_blocked_s"] > 0
    assert report.stages_s["commit"] >= 0.05 * 2


def test_stream_midstream_shutdown_drains_cleanly():
    """shutdown() mid-run: the feed stops, in-flight work drains, the
    commit stage flushes, and the engine sits exactly on the root of
    the last committed block."""
    genesis, blocks = build_transfer_chain(n_blocks=16, txs_per_block=4)
    stream_eng, gblock = _fresh_engine(genesis, window=2)
    pipe = StreamingPipeline(stream_eng, ChainFeed(list(blocks), rate=20),
                             depth=4)
    timer = threading.Timer(0.4, pipe.shutdown)
    timer.start()
    try:
        report = pipe.run()
    finally:
        timer.cancel()
    assert report.shutdown
    n = report.blocks
    want = gblock.root if n == 0 else blocks[n - 1].header.root
    assert stream_eng.root == want
    # a fresh engine replays the committed prefix to the same root
    if n:
        check_eng, _ = _fresh_engine(genesis)
        assert check_eng.replay(list(blocks[:n])) == stream_eng.root


# ------------------------------------------------------------- mempool mode

def test_mempool_feed_streams_built_blocks():
    """Blocks assembled live from the txpool/miner under load stream
    into a replica engine that must reproduce the builder's roots."""
    from coreth_tpu.miner import Miner
    from coreth_tpu.txpool import TxPool
    genesis = Genesis(config=CFG, gas_limit=8_000_000,
                      alloc={a: GenesisAccount(balance=10**24)
                             for a in ADDRS})
    chain = BlockChain(genesis)
    pool = TxPool(CFG, chain)
    miner = Miner(CFG, chain, pool,
                  clock=lambda: chain.current_block().time + 10)
    nonces = {k: 0 for k in KEYS}
    waves = [16, 16, 16]

    def tx_source(p):
        if not waves:
            return False
        n = waves.pop(0)
        for j in range(n):
            k = KEYS[j % len(KEYS)]
            p.add_local(sign_tx(DynamicFeeTx(
                chain_id_=CFG.chain_id, nonce=nonces[k],
                gas_tip_cap_=GWEI, gas_fee_cap_=2000 * GWEI,
                gas=21_000, to=bytes([0x60 + j % 8]) * 20, value=7 + j,
            ), k, CFG.chain_id))
            nonces[k] += 1
        return True

    feed = MempoolFeed(chain, pool, miner, tx_source)
    replica, _ = _fresh_engine(genesis)
    pipe = StreamingPipeline(replica, feed)
    report = pipe.run()
    assert feed.built > 0
    assert report.blocks == feed.built
    assert replica.root == chain.last_accepted.root
    assert report.txs == 48
    feed.close()

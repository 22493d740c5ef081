"""Level-synchronous batched trie rehash on device.

The reference parallelizes trie hashing with fork-join goroutines per
fullNode (trie/hasher.go:57 newHasher(parallel)).  The TPU-native design
replaces recursion with level batches: collect every dirty (unmemoized)
node, process depths bottom-up, RLP-encode each level on host (cheap —
child refs are ready), and hash the whole level in ONE batched
keccak-f[1600] device call (coreth_tpu.ops.keccak).  Memos are filled in
place, so the host ``Trie.hash()``/``commit()`` afterwards is O(1).

Below ``min_batch`` dirty nodes the host (native C++) keccak wins on
dispatch latency and is used instead — callers can always call this; it
degrades gracefully.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from coreth_tpu import rlp
from coreth_tpu.crypto import keccak256
from coreth_tpu.mpt.trie import (
    BRANCH, EXT, HASHREF, LEAF, _MEMO, EMPTY_ROOT, Trie, hex_prefix,
)

_device_hasher = None


def _get_device_hasher():
    global _device_hasher
    if _device_hasher is None:
        from coreth_tpu.ops import keccak as K

        def hasher(msgs: List[bytes]) -> List[bytes]:
            blocks, nblocks = K.pack_blocks(msgs)
            words = K.keccak256_blocks(blocks, nblocks)
            return K.digest_words_to_bytes(np.asarray(words))[:len(msgs)]
        _device_hasher = hasher
    return _device_hasher


def collect_dirty(trie: Trie):
    """(node, depth) for every resident node lacking a memo, via
    iterative DFS.  Children of memoized nodes are skipped — their
    hashes are already final."""
    out = []
    if trie.root is None or trie.root[0] == HASHREF:
        return out
    stack = [(trie.root, 0)]
    while stack:
        node, depth = stack.pop()
        if node is None or node[0] == HASHREF:
            continue
        if node[_MEMO] is not None:
            continue
        out.append((node, depth))
        kind = node[0]
        if kind == EXT:
            stack.append((node[2], depth + 1))
        elif kind == BRANCH:
            for c in node[1]:
                stack.append((c, depth + 1))
    return out


def _child_ref(node):
    """Parent-embedded reference of an already-processed child."""
    if node[0] == HASHREF:
        return node[1]
    encoded, ref = node[_MEMO]
    return ref


def _encode(node) -> bytes:
    kind = node[0]
    if kind == LEAF:
        return rlp.encode([hex_prefix(node[1], True), node[2]])
    if kind == EXT:
        return rlp.encode([hex_prefix(node[1], False), _child_ref(node[2])])
    items = [_child_ref(c) if c is not None else b"" for c in node[1]]
    items.append(node[2])
    return rlp.encode(items)


# Default threshold: the default effectively disables device rehash
# (per-level host serialization and the transfers around each level
# sit on the device path; the host C++ keccak path has none).  The
# crossover is not re-measured on a locally attached chip:
# tools/rehash_crossover.py measures it, CORETH_REHASH_MIN_BATCH sets
# it (ROADMAP Design D8).
import os as _os
DEFAULT_MIN_BATCH = int(_os.environ.get("CORETH_REHASH_MIN_BATCH",
                                        "1000000"))


def device_rehash(trie: Trie, min_batch: int = DEFAULT_MIN_BATCH,
                  hasher=None) -> bytes:
    """Fill memos for all dirty nodes using batched device keccak,
    then return the root hash.

    Bit-identical to ``trie.hash()`` — asserted by tests — but the hash
    work runs as one device call per trie level.
    """
    dirty = collect_dirty(trie)
    if not dirty:
        return trie.hash()
    if len(dirty) < min_batch:
        return trie.hash()  # host native keccak path
    hasher = hasher or _get_device_hasher()
    max_depth = max(d for _, d in dirty)
    by_depth: List[List] = [[] for _ in range(max_depth + 1)]
    for node, d in dirty:
        by_depth[d].append(node)
    for depth in range(max_depth, -1, -1):
        level = by_depth[depth]
        if not level:
            continue
        encodings = [_encode(n) for n in level]
        # small encodings inline (no hash); big ones batch to device
        to_hash = [(i, e) for i, e in enumerate(encodings) if len(e) >= 32]
        if len(to_hash) >= min_batch:
            digests = hasher([e for _, e in to_hash])
        else:
            digests = [keccak256(e) for _, e in to_hash]
        hash_map = {i: dg for (i, _), dg in zip(to_hash, digests)}
        for i, (node, encoded) in enumerate(zip(level, encodings)):
            if i in hash_map:
                node[_MEMO] = (encoded, hash_map[i])
            else:
                node[_MEMO] = (encoded, rlp.decode(encoded))
    return trie.hash()

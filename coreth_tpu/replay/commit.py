"""Window-batched trie commit — state-root folding off the critical path.

Both replay execution paths (the transfer/token fast path's
``_validate_and_advance`` and the machine executor's ``_finish_block``)
used to fold every block's storage/account writes into the trie and
rehash PER BLOCK — the remaining serial cost once execution itself
parallelized (the FAFO observation: Merkleizing every block is the
throughput ceiling).  This pipeline decouples commitment from
execution, Reddio-style:

- finished blocks STAGE their effects — storage writes dedupe to
  last-value-per-(contract, slot) and account states to
  last-value-per-address across the whole fused window (dict updates,
  O(writes));
- ``flush()`` — called once per window, after the next window's device
  dispatch is already in flight — folds the deduped set in ONE batched
  fold-and-root call per contract plus one for the account trie
  (native backend: ``coreth_trie_fold_storage`` /
  ``coreth_trie_fold_accounts_root``; python backend: the same deduped
  loop through ``mpt.trie`` with the measured ``mpt.rehash`` device
  batched-keccak policy), then verifies the root against the LAST
  staged block's header.

Roots stay bit-identical: intermediate per-block roots are never
materialized (that is the point), but the window root must equal the
chain's, and ``CORETH_TRIE_CHECK=1`` re-derives every window root on
the Python trie (mpt.native_trie.CheckedSecureTrie).  Reads that could
race a pending fold go through ``account_view``/``base_value`` so the
deferred writes are always visible; every path that hands the tries to
another consumer (host fallback, engine commit, scratch StateDBs)
flushes first.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from coreth_tpu import faults, rlp
from coreth_tpu.obs import recorder as forensics
from coreth_tpu.crypto import keccak256
from coreth_tpu.mpt.rehash import device_rehash
from coreth_tpu.state.flat import DELETED as FLAT_DELETED
from coreth_tpu.types.account import EMPTY_CODE_HASH, EMPTY_ROOT_HASH

# Injection point: the window fold fails (a device rehash hiccup, an
# I/O error in the native trie).  Transient plans retry with the
# supervisor's backoff; a persistent flush failure is fatal — there is
# no alternative commit backend, so it surfaces to the caller.
PT_FLUSH = faults.declare(
    "commit/flush_fail", "window trie-fold flush failure")


class CommitPipeline:
    """Per-engine staging buffer + window flusher for trie commits."""

    def __init__(self, engine):
        self.e = engine
        # last-value-per-(contract, slot) across the staged window;
        # values are ints (0 => delete), exactly the writes_final shape
        self.writes: Dict[Tuple[bytes, bytes], int] = {}
        # last-value-per-address: addr -> (balance, nonce)
        self.accounts: Dict[bytes, Tuple[int, int]] = {}
        self.expected_root: Optional[bytes] = None
        self.expected_number: Optional[int] = None
        self.expected_header = None
        self.staged_blocks = 0
        # commit-phase attribution (bench.py fold_ms_per_block)
        self.fold_s = 0.0
        self.fold_calls = 0
        self.fold_blocks = 0
        # slot-key keccak memo: slots recur across windows, the key
        # hash never changes (the addr_hashes analog for storage)
        self._key_hash: Dict[bytes, bytes] = {}

    # ------------------------------------------------------------ staging
    def stage(self, header, writes: Dict[Tuple[bytes, bytes], int],
              accounts: Dict[bytes, Tuple[int, int]]) -> None:
        """Queue one finished block's trie effects; later stages of the
        same slot/account overwrite earlier ones (window dedup)."""
        self.writes.update(writes)
        self.accounts.update(accounts)
        self.expected_root = header.root
        self.expected_number = header.number
        self.expected_header = header
        self.staged_blocks += 1

    def pending(self) -> bool:
        return self.staged_blocks > 0

    def account_view(self, addr: bytes) -> Optional[Tuple[int, int]]:
        """(balance, nonce) staged but not yet folded, else None."""
        return self.accounts.get(addr)

    def base_value(self, contract: bytes, key: bytes) -> Optional[int]:
        """Staged-but-unfolded storage value, else None."""
        return self.writes.get((contract, key))

    # ------------------------------------------------------------- flush
    def _hash_key(self, key: bytes) -> bytes:
        h = self._key_hash.get(key)
        if h is None:
            h = keccak256(key)
            self._key_hash[key] = h
        return h

    def _fold_storage(self) -> None:
        e = self.e
        by_contract: Dict[bytes, List[Tuple[bytes, int]]] = {}
        for (contract, key), v in self.writes.items():
            by_contract.setdefault(contract, []).append((key, v))
        for contract, kvs in by_contract.items():
            st = e._storage_trie(contract)
            if e._native:
                keys = b"".join(self._hash_key(k) for k, _v in kvs)
                vals = b"".join(v.to_bytes(32, "big") for _k, v in kvs)
                root = st.fold_storage(keys, vals, len(kvs))
            else:
                for key, v in kvs:
                    if v == 0:
                        st.delete(key)
                    else:
                        st.update(key, rlp.encode(
                            v.to_bytes(32, "big").lstrip(b"\x00")))
                root = device_rehash(st)
            e.state.roots[e.state.index[contract]] = root

    def _fold_accounts(self) -> bytes:
        e = self.e
        state = e.state
        if e._native:
            n = len(self.accounts)
            keys = bytearray()
            bals = bytearray()
            roots = bytearray()
            hashes = bytearray()
            mc = bytearray(n)
            dels = bytearray(n)
            nlist = []
            for i, (addr, (balance, nonce)) in enumerate(
                    self.accounts.items()):
                idx = e._account(addr)
                keys += state.addr_hashes[idx]
                code_hash = state.code_hashes[idx]
                storage_root = state.roots[idx]
                if (balance == 0 and nonce == 0
                        and code_hash == EMPTY_CODE_HASH
                        and storage_root == EMPTY_ROOT_HASH
                        and not state.multicoin[idx]):
                    dels[i] = 1  # EIP-158 touched-empty deletion
                    balance = 0
                bals += balance.to_bytes(32, "big")
                roots += storage_root
                hashes += code_hash
                mc[i] = 1 if state.multicoin[idx] else 0
                nlist.append(nonce)
            return e.trie.fold_accounts_root(
                bytes(keys), bytes(bals), nlist, bytes(roots),
                bytes(hashes), bytes(mc), bytes(dels))
        from coreth_tpu.types import StateAccount
        for addr, (balance, nonce) in self.accounts.items():
            idx = e._account(addr)
            code_hash = state.code_hashes[idx]
            storage_root = state.roots[idx]
            if (balance == 0 and nonce == 0
                    and code_hash == EMPTY_CODE_HASH
                    and storage_root == EMPTY_ROOT_HASH
                    and not state.multicoin[idx]):
                e.trie.delete(addr)
            else:
                e.trie.update(addr, StateAccount(
                    nonce=nonce, balance=balance, root=storage_root,
                    code_hash=code_hash,
                    is_multi_coin=state.multicoin[idx]).rlp())
        return device_rehash(e.trie)

    def flush(self) -> bytes:
        """Fold the staged window (storage first — the account fold
        consumes the fresh storage roots — then accounts), verify the
        root against the last staged header, advance engine.root."""
        if not self.staged_blocks:
            return self.e.root
        with self.e.account.enter("commit/flush"):
            return self._flush()

    def _flush(self) -> bytes:
        e = self.e
        from coreth_tpu.replay.engine import StateRootMismatch
        sup = getattr(e, "supervisor", None)
        if sup is not None:
            # the injected gate retries transient faults with backoff
            # BEFORE the fold runs (the fold itself must not re-run)
            sup.retry_point("commit", PT_FLUSH)
        else:
            faults.fire(PT_FLUSH)
        prev_root = e.root
        t0 = time.monotonic()
        try:
            self._fold_storage()
            root = self._fold_accounts()
        except AssertionError as exc:
            # the CORETH_TRIE_CHECK python-twin oracle tripped inside
            # the fold (mpt.native_trie.TrieOracleError): route the
            # evidence through the flight recorder before the raise
            # unwinds the window (a witness for the staged tip may
            # never come — flush_pending writes the context bundle)
            forensics.note_trigger(
                forensics.TR_TRIE, repr(exc),
                number=self.expected_number)
            raise
        dt = time.monotonic() - t0
        self.fold_s += dt
        e.stats.t_trie += dt
        self.fold_calls += 1
        self.fold_blocks += self.staged_blocks
        expected = self.expected_root
        number = self.expected_number
        header = self.expected_header
        n_blocks = self.staged_blocks
        writes = self.writes
        accounts = self.accounts
        self.writes = {}
        self.accounts = {}
        self.staged_blocks = 0
        self.expected_root = None
        self.expected_number = None
        self.expected_header = None
        if root != expected:
            forensics.note_trigger(
                forensics.TR_ROOT,
                f"window fold root mismatch at block {number} "
                f"({n_blocks} staged)", number=number,
                got=root.hex(), want=expected.hex())
            # the tries hold the fold all the same: the error carries
            # the keys it wrote, for a caller that can take them back
            # out (ReplayEngine.replay_block(hold=True))
            raise StateRootMismatch(
                f"state root mismatch at block {number} "
                f"(commit window of {n_blocks}): {root.hex()} != "
                f"{expected.hex()}", accounts, writes)
        e.root = root
        flat = getattr(e, "flat", None)
        if flat is not None:
            # seal the window as ONE flat generation — the post-fold
            # storage roots are fresh in e.state.roots, so the account
            # tuples are complete (the background exporter re-derives
            # and root-checks the trie from exactly this diff)
            state = e.state
            gen_accounts: Dict[bytes, object] = {}
            for addr, (balance, nonce) in accounts.items():
                idx = state.index[addr]
                code_hash = state.code_hashes[idx]
                storage_root = state.roots[idx]
                multicoin = bool(state.multicoin[idx])
                if (balance == 0 and nonce == 0
                        and code_hash == EMPTY_CODE_HASH
                        and storage_root == EMPTY_ROOT_HASH
                        and not multicoin):
                    gen_accounts[addr] = FLAT_DELETED  # EIP-158 deletion
                else:
                    gen_accounts[addr] = (balance, nonce, storage_root,
                                          code_hash, multicoin)
            flat.apply_generation(
                number=number, block_hash=header.hash(), root=root,
                header=header, prev_root=prev_root,
                accounts=gen_accounts, storage=writes, kind="window")
        return root

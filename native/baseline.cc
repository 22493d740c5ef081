// Compiled sequential value-transfer processor — the Go-proxy baseline.
//
// The ≥20x north-star target (BASELINE.md) is defined against the Go
// reference's single-threaded StateProcessor, but no Go toolchain exists
// in this image (and there is no network egress to install one), so the
// reference cannot be built here.  This file is the closest honest
// stand-in: a single-threaded, compiled (C++ -O3) replay of the same
// transfer workload doing the same per-tx and per-block work the Go hot
// path does (reference core/state_processor.go:95 loop +
// core/state/statedb.go IntermediateRoot):
//
//   per tx:    ecrecover (libsecp-style ladder, secp256k1.cc) -> sender,
//              nonce check, balance-requirement check, balance moves
//   per block: fold touched accounts into a secure Merkle-Patricia trie
//              (keccak-hashed keys, RLP account encoding, memoized
//              incremental rehash — the hasher.go/statedb analog) and
//              compare the root against the block header.
//
// Exposed via the C ABI for bench.py.  Big-int balances are unsigned
// __int128 — ample for the bench workload; inputs above 2^127 are
// rejected so the Python caller can fall back.

#include <cstdint>
#include <algorithm>
#include <cstring>
#include <map>
#include <memory>
#include <string>
#include <unordered_map>
#include <vector>

extern "C" void coreth_keccak256(const uint8_t*, uint64_t, uint8_t*);
extern "C" int coreth_ecrecover(const uint8_t*, const uint8_t*,
                                const uint8_t*, int, uint8_t*);

namespace {

using u128 = unsigned __int128;
using Bytes = std::vector<uint8_t>;

// ------------------------------------------------------------------ RLP

void rlp_append_len(Bytes& out, size_t len, uint8_t short_base,
                    uint8_t long_base) {
  if (len < 56) {
    out.push_back(short_base + (uint8_t)len);
  } else {
    uint8_t be[8];
    int n = 0;
    size_t v = len;
    while (v) {
      be[n++] = (uint8_t)(v & 0xff);
      v >>= 8;
    }
    out.push_back(long_base + n);
    for (int i = n - 1; i >= 0; --i) out.push_back(be[i]);
  }
}

void rlp_string(Bytes& out, const uint8_t* data, size_t len) {
  if (len == 1 && data[0] < 0x80) {
    out.push_back(data[0]);
    return;
  }
  rlp_append_len(out, len, 0x80, 0xb7);
  out.insert(out.end(), data, data + len);
}

void rlp_uint(Bytes& out, u128 v) {
  uint8_t be[16];
  int n = 0;
  while (v) {
    be[n++] = (uint8_t)(v & 0xff);
    v >>= 8;
  }
  // big-endian, no leading zeros; zero encodes as empty string
  uint8_t tmp[16];
  for (int i = 0; i < n; ++i) tmp[i] = be[n - 1 - i];
  rlp_string(out, tmp, n);
}

Bytes rlp_list(const Bytes& payload) {
  Bytes out;
  rlp_append_len(out, payload.size(), 0xc0, 0xf7);
  out.insert(out.end(), payload.begin(), payload.end());
  return out;
}

// keccak256(rlp("")) / keccak256(rlp(empty list)) constants
const uint8_t EMPTY_ROOT[32] = {
    0x56, 0xe8, 0x1f, 0x17, 0x1b, 0xcc, 0x55, 0xa6, 0xff, 0x83, 0x45,
    0xe6, 0x92, 0xc0, 0xf8, 0x6e, 0x5b, 0x48, 0xe0, 0x1b, 0x99, 0x6c,
    0xad, 0xc0, 0x01, 0x62, 0x2f, 0xb5, 0xe3, 0x63, 0xb4, 0x21};
const uint8_t EMPTY_CODE[32] = {
    0xc5, 0xd2, 0x46, 0x01, 0x86, 0xf7, 0x23, 0x3c, 0x92, 0x7e, 0x7d,
    0xb2, 0xdc, 0xc7, 0x03, 0xc0, 0xe5, 0x00, 0xb6, 0x53, 0xca, 0x82,
    0x27, 0x3b, 0x7b, 0xfa, 0xd8, 0x04, 0x5d, 0x85, 0xa4, 0x70};

Bytes account_rlp(u128 balance, uint64_t nonce) {
  Bytes payload;
  rlp_uint(payload, nonce);
  rlp_uint(payload, balance);
  rlp_string(payload, EMPTY_ROOT, 32);
  rlp_string(payload, EMPTY_CODE, 32);
  rlp_uint(payload, 0);  // is_multi_coin
  return rlp_list(payload);
}

// ------------------------------------------------------- secure MPT

// Node kinds; keys are 64 uniform-depth nibbles (keccak-hashed
// addresses) so the trie only ever needs leaf/ext/branch inserts into
// prefix-free keys — exactly the shape statedb's account trie has.
struct Node {
  enum Kind { LEAF, EXT, BRANCH } kind;
  Bytes path;                      // leaf/ext nibbles
  Bytes value;                     // leaf value
  std::unique_ptr<Node> child;     // ext child
  std::unique_ptr<Node> kids[16];  // branch children
  // memo: rlp encoding + ref (hash or inline); dirty => recompute
  Bytes enc;
  Bytes ref;  // 32-byte hash, or inline rlp (< 32 bytes)
  bool dirty = true;
  bool exported = false;  // emitted by export_nodes since last change

  explicit Node(Kind k) : kind(k) {}
};

Bytes hex_prefix(const Bytes& nibbles, bool leaf) {
  Bytes out;
  uint8_t flag = leaf ? 2 : 0;
  if (nibbles.size() % 2) {
    out.push_back((uint8_t)(((flag | 1) << 4) | nibbles[0]));
    for (size_t i = 1; i + 1 < nibbles.size() + 1; i += 2)
      out.push_back((uint8_t)((nibbles[i] << 4) | nibbles[i + 1]));
  } else {
    out.push_back((uint8_t)(flag << 4));
    for (size_t i = 0; i + 1 < nibbles.size() + 1 && i < nibbles.size();
         i += 2)
      out.push_back((uint8_t)((nibbles[i] << 4) | nibbles[i + 1]));
  }
  return out;
}

struct Trie {
  std::unique_ptr<Node> root;

  void insert(const uint8_t* nibbles, size_t depth, const Bytes& value) {
    root = insert_node(std::move(root), nibbles, depth, value);
  }

  std::unique_ptr<Node> insert_node(std::unique_ptr<Node> n,
                                    const uint8_t* key, size_t len,
                                    const Bytes& value) {
    if (!n) {
      auto leaf = std::make_unique<Node>(Node::LEAF);
      leaf->path.assign(key, key + len);
      leaf->value = value;
      return leaf;
    }
    n->dirty = true;
    n->exported = false;
    if (n->kind == Node::BRANCH) {
      uint8_t idx = key[0];
      n->kids[idx] =
          insert_node(std::move(n->kids[idx]), key + 1, len - 1, value);
      return n;
    }
    // common prefix with leaf/ext path
    size_t cp = 0;
    while (cp < n->path.size() && cp < len && n->path[cp] == key[cp]) ++cp;
    if (n->kind == Node::LEAF) {
      if (cp == n->path.size() && cp == len) {
        n->value = value;  // overwrite
        return n;
      }
    } else if (cp == n->path.size()) {  // ext fully matched
      n->child = insert_node(std::move(n->child), key + cp, len - cp, value);
      return n;
    }
    // split at cp
    auto branch = std::make_unique<Node>(Node::BRANCH);
    // old side
    uint8_t old_idx = n->path[cp];
    if (n->kind == Node::LEAF) {
      auto old_leaf = std::make_unique<Node>(Node::LEAF);
      old_leaf->path.assign(n->path.begin() + cp + 1, n->path.end());
      old_leaf->value = std::move(n->value);
      branch->kids[old_idx] = std::move(old_leaf);
    } else {
      if (cp + 1 == n->path.size()) {
        branch->kids[old_idx] = std::move(n->child);
      } else {
        auto old_ext = std::make_unique<Node>(Node::EXT);
        old_ext->path.assign(n->path.begin() + cp + 1, n->path.end());
        old_ext->child = std::move(n->child);
        branch->kids[old_idx] = std::move(old_ext);
      }
    }
    // new side (uniform-depth keys => never exhausted at a split)
    auto new_leaf = std::make_unique<Node>(Node::LEAF);
    new_leaf->path.assign(key + cp + 1, key + len);
    new_leaf->value = value;
    branch->kids[key[cp]] = std::move(new_leaf);
    if (cp > 0) {
      auto ext = std::make_unique<Node>(Node::EXT);
      ext->path.assign(key, key + cp);
      ext->child = std::move(branch);
      return ext;
    }
    return branch;
  }

  // ------------------------------------------------------------ get
  const Bytes* get(const uint8_t* key, size_t len) const {
    const Node* n = root.get();
    while (n) {
      if (n->kind == Node::BRANCH) {
        if (len == 0) return nullptr;
        n = n->kids[key[0]].get();
        ++key;
        --len;
        continue;
      }
      size_t pl = n->path.size();
      if (pl > len || !std::equal(n->path.begin(), n->path.end(), key))
        return nullptr;
      if (n->kind == Node::LEAF)
        return pl == len ? &n->value : nullptr;
      key += pl;
      len -= pl;
      n = n->child.get();
    }
    return nullptr;
  }

  // --------------------------------------------------------- delete
  void erase(const uint8_t* nibbles, size_t len) {
    root = erase_node(std::move(root), nibbles, len);
  }

  // collapse helper: absorb a lone child into its parent slot
  static std::unique_ptr<Node> collapse(uint8_t idx,
                                        std::unique_ptr<Node> child) {
    if (child->kind == Node::BRANCH) {
      auto ext = std::make_unique<Node>(Node::EXT);
      ext->path.push_back(idx);
      ext->child = std::move(child);
      return ext;
    }
    // leaf/ext: prepend the branch nibble to its path
    child->path.insert(child->path.begin(), idx);
    child->dirty = true;
    child->exported = false;
    child->ref.clear();
    return child;
  }

  std::unique_ptr<Node> erase_node(std::unique_ptr<Node> n,
                                   const uint8_t* key, size_t len) {
    if (!n) return nullptr;
    n->dirty = true;
    n->exported = false;
    n->ref.clear();
    if (n->kind == Node::BRANCH) {
      if (len == 0) return n;  // no branch values in secure tries
      uint8_t idx = key[0];
      n->kids[idx] = erase_node(std::move(n->kids[idx]), key + 1,
                                len - 1);
      int live = -1, count = 0;
      for (int i = 0; i < 16; ++i)
        if (n->kids[i]) {
          live = i;
          ++count;
        }
      if (count == 0) return nullptr;
      if (count == 1) {
        auto merged = collapse((uint8_t)live, std::move(n->kids[live]));
        return merged;
      }
      return n;
    }
    size_t pl = n->path.size();
    if (pl > len || !std::equal(n->path.begin(), n->path.end(), key))
      return n;  // key absent
    if (n->kind == Node::LEAF) {
      if (pl == len) return nullptr;
      return n;
    }
    n->child = erase_node(std::move(n->child), key + pl, len - pl);
    if (!n->child) return nullptr;
    if (n->child->kind != Node::BRANCH) {
      // merge ext with its short child
      n->child->path.insert(n->child->path.begin(), n->path.begin(),
                            n->path.end());
      n->child->dirty = true;
      n->child->exported = false;
      n->child->ref.clear();
      return std::move(n->child);
    }
    return n;
  }

  // ------------------------------------------------------- export
  // Incremental: a clean, already-exported node encodes an unchanged
  // subtree, so the walk prunes there — repeat exports cost O(changed)
  // instead of O(trie).
  void export_nodes(std::vector<std::pair<Bytes, Bytes>>& out, Node* n,
                    bool mark) {
    if (!n) return;
    if (!n->dirty && n->exported) return;
    encode(n);
    if (n->enc.size() >= 32) out.emplace_back(n->ref, n->enc);
    if (mark) n->exported = true;  // size probe must not mutate
    if (n->kind == Node::EXT) {
      export_nodes(out, n->child.get(), mark);
    } else if (n->kind == Node::BRANCH) {
      for (int i = 0; i < 16; ++i)
        export_nodes(out, n->kids[i].get(), mark);
    }
  }

  // memoized encode: fills enc/ref, clears dirty
  const Bytes& encode(Node* n) {
    if (!n->dirty && !n->ref.empty()) return n->ref;
    Bytes payload;
    if (n->kind == Node::LEAF) {
      Bytes hp = hex_prefix(n->path, true);
      rlp_string(payload, hp.data(), hp.size());
      rlp_string(payload, n->value.data(), n->value.size());
    } else if (n->kind == Node::EXT) {
      Bytes hp = hex_prefix(n->path, false);
      rlp_string(payload, hp.data(), hp.size());
      const Bytes& cref = encode(n->child.get());
      if (cref.size() == 32) {
        rlp_string(payload, cref.data(), 32);
      } else {
        payload.insert(payload.end(), cref.begin(), cref.end());
      }
    } else {
      for (int i = 0; i < 16; ++i) {
        if (!n->kids[i]) {
          payload.push_back(0x80);
          continue;
        }
        const Bytes& cref = encode(n->kids[i].get());
        if (cref.size() == 32) {
          rlp_string(payload, cref.data(), 32);
        } else {
          payload.insert(payload.end(), cref.begin(), cref.end());
        }
      }
      payload.push_back(0x80);  // empty branch value
    }
    n->enc = rlp_list(payload);
    if (n->enc.size() >= 32) {
      n->ref.resize(32);
      coreth_keccak256(n->enc.data(), n->enc.size(), n->ref.data());
    } else {
      n->ref = n->enc;  // inline
    }
    n->dirty = false;
    return n->ref;
  }

  void hash_root(uint8_t out[32]) {
    if (!root) {
      std::memcpy(out, EMPTY_ROOT, 32);
      return;
    }
    const Bytes& ref = encode(root.get());
    if (ref.size() == 32) {
      std::memcpy(out, ref.data(), 32);
    } else {
      coreth_keccak256(root->enc.data(), root->enc.size(), out);
    }
  }
};

struct AddrHash {
  size_t operator()(const std::string& k) const {
    size_t h;
    std::memcpy(&h, k.data(), sizeof(h));
    return h;
  }
};

struct Account {
  u128 balance = 0;
  uint64_t nonce = 0;
};

u128 load_u128_be32(const uint8_t* p, bool* too_big) {
  for (int i = 0; i < 16; ++i)
    if (p[i]) *too_big = true;
  u128 v = 0;
  for (int i = 16; i < 32; ++i) v = (v << 8) | p[i];
  if (p[16] & 0x80) *too_big = true;  // keep headroom for sums
  return v;
}

}  // namespace

extern "C" {

// ------------------------------------------------------ trie handle API
//
// The engine's account/storage-trie fold in C++ (the hasher.go +
// statedb updateTrie role): handle-based secure-trie operations over
// pre-hashed 32-byte keys.  Batch update/delete amortizes the ctypes
// boundary; export dumps (hash, rlp) node pairs for interop with the
// Python node store.

void* coreth_trie_new() { return new Trie(); }

void coreth_trie_free(void* h) { delete (Trie*)h; }

static void key_to_nibs(const uint8_t* key32, uint8_t nib[64]) {
  for (int i = 0; i < 32; ++i) {
    nib[2 * i] = key32[i] >> 4;
    nib[2 * i + 1] = key32[i] & 0x0F;
  }
}

// records: n entries of key_hash32; vals packed with u32 lengths
// (length 0 = delete)
void coreth_trie_update_batch(void* h, const uint8_t* keys32,
                              const uint8_t* vals,
                              const uint32_t* val_lens, uint64_t n) {
  Trie* t = (Trie*)h;
  uint8_t nib[64];
  size_t off = 0;
  for (uint64_t i = 0; i < n; ++i) {
    key_to_nibs(keys32 + 32 * i, nib);
    uint32_t vl = val_lens[i];
    if (vl == 0) {
      t->erase(nib, 64);
    } else {
      t->insert(nib, 64, Bytes(vals + off, vals + off + vl));
      off += vl;
    }
  }
}

// returns 1 + copies value when present (cap bytes available), else 0
int coreth_trie_get(void* h, const uint8_t* key32, uint8_t* out,
                    uint32_t cap, uint32_t* out_len) {
  Trie* t = (Trie*)h;
  uint8_t nib[64];
  key_to_nibs(key32, nib);
  const Bytes* v = t->get(nib, 64);
  if (!v) return 0;
  *out_len = (uint32_t)v->size();
  if (v->size() <= cap) std::memcpy(out, v->data(), v->size());
  return 1;
}

void coreth_trie_hash(void* h, uint8_t out32[32]) {
  ((Trie*)h)->hash_root(out32);
}

// Ordered (derive_sha-shaped) batch insert: VARIABLE-length keys — the
// rlp(index) keys of tx/receipt tries are 1..9 bytes, not the
// pre-hashed 32-byte secure keys above.  Insert order is free (the
// handle trie is pointer-based, not streaming); one crossing folds a
// whole block's receipts, coreth_trie_hash reads the root.
void coreth_trie_update_ordered(void* h, const uint8_t* keys,
                                const uint32_t* key_lens,
                                const uint8_t* vals,
                                const uint32_t* val_lens, uint64_t n) {
  Trie* t = (Trie*)h;
  uint8_t nib[32];
  size_t ko = 0, vo = 0;
  for (uint64_t i = 0; i < n; ++i) {
    uint32_t kl = key_lens[i];
    uint32_t use = kl > 16 ? 16 : kl;  // rlp(u64 index) caps at 9
    for (uint32_t j = 0; j < use; ++j) {
      nib[2 * j] = keys[ko + j] >> 4;
      nib[2 * j + 1] = keys[ko + j] & 0x0F;
    }
    ko += kl;
    uint32_t vl = val_lens[i];
    t->insert(nib, 2 * use, Bytes(vals + vo, vals + vo + vl));
    vo += vl;
  }
}

// Batched account fold (the statedb updateTrie + IntermediateRoot hot
// loop in one call): n records of pre-hashed key, 32-byte BE balance,
// nonce, storage root, code hash, multicoin flag; del[i] != 0 deletes.
void coreth_trie_fold_accounts(void* h, const uint8_t* keys32,
                               const uint8_t* balances32,
                               const uint64_t* nonces,
                               const uint8_t* roots32,
                               const uint8_t* code_hashes32,
                               const uint8_t* mc, const uint8_t* del,
                               uint64_t n) {
  Trie* t = (Trie*)h;
  uint8_t nib[64];
  for (uint64_t i = 0; i < n; ++i) {
    key_to_nibs(keys32 + 32 * i, nib);
    if (del[i]) {
      t->erase(nib, 64);
      continue;
    }
    Bytes payload;
    rlp_uint(payload, nonces[i]);
    {  // arbitrary-width balance from 32-byte BE
      const uint8_t* b = balances32 + 32 * i;
      int lead = 0;
      while (lead < 32 && b[lead] == 0) ++lead;
      rlp_string(payload, b + lead, 32 - lead);
    }
    rlp_string(payload, roots32 + 32 * i, 32);
    rlp_string(payload, code_hashes32 + 32 * i, 32);
    rlp_uint(payload, mc[i] ? 1 : 0);
    t->insert(nib, 64, rlp_list(payload));
  }
}

// Explicit single-key deletion (zeroed slot / EIP-158 empty-account
// removal) — the one-record form of the len==0 update_batch path.
void coreth_trie_delete(void* h, const uint8_t* key32) {
  Trie* t = (Trie*)h;
  uint8_t nib[64];
  key_to_nibs(key32, nib);
  t->erase(nib, 64);
}

// Batched storage fold-and-root: ONE call per contract per commit
// window.  n records of pre-hashed slot key + raw 32-byte BE value;
// an all-zero value deletes the slot (slot zeroing), otherwise the
// stored leaf is RLP(value stripped of leading zeros) — the exact
// encoding state_object.go updateTrie writes.  The new storage root
// lands in root_out, so the caller pays one ctypes crossing for the
// whole deduped window instead of one per slot plus a hash call.
void coreth_trie_fold_storage(void* h, const uint8_t* keys32,
                              const uint8_t* vals32, uint64_t n,
                              uint8_t root_out[32]) {
  Trie* t = (Trie*)h;
  uint8_t nib[64];
  for (uint64_t i = 0; i < n; ++i) {
    key_to_nibs(keys32 + 32 * i, nib);
    const uint8_t* v = vals32 + 32 * i;
    int lead = 0;
    while (lead < 32 && v[lead] == 0) ++lead;
    if (lead == 32) {
      t->erase(nib, 64);
      continue;
    }
    Bytes payload;
    rlp_string(payload, v + lead, 32 - lead);
    t->insert(nib, 64, payload);
  }
  t->hash_root(root_out);
}

// Account fold-and-root: fold_accounts + rehash in one crossing (the
// per-window account-trie commit).
void coreth_trie_fold_accounts_root(
    void* h, const uint8_t* keys32, const uint8_t* balances32,
    const uint64_t* nonces, const uint8_t* roots32,
    const uint8_t* code_hashes32, const uint8_t* mc, const uint8_t* del,
    uint64_t n, uint8_t root_out[32]) {
  coreth_trie_fold_accounts(h, keys32, balances32, nonces, roots32,
                            code_hashes32, mc, del, n);
  ((Trie*)h)->hash_root(root_out);
}

// export all hashed nodes: returns byte size written into `out`
// ([hash32][u32 len][rlp])*, or the required size when out == NULL.
uint64_t coreth_trie_export(void* h, uint8_t* out, uint64_t cap) {
  Trie* t = (Trie*)h;
  std::vector<std::pair<Bytes, Bytes>> nodes;
  if (t->root) t->export_nodes(nodes, t->root.get(), out != nullptr);
  uint64_t need = 0;
  for (auto& kv : nodes) need += 32 + 4 + kv.second.size();
  if (!out || cap < need) return need;
  uint64_t off = 0;
  for (auto& kv : nodes) {
    std::memcpy(out + off, kv.first.data(), 32);
    off += 32;
    uint32_t l = (uint32_t)kv.second.size();
    std::memcpy(out + off, &l, 4);
    off += 4;
    std::memcpy(out + off, kv.second.data(), l);
    off += l;
  }
  return need;
}

}  // extern "C"

// --------------------------------------------------------- receipt check
//
// A device-path block's receipt root, header bloom and gas in ONE
// ctypes call (the DeriveSha/StackTrie + CreateBloom role, reference
// core/types/hashing.go:97 + bloom9.go), from the columns the replay
// engine's classifier builds.  Device-path receipts are uniform: status
// 1, cumulative gas, and 0 or 1 log of the ERC-20 Transfer shape
// (address20 ++ 3 topics32 ++ data32 — LOG_REC bytes packed a log).
//
// The trie's keys are rlp(0..n-1), so its shape follows from n alone:
// ReceiptTrie walks the keys in sorted order (DeriveSha's 1..0x7f, 0,
// 0x80.. — hashing.go:87-110) over index ranges, one stack frame a
// nibble level, and hashes each node as soon as its children are
// known.  No heap node and no buffer a receipt: every encoding goes
// through a fixed buffer of its frame.

namespace {

constexpr size_t LOG_REC = 148;
// the largest encoding a frame holds: a branch of 16 hashed children
// (16 x 33 + 1 bytes and its list header), or a leaf of one receipt
// (type byte, status, gas, bloom, one log: ~450 bytes)
constexpr size_t NODE_BUF = 640;
constexpr size_t HEAD = 16;  // room for the headers written in front

void bloom_or(uint8_t bloom[256], const uint8_t* value, size_t len) {
  uint8_t h[32];
  coreth_keccak256(value, len, h);
  for (int i = 0; i < 6; i += 2) {
    uint32_t bit = (((uint32_t)h[i] << 8) | h[i + 1]) & 0x7FF;
    bloom[255 - bit / 8] |= (uint8_t)(1u << (bit % 8));
  }
}

// The RLP header of a payload of len bytes, written just in front of
// it (at most 9 bytes); returns the header's first byte.
uint8_t* put_head(uint8_t* payload, size_t len, uint8_t short_base,
                  uint8_t long_base) {
  if (len < 56) {
    *--payload = (uint8_t)(short_base + len);
    return payload;
  }
  int nb = 0;
  for (size_t v = len; v; v >>= 8) {
    *--payload = (uint8_t)(v & 0xff);
    ++nb;
  }
  *--payload = (uint8_t)(long_base + nb);
  return payload;
}

uint8_t* put_string(uint8_t* out, const uint8_t* data, size_t len) {
  if (len == 1 && data[0] < 0x80) {
    *out = data[0];
    return out + 1;
  }
  uint8_t* payload = out + (len < 56 ? 1 : 1 + (len < 256 ? 1 : 2));
  put_head(payload, len, 0x80, 0xb7);  // len < 65536 here
  std::memcpy(payload, data, len);
  return payload + len;
}

uint8_t* put_uint(uint8_t* out, uint64_t v) {
  uint8_t be[8];
  int n = 0;
  for (uint64_t x = v; x; x >>= 8) ++n;
  for (int i = 0; i < n; ++i) be[i] = (uint8_t)(v >> (8 * (n - 1 - i)));
  return put_string(out, be, n);
}

// A node's reference in its parent: its hash, or its encoding itself
// when that is shorter than 32 bytes.  Returns the reference's length.
size_t node_ref(const uint8_t* enc, size_t len, uint8_t ref[32]) {
  if (len >= 32) {
    coreth_keccak256(enc, len, ref);
    return 32;
  }
  std::memcpy(ref, enc, len);
  return len;
}

uint8_t* put_ref(uint8_t* out, const uint8_t* ref, size_t len) {
  if (len == 32) return put_string(out, ref, 32);
  std::memcpy(out, ref, len);  // an inline node
  return out + len;
}

// hex-prefix encoding of nib[0..len) as an RLP string
uint8_t* put_path(uint8_t* out, const uint8_t* nib, size_t len,
                  bool leaf) {
  uint8_t hp[10];  // rlp(u64) keys are at most 18 nibbles
  size_t k = 0;
  uint8_t flag = leaf ? 2 : 0;
  size_t i = 0;
  if (len % 2) {
    hp[k++] = (uint8_t)(((flag | 1) << 4) | nib[0]);
    i = 1;
  } else {
    hp[k++] = (uint8_t)(flag << 4);
  }
  for (; i < len; i += 2) hp[k++] = (uint8_t)((nib[i] << 4) | nib[i + 1]);
  return put_string(out, hp, k);
}

struct ReceiptTrie {
  const uint64_t* gas;
  const uint8_t* types;
  const uint8_t* has_log;
  const uint8_t* logs;
  uint64_t n;
  uint8_t* bloom;
  // the leaves come in key order: 1, 2, .. 0x7f, then 0, then 0x80 ..
  // so every index but 0 is met after the one before it, and these
  // run on from receipt 0's gas and log
  uint64_t run_cum;
  uint64_t run_log;

  // the receipt index at sorted position p
  uint64_t index_at(uint64_t p) const {
    uint64_t m = n < 0x80 ? n : 0x80;
    if (p + 1 < m) return p + 1;
    if (p + 1 == m) return 0;
    return p;
  }

  // rlp(i) as nibbles; returns their count
  static size_t key(uint64_t i, uint8_t nib[18]) {
    uint8_t k[9];
    size_t kl = 1;
    if (i == 0) {
      k[0] = 0x80;
    } else if (i < 0x80) {
      k[0] = (uint8_t)i;
    } else {
      int nb = 0;
      for (uint64_t v = i; v; v >>= 8) ++nb;
      k[0] = (uint8_t)(0x80 + nb);
      for (int j = 0; j < nb; ++j)
        k[1 + j] = (uint8_t)(i >> (8 * (nb - 1 - j)));
      kl = 1 + nb;
    }
    for (size_t j = 0; j < kl; ++j) {
      nib[2 * j] = k[j] >> 4;
      nib[2 * j + 1] = k[j] & 0x0F;
    }
    return 2 * kl;
  }

  uint8_t nibble_at(uint64_t p, size_t depth) const {
    uint8_t nib[18];
    key(index_at(p), nib);
    return nib[depth];
  }

  size_t leaf(uint64_t p, size_t depth, uint8_t ref[32]) {
    uint64_t i = index_at(p);
    uint64_t cum;
    const uint8_t* lg = nullptr;
    if (i == 0) {
      cum = gas[0];
      if (has_log[0]) lg = logs;
    } else {
      run_cum += gas[i];
      cum = run_cum;
      if (has_log[i]) {
        lg = logs + run_log;
        run_log += LOG_REC;
      }
    }
    // the receipt: [status 1, cumulative gas, bloom, logs]
    uint8_t rbloom[256] = {0};
    uint8_t rec[NODE_BUF];
    uint8_t* body = rec + HEAD;
    uint8_t* o = body;
    *o++ = 0x01;
    o = put_uint(o, cum);
    uint8_t* bloom_at = o + 3;  // 0xb9 0x01 0x00, filled below
    o = put_string(o, rbloom, 256);
    if (lg) {
      // [[address, [topic0, topic1, topic2], data]]: fixed lengths
      bloom_or(bloom_at, lg, 20);
      for (int t = 0; t < 3; ++t) bloom_or(bloom_at, lg + 20 + 32 * t, 32);
      for (int b = 0; b < 256; ++b) bloom[b] |= bloom_at[b];
      // list headers, 2 bytes each: the log list (157 bytes), the log
      // (155) and its topics (99)
      uint8_t* logs_at = o;
      o += 4;
      o = put_string(o, lg, 20);
      uint8_t* topics_at = o;
      o += 2;
      for (int t = 0; t < 3; ++t) o = put_string(o, lg + 20 + 32 * t, 32);
      put_head(topics_at + 2, 99, 0xc0, 0xf7);
      o = put_string(o, lg + 116, 32);
      put_head(logs_at + 4, (size_t)(o - logs_at - 4), 0xc0, 0xf7);
      put_head(logs_at + 2, (size_t)(o - logs_at - 2), 0xc0, 0xf7);
    } else {
      *o++ = 0xc0;
    }
    uint8_t* enc = put_head(body, (size_t)(o - body), 0xc0, 0xf7);
    if (types[i]) *--enc = types[i];
    size_t enc_len = (size_t)(o - enc);
    // the leaf node: [hex-prefix path, receipt]
    uint8_t node[NODE_BUF];
    uint8_t nib[18];
    size_t kl = key(i, nib);
    uint8_t* nb = node + HEAD;
    uint8_t* q = put_path(nb, nib + depth, kl - depth, true);
    q = put_string(q, enc, enc_len);
    uint8_t* start = put_head(nb, (size_t)(q - nb), 0xc0, 0xf7);
    return node_ref(start, (size_t)(q - start), ref);
  }

  // the branch over positions [lo, hi) (at least two), which share
  // their first depth nibbles and differ at the next
  size_t branch(uint64_t lo, uint64_t hi, size_t depth, uint8_t ref[32]) {
    uint8_t node[NODE_BUF];
    uint8_t* nb = node + HEAD;
    uint8_t* q = nb;
    uint64_t p = lo;
    for (uint8_t c = 0; c < 16; ++c) {
      if (p == hi || nibble_at(p, depth) != c) {
        *q++ = 0x80;
        continue;
      }
      // the first position past p whose nibble is not c
      uint64_t a = p + 1, b = hi;
      while (a < b) {
        uint64_t mid = a + (b - a) / 2;
        if (nibble_at(mid, depth) == c) a = mid + 1; else b = mid;
      }
      uint8_t child[32];
      size_t cl = subtrie(p, a, depth + 1, child);
      q = put_ref(q, child, cl);
      p = a;
    }
    *q++ = 0x80;  // no value: rlp keys are prefix-free
    uint8_t* start = put_head(nb, (size_t)(q - nb), 0xc0, 0xf7);
    return node_ref(start, (size_t)(q - start), ref);
  }

  size_t subtrie(uint64_t lo, uint64_t hi, size_t depth, uint8_t ref[32]) {
    if (hi - lo == 1) return leaf(lo, depth, ref);
    uint8_t first[18], last[18];
    size_t fl = key(index_at(lo), first);
    size_t ll = key(index_at(hi - 1), last);
    size_t cp = depth;
    while (cp < fl && cp < ll && first[cp] == last[cp]) ++cp;
    if (cp == depth) return branch(lo, hi, depth, ref);
    // an extension node over the shared nibbles, then the branch
    uint8_t child[32];
    size_t cl = branch(lo, hi, cp, child);
    uint8_t node[NODE_BUF];
    uint8_t* nb = node + HEAD;
    uint8_t* q = put_path(nb, first + depth, cp - depth, false);
    q = put_ref(q, child, cl);
    uint8_t* start = put_head(nb, (size_t)(q - nb), 0xc0, 0xf7);
    return node_ref(start, (size_t)(q - start), ref);
  }
};

}  // namespace

extern "C" {

// gas:      n per-transaction gas used (the cumulative sums are made here)
// tx_types: n bytes (0 = legacy untyped, else typed prefix byte)
// has_log:  n bytes (0 / 1); log_blob: LOG_REC bytes a has_log entry,
//           log_len bytes in all
// Writes the receipt root, the block bloom (OR of the receipt blooms,
// 256B BE) and the block's gas.  Returns 0, or 1 for malformed input:
// a has_log byte other than 0 / 1, a log blob of another length than
// LOG_REC a log, or gas whose sum passes 2^64 - 1.
int coreth_receipt_check(const uint64_t* gas, const uint8_t* tx_types,
                         const uint8_t* has_log, uint64_t n,
                         const uint8_t* log_blob, uint64_t log_len,
                         uint8_t root_out[32], uint8_t bloom_out[256],
                         uint64_t* gas_out) {
  uint64_t total = 0, n_logs = 0;
  for (uint64_t i = 0; i < n; ++i) {
    if (has_log[i] > 1) return 1;
    n_logs += has_log[i];
    if (gas[i] > UINT64_MAX - total) return 1;
    total += gas[i];
  }
  // overflow-safe: compare counts, not byte products
  if (log_len % LOG_REC != 0 || log_len / LOG_REC != n_logs) return 1;
  std::memset(bloom_out, 0, 256);
  *gas_out = total;
  if (n == 0) {
    std::memcpy(root_out, EMPTY_ROOT, 32);
    return 0;
  }
  ReceiptTrie t{gas, tx_types, has_log, log_blob, n, bloom_out,
                gas[0], has_log[0] ? LOG_REC : 0};
  uint8_t ref[32];
  size_t len = t.subtrie(0, n, 0, ref);
  if (len == 32) {
    std::memcpy(root_out, ref, 32);
  } else {
    coreth_keccak256(ref, len, root_out);  // a root shorter than a hash
  }
  return 0;
}

// Packed tx record layout (byte offsets):
//   sighash 0:32 | r 32:64 | s 64:96 | recid 96 | to 97:117
//   | value 117:149 | fee 149:181 | required 181:213 | nonce 213:221
//   => 221 bytes per record
// accounts: addr20 | balance32 | nonce8 => 60 bytes
// Returns 0 on success; 1 root mismatch; 2 invalid sig; 3 nonce/balance
// check failed; 4 unsupported big value; 5 malformed input (offsets
// not monotone, or a record extending past txs_len — the explicit
// length makes the packed-blob decode bounds-checked instead of
// trusted; fuzzed under ASan by tests/test_sanitize.py).
int coreth_baseline_replay(const uint8_t* txs, uint64_t txs_len,
                           const uint64_t* block_off,
                           uint64_t n_blocks, const uint8_t* roots,
                           const uint8_t* coinbases,
                           const uint8_t* genesis_accounts,
                           uint64_t n_accounts, double* phases) {
  constexpr size_t REC = 221;
  for (uint64_t b = 0; b < n_blocks; ++b)
    if (block_off[b] > block_off[b + 1]) return 5;
  // overflow-safe: compare counts, not byte products
  if (n_blocks > 0 && block_off[n_blocks] > txs_len / REC) return 5;
  std::unordered_map<std::string, Account, AddrHash> state;
  state.reserve(1 << 14);
  bool too_big = false;
  for (uint64_t i = 0; i < n_accounts; ++i) {
    const uint8_t* p = genesis_accounts + 60 * i;
    Account a;
    a.balance = load_u128_be32(p + 20, &too_big);
    uint64_t nonce = 0;
    for (int j = 0; j < 8; ++j) nonce = (nonce << 8) | p[52 + j];
    a.nonce = nonce;
    state.emplace(std::string((const char*)p, 20), a);
  }
  if (too_big) return 4;

  // seed the trie with genesis accounts (hashed keys)
  Trie trie;
  uint8_t nib[64], hk[32];
  for (auto& kv : state) {
    coreth_keccak256((const uint8_t*)kv.first.data(), 20, hk);
    for (int i = 0; i < 32; ++i) {
      nib[2 * i] = hk[i] >> 4;
      nib[2 * i + 1] = hk[i] & 0x0f;
    }
    trie.insert(nib, 64, account_rlp(kv.second.balance, kv.second.nonce));
  }
  uint8_t root[32];
  trie.hash_root(root);

  double t_sender = 0, t_exec = 0, t_trie = 0;
  auto now = []() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return ts.tv_sec + 1e-9 * ts.tv_nsec;
  };

  std::vector<std::string> touched;
  for (uint64_t b = 0; b < n_blocks; ++b) {
    touched.clear();
    std::string coinbase((const char*)(coinbases + 20 * b), 20);
    for (uint64_t t = block_off[b]; t < block_off[b + 1]; ++t) {
      const uint8_t* r = txs + REC * t;
      // --- sender recovery (the sender_cacher.go work, sequential)
      double t0 = now();
      uint8_t sender[20];
      if (!coreth_ecrecover(r, r + 32, r + 64, r[96], sender)) return 2;
      t_sender += now() - t0;
      // --- state transition (state_transition.go TransitionDb scalar)
      t0 = now();
      std::string from((const char*)sender, 20);
      std::string to((const char*)(r + 97), 20);
      bool big = false;
      u128 value = load_u128_be32(r + 117, &big);
      u128 fee = load_u128_be32(r + 149, &big);
      // required (buyGas pre-check, gas_limit*cap + value) is passed
      // precomputed; still compared against the live balance here
      u128 required = load_u128_be32(r + 181, &big);
      if (big) return 4;
      uint64_t tx_nonce = 0;
      for (int i = 0; i < 8; ++i) tx_nonce = (tx_nonce << 8) | r[213 + i];
      Account& fa = state[from];
      if (fa.nonce != tx_nonce) return 3;
      if (fa.balance < required || fa.balance < value + fee) return 3;
      fa.nonce += 1;
      fa.balance -= value + fee;
      state[to].balance += value;
      state[coinbase].balance += fee;
      touched.push_back(from);
      touched.push_back(to);
      t_exec += now() - t0;
    }
    touched.push_back(coinbase);
    // --- per-block trie fold + incremental rehash (IntermediateRoot)
    // dedupe first: the statedb analog folds a deduped dirty set, and
    // duplicate folds would inflate this baseline's trie phase
    double t0 = now();
    std::sort(touched.begin(), touched.end());
    touched.erase(std::unique(touched.begin(), touched.end()),
                  touched.end());
    for (const auto& addr : touched) {
      const Account& a = state[addr];
      coreth_keccak256((const uint8_t*)addr.data(), 20, hk);
      for (int i = 0; i < 32; ++i) {
        nib[2 * i] = hk[i] >> 4;
        nib[2 * i + 1] = hk[i] & 0x0f;
      }
      trie.insert(nib, 64, account_rlp(a.balance, a.nonce));
    }
    trie.hash_root(root);
    t_trie += now() - t0;
    if (std::memcmp(root, roots + 32 * b, 32) != 0) return 1;
  }
  if (phases) {
    phases[0] = t_sender;
    phases[1] = t_exec;
    phases[2] = t_trie;
  }
  return 0;
}

}  // extern "C"

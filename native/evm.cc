// Compiled host EVM: sequential replay baseline + tx-level host
// execution backend.
//
// Two entry points share one frame-based interpreter:
//
// - coreth_evm_replay: the bench denominator (BASELINE.md round 5) — a
//   single-threaded replay of whole contract chains with per-block
//   storage-trie + account-trie folds and bit-identical root checks.
// - coreth_hostexec_*: a session API that executes ONE full transaction
//   against a StateDB-backed host interface (storage/code resolved
//   through Python callbacks) and returns gas, status, logs, return
//   data, and the cross-contract write set — the production executor
//   for the replay engine's host escape paths (evm/hostexec/).
//
// The interpreter models the durango rule set the host jump table
// implements for AP2+ chains (EIP-2929 warm/cold with journaled access
// sets, EIP-2200/3529 SSTORE ladder with the refund counter tracked,
// quadratic memory, copy/log/keccak/exp word costs) plus nested
// value-0 CALL/STATICCALL with EIP-150 63/64 forwarding,
// RETURNDATASIZE/RETURNDATACOPY and EXTCODESIZE.  Anything outside that set (defined
// per fork but not compiled here: BALANCE, CREATE, DELEGATECALL,
// value-carrying subcalls, precompile targets, ...) aborts the tx with
// a HOST status so the caller re-runs it on the exact Python
// interpreter — per-tx automatic fallback, never a wrong answer.
//
// Reference roles: core/vm/interpreter.go:121 (Run),
// core/state_processor.go:95 (tx loop), core/vm/operations_acl.go
// (2929 pricing + journaled access lists), trie/hasher.go (per-block
// rehash).

#include <cstdint>
#include <cstring>
#include <map>
#include <string>
#include <unordered_map>
#include <unordered_set>
#include <vector>
#include <ctime>

typedef unsigned __int128 u128;
typedef std::vector<uint8_t> Bytes;

extern "C" void coreth_keccak256(const uint8_t*, uint64_t, uint8_t*);
extern "C" int coreth_ecrecover(const uint8_t*, const uint8_t*,
                                const uint8_t*, int, uint8_t*);
// trie handle API from baseline.cc (secure MPT over pre-hashed keys)
extern "C" void* coreth_trie_new();
extern "C" void coreth_trie_free(void*);
extern "C" void coreth_trie_update_batch(void*, const uint8_t*,
                                         const uint8_t*,
                                         const uint32_t*, uint64_t);
extern "C" void coreth_trie_hash(void*, uint8_t*);
extern "C" void coreth_trie_fold_accounts(void*, const uint8_t*,
                                          const uint8_t*,
                                          const uint64_t*,
                                          const uint8_t*,
                                          const uint8_t*,
                                          const uint8_t*,
                                          const uint8_t*, uint64_t);

namespace {

// ----------------------------------------------------------------- u256

struct U256 {
  uint64_t w[4] = {0, 0, 0, 0};  // little-endian 64-bit limbs

  bool is_zero() const { return !(w[0] | w[1] | w[2] | w[3]); }
  bool bit(int i) const { return (w[i >> 6] >> (i & 63)) & 1; }
  int bitlen() const {
    for (int i = 3; i >= 0; --i)
      if (w[i]) return 64 * i + 64 - __builtin_clzll(w[i]);
    return 0;
  }
};

U256 from_be(const uint8_t* p, size_t n = 32) {
  U256 v;
  for (size_t i = 0; i < n; ++i) {
    size_t bit = 8 * (n - 1 - i);
    v.w[bit >> 6] |= (uint64_t)p[i] << (bit & 63);
  }
  return v;
}

void to_be(const U256& v, uint8_t out[32]) {
  for (int i = 0; i < 32; ++i) {
    int bit = 8 * (31 - i);
    out[i] = (uint8_t)(v.w[bit >> 6] >> (bit & 63));
  }
}

U256 u256_from64(uint64_t x) { U256 v; v.w[0] = x; return v; }

bool eq(const U256& a, const U256& b) {
  return !std::memcmp(a.w, b.w, 32);
}

int cmp(const U256& a, const U256& b) {
  for (int i = 3; i >= 0; --i) {
    if (a.w[i] < b.w[i]) return -1;
    if (a.w[i] > b.w[i]) return 1;
  }
  return 0;
}

U256 add(const U256& a, const U256& b) {
  U256 r;
  u128 c = 0;
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a.w[i] + b.w[i] + c;
    r.w[i] = (uint64_t)s;
    c = s >> 64;
  }
  return r;
}

U256 sub(const U256& a, const U256& b) {
  U256 r;
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.w[i] - b.w[i] - borrow;
    r.w[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  return r;
}

U256 mul(const U256& a, const U256& b) {
  uint64_t out[4] = {0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; i + j < 4; ++j) {
      u128 cur = (u128)a.w[i] * b.w[j] + out[i + j] + carry;
      out[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
  }
  U256 r;
  std::memcpy(r.w, out, 32);
  return r;
}

U256 shl_k(const U256& a, unsigned k) {
  U256 r;
  if (k >= 256) return r;
  unsigned limb = k / 64, off = k % 64;
  for (int i = 3; i >= 0; --i) {
    uint64_t v = 0;
    int src = i - (int)limb;
    if (src >= 0) v = a.w[src] << off;
    if (off && src - 1 >= 0) v |= a.w[src - 1] >> (64 - off);
    r.w[i] = v;
  }
  return r;
}

U256 shr_k(const U256& a, unsigned k) {
  U256 r;
  if (k >= 256) return r;
  unsigned limb = k / 64, off = k % 64;
  for (int i = 0; i < 4; ++i) {
    uint64_t v = 0;
    unsigned src = i + limb;
    if (src < 4) v = a.w[src] >> off;
    if (off && src + 1 < 4) v |= a.w[src + 1] << (64 - off);
    r.w[i] = v;
  }
  return r;
}

// divides by a divisor that fits 64 bits (the workload-hot path);
// general case falls back to bit-serial restoring division.
void divmod(const U256& a, const U256& b, U256* q, U256* r) {
  *q = U256();
  *r = U256();
  if (b.is_zero()) return;
  if (!(b.w[1] | b.w[2] | b.w[3])) {
    uint64_t d = b.w[0];
    u128 rem = 0;
    for (int i = 3; i >= 0; --i) {
      u128 cur = (rem << 64) | a.w[i];
      q->w[i] = (uint64_t)(cur / d);
      rem = cur % d;
    }
    r->w[0] = (uint64_t)rem;
    return;
  }
  U256 rem;
  for (int i = 255; i >= 0; --i) {
    rem = shl_k(rem, 1);
    rem.w[0] |= a.bit(i) ? 1 : 0;
    if (cmp(rem, b) >= 0) {
      rem = sub(rem, b);
      q->w[i >> 6] |= 1ULL << (i & 63);
    }
  }
  *r = rem;
}

bool sign_neg(const U256& a) { return a.w[3] >> 63; }

U256 neg(const U256& a) { return sub(U256(), a); }

U256 u_abs(const U256& a) { return sign_neg(a) ? neg(a) : a; }

// (a + b) % n and (a * b) % n over the wide intermediate: shift-add /
// shift-mod loops — correctness parity only, never on the bench path.
U256 addmod_(const U256& a, const U256& b, const U256& n) {
  if (n.is_zero()) return U256();
  U256 q, ra, rb;
  divmod(a, n, &q, &ra);
  divmod(b, n, &q, &rb);
  U256 s = add(ra, rb);
  // one conditional subtract handles the possible 257-bit overflow
  if (cmp(s, ra) < 0 || cmp(s, n) >= 0) s = sub(s, n);
  return s;
}

U256 mulmod_(const U256& a, const U256& b, const U256& n) {
  if (n.is_zero()) return U256();
  U256 q, x, result;
  divmod(a, n, &q, &x);
  U256 y;
  divmod(b, n, &q, &y);
  // double-and-add: result = x*y mod n without a 512-bit intermediate
  for (int i = y.bitlen() - 1; i >= 0; --i) {
    result = addmod_(result, result, n);
    if (y.bit(i)) result = addmod_(result, x, n);
  }
  return result;
}

// ------------------------------------------------------------ gas rules
// durango-level constants (params/protocol.py twins)

constexpr int64_t G_QUICK = 2, G_FASTEST = 3, G_FAST = 5, G_MID = 8,
                  G_SLOW = 10;
constexpr int64_t G_KECCAK = 30, G_KECCAK_WORD = 6, G_MEM = 3,
                  G_COPY = 3, G_LOG = 375, G_LOGTOPIC = 375,
                  G_LOGDATA = 8, G_JUMPDEST = 1, G_EXP = 10,
                  G_EXPBYTE = 50;
constexpr int64_t COLD_SLOAD = 2100, WARM_READ = 100,
                  SSTORE_SET = 20000, SSTORE_RESET = 5000,
                  SSTORE_SENTRY = 2300, SSTORE_CLEARS_REFUND = 4800,
                  COLD_ACCOUNT = 2600;
constexpr uint64_t QUAD_DIV = 512;

int64_t mem_cost(uint64_t words) {
  return (int64_t)(words * G_MEM + words * words / QUAD_DIV);
}

struct Key32 {
  uint8_t b[32];
  bool operator==(const Key32& o) const {
    return !std::memcmp(b, o.b, 32);
  }
  bool operator<(const Key32& o) const {
    return std::memcmp(b, o.b, 32) < 0;
  }
};
struct Key32Hash {
  size_t operator()(const Key32& k) const {
    size_t h;
    std::memcpy(&h, k.b, sizeof(h));
    return h;
  }
};
typedef std::unordered_map<Key32, U256, Key32Hash> SlotMap;

struct Contract {
  Bytes code;
  uint8_t code_hash[32];
  SlotMap storage;               // committed (as of last block/fetch)
  std::vector<bool> jumpdest;
  bool dirty = false;            // storage touched since last fold
  SlotMap block_dirty;           // writes since last fold
};

struct Account {
  u128 balance = 0;
  uint64_t nonce = 0;
  Contract* contract = nullptr;
};

struct Env {
  uint8_t coinbase[20] = {0};
  uint64_t timestamp = 0, number = 0, gaslimit = 0, chain_id = 0,
           difficulty = 1;
  U256 basefee;
};

U256 addr_word(const uint8_t* a20) {
  uint8_t p[32] = {0};
  std::memcpy(p + 12, a20, 20);
  return from_be(p);
}

std::string low20(const U256& w) {
  uint8_t be[32];
  to_be(w, be);
  return std::string((const char*)be + 12, 20);
}

void analyze_jumpdests(Contract* c) {
  c->jumpdest.assign(c->code.size(), false);
  for (size_t i = 0; i < c->code.size();) {
    uint8_t op = c->code[i];
    if (op == 0x5B) c->jumpdest[i] = true;
    i += (op >= 0x60 && op <= 0x7F) ? op - 0x5F + 1 : 1;
  }
}

// -------------------------------------------------------- tx-level state

// statuses mirror the device machine codes (evm/device/machine.py)
constexpr int ST_STOP = 1, ST_REVERT = 2, ST_ERR = 3, ST_HOST = 4;

struct LogRec {
  uint8_t addr[20];
  int nt = 0;
  uint8_t topics[4][32];
  Bytes data;
};

// optable entries: 0 undefined (INVALID at runtime), 1 native,
// 2 defined-but-host-only (HOST escape)
constexpr uint8_t OP_UNDEF = 0, OP_NATIVE = 1, OP_HOSTONLY = 2;

typedef int (*FetchSlotCb)(const uint8_t* addr20, const uint8_t* key32,
                           uint8_t* out32);
typedef int (*FetchCodeCb)(const uint8_t* addr20);

struct Sess;

// per-transaction interpreter context: the journaled warm sets, the
// cross-contract dirty overlay, logs, and the refund counter — the
// compiled analog of the StateDB journal scoped to one tx.
struct Exec {
  const Env* env = nullptr;
  const uint8_t* origin = nullptr;  // 20
  U256 gasprice;
  const uint8_t* optable = nullptr;  // 256 entries
  bool refunds_on = false;
  Sess* sess = nullptr;                                   // hostexec mode
  std::unordered_map<std::string, Account>* replay_state = nullptr;
  // tx-mutable
  std::map<std::string, U256> dirty;   // addr20+maskedkey32 -> value
  std::vector<LogRec> logs;
  int64_t refund = 0;
  std::unordered_set<std::string> warm_addr;   // addr20
  std::unordered_set<std::string> warm_slot;   // addr20+RAWkey32
  std::vector<std::string> addr_jour, slot_jour;
  int host_reason = 0;                          // opcode forcing HOST
};

struct Snap {
  std::map<std::string, U256> dirty;
  size_t nlogs, aj, sj;
  int64_t refund;
};

Snap take_snap(Exec& X) {
  return Snap{X.dirty, X.logs.size(), X.addr_jour.size(),
              X.slot_jour.size(), X.refund};
}

void restore_snap(Exec& X, Snap& s) {
  X.dirty = s.dirty;
  X.logs.resize(s.nlogs);
  X.refund = s.refund;
  while (X.addr_jour.size() > s.aj) {
    X.warm_addr.erase(X.addr_jour.back());
    X.addr_jour.pop_back();
  }
  while (X.slot_jour.size() > s.sj) {
    X.warm_slot.erase(X.slot_jour.back());
    X.slot_jour.pop_back();
  }
}

// true when already warm; adds + journals when cold
bool warm_addr_check(Exec& X, const std::string& a) {
  if (X.warm_addr.count(a)) return true;
  X.warm_addr.insert(a);
  X.addr_jour.push_back(a);
  return false;
}

bool warm_slot_check(Exec& X, const std::string& k) {
  if (X.warm_slot.count(k)) return true;
  X.warm_slot.insert(k);
  X.slot_jour.push_back(k);
  return false;
}

struct SessOut {
  int status = 0;
  int64_t gas_left = 0, refund = 0;
  int host_reason = 0;
  std::map<std::string, U256> writes;
  std::vector<LogRec> logs;
  Bytes ret;
};

struct Sess {
  Env env;
  std::unordered_map<std::string, Contract> contracts;
  std::unordered_map<std::string, int> kind;  // 1 contract, 0 eoa
  FetchSlotCb fetch_slot = nullptr;
  FetchCodeCb fetch_code = nullptr;
  uint8_t optable[256] = {0};
  int refunds_on = 0;
  std::vector<std::string> seed_warm_addr, seed_warm_slot;
  SessOut out;
};

// code lookup: 1 contract (out set), 0 EOA, -1 host must handle
int lookup_code(Exec& X, const std::string& addr, Contract** out) {
  if (X.replay_state) {
    auto it = X.replay_state->find(addr);
    if (it == X.replay_state->end() || !it->second.contract) return 0;
    *out = it->second.contract;
    return 1;
  }
  Sess* s = X.sess;
  auto k = s->kind.find(addr);
  if (k == s->kind.end()) {
    if (!s->fetch_code) return -1;
    int r = s->fetch_code((const uint8_t*)addr.data());
    if (r < 0) return -1;
    if (r == 0) {
      s->kind[addr] = 0;
      return 0;
    }
    k = s->kind.find(addr);  // set_code (re-entrant) registered it
    if (k == s->kind.end()) return -1;
  }
  if (k->second == 0) return 0;
  *out = &s->contracts[addr];
  return 1;
}

// committed (pre-tx) value of a masked storage key
U256 committed_read(Exec& X, const std::string& addr, const Key32& mk) {
  if (X.replay_state) {
    auto it = X.replay_state->find(addr);
    if (it == X.replay_state->end() || !it->second.contract)
      return U256();
    auto s = it->second.contract->storage.find(mk);
    return s == it->second.contract->storage.end() ? U256() : s->second;
  }
  Contract& c = X.sess->contracts[addr];
  auto s = c.storage.find(mk);
  if (s != c.storage.end()) return s->second;
  U256 v;
  if (X.sess->fetch_slot) {
    uint8_t out[32] = {0};
    X.sess->fetch_slot((const uint8_t*)addr.data(), mk.b, out);
    v = from_be(out);
  }
  c.storage[mk] = v;
  return v;
}

U256 current_read(Exec& X, const std::string& addr, const Key32& mk) {
  std::string dk = addr + std::string((const char*)mk.b, 32);
  auto it = X.dirty.find(dk);
  if (it != X.dirty.end()) return it->second;
  return committed_read(X, addr, mk);
}

// result of one interpreter frame
struct FrameRes {
  int status = ST_ERR;
  int64_t gas = 0;
  Bytes out;
};

// the interpreter: a direct switch loop (the compiled analog of
// interpreter.go Run).  `depth` counts running frames including this
// one (root == 1); subcall ceilings follow evm.go's depth > 1024.
FrameRes run_frame(Exec& X, const uint8_t* caller,
                   const std::string& self_addr, Contract* c,
                   const uint8_t* input, uint64_t inlen, int64_t gas,
                   const U256& value, bool is_static, int depth) {
  FrameRes res;
  std::vector<U256> stack;
  stack.reserve(64);
  Bytes mem;
  Bytes retdata;  // frame-local last-subcall return data
  uint64_t pc = 0;
  const Bytes& code = c->code;

#define NEED(n) if (stack.size() < (n)) { res.gas = 0; return res; }
#define USE(g) do { if (gas < (int64_t)(g)) { res.gas = 0; \
  return res; } gas -= (g); } while (0)

  auto expand = [&](uint64_t need) -> bool {
    if (need <= mem.size()) return true;
    if (need > (1ULL << 25)) return false;
    uint64_t new_words = (need + 31) / 32;
    int64_t cost = mem_cost(new_words) - mem_cost(mem.size() / 32);
    if (gas < cost) return false;
    gas -= cost;
    mem.resize(new_words * 32, 0);
    return true;
  };
  auto u64_arg = [&](const U256& v, bool* okf) -> uint64_t {
    if (v.w[1] | v.w[2] | v.w[3] || v.w[0] > (1ULL << 32)) {
      *okf = false;
      return 1ULL << 32;
    }
    *okf = true;
    return v.w[0];
  };

  while (pc < code.size()) {
    uint8_t op = code[pc];
    // per-fork dispatch gate BEFORE the switch: an opcode this engine
    // compiles may still be UNDEFINED under the session's fork (PUSH0
    // pre-durango, BASEFEE pre-ap3) — it must INVALID-err exactly like
    // the interpreter, not execute; host-only opcodes escape here too
    if (X.optable) {
      uint8_t cls = X.optable[op];
      if (cls == OP_UNDEF) { res.gas = 0; return res; }
      if (cls == OP_HOSTONLY) {
        X.host_reason = op;
        res.status = ST_HOST;
        return res;
      }
    }
    switch (op) {
      case 0x00: res.status = ST_STOP; res.gas = gas;    // STOP
                 return res;
      case 0x01: { NEED(2); USE(G_FASTEST);                // ADD
        U256 a = stack.back(); stack.pop_back();
        stack.back() = add(a, stack.back()); break; }
      case 0x02: { NEED(2); USE(G_FAST);                   // MUL
        U256 a = stack.back(); stack.pop_back();
        stack.back() = mul(a, stack.back()); break; }
      case 0x03: { NEED(2); USE(G_FASTEST);                // SUB
        U256 a = stack.back(); stack.pop_back();
        stack.back() = sub(a, stack.back()); break; }
      case 0x04: { NEED(2); USE(G_FAST);                   // DIV
        U256 a = stack.back(); stack.pop_back();
        U256 q, r; divmod(a, stack.back(), &q, &r);
        stack.back() = q; break; }
      case 0x05: { NEED(2); USE(G_FAST);                   // SDIV
        U256 a = stack.back(); stack.pop_back();
        U256 b = stack.back();
        U256 q, r; divmod(u_abs(a), u_abs(b), &q, &r);
        stack.back() = (sign_neg(a) != sign_neg(b) && !b.is_zero())
                           ? neg(q) : q;
        break; }
      case 0x06: { NEED(2); USE(G_FAST);                   // MOD
        U256 a = stack.back(); stack.pop_back();
        U256 q, r; divmod(a, stack.back(), &q, &r);
        stack.back() = r; break; }
      case 0x07: { NEED(2); USE(G_FAST);                   // SMOD
        U256 a = stack.back(); stack.pop_back();
        U256 b = stack.back();
        U256 q, r; divmod(u_abs(a), u_abs(b), &q, &r);
        stack.back() = sign_neg(a) ? neg(r) : r; break; }
      case 0x08: { NEED(3); USE(G_MID);                    // ADDMOD
        U256 a = stack.back(); stack.pop_back();
        U256 b = stack.back(); stack.pop_back();
        stack.back() = addmod_(a, b, stack.back()); break; }
      case 0x09: { NEED(3); USE(G_MID);                    // MULMOD
        U256 a = stack.back(); stack.pop_back();
        U256 b = stack.back(); stack.pop_back();
        stack.back() = mulmod_(a, b, stack.back()); break; }
      case 0x0A: { NEED(2);                                // EXP
        U256 b = stack.back(); stack.pop_back();
        U256 e = stack.back();
        USE(G_EXP + G_EXPBYTE * ((e.bitlen() + 7) / 8));
        U256 r = u256_from64(1), cur = b;
        int n = e.bitlen();
        for (int i = 0; i < n; ++i) {
          if (e.bit(i)) r = mul(r, cur);
          cur = mul(cur, cur);
        }
        stack.back() = r; break; }
      case 0x0B: { NEED(2); USE(G_FAST);                   // SIGNEXTEND
        U256 b = stack.back(); stack.pop_back();
        U256 x = stack.back();
        if (b.w[0] < 31 && !(b.w[1] | b.w[2] | b.w[3])) {
          int t = 8 * (int)(b.w[0] + 1);
          bool neg_bit = x.bit(t - 1);
          U256 mask = sub(shl_k(u256_from64(1), t), u256_from64(1));
          if (neg_bit) {
            U256 inv;
            for (int i = 0; i < 4; ++i) inv.w[i] = ~mask.w[i];
            for (int i = 0; i < 4; ++i) x.w[i] |= inv.w[i];
          } else {
            for (int i = 0; i < 4; ++i) x.w[i] &= mask.w[i];
          }
          stack.back() = x;
        }
        break; }
      case 0x10: case 0x11: case 0x12: case 0x13: case 0x14: {
        NEED(2); USE(G_FASTEST);        // LT GT SLT SGT EQ
        U256 a = stack.back(); stack.pop_back();
        U256 b = stack.back();
        bool r = false;
        if (op == 0x10) r = cmp(a, b) < 0;
        else if (op == 0x11) r = cmp(a, b) > 0;
        else if (op == 0x14) r = eq(a, b);
        else {
          bool sa = sign_neg(a), sb = sign_neg(b);
          int c0 = cmp(a, b);
          bool lt = sa != sb ? sa : c0 < 0;
          r = (op == 0x12) ? lt : (c0 != 0 && !lt);
        }
        stack.back() = u256_from64(r ? 1 : 0); break; }
      case 0x15: { NEED(1); USE(G_FASTEST);                // ISZERO
        stack.back() = u256_from64(stack.back().is_zero() ? 1 : 0);
        break; }
      case 0x16: case 0x17: case 0x18: { NEED(2); USE(G_FASTEST);
        U256 a = stack.back(); stack.pop_back();           // AND OR XOR
        U256& b = stack.back();
        for (int i = 0; i < 4; ++i)
          b.w[i] = op == 0x16 ? (a.w[i] & b.w[i])
                 : op == 0x17 ? (a.w[i] | b.w[i]) : (a.w[i] ^ b.w[i]);
        break; }
      case 0x19: { NEED(1); USE(G_FASTEST);                // NOT
        for (int i = 0; i < 4; ++i)
          stack.back().w[i] = ~stack.back().w[i];
        break; }
      case 0x1A: { NEED(2); USE(G_FASTEST);                // BYTE
        U256 i = stack.back(); stack.pop_back();
        U256 x = stack.back();
        uint64_t v = 0;
        if (i.w[0] < 32 && !(i.w[1] | i.w[2] | i.w[3])) {
          uint8_t be[32];
          to_be(x, be);
          v = be[i.w[0]];
        }
        stack.back() = u256_from64(v); break; }
      case 0x1B: case 0x1C: { NEED(2); USE(G_FASTEST);     // SHL SHR
        U256 s = stack.back(); stack.pop_back();
        U256 x = stack.back();
        unsigned k = (s.w[1] | s.w[2] | s.w[3] || s.w[0] > 255)
                         ? 256 : (unsigned)s.w[0];
        stack.back() = op == 0x1B ? shl_k(x, k) : shr_k(x, k);
        break; }
      case 0x1D: { NEED(2); USE(G_FASTEST);                // SAR
        U256 s = stack.back(); stack.pop_back();
        U256 x = stack.back();
        bool negx = sign_neg(x);
        unsigned k = (s.w[1] | s.w[2] | s.w[3] || s.w[0] > 255)
                         ? 256 : (unsigned)s.w[0];
        if (k >= 256) {
          stack.back() = negx ? neg(u256_from64(1)) : U256();
        } else {
          U256 r = shr_k(x, k);
          if (negx && k) {
            U256 fill = shl_k(neg(u256_from64(1)), 256 - k);
            for (int i = 0; i < 4; ++i) r.w[i] |= fill.w[i];
          }
          stack.back() = r;
        }
        break; }
      case 0x20: { NEED(2); USE(G_KECCAK);                 // KECCAK256
        U256 offv = stack.back(); stack.pop_back();
        U256 lenv = stack.back(); stack.pop_back();
        bool okf1, okf2;
        uint64_t off = u64_arg(offv, &okf1), len = u64_arg(lenv, &okf2);
        if (len) {
          if (!okf1 || !okf2 || !expand(off + len)) {
            res.gas = 0;
            return res;
          }
        }
        USE(G_KECCAK_WORD * ((len + 31) / 32));
        uint8_t h[32];
        coreth_keccak256(len ? mem.data() + off : nullptr, len, h);
        stack.push_back(from_be(h)); break; }
      case 0x30: USE(G_QUICK);                             // ADDRESS
        stack.push_back(addr_word((const uint8_t*)self_addr.data()));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x32: USE(G_QUICK);                             // ORIGIN
        stack.push_back(addr_word(X.origin));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x33: USE(G_QUICK);                             // CALLER
        stack.push_back(addr_word(caller));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x34: USE(G_QUICK);                             // CALLVALUE
        stack.push_back(value);
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x35: { NEED(1); USE(G_FASTEST);                // CALLDATALOAD
        U256 offv = stack.back();
        uint8_t word[32] = {0};
        if (!(offv.w[1] | offv.w[2] | offv.w[3])
            && offv.w[0] < inlen) {
          uint64_t off = offv.w[0];
          uint64_t n = inlen - off < 32 ? inlen - off : 32;
          std::memcpy(word, input + off, n);
        }
        stack.back() = from_be(word); break; }
      case 0x36: USE(G_QUICK);                             // CALLDATASIZE
        stack.push_back(u256_from64(inlen));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x37: { NEED(3); USE(G_FASTEST);                // CALLDATACOPY
        U256 dstv = stack.back(); stack.pop_back();
        U256 srcv = stack.back(); stack.pop_back();
        U256 lenv = stack.back(); stack.pop_back();
        bool ok1, ok3;
        uint64_t dst = u64_arg(dstv, &ok1);
        uint64_t len = u64_arg(lenv, &ok3);
        if (len) {
          if (!ok1 || !ok3 || !expand(dst + len)) {
            res.gas = 0;
            return res;
          }
        }
        USE(G_COPY * ((len + 31) / 32));
        for (uint64_t j = 0; j < len; ++j) {
          uint64_t s = (srcv.w[1] | srcv.w[2] | srcv.w[3])
                           ? inlen : srcv.w[0] + j;
          mem[dst + j] = s < inlen ? input[s] : 0;
        }
        break; }
      case 0x38: USE(G_QUICK);                             // CODESIZE
        stack.push_back(u256_from64(code.size()));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x39: { NEED(3); USE(G_FASTEST);                // CODECOPY
        U256 dstv = stack.back(); stack.pop_back();
        U256 srcv = stack.back(); stack.pop_back();
        U256 lenv = stack.back(); stack.pop_back();
        bool ok1, ok3;
        uint64_t dst = u64_arg(dstv, &ok1);
        uint64_t len = u64_arg(lenv, &ok3);
        if (len) {
          if (!ok1 || !ok3 || !expand(dst + len)) {
            res.gas = 0;
            return res;
          }
        }
        USE(G_COPY * ((len + 31) / 32));
        for (uint64_t j = 0; j < len; ++j) {
          uint64_t s = (srcv.w[1] | srcv.w[2] | srcv.w[3])
                           ? code.size() : srcv.w[0] + j;
          mem[dst + j] = s < code.size() ? code[s] : 0;
        }
        break; }
      case 0x3A: USE(G_QUICK);                             // GASPRICE
        stack.push_back(X.gasprice);
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x3B: { NEED(1); USE(WARM_READ);                // EXTCODESIZE
        // EIP-2929: the cold-account surcharge, as CALL pays it
        std::string target = low20(stack.back());
        int64_t cold = warm_addr_check(X, target)
                           ? 0 : COLD_ACCOUNT - WARM_READ;
        if (gas < cold) { res.gas = 0; return res; }
        gas -= cold;
        Contract* cc = nullptr;
        int kind = lookup_code(X, target, &cc);
        if (kind < 0) {
          X.host_reason = op;
          res.status = ST_HOST;
          return res;
        }
        stack.back() = u256_from64(kind == 1 ? cc->code.size() : 0);
        ++pc; continue; }
      case 0x3D: USE(G_QUICK);                             // RETURNDATASIZE
        stack.push_back(u256_from64(retdata.size()));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x3E: { NEED(3); USE(G_FASTEST);                // RETURNDATACOPY
        U256 dstv = stack.back(); stack.pop_back();
        U256 srcv = stack.back(); stack.pop_back();
        U256 lenv = stack.back(); stack.pop_back();
        bool ok1, ok2, ok3;
        uint64_t dst = u64_arg(dstv, &ok1);
        uint64_t src = u64_arg(srcv, &ok2);
        uint64_t len = u64_arg(lenv, &ok3);
        if (len) {
          if (!ok1 || !ok3 || !expand(dst + len)) {
            res.gas = 0;
            return res;
          }
        }
        USE(G_COPY * ((len + 31) / 32));
        // bounds: src + len must sit inside the last return data
        // (EIP-211; geth opReturnDataCopy -> ErrReturnDataOutOfBounds)
        if (!ok2 || src + len > retdata.size()) {
          res.gas = 0;
          return res;
        }
        if (len) std::memcpy(mem.data() + dst, retdata.data() + src, len);
        break; }
      case 0x41: USE(G_QUICK);                             // COINBASE
        stack.push_back(addr_word(X.env->coinbase));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x42: USE(G_QUICK);
        stack.push_back(u256_from64(X.env->timestamp));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x43: USE(G_QUICK);
        stack.push_back(u256_from64(X.env->number));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x44: USE(G_QUICK);                             // DIFFICULTY
        stack.push_back(u256_from64(X.env->difficulty));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x45: USE(G_QUICK);
        stack.push_back(u256_from64(X.env->gaslimit));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x46: USE(G_QUICK);
        stack.push_back(u256_from64(X.env->chain_id));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x48: USE(G_QUICK);
        stack.push_back(X.env->basefee);
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x50: NEED(1); USE(G_QUICK); stack.pop_back();
        ++pc; continue;
      case 0x51: { NEED(1); USE(G_FASTEST);                // MLOAD
        U256 offv = stack.back();
        bool okf;
        uint64_t off = u64_arg(offv, &okf);
        if (!okf || !expand(off + 32)) { res.gas = 0; return res; }
        stack.back() = from_be(mem.data() + off); break; }
      case 0x52: { NEED(2); USE(G_FASTEST);                // MSTORE
        U256 offv = stack.back(); stack.pop_back();
        U256 val = stack.back(); stack.pop_back();
        bool okf;
        uint64_t off = u64_arg(offv, &okf);
        if (!okf || !expand(off + 32)) { res.gas = 0; return res; }
        to_be(val, mem.data() + off); break; }
      case 0x53: { NEED(2); USE(G_FASTEST);                // MSTORE8
        U256 offv = stack.back(); stack.pop_back();
        U256 val = stack.back(); stack.pop_back();
        bool okf;
        uint64_t off = u64_arg(offv, &okf);
        if (!okf || !expand(off + 1)) { res.gas = 0; return res; }
        mem[off] = (uint8_t)val.w[0]; break; }
      case 0x54: { NEED(1);                                // SLOAD
        U256 keyv = stack.back();
        Key32 rawk, mk;
        to_be(keyv, rawk.b);
        mk = rawk;
        mk.b[0] &= 0xFE;  // multicoin normal-storage partition
        // warm set keyed on the RAW key, exactly like the StateDB
        // access list (gas.py gas_sload_eip2929 peeks the unmasked key)
        std::string wk = self_addr + std::string((const char*)rawk.b, 32);
        // hoisted: USE() evaluates its argument twice (gas check +
        // charge), and warm_slot_check must run exactly once
        int64_t sload_cost =
            warm_slot_check(X, wk) ? WARM_READ : COLD_SLOAD;
        USE(sload_cost);
        stack.back() = current_read(X, self_addr, mk);
        break; }
      case 0x55: { NEED(2);                                // SSTORE
        if (is_static) { res.gas = 0; return res; }  // write protection
        if (gas <= SSTORE_SENTRY) { res.gas = 0; return res; }
        U256 keyv = stack.back(); stack.pop_back();
        U256 val = stack.back(); stack.pop_back();
        Key32 rawk, mk;
        to_be(keyv, rawk.b);
        mk = rawk;
        mk.b[0] &= 0xFE;
        int64_t cost = 0;
        std::string wk = self_addr + std::string((const char*)rawk.b, 32);
        if (!warm_slot_check(X, wk)) cost += COLD_SLOAD;
        U256 orig = committed_read(X, self_addr, mk);
        std::string dk = self_addr + std::string((const char*)mk.b, 32);
        auto di = X.dirty.find(dk);
        U256 cur = di == X.dirty.end() ? orig : di->second;
        if (eq(cur, val)) {
          cost += WARM_READ;
        } else if (eq(orig, cur)) {
          if (orig.is_zero()) {
            cost += SSTORE_SET;
          } else {
            if (X.refunds_on && val.is_zero())
              X.refund += SSTORE_CLEARS_REFUND;
            cost += SSTORE_RESET - COLD_SLOAD;
          }
        } else {
          // dirty slot: EIP-2200/3529 refund ladder (gas.py
          // make_gas_sstore_eip2929 with_refunds branch)
          if (X.refunds_on) {
            if (!orig.is_zero()) {
              if (cur.is_zero()) X.refund -= SSTORE_CLEARS_REFUND;
              else if (val.is_zero()) X.refund += SSTORE_CLEARS_REFUND;
            }
            if (eq(orig, val)) {
              if (orig.is_zero())
                X.refund += SSTORE_SET - WARM_READ;
              else
                X.refund += SSTORE_RESET - COLD_SLOAD - WARM_READ;
            }
          }
          cost += WARM_READ;
        }
        USE(cost);
        X.dirty[dk] = val;
        break; }
      case 0x56: { NEED(1); USE(G_MID);                    // JUMP
        U256 d = stack.back(); stack.pop_back();
        if (d.w[1] | d.w[2] | d.w[3] || d.w[0] >= code.size()
            || !c->jumpdest[d.w[0]]) {
          res.gas = 0;
          return res;
        }
        pc = d.w[0];
        continue; }
      case 0x57: { NEED(2); USE(G_SLOW);                   // JUMPI
        U256 d = stack.back(); stack.pop_back();
        U256 cond = stack.back(); stack.pop_back();
        if (!cond.is_zero()) {
          if (d.w[1] | d.w[2] | d.w[3] || d.w[0] >= code.size()
              || !c->jumpdest[d.w[0]]) {
            res.gas = 0;
            return res;
          }
          pc = d.w[0];
          continue;
        }
        break; }
      case 0x58: USE(G_QUICK);
        stack.push_back(u256_from64(pc));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x59: USE(G_QUICK);
        stack.push_back(u256_from64(mem.size()));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x5A: USE(G_QUICK);
        stack.push_back(u256_from64((uint64_t)gas));
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;
      case 0x5B: USE(G_JUMPDEST); ++pc; continue;
      case 0x5F: USE(G_QUICK); stack.push_back(U256());
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc; continue;                                    // PUSH0
      case 0xF1: case 0xFA: {                              // CALL STATICCALL
        unsigned nargs = op == 0xF1 ? 7 : 6;
        NEED(nargs);
        USE(WARM_READ);  // constant gas (2929 call variants)
        U256 greq = stack.back(); stack.pop_back();
        U256 addrw = stack.back(); stack.pop_back();
        U256 callv;
        if (op == 0xF1) { callv = stack.back(); stack.pop_back(); }
        U256 inoffv = stack.back(); stack.pop_back();
        U256 inszv = stack.back(); stack.pop_back();
        U256 outoffv = stack.back(); stack.pop_back();
        U256 outszv = stack.back(); stack.pop_back();
        std::string target = low20(addrw);
        // cold-account surcharge, deducted before the 63/64 split
        // (gas.py make_gas_call_eip2929)
        int64_t cold = warm_addr_check(X, target)
                           ? 0 : COLD_ACCOUNT - WARM_READ;
        if (gas < cold) { res.gas = 0; return res; }
        gas -= cold;
        bool ok1, ok2, ok3, ok4;
        uint64_t inoff = u64_arg(inoffv, &ok1);
        uint64_t insz = u64_arg(inszv, &ok2);
        uint64_t outoff = u64_arg(outoffv, &ok3);
        uint64_t outsz = u64_arg(outszv, &ok4);
        uint64_t in_end = insz ? inoff + insz : 0;
        uint64_t out_end = outsz ? outoff + outsz : 0;
        if ((insz && (!ok1 || !ok2)) || (outsz && (!ok3 || !ok4))) {
          res.gas = 0;
          return res;
        }
        uint64_t msz = in_end > out_end ? in_end : out_end;
        uint64_t new_words = (msz + 31) / 32;
        if (msz > (1ULL << 25)) { res.gas = 0; return res; }
        int64_t memgas = msz <= mem.size() ? 0
            : mem_cost(new_words) - mem_cost(mem.size() / 32);
        if (op == 0xF1 && !callv.is_zero()) {
          if (is_static) { res.gas = 0; return res; }  // write protect
          // value-carrying subcalls need balances + new-account checks
          // the compiled engine does not model -> host interpreter
          X.host_reason = op;
          res.status = ST_HOST;
          return res;
        }
        if (gas < memgas) { res.gas = 0; return res; }
        int64_t avail = gas - memgas;
        int64_t cap = avail - avail / 64;   // EIP-150 63/64
        int64_t child_gas = cap;
        if (!(greq.w[1] | greq.w[2] | greq.w[3])
            && greq.w[0] < (uint64_t)cap)
          child_gas = (int64_t)greq.w[0];
        gas -= memgas + child_gas;
        if (msz > mem.size()) mem.resize(new_words * 32, 0);
        // resolve callee
        Contract* cc = nullptr;
        int kind = lookup_code(X, target, &cc);
        if (kind < 0) {
          X.host_reason = op;
          res.status = ST_HOST;
          return res;
        }
        Bytes args;
        if (insz) args.assign(mem.begin() + inoff,
                              mem.begin() + inoff + insz);
        FrameRes cres;
        if (depth > 1024) {
          // ErrDepth: the subcall fails but returns its gas untouched
          cres.status = ST_ERR;
          cres.gas = child_gas;
        } else if (kind == 1 && !cc->code.empty()) {
          Snap sn = take_snap(X);
          cres = run_frame(X, (const uint8_t*)self_addr.data(), target,
                           cc, args.data(), args.size(), child_gas,
                           callv, is_static || op == 0xFA, depth + 1);
          if (cres.status == ST_HOST) {
            res.status = ST_HOST;
            return res;
          }
          if (cres.status != ST_STOP) restore_snap(X, sn);
        } else {
          // EOA / empty code: trivially successful subcall
          cres.status = ST_STOP;
          cres.gas = child_gas;
        }
        gas += cres.gas;
        retdata = cres.out;
        stack.push_back(u256_from64(cres.status == ST_STOP ? 1 : 0));
        if (cres.status == ST_STOP || cres.status == ST_REVERT) {
          uint64_t n = cres.out.size() < outsz ? cres.out.size() : outsz;
          if (n) std::memcpy(mem.data() + outoff, cres.out.data(), n);
        }
        if (stack.size() > 1024) { res.gas = 0; return res; }
        ++pc;
        continue; }
      case 0xF3: case 0xFD: {                              // RETURN REVERT
        NEED(2);
        U256 offv = stack.back(); stack.pop_back();
        U256 lenv = stack.back(); stack.pop_back();
        bool ok1, ok2;
        uint64_t off = u64_arg(offv, &ok1), len = u64_arg(lenv, &ok2);
        if (len) {
          if (!ok1 || !ok2 || !expand(off + len)) {
            res.gas = 0;
            return res;
          }
        }
        res.gas = gas;
        if (len) res.out.assign(mem.begin() + off,
                                mem.begin() + off + len);
        res.status = op == 0xF3 ? ST_STOP : ST_REVERT;
        return res; }
      case 0xFE: res.gas = 0; return res;                  // INVALID
      default:
        if (op >= 0x60 && op <= 0x7F) {                    // PUSHn
          USE(G_FASTEST);
          unsigned n = op - 0x5F;
          uint8_t buf[32] = {0};
          for (unsigned j = 0; j < n; ++j) {
            size_t src = pc + 1 + j;
            buf[32 - n + j] = src < code.size() ? code[src] : 0;
          }
          stack.push_back(from_be(buf));
          pc += 1 + n;
          if (stack.size() > 1024) { res.gas = 0; return res; }
          continue;
        }
        if (op >= 0x80 && op <= 0x8F) {                    // DUPn
          unsigned n = op - 0x7F;
          NEED(n); USE(G_FASTEST);
          stack.push_back(stack[stack.size() - n]);
          if (stack.size() > 1024) { res.gas = 0; return res; }
          ++pc;
          continue;
        }
        if (op >= 0x90 && op <= 0x9F) {                    // SWAPn
          unsigned n = op - 0x8F;
          NEED(n + 1); USE(G_FASTEST);
          std::swap(stack.back(), stack[stack.size() - 1 - n]);
          ++pc;
          continue;
        }
        if (op >= 0xA0 && op <= 0xA4) {                    // LOGn
          unsigned n = op - 0xA0;
          NEED(2 + n);
          if (is_static) { res.gas = 0; return res; }  // write protect
          U256 offv = stack.back(); stack.pop_back();
          U256 lenv = stack.back(); stack.pop_back();
          LogRec lg;
          std::memcpy(lg.addr, self_addr.data(), 20);
          lg.nt = (int)n;
          for (unsigned j = 0; j < n; ++j) {
            to_be(stack.back(), lg.topics[j]);
            stack.pop_back();
          }
          bool ok1, ok2;
          uint64_t off = u64_arg(offv, &ok1),
                   len = u64_arg(lenv, &ok2);
          if (len) {
            if (!ok1 || !ok2 || !expand(off + len)) {
              res.gas = 0;
              return res;
            }
          }
          USE(G_LOG + G_LOGTOPIC * n + G_LOGDATA * (int64_t)len);
          if (len) lg.data.assign(mem.begin() + off,
                                  mem.begin() + off + len);
          X.logs.push_back(std::move(lg));
          ++pc;
          continue;
        }
        if (X.optable && X.optable[op] == OP_HOSTONLY) {
          // defined in the fork's jump table but not compiled here:
          // the whole tx re-runs on the Python interpreter
          X.host_reason = op;
          res.status = ST_HOST;
          return res;
        }
        res.gas = 0;  // undefined opcode
        return res;
    }
    ++pc;
  }
  res.status = ST_STOP;  // implicit STOP past code end
  res.gas = gas;
  res.out.clear();
  return res;
}

// native ops the interpreter executes directly (replay optable)
void build_replay_optable(uint8_t* t) {
  std::memset(t, OP_UNDEF, 256);
  static const uint8_t ops[] = {
      0x00, 0x01, 0x02, 0x03, 0x04, 0x05, 0x06, 0x07, 0x08, 0x09,
      0x0A, 0x0B, 0x10, 0x11, 0x12, 0x13, 0x14, 0x15, 0x16, 0x17,
      0x18, 0x19, 0x1A, 0x1B, 0x1C, 0x1D, 0x20, 0x30, 0x32, 0x33,
      0x34, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A, 0x3B, 0x3D, 0x3E,
      0x41, 0x42, 0x43, 0x44, 0x45, 0x46, 0x48, 0x50, 0x51, 0x52,
      0x53, 0x54, 0x55, 0x56, 0x57, 0x58, 0x59, 0x5A, 0x5B, 0x5F,
      0xF1, 0xF3, 0xFA, 0xFD, 0xFE};
  for (uint8_t op : ops) t[op] = OP_NATIVE;
  for (int op = 0x60; op <= 0xA4; ++op) t[op] = OP_NATIVE;
}

double now_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return ts.tv_sec + ts.tv_nsec * 1e-9;
}

}  // namespace

extern "C" {

// Sequential compiled EVM replay over packed inputs; returns 0 on
// success, 1000+i on a root mismatch at block i, negative on malformed
// input (-5: a tx needed a host-only feature — never on the bench
// workloads; -10: offsets not monotone or a length-prefixed record
// extending past its blob — txs_len/contracts_len make the decode
// bounds-checked instead of trusted; fuzzed under ASan by
// tests/test_sanitize.py).  phases: [t_sender, t_exec, t_trie].
//
// tx record: sighash32 r32 s32 recid1 to20 value32 gas8 price32
//            required32 nonce8 dlen4 data
// block env record (per block): root32 coinbase20 ts8 num8 gaslimit8
//            basefee32 gasused8
// accounts: addr20 bal32 nonce8
// contracts: addr20 codehash32 bal32 nonce8 len4 code nslots4
//            (key32 val32)*
int coreth_evm_replay(const uint8_t* txs, uint64_t txs_len,
                      const uint64_t* block_off,
                      uint64_t n_blocks, const uint8_t* block_env,
                      const uint8_t* accounts, uint64_t n_accounts,
                      const uint8_t* contracts, uint64_t contracts_len,
                      uint64_t n_contracts,
                      uint64_t chain_id, double* phases) {
  for (uint64_t b = 0; b < n_blocks; ++b)
    if (block_off[b] > block_off[b + 1]) return -10;
  std::unordered_map<std::string, Account> state;
  std::vector<Contract> pool(n_contracts);
  state.reserve(n_accounts * 2);
  const uint8_t* p = accounts;
  for (uint64_t i = 0; i < n_accounts; ++i) {
    std::string addr((const char*)p, 20);
    Account a;
    bool too_big = false;
    for (int j = 0; j < 16; ++j)
      if (p[20 + j]) too_big = true;
    for (int j = 16; j < 32; ++j)
      a.balance = (a.balance << 8) | p[20 + j];
    if (too_big) return -1;
    uint64_t nonce = 0;
    for (int j = 0; j < 8; ++j) nonce = (nonce << 8) | p[52 + j];
    a.nonce = nonce;
    state[addr] = a;
    p += 60;
  }
  p = contracts;
  const uint8_t* cend = contracts + contracts_len;
  for (uint64_t i = 0; i < n_contracts; ++i) {
    // fixed header (addr20 hash32 bal32 nonce8 len4) must fit before
    // its length prefixes are trusted
    if (cend - p < 96) return -10;
    std::string addr((const char*)p, 20);
    Contract& c = pool[i];
    std::memcpy(c.code_hash, p + 20, 32);
    u128 cbal = 0;
    bool cbig = false;
    for (int j = 0; j < 16; ++j)
      if (p[52 + j]) cbig = true;
    for (int j = 16; j < 32; ++j) cbal = (cbal << 8) | p[52 + j];
    if (cbig) return -1;
    uint64_t cnonce = 0;
    for (int j = 0; j < 8; ++j) cnonce = (cnonce << 8) | p[84 + j];
    uint32_t clen;
    std::memcpy(&clen, p + 92, 4);
    if ((uint64_t)(cend - p) < 96 + (uint64_t)clen + 4) return -10;
    c.code.assign(p + 96, p + 96 + clen);
    analyze_jumpdests(&c);
    p += 96 + clen;
    uint32_t nslots;
    std::memcpy(&nslots, p, 4);
    p += 4;
    if ((uint64_t)(cend - p) < 64 * (uint64_t)nslots) return -10;
    for (uint32_t j = 0; j < nslots; ++j) {
      Key32 k;
      std::memcpy(k.b, p, 32);
      c.storage[k] = from_be(p + 32);
      p += 64;
    }
    auto& acct = state[addr];
    acct.contract = &c;
    acct.balance = cbal;
    acct.nonce = cnonce;
  }

  // per-contract storage tries built once from initial slots
  std::vector<void*> stries(n_contracts);
  std::vector<uint8_t> sroots(n_contracts * 32);
  auto fold_slots = [&](uint64_t ci, const SlotMap& slots) {
    std::vector<uint8_t> keys, vals;
    std::vector<uint32_t> lens;
    uint8_t hk[32], be[32];
    for (auto& kv : slots) {
      coreth_keccak256(kv.first.b, 32, hk);
      keys.insert(keys.end(), hk, hk + 32);
      if (kv.second.is_zero()) {
        lens.push_back(0);
        continue;
      }
      to_be(kv.second, be);
      int lead = 0;
      while (lead < 32 && be[lead] == 0) ++lead;
      // rlp of the stripped big-endian integer
      Bytes v;
      int n = 32 - lead;
      if (n == 1 && be[31] < 0x80) {
        v.push_back(be[31]);
      } else {
        v.push_back(0x80 + n);
        v.insert(v.end(), be + lead, be + 32);
      }
      lens.push_back((uint32_t)v.size());
      vals.insert(vals.end(), v.begin(), v.end());
    }
    coreth_trie_update_batch(stries[ci], keys.data(), vals.data(),
                             lens.data(), lens.size());
    coreth_trie_hash(stries[ci], sroots.data() + 32 * ci);
  };
  for (uint64_t i = 0; i < n_contracts; ++i) {
    stries[i] = coreth_trie_new();
    fold_slots(i, pool[i].storage);
  }
  void* atrie = coreth_trie_new();
  // empty-storage / empty-code constants (keccak of "" / rlp(""))
  uint8_t empty_root[32], empty_code[32];
  {
    uint8_t rlp_empty = 0x80;
    coreth_keccak256(&rlp_empty, 1, empty_root);
    coreth_keccak256(nullptr, 0, empty_code);
  }
  // seed the account trie with every genesis account
  {
    std::vector<uint8_t> keys, bals, roots, hashes;
    std::vector<uint64_t> nonces;
    std::vector<uint8_t> mc, del;
    for (auto& kv : state) {
      uint8_t hk[32];
      coreth_keccak256((const uint8_t*)kv.first.data(), 20, hk);
      keys.insert(keys.end(), hk, hk + 32);
      uint8_t be[32] = {0};
      u128 b = kv.second.balance;
      for (int j = 31; j >= 0; --j) {
        be[j] = (uint8_t)b;
        b >>= 8;
      }
      bals.insert(bals.end(), be, be + 32);
      nonces.push_back(kv.second.nonce);
      if (kv.second.contract) {
        uint64_t ci = kv.second.contract - pool.data();
        roots.insert(roots.end(), sroots.data() + 32 * ci,
                     sroots.data() + 32 * ci + 32);
        hashes.insert(hashes.end(), kv.second.contract->code_hash,
                      kv.second.contract->code_hash + 32);
      } else {
        roots.insert(roots.end(), empty_root, empty_root + 32);
        hashes.insert(hashes.end(), empty_code, empty_code + 32);
      }
      mc.push_back(0);
      del.push_back(0);
    }
    coreth_trie_fold_accounts(atrie, keys.data(), bals.data(),
                              nonces.data(), roots.data(),
                              hashes.data(), mc.data(), del.data(),
                              nonces.size());
  }

  uint8_t optable[256];
  build_replay_optable(optable);

  double t_sender = 0, t_exec = 0, t_trie = 0;
  int rc = 0;
  const uint8_t* tp = txs;
  for (uint64_t bi = 0; bi < n_blocks && rc == 0; ++bi) {
    const uint8_t* be = block_env + bi * 116;
    Env env;
    std::memcpy(env.coinbase, be + 32, 20);
    uint64_t v = 0;
    for (int j = 0; j < 8; ++j) v = (v << 8) | be[52 + j];
    env.timestamp = v;
    v = 0;
    for (int j = 0; j < 8; ++j) v = (v << 8) | be[60 + j];
    env.number = v;
    v = 0;
    for (int j = 0; j < 8; ++j) v = (v << 8) | be[68 + j];
    env.gaslimit = v;
    env.basefee = from_be(be + 76);
    env.chain_id = chain_id;

    std::unordered_set<std::string> touched;
    std::unordered_set<uint64_t> dirty_contracts;
    touched.insert(std::string((const char*)env.coinbase, 20));
    for (uint64_t ti = block_off[bi]; ti < block_off[bi + 1]; ++ti) {
      // the fixed record head (233 bytes through dlen) and then the
      // dlen-prefixed calldata must both fit inside txs_len
      if ((uint64_t)(txs + txs_len - tp) < 233) return -10;
      {
        uint32_t dl;
        std::memcpy(&dl, tp + 229, 4);
        if ((uint64_t)(txs + txs_len - tp) < 233 + (uint64_t)dl)
          return -10;
      }
      // --- sender recovery
      double t0 = now_s();
      uint8_t sender[20];
      if (!coreth_ecrecover(tp, tp + 32, tp + 64, tp[96], sender))
        return -2;
      t_sender += now_s() - t0;
      t0 = now_s();
      const uint8_t* to = tp + 97;
      bool too_big = false;
      u128 value = 0, price = 0, required = 0;
      for (int j = 16; j < 32; ++j)
        value = (value << 8) | tp[117 + j];
      for (int j = 0; j < 16; ++j)
        if (tp[117 + j] | tp[157 + j] | tp[189 + j]) too_big = true;
      uint64_t gas_limit = 0;
      for (int j = 0; j < 8; ++j)
        gas_limit = (gas_limit << 8) | tp[149 + j];
      for (int j = 16; j < 32; ++j)
        price = (price << 8) | tp[157 + j];
      for (int j = 16; j < 32; ++j)
        required = (required << 8) | tp[189 + j];
      uint64_t nonce = 0;
      for (int j = 0; j < 8; ++j)
        nonce = (nonce << 8) | tp[221 + j];
      uint32_t dlen;
      std::memcpy(&dlen, tp + 229, 4);
      const uint8_t* data = tp + 233;
      tp += 233 + dlen;
      if (too_big) return -3;

      std::string saddr((const char*)sender, 20);
      std::string taddr((const char*)to, 20);
      std::string cbaddr((const char*)env.coinbase, 20);
      // insert all three keys BEFORE taking references: operator[]
      // may rehash and invalidate earlier references
      state.try_emplace(taddr);
      state.try_emplace(cbaddr);
      Account& sa = state[saddr];
      if (sa.nonce != nonce) return 2000 + (int)bi;
      if (sa.balance < required) return 3000 + (int)bi;
      Account& ta = state[taddr];
      uint64_t used;
      bool ok_tx = true;
      // intrinsic gas: 21000 + calldata bytes (durango/EIP-2028)
      uint64_t intrinsic = 21000;
      for (uint32_t j = 0; j < dlen; ++j)
        intrinsic += data[j] ? 16 : 4;
      if (gas_limit < intrinsic) return -4;
      if (ta.contract) {
        Exec X;
        X.env = &env;
        X.origin = sender;
        uint8_t pb[32] = {0};
        u128 pv = price;
        for (int j = 31; j >= 16; --j) {
          pb[j] = (uint8_t)pv;
          pv >>= 8;
        }
        X.gasprice = from_be(pb);
        X.optable = optable;
        X.refunds_on = true;  // durango tracks (never pays) refunds
        X.replay_state = &state;
        // tx-start warm set: sender, target, coinbase (EIP-3651)
        X.warm_addr.insert(saddr);
        X.warm_addr.insert(taddr);
        X.warm_addr.insert(cbaddr);
        uint8_t vb[32] = {0};
        u128 vv = value;
        for (int j = 31; j >= 16; --j) {
          vb[j] = (uint8_t)vv;
          vv >>= 8;
        }
        FrameRes r = run_frame(X, sender, taddr, ta.contract, data,
                               dlen, (int64_t)(gas_limit - intrinsic),
                               from_be(vb), false, 1);
        if (r.status == ST_HOST) return -5;
        used = gas_limit - (uint64_t)r.gas;
        ok_tx = r.status == ST_STOP;
        if (ok_tx) {
          for (auto& kv : X.dirty) {
            std::string caddr = kv.first.substr(0, 20);
            Key32 k;
            std::memcpy(k.b, kv.first.data() + 20, 32);
            auto it = state.find(caddr);
            if (it == state.end() || !it->second.contract) continue;
            Contract* wc = it->second.contract;
            wc->storage[k] = kv.second;
            wc->block_dirty[k] = kv.second;
            dirty_contracts.insert(wc - pool.data());
            touched.insert(caddr);
          }
        }
      } else {
        used = intrinsic;
      }
      sa.nonce += 1;
      sa.balance -= (u128)used * price;
      if (ok_tx && value) {
        sa.balance -= value;
        ta.balance += value;
      }
      state[cbaddr].balance += (u128)used * price;
      touched.insert(saddr);
      touched.insert(taddr);
      t_exec += now_s() - t0;
    }

    // --- per-block fold + root check
    double t0 = now_s();
    for (uint64_t ci : dirty_contracts) {
      fold_slots(ci, pool[ci].block_dirty);
      pool[ci].block_dirty.clear();
    }
    {
      std::vector<uint8_t> keys, bals, roots, hashes;
      std::vector<uint64_t> nonces;
      std::vector<uint8_t> mc, del;
      for (auto& addr : touched) {
        Account& a = state[addr];
        uint8_t hk[32];
        coreth_keccak256((const uint8_t*)addr.data(), 20, hk);
        keys.insert(keys.end(), hk, hk + 32);
        uint8_t beb[32] = {0};
        u128 b = a.balance;
        for (int j = 31; j >= 0; --j) {
          beb[j] = (uint8_t)b;
          b >>= 8;
        }
        bals.insert(bals.end(), beb, beb + 32);
        nonces.push_back(a.nonce);
        bool empty = a.balance == 0 && a.nonce == 0 && !a.contract;
        if (a.contract) {
          uint64_t ci = a.contract - pool.data();
          roots.insert(roots.end(), sroots.data() + 32 * ci,
                       sroots.data() + 32 * ci + 32);
          hashes.insert(hashes.end(), a.contract->code_hash,
                        a.contract->code_hash + 32);
        } else {
          roots.insert(roots.end(), empty_root, empty_root + 32);
          hashes.insert(hashes.end(), empty_code, empty_code + 32);
        }
        mc.push_back(0);
        del.push_back(empty ? 1 : 0);
      }
      coreth_trie_fold_accounts(atrie, keys.data(), bals.data(),
                                nonces.data(), roots.data(),
                                hashes.data(), mc.data(), del.data(),
                                nonces.size());
    }
    uint8_t got[32];
    coreth_trie_hash(atrie, got);
    t_trie += now_s() - t0;
    if (std::memcmp(got, be, 32) != 0) rc = 1000 + (int)bi;
  }

  for (void* h : stries) coreth_trie_free(h);
  coreth_trie_free(atrie);
  phases[0] = t_sender;
  phases[1] = t_exec;
  phases[2] = t_trie;
  return rc;
}

// ------------------------------------------------- hostexec session ABI
//
// Executes full transactions against a StateDB-backed host interface:
// storage slots and callee code resolve through Python callbacks; the
// call returns gas/status and the caller fetches logs + cross-contract
// writes + return data through the out_* getters.  One session holds a
// committed-storage cache that the caller seeds (OCC prefix overlays)
// or invalidates (epoch bumps) explicitly.

void* coreth_hostexec_new(uint64_t chain_id, FetchSlotCb fetch_slot,
                          FetchCodeCb fetch_code,
                          const uint8_t* optable256, int refunds_on) {
  Sess* s = new Sess();
  s->env.chain_id = chain_id;
  s->fetch_slot = fetch_slot;
  s->fetch_code = fetch_code;
  std::memcpy(s->optable, optable256, 256);
  s->refunds_on = refunds_on;
  return s;
}

void coreth_hostexec_free(void* hp) { delete (Sess*)hp; }

void coreth_hostexec_env(void* hp, const uint8_t* coinbase20,
                         uint64_t timestamp, uint64_t number,
                         uint64_t gaslimit, uint64_t difficulty,
                         const uint8_t* basefee32) {
  Sess* s = (Sess*)hp;
  std::memcpy(s->env.coinbase, coinbase20, 20);
  s->env.timestamp = timestamp;
  s->env.number = number;
  s->env.gaslimit = gaslimit;
  s->env.difficulty = difficulty;
  s->env.basefee = from_be(basefee32);
}

void coreth_hostexec_set_code(void* hp, const uint8_t* addr20,
                              const uint8_t* code, uint32_t len) {
  Sess* s = (Sess*)hp;
  std::string addr((const char*)addr20, 20);
  Contract& c = s->contracts[addr];
  c.code.assign(code, code + len);
  analyze_jumpdests(&c);
  s->kind[addr] = len ? 1 : 0;
}

// drop every cached committed slot (underlying state moved: new tx on
// a mutating StateDB, or an engine storage-epoch bump)
void coreth_hostexec_clear_storage(void* hp) {
  Sess* s = (Sess*)hp;
  for (auto& kv : s->contracts) kv.second.storage.clear();
}

// drop EVERYTHING resolved so far — codes, EOA/contract kinds, and
// storage.  The StateDB bridge calls this per tx: a mid-block deploy
// (CREATE on the interpreter path) can turn a cached EOA into a
// contract or swap bytecode, so per-tx resolution must start fresh.
// The serial short-circuit keeps the cheaper clear_storage/commit
// protocol — machine blocks cannot deploy code.
void coreth_hostexec_reset(void* hp) {
  Sess* s = (Sess*)hp;
  s->contracts.clear();
  s->kind.clear();
}

// drop ONLY the cached EOA verdicts (kind == 0): an account can
// spring into existence — or become existing-but-empty — through pure
// balance moves, which the bridge's storage_gen reuse check cannot
// see, and a stale EOA verdict would skip the code_resolver's
// exist-and-empty host guard (EIP-158 touch deletion).  Registered
// contracts keep their code, jumpdest analysis, and storage cache: a
// code change always goes through StateDB.set_code, which bumps
// storage_gen and forces the full reset.
void coreth_hostexec_reset_kinds(void* hp) {
  Sess* s = (Sess*)hp;
  for (auto it = s->kind.begin(); it != s->kind.end();) {
    if (it->second == 0) it = s->kind.erase(it);
    else ++it;
  }
}

// seed a committed value (OCC prefix overlay / sequential carry)
void coreth_hostexec_seed_slot(void* hp, const uint8_t* addr20,
                               const uint8_t* key32,
                               const uint8_t* val32) {
  Sess* s = (Sess*)hp;
  std::string addr((const char*)addr20, 20);
  Key32 k;
  std::memcpy(k.b, key32, 32);
  k.b[0] &= 0xFE;
  s->contracts[addr].storage[k] = from_be(val32);
}

void coreth_hostexec_warm_addr(void* hp, const uint8_t* addr20) {
  ((Sess*)hp)->seed_warm_addr.emplace_back((const char*)addr20, 20);
}

void coreth_hostexec_warm_slot(void* hp, const uint8_t* addr20,
                               const uint8_t* key32) {
  Sess* s = (Sess*)hp;
  std::string k((const char*)addr20, 20);
  k.append((const char*)key32, 32);
  s->seed_warm_slot.push_back(k);
}

// Execute one root call.  Returns the machine status code
// (1 STOP / 2 REVERT / 3 ERR / 4 HOST); out[] = [gas_left, refund,
// n_writes, n_logs, log_data_total, ret_len, host_reason].
// Warm seeds accumulated since the last call are consumed.
int coreth_hostexec_call(void* hp, const uint8_t* caller20,
                         const uint8_t* to20, const uint8_t* value32,
                         const uint8_t* gasprice32, const uint8_t* data,
                         uint32_t dlen, int64_t gas, int64_t* out) {
  Sess* s = (Sess*)hp;
  Exec X;
  X.env = &s->env;
  X.origin = caller20;
  X.gasprice = from_be(gasprice32);
  X.optable = s->optable;
  X.refunds_on = s->refunds_on != 0;
  X.sess = s;
  for (auto& a : s->seed_warm_addr) X.warm_addr.insert(a);
  for (auto& k : s->seed_warm_slot) X.warm_slot.insert(k);
  s->seed_warm_addr.clear();
  s->seed_warm_slot.clear();

  s->out = SessOut();
  std::string target((const char*)to20, 20);
  Contract* c = nullptr;
  int kind = lookup_code(X, target, &c);
  if (kind != 1 || c->code.empty()) {
    // the bridge only routes code-bearing targets here
    s->out.status = ST_HOST;
    s->out.host_reason = 0;
  } else {
    FrameRes r = run_frame(X, caller20, target, c, data, dlen, gas,
                           from_be(value32), false, 1);
    s->out.status = r.status;
    s->out.gas_left = r.gas;
    s->out.refund = X.refund;
    s->out.host_reason = X.host_reason;
    s->out.ret = std::move(r.out);
    if (r.status == ST_STOP) {
      s->out.writes = std::move(X.dirty);
      s->out.logs = std::move(X.logs);
    }
  }
  uint64_t log_data = 0;
  for (auto& lg : s->out.logs) log_data += lg.data.size();
  out[0] = s->out.gas_left;
  out[1] = s->out.refund;
  out[2] = (int64_t)s->out.writes.size();
  out[3] = (int64_t)s->out.logs.size();
  out[4] = (int64_t)log_data;
  out[5] = (int64_t)s->out.ret.size();
  out[6] = s->out.host_reason;
  return s->out.status;
}

// write set of the last successful call, sorted by (address, key) —
// a deterministic writeback order for the StateDB/trie fold
void coreth_hostexec_out_writes(void* hp, uint8_t* addrs20,
                                uint8_t* keys32, uint8_t* vals32) {
  Sess* s = (Sess*)hp;
  size_t i = 0;
  for (auto& kv : s->out.writes) {
    std::memcpy(addrs20 + 20 * i, kv.first.data(), 20);
    std::memcpy(keys32 + 32 * i, kv.first.data() + 20, 32);
    to_be(kv.second, vals32 + 32 * i);
    ++i;
  }
}

void coreth_hostexec_out_logs(void* hp, uint8_t* addrs20,
                              int32_t* ntopics, uint8_t* topics,
                              int32_t* dlens, uint8_t* datablob) {
  Sess* s = (Sess*)hp;
  uint8_t* dp = datablob;
  for (size_t i = 0; i < s->out.logs.size(); ++i) {
    LogRec& lg = s->out.logs[i];
    std::memcpy(addrs20 + 20 * i, lg.addr, 20);
    ntopics[i] = lg.nt;
    for (int j = 0; j < lg.nt; ++j)
      std::memcpy(topics + (4 * i + j) * 32, lg.topics[j], 32);
    dlens[i] = (int32_t)lg.data.size();
    if (!lg.data.empty()) {
      std::memcpy(dp, lg.data.data(), lg.data.size());
      dp += lg.data.size();
    }
  }
}

void coreth_hostexec_out_ret(void* hp, uint8_t* buf) {
  Sess* s = (Sess*)hp;
  if (!s->out.ret.empty())
    std::memcpy(buf, s->out.ret.data(), s->out.ret.size());
}

// fold the last call's writes into the session's committed cache so
// the next call in the same block sees them (sequential carry)
void coreth_hostexec_commit(void* hp) {
  Sess* s = (Sess*)hp;
  for (auto& kv : s->out.writes) {
    std::string addr = kv.first.substr(0, 20);
    Key32 k;
    std::memcpy(k.b, kv.first.data() + 20, 32);
    s->contracts[addr].storage[k] = kv.second;
  }
}

}  // extern "C"

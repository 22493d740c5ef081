// Native secp256k1 ECDSA public-key recovery for the coreth-tpu host runtime.
//
// Role parity with the reference's cgo libsecp256k1 binding (geth
// crypto/secp256k1), which coreth drives in parallel for every block via
// core/sender_cacher.go.  This implementation: field elements in 5x52-bit
// limbs with lazy reduction (the design of libsecp256k1's
// field_5x52_int128), scalars mod n in 4x64-bit limbs, Jacobian points,
// inversions mod p and mod n by variable-time safegcd (Bernstein-Yang
// divsteps, as libsecp256k1's modinv64_var).  Keccak for the address
// derivation comes from keccak.cc.
//
// Correctness is anchored by the test suite: cross-checked against the
// pure-Python implementation, which is itself anchored by the well-known
// privkey=1 -> 0x7E5F4552091A69125d5DfCb7b8C2659029395Bdf vector; the
// field and scalar arithmetic is held to Python integers limb by limb
// (tests/test_secp_field.py).

#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

extern "C" void coreth_keccak256(const uint8_t*, uint64_t, uint8_t*);
extern "C" int coreth_ecrecover(const uint8_t*, const uint8_t*,
                                const uint8_t*, int, uint8_t*);

namespace {

typedef unsigned __int128 u128;
typedef __int128 i128;

struct U256 {
  uint64_t v[4];  // little-endian limbs
};

const U256 ONE = {{1, 0, 0, 0}};

// p = 2^256 - 2^32 - 977
const U256 PRIME = {{0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                     0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL}};

// group order n
const U256 ORDER = {{0xBFD25E8CD0364141ULL, 0xBAAEDCE6AF48A03BULL,
                     0xFFFFFFFFFFFFFFFEULL, 0xFFFFFFFFFFFFFFFFULL}};

const U256 GX = {{0x59F2815B16F81798ULL, 0x029BFCDB2DCE28D9ULL,
                  0x55A06295CE870B07ULL, 0x79BE667EF9DCBBACULL}};
const U256 GY = {{0x9C47D08FFB10D4B8ULL, 0xFD17B448A6855419ULL,
                  0x5DA4FBFC0E1108A8ULL, 0x483ADA7726A3C465ULL}};

inline bool is_zero(const U256& a) {
  return (a.v[0] | a.v[1] | a.v[2] | a.v[3]) == 0;
}

inline int cmp(const U256& a, const U256& b) {
  for (int i = 3; i >= 0; --i) {
    if (a.v[i] < b.v[i]) return -1;
    if (a.v[i] > b.v[i]) return 1;
  }
  return 0;
}

// returns carry out
inline uint64_t add_raw(U256& r, const U256& a, const U256& b) {
  u128 c = 0;
  for (int i = 0; i < 4; ++i) {
    c += (u128)a.v[i] + b.v[i];
    r.v[i] = (uint64_t)c;
    c >>= 64;
  }
  return (uint64_t)c;
}

// returns borrow out
inline uint64_t sub_raw(U256& r, const U256& a, const U256& b) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - b.v[i] - borrow;
    r.v[i] = (uint64_t)d;
    borrow = (d >> 64) & 1;  // two's complement: top bit set iff underflow
  }
  return (uint64_t)borrow;
}

inline void mod_add(U256& r, const U256& a, const U256& b, const U256& m) {
  uint64_t carry = add_raw(r, a, b);
  if (carry || cmp(r, m) >= 0) {
    U256 t;
    sub_raw(t, r, m);
    r = t;
  }
}

inline void mod_sub(U256& r, const U256& a, const U256& b, const U256& m) {
  U256 t;
  if (sub_raw(t, a, b)) {
    U256 t2;
    add_raw(t2, t, m);  // wraps back into range
    r = t2;
  } else {
    r = t;
  }
}

// ---- field elements mod p: 5x52-bit limbs, lazy reduction ----
//
// x = n[0] + n[1] 2^52 + n[2] 2^104 + n[3] 2^156 + n[4] 2^208.  A value
// of MAGNITUDE m has n[0..3] <= 2m(2^52 - 1) and n[4] <= 2m(2^48 - 1):
// any representative of its residue, not necessarily below p.  The
// magnitudes are tracked by the code's structure (the "(m)" beside each
// step), never at run time:
//   fe_mul, fe_sqr      inputs <= 8, result 1
//   fe_add              magnitudes add
//   fe_negate(a, m)     a <= m, result m + 1
//   fe_mul_int(a, k)    magnitude times k
//   fe_normalize_weak   input <= 32, result 1
//   fe_normalize        result below p: the only form that may be
//                       serialized, tested for zero or have its parity
//                       read (fe_normalizes_to_zero / fe_equal test a
//                       residue without it)
// Adds, negations and small multiples carry nothing and compare nothing:
// a point formula pays one weak normalization where its sums would pass
// 8.  A Point's coordinates are <= 4 between point operations, an
// APoint's <= 2.

struct Fe {
  uint64_t n[5];
};

const uint64_t M52 = 0xFFFFFFFFFFFFFULL;
const uint64_t M48 = 0xFFFFFFFFFFFFULL;
const uint64_t P_C = 0x1000003D1ULL;  // 2^256 - p

const Fe FE_ONE = {{1, 0, 0, 0, 0}};
const Fe FE_SEVEN = {{7, 0, 0, 0, 0}};

// any 256-bit value (magnitude 1)
inline void fe_from_u256(Fe& r, const U256& a) {
  r.n[0] = a.v[0] & M52;
  r.n[1] = (a.v[0] >> 52 | a.v[1] << 12) & M52;
  r.n[2] = (a.v[1] >> 40 | a.v[2] << 24) & M52;
  r.n[3] = (a.v[2] >> 28 | a.v[3] << 36) & M52;
  r.n[4] = a.v[3] >> 16;
}

// a normalized
inline void fe_to_u256(U256& r, const Fe& a) {
  r.v[0] = a.n[0] | a.n[1] << 52;
  r.v[1] = a.n[1] >> 12 | a.n[2] << 40;
  r.v[2] = a.n[2] >> 24 | a.n[3] << 28;
  r.v[3] = a.n[3] >> 36 | a.n[4] << 16;
}

Fe fe_const(const U256& a) {
  Fe r;
  fe_from_u256(r, a);
  return r;
}

inline void fe_add(Fe& r, const Fe& a) {
  for (int i = 0; i < 5; ++i) r.n[i] += a.n[i];
}

// r = 2(m + 1) p - a
inline void fe_negate(Fe& r, const Fe& a, int m) {
  const uint64_t k = 2 * (uint64_t)(m + 1);
  r.n[0] = 0xFFFFEFFFFFC2FULL * k - a.n[0];
  r.n[1] = M52 * k - a.n[1];
  r.n[2] = M52 * k - a.n[2];
  r.n[3] = M52 * k - a.n[3];
  r.n[4] = M48 * k - a.n[4];
}

inline void fe_mul_int(Fe& r, int k) {
  for (int i = 0; i < 5; ++i) r.n[i] *= (uint64_t)k;
}

// magnitude 1: every limb carried, bit 256 folded back once
inline void fe_normalize_weak(Fe& r) {
  uint64_t t0 = r.n[0], t1 = r.n[1], t2 = r.n[2], t3 = r.n[3], t4 = r.n[4];
  const uint64_t x = t4 >> 48;
  t4 &= M48;
  t0 += x * P_C;
  t1 += t0 >> 52; t0 &= M52;
  t2 += t1 >> 52; t1 &= M52;
  t3 += t2 >> 52; t2 &= M52;
  t4 += t3 >> 52; t3 &= M52;
  r = {{t0, t1, t2, t3, t4}};
}

// fully reduced: below p
void fe_normalize(Fe& r) {
  uint64_t t0 = r.n[0], t1 = r.n[1], t2 = r.n[2], t3 = r.n[3], t4 = r.n[4];
  uint64_t x = t4 >> 48;
  t4 &= M48;
  t0 += x * P_C;
  t1 += t0 >> 52; t0 &= M52;
  t2 += t1 >> 52; t1 &= M52; uint64_t m = t1;
  t3 += t2 >> 52; t2 &= M52; m &= t2;
  t4 += t3 >> 52; t3 &= M52; m &= t3;
  // at most one subtraction of p is left: at bit 256, or at p itself
  x = (t4 >> 48) |
      ((t4 == M48) & (m == M52) & (t0 >= 0xFFFFEFFFFFC2FULL));
  t0 += x * P_C;
  t1 += t0 >> 52; t0 &= M52;
  t2 += t1 >> 52; t1 &= M52;
  t3 += t2 >> 52; t2 &= M52;
  t4 += t3 >> 52; t3 &= M52;
  t4 &= M48;
  r = {{t0, t1, t2, t3, t4}};
}

// a == 0 (mod p), for a <= 32: decided by the low limb in almost every
// call (variable time; see fe_inv for why that is sound here)
bool fe_normalizes_to_zero(const Fe& a) {
  uint64_t t0 = a.n[0], t1 = a.n[1], t2 = a.n[2], t3 = a.n[3], t4 = a.n[4];
  const uint64_t x = t4 >> 48;
  t0 += x * P_C;
  // z0 tracks a raw value of 0, z1 a raw value of p
  uint64_t z0 = t0 & M52, z1 = z0 ^ 0x1000003D0ULL;
  if (z0 != 0 && z1 != M52) return false;
  t4 &= M48;
  t1 += t0 >> 52; t0 &= M52; z0 = t0; z1 = t0 ^ 0x1000003D0ULL;
  t2 += t1 >> 52; t1 &= M52; z0 |= t1; z1 &= t1;
  t3 += t2 >> 52; t2 &= M52; z0 |= t2; z1 &= t2;
  t4 += t3 >> 52; t3 &= M52; z0 |= t3; z1 &= t3;
  z0 |= t4; z1 &= t4 ^ 0xF000000000000ULL;
  return z0 == 0 || z1 == M52;
}

// a == b (mod p), for b <= 30
inline bool fe_equal(const Fe& a, const Fe& b, int mb) {
  Fe d;
  fe_negate(d, b, mb);
  fe_add(d, a);
  return fe_normalizes_to_zero(d);
}

// r = a * b: the 5x52 product with the fold 2^260 == R (mod p) done
// while the columns are summed (libsecp256k1 field_5x52_int128).
// "[... c b a]" reads a + b 2^52 + c 2^104 + ...; px is column x of the
// product.  Inputs <= 8 (limbs < 2^56), result magnitude 1; r may alias
// a or b.
void fe_mul(Fe& r, const Fe& a, const Fe& b) {
  const uint64_t a0 = a.n[0], a1 = a.n[1], a2 = a.n[2], a3 = a.n[3],
                 a4 = a.n[4];
  const uint64_t b0 = b.n[0], b1 = b.n[1], b2 = b.n[2], b3 = b.n[3],
                 b4 = b.n[4];
  const uint64_t R = 0x1000003D10ULL;  // 2^260 mod p
  u128 c, d;
  uint64_t t3, t4, tx, u0;
  d = (u128)a0 * b3 + (u128)a1 * b2 + (u128)a2 * b1 + (u128)a3 * b0;
  c = (u128)a4 * b4;                      // [c 0 0 0 0 d 0 0 0] = [p8 .. p3 ..]
  d += (u128)R * (uint64_t)c; c >>= 64;   // p8's low word into column 3
  t3 = (uint64_t)d & M52; d >>= 52;
  d += (u128)a0 * b4 + (u128)a1 * b3 + (u128)a2 * b2 + (u128)a3 * b1 +
       (u128)a4 * b0;
  d += (u128)(R << 12) * (uint64_t)c;     // p8's high word into column 4
  t4 = (uint64_t)d & M52; d >>= 52;
  tx = t4 >> 48; t4 &= M48;               // bit 256 of column 4 apart
  c = (u128)a0 * b0;
  d += (u128)a1 * b4 + (u128)a2 * b3 + (u128)a3 * b2 + (u128)a4 * b1;
  u0 = (uint64_t)d & M52; d >>= 52;       // column 5
  u0 = (u0 << 4) | tx;                    // with bit 256: a multiple of 2^256
  c += (u128)u0 * (R >> 4);
  r.n[0] = (uint64_t)c & M52; c >>= 52;
  c += (u128)a0 * b1 + (u128)a1 * b0;
  d += (u128)a2 * b4 + (u128)a3 * b3 + (u128)a4 * b2;
  c += (u128)((uint64_t)d & M52) * R; d >>= 52;  // column 6 into 1
  r.n[1] = (uint64_t)c & M52; c >>= 52;
  c += (u128)a0 * b2 + (u128)a1 * b1 + (u128)a2 * b0;
  d += (u128)a3 * b4 + (u128)a4 * b3;
  c += (u128)R * (uint64_t)d; d >>= 64;   // column 7's low word into 2
  r.n[2] = (uint64_t)c & M52; c >>= 52;
  c += (u128)(R << 12) * (uint64_t)d + t3;  // and its high word into 3
  r.n[3] = (uint64_t)c & M52; c >>= 52;
  c += t4;
  r.n[4] = (uint64_t)c;
}

// r = a^2, the product above with its symmetric terms doubled once
void fe_sqr(Fe& r, const Fe& a) {
  uint64_t a0 = a.n[0], a1 = a.n[1], a2 = a.n[2], a3 = a.n[3], a4 = a.n[4];
  const uint64_t R = 0x1000003D10ULL;
  u128 c, d;
  uint64_t t3, t4, tx, u0;
  d = (u128)(a0 * 2) * a3 + (u128)(a1 * 2) * a2;
  c = (u128)a4 * a4;
  d += (u128)R * (uint64_t)c; c >>= 64;
  t3 = (uint64_t)d & M52; d >>= 52;
  a4 *= 2;
  d += (u128)a0 * a4 + (u128)(a1 * 2) * a3 + (u128)a2 * a2;
  d += (u128)(R << 12) * (uint64_t)c;
  t4 = (uint64_t)d & M52; d >>= 52;
  tx = t4 >> 48; t4 &= M48;
  c = (u128)a0 * a0;
  d += (u128)a1 * a4 + (u128)(a2 * 2) * a3;
  u0 = (uint64_t)d & M52; d >>= 52;
  u0 = (u0 << 4) | tx;
  c += (u128)u0 * (R >> 4);
  r.n[0] = (uint64_t)c & M52; c >>= 52;
  a0 *= 2;
  c += (u128)a0 * a1;
  d += (u128)a2 * a4 + (u128)a3 * a3;
  c += (u128)((uint64_t)d & M52) * R; d >>= 52;
  r.n[1] = (uint64_t)c & M52; c >>= 52;
  c += (u128)a0 * a2 + (u128)a1 * a1;
  d += (u128)a3 * a4;
  c += (u128)R * (uint64_t)d; d >>= 64;
  r.n[2] = (uint64_t)c & M52; c >>= 52;
  c += (u128)(R << 12) * (uint64_t)d + t3;
  r.n[3] = (uint64_t)c & M52; c >>= 52;
  c += t4;
  r.n[4] = (uint64_t)c;
}

// ---- modular inversion by safegcd, variable time ----
//
// Bernstein & Yang, "Fast constant-time gcd computation and modular
// inversion" (2019), in the variable-time form of libsecp256k1's
// secp256k1_modinv64_var: divsteps in batches of 62 on the low words of
// f and g, each batch's 2x2 transition matrix then applied to the full
// f, g (shrinking them by 62 bits) and to d, e (kept mod the modulus
// with a multiple chosen to clear their low 62 bits).  Numbers are five
// signed 62-bit limbs.  ~12 batches for a 256-bit input against ~500
// multiplies for Fermat's a^(m-2).
//
// Variable time is sound here: recovery runs on public data alone (the
// signature and the signed hash), never on a key.

struct S62 {
  int64_t v[5];  // sum v[i] 2^(62 i)
};

struct ModInfo {
  S62 m;           // the modulus, limbs in (-2^62, 2^62)
  uint64_t inv62;  // m^-1 mod 2^62
};

const ModInfo P_INFO = {{{-0x1000003D1LL, 0, 0, 0, 256}},
                        0x27C7F6E22DDACACFULL};
const ModInfo N_INFO = {{{-0x2DA1732FC9BEBFLL, -0x15448C6542DD7F11LL,
                          -0x14LL, 0, 256}},
                        0x34F20099AA774EC1ULL};

const uint64_t M62 = UINT64_MAX >> 2;

struct Trans {
  int64_t u, v, q, r;
};

// 62 divsteps on the low words f0 (odd) and g0; returns the new eta
// (-delta).  t maps [f, g] to 2^62 [f', g'].
int64_t divsteps_62_var(int64_t eta, uint64_t f0, uint64_t g0, Trans& t) {
  uint64_t u = 1, v = 0, q = 0, r = 1, f = f0, g = g0, m;
  uint32_t w;
  int i = 62, limit, zeros;
  for (;;) {
    // the zero bits of g all at once, a sentinel stopping at i
    zeros = __builtin_ctzll(g | (UINT64_MAX << i));
    g >>= zeros;
    u <<= zeros;
    v <<= zeros;
    eta -= zeros;
    i -= zeros;
    if (i == 0) break;
    // f and g odd here
    if (eta < 0) {  // swap to (g, -f)
      uint64_t tmp;
      eta = -eta;
      tmp = f; f = g; g = -tmp;
      tmp = u; u = q; q = -tmp;
      tmp = v; v = r; r = -tmp;
      // cancel up to 6 bits of g, no more than i nor eta + 1 of them
      limit = ((int)eta + 1) > i ? i : ((int)eta + 1);
      m = (UINT64_MAX >> (64 - limit)) & 63U;
      w = (uint32_t)((f * g * (f * f - 2)) & m);  // -g/f mod 2^6
    } else {
      // eta tends to be small here: up to 4 bits
      limit = ((int)eta + 1) > i ? i : ((int)eta + 1);
      m = (UINT64_MAX >> (64 - limit)) & 15U;
      w = (uint32_t)(f + (((f + 1) & 4) << 1));  // 1/f mod 2^4
      w = (uint32_t)((-(uint64_t)w * g) & m);
    }
    g += f * w;
    q += u * w;
    r += v * w;
  }
  t.u = (int64_t)u;
  t.v = (int64_t)v;
  t.q = (int64_t)q;
  t.r = (int64_t)r;
  return eta;
}

// [d, e] = t [d, e] / 2^62 (mod m), d and e kept in (-2m, m)
void update_de_62(S62& d, S62& e, const Trans& t, const ModInfo& mi) {
  const int64_t u = t.u, v = t.v, q = t.q, r = t.r;
  // start from [u, q] if d < 0 and [v, r] if e < 0 (keeps the range),
  // then the multiple of m that clears the low 62 bits
  const int64_t sd = d.v[4] >> 63, se = e.v[4] >> 63;
  int64_t md = (u & sd) + (v & se);
  int64_t me = (q & sd) + (r & se);
  i128 cd = (i128)u * d.v[0] + (i128)v * e.v[0];
  i128 ce = (i128)q * d.v[0] + (i128)r * e.v[0];
  md -= (int64_t)((mi.inv62 * (uint64_t)cd + (uint64_t)md) & M62);
  me -= (int64_t)((mi.inv62 * (uint64_t)ce + (uint64_t)me) & M62);
  cd += (i128)mi.m.v[0] * md;
  ce += (i128)mi.m.v[0] * me;
  cd >>= 62;
  ce >>= 62;
  for (int i = 1; i < 5; ++i) {
    cd += (i128)u * d.v[i] + (i128)v * e.v[i] + (i128)mi.m.v[i] * md;
    ce += (i128)q * d.v[i] + (i128)r * e.v[i] + (i128)mi.m.v[i] * me;
    d.v[i - 1] = (int64_t)((uint64_t)cd & M62); cd >>= 62;
    e.v[i - 1] = (int64_t)((uint64_t)ce & M62); ce >>= 62;
  }
  d.v[4] = (int64_t)cd;
  e.v[4] = (int64_t)ce;
}

// [f, g] = t [f, g] / 2^62 over their len low limbs
void update_fg_62_var(int len, S62& f, S62& g, const Trans& t) {
  const int64_t u = t.u, v = t.v, q = t.q, r = t.r;
  i128 cf = (i128)u * f.v[0] + (i128)v * g.v[0];
  i128 cg = (i128)q * f.v[0] + (i128)r * g.v[0];
  cf >>= 62;  // the low 62 bits are zero by construction
  cg >>= 62;
  for (int i = 1; i < len; ++i) {
    cf += (i128)u * f.v[i] + (i128)v * g.v[i];
    cg += (i128)q * f.v[i] + (i128)r * g.v[i];
    f.v[i - 1] = (int64_t)((uint64_t)cf & M62); cf >>= 62;
    g.v[i - 1] = (int64_t)((uint64_t)cg & M62); cg >>= 62;
  }
  f.v[len - 1] = (int64_t)cf;
  g.v[len - 1] = (int64_t)cg;
}

// r in (-2m, m), negated when sign < 0, into [0, m)
void normalize_62(S62& r, int64_t sign, const ModInfo& mi) {
  const int64_t M = (int64_t)M62;
  int64_t cond = r.v[4] >> 63;
  for (int i = 0; i < 5; ++i) r.v[i] += mi.m.v[i] & cond;
  cond = sign >> 63;
  for (int i = 0; i < 5; ++i) r.v[i] = (r.v[i] ^ cond) - cond;
  for (int i = 0; i < 4; ++i) {
    r.v[i + 1] += r.v[i] >> 62;
    r.v[i] &= M;
  }
  cond = r.v[4] >> 63;
  for (int i = 0; i < 5; ++i) r.v[i] += mi.m.v[i] & cond;
  for (int i = 0; i < 4; ++i) {
    r.v[i + 1] += r.v[i] >> 62;
    r.v[i] &= M;
  }
}

// a^-1 mod m for 0 <= a < m (0 -> 0)
void modinv_var(U256& r, const U256& a, const ModInfo& mi) {
  S62 d = {{0, 0, 0, 0, 0}}, e = {{1, 0, 0, 0, 0}}, f = mi.m;
  S62 g = {{(int64_t)(a.v[0] & M62),
            (int64_t)((a.v[0] >> 62 | a.v[1] << 2) & M62),
            (int64_t)((a.v[1] >> 60 | a.v[2] << 4) & M62),
            (int64_t)((a.v[2] >> 58 | a.v[3] << 6) & M62),
            (int64_t)(a.v[3] >> 56)}};
  int len = 5;
  int64_t eta = -1;  // -delta, delta starting at 1
  for (;;) {
    Trans t;
    eta = divsteps_62_var(eta, (uint64_t)f.v[0], (uint64_t)g.v[0], t);
    update_de_62(d, e, t, mi);
    update_fg_62_var(len, f, g, t);
    if (g.v[0] == 0) {
      int64_t cond = 0;
      for (int j = 1; j < len; ++j) cond |= g.v[j];
      if (cond == 0) break;  // g = 0: f = +-1, d = +-a^-1
    }
    // drop the top limb of f and g once both are 0 or -1
    const int64_t fn = f.v[len - 1], gn = g.v[len - 1];
    int64_t cond = ((int64_t)len - 2) >> 63;
    cond |= fn ^ (fn >> 63);
    cond |= gn ^ (gn >> 63);
    if (cond == 0) {
      f.v[len - 2] |= (int64_t)((uint64_t)fn << 62);
      g.v[len - 2] |= (int64_t)((uint64_t)gn << 62);
      --len;
    }
  }
  normalize_62(d, f.v[len - 1], mi);
  r.v[0] = (uint64_t)d.v[0] | (uint64_t)d.v[1] << 62;
  r.v[1] = (uint64_t)d.v[1] >> 2 | (uint64_t)d.v[2] << 60;
  r.v[2] = (uint64_t)d.v[2] >> 4 | (uint64_t)d.v[3] << 58;
  r.v[3] = (uint64_t)d.v[3] >> 6 | (uint64_t)d.v[4] << 56;
}

// r = a^-1 mod p (a <= 8; a == 0 -> 0), magnitude 1
void fe_inv(Fe& r, const Fe& a) {
  Fe t = a;
  fe_normalize(t);
  U256 x;
  fe_to_u256(x, t);
  modinv_var(x, x, P_INFO);
  fe_from_u256(r, x);
}

// ---- scalar arithmetic mod n ----
//
// 4x4-limb schoolbook product + fold reduction: with K = 2^256 - n
// (129 bits), hi*2^256 + lo == hi*K + lo (mod n); three folds bring any
// 512-bit value under ~2^257, then conditional subtracts finish.

// K = 2^256 - n, little-endian limbs (third limb = 1, fourth = 0)
const uint64_t ORDER_K[3] = {0x402DA1732FC9BEBFULL, 0x4551231950B75FC4ULL,
                             1ULL};

// w[0..7] = a * b (little-endian 64-bit limbs)
inline void mul_wide(uint64_t w[8], const U256& a, const U256& b) {
  for (int i = 0; i < 8; ++i) w[i] = 0;
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a.v[i] * b.v[j] + w[i + j] + carry;
      w[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    w[i + 4] += (uint64_t)carry;
  }
}

// fold an 8-limb value once: out(<= 7 limbs) = lo(4) + hi(4) * K
inline int fold_once(uint64_t out[8], const uint64_t in[8], int limbs) {
  uint64_t hiK[8] = {0};
  int hi_limbs = limbs - 4;
  for (int i = 0; i < hi_limbs; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 3; ++j) {
      u128 cur = (u128)in[4 + i] * ORDER_K[j] + hiK[i + j] + carry;
      hiK[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    int k = i + 3;
    while (carry) {
      u128 cur = (u128)hiK[k] + carry;
      hiK[k] = (uint64_t)cur;
      carry = cur >> 64;
      ++k;
    }
  }
  u128 carry = 0;
  for (int i = 0; i < 8; ++i) {
    u128 cur = (u128)hiK[i] + (i < 4 ? in[i] : 0) + carry;
    out[i] = (uint64_t)cur;
    carry = cur >> 64;
  }
  int top = 8;
  while (top > 4 && out[top - 1] == 0) --top;
  return top;
}

void sc_reduce_wide(U256& r, const uint64_t w[8]) {
  uint64_t a[8], b[8];
  int limbs = 8;
  for (int i = 0; i < 8; ++i) a[i] = w[i];
  // each fold strictly shrinks the value; 8 passes is a safe bound
  for (int pass = 0; pass < 8 && limbs > 4; ++pass) {
    limbs = fold_once(b, a, limbs);
    for (int i = 0; i < 8; ++i) a[i] = b[i];
  }
  U256 t = {{a[0], a[1], a[2], a[3]}};
  while (cmp(t, ORDER) >= 0) {
    U256 t2;
    sub_raw(t2, t, ORDER);
    t = t2;
  }
  r = t;
}

void sc_mul(U256& r, const U256& a, const U256& b) {
  uint64_t w[8];
  mul_wide(w, a, b);
  sc_reduce_wide(r, w);
}

// r = a^-1 mod n for 0 <= a < n
inline void sc_inv(U256& r, const U256& a) { modinv_var(r, a, N_INFO); }

// ---- Jacobian point arithmetic over Fe ----
//
// The formulas of the Python twin (crypto/secp256k1._jac_double /
// _jac_add), over lazily reduced coordinates.  Every function may write
// its result over an input.

struct Point {
  Fe x, y, z;  // z == 0 (mod p) => infinity; coordinates <= 4
};

struct APoint {
  Fe x, y;  // <= 2
  bool inf;
};

const Point INF = {{{0, 0, 0, 0, 0}}, FE_ONE, {{0, 0, 0, 0, 0}}};

inline bool pt_is_inf(const Point& p) { return fe_normalizes_to_zero(p.z); }

// 2P.  The group has odd prime order, so no point has y == 0: infinity
// is the only special case.  Out: X 1, Y 1, Z 2.
void pt_double(Point& r, const Point& p) {
  if (pt_is_inf(p)) {
    r = INF;
    return;
  }
  Fe ysq, s, m, t, nx, ny;
  fe_sqr(ysq, p.y);       // y^2 (1)
  fe_mul(s, p.x, ysq);
  fe_mul_int(s, 4);       // s = 4 x y^2 (4)
  fe_sqr(m, p.x);
  fe_mul_int(m, 3);       // m = 3 x^2 (3)
  fe_sqr(nx, m);          // (1)
  fe_negate(t, s, 4);     // (5)
  fe_add(nx, t);
  fe_add(nx, t);          // nx = m^2 - 2 s (11)
  fe_normalize_weak(nx);  // (1)
  fe_negate(t, nx, 1);
  fe_add(t, s);           // s - nx (6)
  fe_mul(ny, m, t);       // (1)
  fe_sqr(t, ysq);
  fe_mul_int(t, 8);       // 8 y^4 (8)
  fe_negate(t, t, 8);     // (9)
  fe_add(ny, t);          // ny = m (s - nx) - 8 y^4 (10)
  fe_normalize_weak(ny);  // (1)
  fe_mul(r.z, p.y, p.z);
  fe_mul_int(r.z, 2);     // nz = 2 y z (2)
  r.x = nx;
  r.y = ny;
}

// The addition once u1 = x1 z2^2 (<= 4), s1 = y1 z2^3 (<= 4), zz = z1 z2,
// h = u2 - u1 (<= 6, not 0) and rr = s2 - s1 (<= 6) are known.  Out:
// X 1, Y 3, Z 1.
void pt_add_finish(Point& r, const Fe& u1, const Fe& s1, const Fe& zz,
                   const Fe& h, const Fe& rr) {
  Fe hsq, hcu, v, rsq, nx, ny, t, w;
  fe_sqr(hsq, h);
  fe_mul(hcu, hsq, h);    // h^3 (1)
  fe_mul(v, u1, hsq);     // v = u1 h^2 (1)
  fe_sqr(rsq, rr);        // (1)
  fe_negate(nx, hcu, 1);
  fe_negate(t, v, 1);
  fe_mul_int(t, 2);
  fe_add(nx, t);
  fe_add(nx, rsq);        // nx = rr^2 - h^3 - 2 v (7)
  fe_negate(t, rsq, 1);
  w = v;
  fe_mul_int(w, 3);
  fe_add(t, w);
  fe_add(t, hcu);         // v - nx = 3 v + h^3 - rr^2 (6)
  fe_mul(ny, rr, t);
  fe_mul(t, s1, hcu);
  fe_negate(t, t, 1);
  fe_add(ny, t);          // ny = rr (v - nx) - s1 h^3 (3)
  fe_mul(r.z, zz, h);     // nz = z1 z2 h (1)
  fe_normalize_weak(nx);  // (1)
  r.x = nx;
  r.y = ny;
}

void pt_add(Point& r, const Point& p1, const Point& p2) {
  if (pt_is_inf(p1)) {
    r = p2;
    return;
  }
  if (pt_is_inf(p2)) {
    r = p1;
    return;
  }
  Fe z1sq, z2sq, u1, u2, s1, s2, t, h, rr;
  fe_sqr(z1sq, p1.z);
  fe_sqr(z2sq, p2.z);
  fe_mul(u1, p1.x, z2sq);
  fe_mul(u2, p2.x, z1sq);
  fe_mul(t, z2sq, p2.z);
  fe_mul(s1, p1.y, t);
  fe_mul(t, z1sq, p1.z);
  fe_mul(s2, p2.y, t);    // u1, u2, s1, s2 (1)
  fe_negate(h, u1, 1);
  fe_add(h, u2);          // (3)
  fe_negate(rr, s1, 1);
  fe_add(rr, s2);         // (3)
  if (fe_normalizes_to_zero(h)) {
    if (!fe_normalizes_to_zero(rr)) {
      r = INF;
      return;
    }
    pt_double(r, p1);
    return;
  }
  fe_mul(t, p1.z, p2.z);
  pt_add_finish(r, u1, s1, t, h, rr);
}

// p1 (Jacobian) + p2 (affine): the 8M+3S mixed addition every table
// hit uses.  Equal-x inputs degrade to pt_double / infinity exactly
// like pt_add.
void pt_add_mixed(Point& r, const Point& p1, const APoint& p2) {
  if (p2.inf) {
    r = p1;
    return;
  }
  if (pt_is_inf(p1)) {
    r = {p2.x, p2.y, FE_ONE};
    return;
  }
  Fe z1sq, u2, s2, t, h, rr;
  fe_sqr(z1sq, p1.z);
  fe_mul(u2, p2.x, z1sq);
  fe_mul(t, z1sq, p1.z);
  fe_mul(s2, p2.y, t);    // u2, s2 (1)
  fe_negate(h, p1.x, 4);
  fe_add(h, u2);          // (6)
  fe_negate(rr, p1.y, 4);
  fe_add(rr, s2);         // (6)
  if (fe_normalizes_to_zero(h)) {
    if (!fe_normalizes_to_zero(rr)) {
      r = INF;
      return;
    }
    pt_double(r, p1);
    return;
  }
  pt_add_finish(r, p1.x, p1.y, p1.z, h, rr);
}

// Shamir: k1*G + k2*Q in one double-and-add ladder.
void pt_shamir(Point& r, const U256& k1, const U256& k2, const Point& q) {
  const Point g = {fe_const(GX), fe_const(GY), FE_ONE};
  Point gq;
  pt_add(gq, g, q);
  Point acc = INF;
  for (int i = 255; i >= 0; --i) {
    pt_double(acc, acc);
    int b1 = (k1.v[i / 64] >> (i % 64)) & 1;
    int b2 = (k2.v[i / 64] >> (i % 64)) & 1;
    if (b1 && b2)
      pt_add(acc, acc, gq);
    else if (b1)
      pt_add(acc, acc, g);
    else if (b2)
      pt_add(acc, acc, q);
  }
  r = acc;
}

void load_be(U256& r, const uint8_t* p) {
  for (int i = 0; i < 4; ++i) {
    uint64_t limb = 0;
    for (int j = 0; j < 8; ++j) limb = (limb << 8) | p[(3 - i) * 8 + j];
    r.v[i] = limb;
  }
}

void store_be(uint8_t* p, const U256& a) {
  for (int i = 0; i < 4; ++i)
    for (int j = 0; j < 8; ++j)
      p[(3 - i) * 8 + j] = (uint8_t)(a.v[i] >> (56 - 8 * j));
}

// a^((p+1)/4) by addition chain: 253 squarings + 13 multiplies (the
// exponent is almost all ones).  a <= 8, result 1.  Chain verified
// against (p+1)/4 in tests.
void fe_sqrt_chain(Fe& r, const Fe& a) {
  auto sqr_n = [](Fe& x, int n) {
    for (int i = 0; i < n; ++i) fe_sqr(x, x);
  };
  Fe x2, x3, x6, x9, x11, x22, x44, x88, x176, x220, x223, t1;
  fe_sqr(x2, a);
  fe_mul(x2, x2, a);            // a^3
  fe_sqr(x3, x2);
  fe_mul(x3, x3, a);            // a^7
  x6 = x3;
  sqr_n(x6, 3);
  fe_mul(x6, x6, x3);
  x9 = x6;
  sqr_n(x9, 3);
  fe_mul(x9, x9, x3);
  x11 = x9;
  sqr_n(x11, 2);
  fe_mul(x11, x11, x2);
  x22 = x11;
  sqr_n(x22, 11);
  fe_mul(x22, x22, x11);
  x44 = x22;
  sqr_n(x44, 22);
  fe_mul(x44, x44, x22);
  x88 = x44;
  sqr_n(x88, 44);
  fe_mul(x88, x88, x44);
  x176 = x88;
  sqr_n(x176, 88);
  fe_mul(x176, x176, x88);
  x220 = x176;
  sqr_n(x220, 44);
  fe_mul(x220, x220, x44);
  x223 = x220;
  sqr_n(x223, 3);
  fe_mul(x223, x223, x3);
  t1 = x223;
  sqr_n(t1, 23);
  fe_mul(t1, t1, x22);
  sqr_n(t1, 6);
  fe_mul(t1, t1, x2);
  sqr_n(t1, 2);
  r = t1;
}

// R from (r, recid): x = r (+ n when recid & 2), y the root of x^3 + 7
// whose parity is recid & 1.  False: x past p, or x^3 + 7 no square.
bool lift_x(const U256& r, int recid, Fe& x, Fe& y) {
  U256 xr = r;
  if (recid & 2) {
    if (add_raw(xr, r, ORDER)) return false;
    if (cmp(xr, PRIME) >= 0) return false;
  }
  fe_from_u256(x, xr);
  Fe ysq, chk;
  fe_sqr(ysq, x);
  fe_mul(ysq, ysq, x);
  fe_add(ysq, FE_SEVEN);          // x^3 + 7 (2)
  fe_sqrt_chain(y, ysq);
  fe_sqr(chk, y);
  if (!fe_equal(chk, ysq, 2)) return false;
  fe_normalize(y);
  if ((y.n[0] & 1) != (uint64_t)(recid & 1)) fe_negate(y, y, 1);  // (2)
  return true;
}

// out20 = the address of affine (x, y): keccak of the 64-byte key, low
// 20 bytes
void address_of(const Fe& x, const Fe& y, uint8_t* out20) {
  Fe nx = x, ny = y;
  fe_normalize(nx);
  fe_normalize(ny);
  U256 ax, ay;
  fe_to_u256(ax, nx);
  fe_to_u256(ay, ny);
  uint8_t pub[64], digest[32];
  store_be(pub, ax);
  store_be(pub + 32, ay);
  coreth_keccak256(pub, 64, digest);
  std::memcpy(out20, digest + 12, 20);
}

// ---- batch-only fast recovery (coreth_ecrecover_batch) ----
//
// The sequential coreth_ecrecover below is the native baseline's
// primitive (one Shamir ladder per call) and the batch's fallback.  The
// batch entry point amortizes what a per-call API cannot:
//   - u1*G via a once-built 32x255 affine comb table (8-bit windows):
//     32 mixed additions, zero doublings, per signature;
//   - u2*R via the GLV endomorphism (R -> (beta*x, y) realizes
//     scalar lambda): u2 splits into two ~128-bit halves, halving the
//     ladder doublings; each half walks a wNAF(5) over the
//     signature's odd-multiple table;
//   - ONE scalar inversion for every r^-1 and ONE field inversion for
//     every Jacobian->affine conversion (Montgomery batch trick), and
//     one shared batch normalization of all wNAF tables so the ladder
//     runs on mixed (affine) additions.
// Every GLV split is verified on the spot (k1 + k2*lambda == k mod n
// and both halves < 2^129); any mismatch — and any signature the fast
// path cannot finish — falls back to coreth_ecrecover for that index,
// which answers ok = 2 when it recovers the key: a constant or carry
// bug degrades to the slow path, never to a wrong address, and is
// counted (ReplayStats.sigs_slow_path).  CORETH_FAST_RECOVER=0 forces
// the per-signature fallback everywhere (the A/B and bisection knob).

// lambda/beta: the cube roots of 1 realizing the curve endomorphism
// (x, y) -> (beta*x, y) == lambda * P; lattice basis and the rounded
// 384-bit division constants g1/g2 are the standard secp256k1 values
// (verified exhaustively against the Python twin in tests).
const U256 GLV_LAMBDA = {{0xDF02967C1B23BD72ULL, 0x122E22EA20816678ULL,
                          0xA5261C028812645AULL, 0x5363AD4CC05C30E0ULL}};
const Fe GLV_BETA = fe_const({{0xC1396C28719501EEULL, 0x9CF0497512F58995ULL,
                               0x6E64479EAC3434E9ULL,
                               0x7AE96A2B657C0710ULL}});
// a1 == b2 (128 bits), B1 == -b1 (128 bits), a2 (129 bits)
const U256 GLV_A1 = {{0xE86C90E49284EB15ULL, 0x3086D221A7D46BCDULL, 0, 0}};
const U256 GLV_B1 = {{0x6F547FA90ABFE4C3ULL, 0xE4437ED6010E8828ULL, 0, 0}};
const U256 GLV_A2 = {{0x57C1108D9D44CFD8ULL, 0x14CA50F7A8E2F3F6ULL,
                      1ULL, 0}};
// g1 = round(2^384 * b2 / n), g2 = round(2^384 * (-b1) / n)
const U256 GLV_G1 = {{0xE893209A45DBB031ULL, 0x3DAA8A1471E8CA7FULL,
                      0xE86C90E49284EB15ULL, 0x3086D221A7D46BCDULL}};
const U256 GLV_G2 = {{0x1571B4AE8AC47F71ULL, 0x221208AC9DF506C6ULL,
                      0x6F547FA90ABFE4C4ULL, 0xE4437ED6010E8828ULL}};

// Normalize Jacobian points to affine with ONE field inversion
// (Montgomery prefix products).  Infinity rows come back inf; the
// others magnitude 1.
void batch_to_affine(const Point* pts, APoint* out, size_t n) {
  std::vector<Fe> prefix(n);
  std::vector<size_t> live;
  live.reserve(n);
  Fe acc = FE_ONE;
  for (size_t i = 0; i < n; ++i) {
    out[i].inf = pt_is_inf(pts[i]);
    if (out[i].inf) continue;
    fe_mul(acc, acc, pts[i].z);
    prefix[i] = acc;
    live.push_back(i);
  }
  if (live.empty()) return;
  Fe inv;
  fe_inv(inv, acc);
  for (size_t k = live.size(); k-- > 0;) {
    size_t i = live[k];
    Fe zinv, zi2, t;
    if (k == 0) {
      zinv = inv;
    } else {
      fe_mul(zinv, inv, prefix[live[k - 1]]);
    }
    fe_mul(inv, inv, pts[i].z);
    fe_sqr(zi2, zinv);
    fe_mul(out[i].x, pts[i].x, zi2);
    fe_mul(t, zi2, zinv);
    fe_mul(out[i].y, pts[i].y, t);
  }
}

// u1*G comb: TBL[w][v-1] = v * 2^(8w) * G, affine, one 64-byte line an
// entry (4x64 limbs, converted at the lookup).  522KB, built once under
// std::call_once on first batch call (the warm replay rep pays it,
// like an XLA compile).
constexpr int COMB_WINDOWS = 32;
constexpr int COMB_VALS = 255;
struct alignas(64) CombPt {
  U256 x, y;
};
std::vector<CombPt> g_comb;
std::once_flag g_comb_once;

void build_g_comb() {
  std::vector<Point> jac(COMB_WINDOWS * COMB_VALS);
  Point base = {fe_const(GX), fe_const(GY), FE_ONE};
  for (int w = 0; w < COMB_WINDOWS; ++w) {
    jac[w * COMB_VALS] = base;
    for (int v = 2; v <= COMB_VALS; ++v)
      pt_add(jac[w * COMB_VALS + v - 1], jac[w * COMB_VALS + v - 2],
             base);
    for (int d = 0; d < 8; ++d) pt_double(base, base);
  }
  std::vector<APoint> aff(jac.size());
  batch_to_affine(jac.data(), aff.data(), jac.size());
  g_comb.resize(jac.size());
  for (size_t i = 0; i < aff.size(); ++i) {
    fe_normalize(aff[i].x);
    fe_normalize(aff[i].y);
    fe_to_u256(g_comb[i].x, aff[i].x);
    fe_to_u256(g_comb[i].y, aff[i].y);
  }
}

// c = round((k * g) / 2^384): the mulhi step of the GLV division.
// k, g < 2^256 so c < 2^128 — two limbs.
inline void glv_mulhi(uint64_t c[2], const U256& k, const U256& g) {
  uint64_t w[8];
  mul_wide(w, k, g);
  uint64_t lo = w[6], hi = w[7];
  if (w[5] >> 63) {  // round up on bit 383
    if (++lo == 0) ++hi;
  }
  c[0] = lo;
  c[1] = hi;
}

// r = a*b for 128-bit a (two limbs) x up-to-129-bit b; result < 2^258
// fits U256 for our constants (|k1|,|k2| construction keeps every
// product near 2^256; overflow would fail the split check and fall
// back).  Returns the carry out of limb 3 so the caller can reject.
inline uint64_t mul_128_u256(U256& r, const uint64_t a[2], const U256& b) {
  uint64_t w[6] = {0};
  for (int i = 0; i < 2; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a[i] * b.v[j] + w[i + j] + carry;
      w[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    w[i + 4] += (uint64_t)carry;
  }
  r = {{w[0], w[1], w[2], w[3]}};
  return w[4] | w[5];
}

// Split k = k1 + k2*lambda (mod n) with |k1|,|k2| < 2^129.  Magnitudes
// and signs come back separately; returns false (caller falls back to
// the sequential path) if the self-check k1 + k2*lambda == k fails or
// a magnitude exceeds 129 bits.
bool glv_split(const U256& k, U256& k1, int& s1, U256& k2, int& s2) {
  uint64_t c1[2], c2[2];
  glv_mulhi(c1, k, GLV_G1);
  glv_mulhi(c2, k, GLV_G2);
  U256 t1, t2, sum;
  if (mul_128_u256(t1, c1, GLV_A1)) return false;
  if (mul_128_u256(t2, c2, GLV_A2)) return false;
  if (add_raw(sum, t1, t2)) return false;
  if (sub_raw(k1, k, sum)) {  // negative: magnitude is sum - k
    U256 m;
    sub_raw(m, sum, k);
    k1 = m;
    s1 = -1;
  } else {
    s1 = 1;
  }
  U256 u, v;
  if (mul_128_u256(u, c1, GLV_B1)) return false;  // c1 * (-b1)
  if (mul_128_u256(v, c2, GLV_A1)) return false;  // c2 * b2
  if (cmp(u, v) >= 0) {
    sub_raw(k2, u, v);
    s2 = 1;
  } else {
    sub_raw(k2, v, u);
    s2 = -1;
  }
  // both halves must fit 129 bits for the wNAF ladder length
  if ((k1.v[3] | k2.v[3]) || (k1.v[2] >> 1) || (k2.v[2] >> 1))
    return false;
  // self-check mod n: (±k1) + (±k2)*lambda == k
  U256 k1m = k1, k2m = k2, chk;
  if (s1 < 0 && !is_zero(k1)) sub_raw(k1m, ORDER, k1);
  if (s2 < 0 && !is_zero(k2)) sub_raw(k2m, ORDER, k2);
  sc_mul(chk, k2m, GLV_LAMBDA);
  mod_add(chk, chk, k1m, ORDER);
  return cmp(chk, k) == 0;
}

// wNAF(5): digits in {0, ±1, ±3, ..., ±15}, at most 131 of them for a
// 129-bit magnitude.  Returns the digit count.
int wnaf5(int8_t* digits, const U256& mag) {
  U256 k = mag;
  int len = 0;
  while (!is_zero(k)) {
    int8_t d = 0;
    if (k.v[0] & 1) {
      int w = (int)(k.v[0] & 31);
      d = (int8_t)(w > 16 ? w - 32 : w);
      // k -= d
      U256 dd = {{(uint64_t)(d < 0 ? -d : d), 0, 0, 0}};
      U256 t;
      if (d > 0) {
        sub_raw(t, k, dd);
      } else {
        add_raw(t, k, dd);
      }
      k = t;
    }
    digits[len++] = d;
    // k >>= 1
    for (int i = 0; i < 4; ++i) {
      k.v[i] >>= 1;
      if (i < 3) k.v[i] |= k.v[i + 1] << 63;
    }
  }
  return len;
}

// Everything the fast path precomputes per signature before the
// shared batch-normalization barrier.
struct FastSig {
  U256 u1, u2;          // -z/r, s/r mod n
  U256 k1, k2;          // |GLV halves| of u2
  int s1, s2;           // their signs
  Point tbl[8];         // {1,3,...,15} * R, Jacobian (then affine)
};

// z mod n for a 256-bit hash (z < 2n)
inline void reduce_hash(U256& z) {
  if (cmp(z, ORDER) >= 0) sub_raw(z, z, ORDER);
}

// One signature's validation + R + scalars; rinv comes from the batch
// inversion.  Returns false -> caller routes index to the fallback.
bool fast_prep(const uint8_t* hash32, const uint8_t* s32, const U256& r,
               const U256& rinv, int recid, FastSig& fs) {
  U256 s, z;
  load_be(s, s32);
  load_be(z, hash32);
  Point rpt;
  rpt.z = FE_ONE;
  if (!lift_x(r, recid, rpt.x, rpt.y)) return false;
  reduce_hash(z);
  sc_mul(fs.u1, z, rinv);
  if (!is_zero(fs.u1)) mod_sub(fs.u1, ORDER, fs.u1, ORDER);
  sc_mul(fs.u2, s, rinv);
  if (!glv_split(fs.u2, fs.k1, fs.s1, fs.k2, fs.s2)) return false;
  // odd multiples of R
  Point d2;
  pt_double(d2, rpt);
  fs.tbl[0] = rpt;
  for (int i = 1; i < 8; ++i) pt_add(fs.tbl[i], fs.tbl[i - 1], d2);
  return true;
}

// The per-signature ladder over affine tables: two wNAF halves of
// u2*R (the second through the beta endomorphism), then the u1*G comb
// — no doublings past the 129 shared ones.
void fast_ladder(Point& acc, const FastSig& fs, const APoint* tbl_aff) {
  int8_t d1[132], d2[132];
  int l1 = wnaf5(d1, fs.k1);
  int l2 = wnaf5(d2, fs.k2);
  int len = l1 > l2 ? l1 : l2;
  acc = INF;
  for (int i = len - 1; i >= 0; --i) {
    pt_double(acc, acc);
    if (i < l1 && d1[i]) {
      int8_t d = d1[i];
      bool neg = (d < 0) != (fs.s1 < 0);
      APoint p = tbl_aff[(d < 0 ? -d : d) >> 1];
      if (neg && !p.inf) fe_negate(p.y, p.y, 1);  // (2)
      pt_add_mixed(acc, acc, p);
    }
    if (i < l2 && d2[i]) {
      int8_t d = d2[i];
      bool neg = (d < 0) != (fs.s2 < 0);
      APoint p = tbl_aff[(d < 0 ? -d : d) >> 1];
      if (!p.inf) {
        fe_mul(p.x, p.x, GLV_BETA);  // phi: (x,y) -> (beta x, y)
        if (neg) fe_negate(p.y, p.y, 1);
      }
      pt_add_mixed(acc, acc, p);
    }
  }
  for (int w = 0; w < COMB_WINDOWS; ++w) {
    int v = (int)((fs.u1.v[w / 8] >> (8 * (w % 8))) & 0xFF);
    if (!v) continue;
    const CombPt& c = g_comb[w * COMB_VALS + v - 1];
    APoint p;
    fe_from_u256(p.x, c.x);
    fe_from_u256(p.y, c.y);
    p.inf = false;
    pt_add_mixed(acc, acc, p);
  }
}

// Fast batch over [lo, hi): shared r^-1 batch inversion, shared wNAF
// table normalization, per-signature ladders, shared final affine
// conversion.  Each index the fast path cannot carry falls back to
// the sequential coreth_ecrecover, and is marked ok = 2 if that
// recovers it.
void fast_recover_range(const uint8_t* hashes, const uint8_t* rs,
                        const uint8_t* ss, const uint8_t* recids,
                        uint64_t lo, uint64_t hi, uint8_t* out,
                        uint8_t* ok) {
  std::call_once(g_comb_once, build_g_comb);
  const uint64_t n = hi - lo;
  std::vector<U256> r_l(n), prefix(n);
  std::vector<uint64_t> live;
  live.reserve(n);
  std::vector<uint8_t> state(n, 0);  // 0 invalid, 1 fast, 2 fallback
  U256 acc = ONE;
  for (uint64_t j = 0; j < n; ++j) {
    uint64_t i = lo + j;
    ok[i] = 0;
    U256 r, s;
    load_be(r, rs + 32 * i);
    load_be(s, ss + 32 * i);
    if (recids[i] > 3 || is_zero(r) || is_zero(s)) continue;
    if (cmp(r, ORDER) >= 0 || cmp(s, ORDER) >= 0) continue;
    r_l[j] = r;
    state[j] = 1;
    sc_mul(acc, acc, r);
    prefix[j] = acc;
    live.push_back(j);
  }
  std::vector<FastSig> sigs(n);
  if (!live.empty()) {
    U256 inv;
    sc_inv(inv, acc);
    for (size_t k = live.size(); k-- > 0;) {
      uint64_t j = live[k];
      uint64_t i = lo + j;
      U256 rinv;
      if (k == 0) {
        rinv = inv;
      } else {
        sc_mul(rinv, inv, prefix[live[k - 1]]);
      }
      sc_mul(inv, inv, r_l[j]);
      if (!fast_prep(hashes + 32 * i, ss + 32 * i, r_l[j], rinv,
                     recids[i], sigs[j]))
        state[j] = 2;  // residue failures land here too; fallback
                       // re-checks and reports ok=0 for those
    }
  }
  // one affine normalization across every signature's wNAF table
  std::vector<Point> flat;
  flat.reserve(8 * n);
  for (uint64_t j = 0; j < n; ++j)
    if (state[j] == 1)
      for (int v = 0; v < 8; ++v) flat.push_back(sigs[j].tbl[v]);
  std::vector<APoint> flat_aff(flat.size());
  batch_to_affine(flat.data(), flat_aff.data(), flat.size());
  // ladders; results collect for one final batch affine conversion
  std::vector<Point> res(n, INF);
  size_t cursor = 0;
  for (uint64_t j = 0; j < n; ++j) {
    if (state[j] != 1) continue;
    fast_ladder(res[j], sigs[j], flat_aff.data() + cursor);
    cursor += 8;
    if (pt_is_inf(res[j])) state[j] = 0;
  }
  std::vector<APoint> res_aff(n);
  batch_to_affine(res.data(), res_aff.data(), n);
  for (uint64_t j = 0; j < n; ++j) {
    uint64_t i = lo + j;
    if (state[j] == 2) {
      ok[i] = coreth_ecrecover(hashes + 32 * i, rs + 32 * i, ss + 32 * i,
                               recids[i], out + 20 * i)
                  ? 2
                  : 0;
      continue;
    }
    if (state[j] != 1 || res_aff[j].inf) continue;
    address_of(res_aff[j].x, res_aff[j].y, out + 20 * i);
    ok[i] = 1;
  }
}

bool fast_recover_disabled() {
  const char* v = std::getenv("CORETH_FAST_RECOVER");
  return v && v[0] == '0' && v[1] == '\0';
}

// fn(lo, hi) over [0, n): on the calling thread under 16 signatures a
// hardware thread, else in contiguous chunks (not strides: each worker
// runs its own batch inversions over a dense range), a thread each.
template <class Fn>
void in_chunks(uint64_t n, Fn fn) {
  unsigned nthreads = std::thread::hardware_concurrency();
  if (nthreads < 2 || n < 16 * nthreads) {
    fn(0, n);
    return;
  }
  std::vector<std::thread> workers;
  workers.reserve(nthreads);
  uint64_t chunk = (n + nthreads - 1) / nthreads;
  for (unsigned w = 0; w < nthreads; ++w) {
    uint64_t lo = (uint64_t)w * chunk;
    uint64_t hi = lo + chunk < n ? lo + chunk : n;
    if (lo >= hi) break;
    workers.emplace_back([=]() { fn(lo, hi); });
  }
  for (auto& t : workers) t.join();
}

// ---- signing hashes from the wire bytes (coreth_recover_wire) ----
//
// A transaction's wire encoding holds everything its sender is
// recovered from: v, r, s are the last three items of its list, and the
// unsigned payload the signing hash is made of is the items before
// them — ONE contiguous span of the wire, hashed under a fresh list
// header (after the type byte of a typed transaction; before the chain
// id and two empty items of an EIP-155 one).  The walk goes by lengths
// alone: nothing is decoded or built, an access list is stepped over
// and never entered.  It refuses what the Python decoder refuses
// (rlp.payload_span, rlp.decode_uint): an item that is truncated or
// runs past its list, a long-form length where the short form fits, a
// wrapped single byte under 0x80, an integer with a leading zero byte.
// So a lane answered here hashed, bit for bit, what signer.sig_hash
// hashes for the decoded transaction, and every other lane is left to
// the per-transaction path (types/transaction.py LatestSigner.sender).

struct RlpItem {
  const uint8_t* at;       // its prefix
  const uint8_t* payload;
  uint64_t len;            // of the payload
  bool list;
};

// The item whose prefix is at p and which has to end by `end`.
bool rlp_item(const uint8_t* p, const uint8_t* end, RlpItem& it) {
  if (p >= end) return false;
  const uint8_t b0 = *p;
  it.at = p;
  it.list = b0 >= 0xC0;
  if (b0 < 0x80) {
    it.payload = p;
    it.len = 1;
    return true;
  }
  // strings from 0x80 and lists from 0xC0 share the low six bits: a
  // payload length up to 55, or 55 + the width of a length that follows
  const unsigned low = b0 & 0x3F;
  if (low < 56) {
    it.payload = p + 1;
    it.len = low;
  } else {
    const unsigned width = low - 55;
    if ((uint64_t)(end - p - 1) < width || p[1] == 0) return false;
    uint64_t len = 0;
    for (unsigned i = 0; i < width; ++i) len = (len << 8) | p[1 + i];
    if (len < 56) return false;
    it.payload = p + 1 + width;
    it.len = len;
  }
  if ((uint64_t)(end - it.payload) < it.len) return false;
  return !(b0 == 0x81 && *it.payload < 0x80);
}

inline bool rlp_is_uint(const RlpItem& it) {
  return !it.list && (it.len == 0 || it.payload[0] != 0);
}

// An integer item that fits 64 bits.
bool rlp_u64(const RlpItem& it, uint64_t& v) {
  if (!rlp_is_uint(it) || it.len > 8) return false;
  v = 0;
  for (uint64_t i = 0; i < it.len; ++i) v = (v << 8) | it.payload[i];
  return true;
}

// v big-endian with no leading zero byte (nothing for 0); the count.
unsigned put_be(uint8_t* out, uint64_t v) {
  unsigned n = 0;
  for (uint64_t t = v; t; t >>= 8) ++n;
  for (unsigned i = 0; i < n; ++i) out[i] = (uint8_t)(v >> (8 * (n - 1 - i)));
  return n;
}

// The RLP header of a list whose payload is `len` bytes.  At most 9.
unsigned rlp_put_list_header(uint8_t* out, uint64_t len) {
  if (len < 56) {
    out[0] = (uint8_t)(0xC0 + len);
    return 1;
  }
  unsigned n = put_be(out + 1, len);
  out[0] = (uint8_t)(0xF7 + n);
  return 1 + n;
}

// An integer as an RLP item.  At most 9 bytes.
unsigned rlp_put_u64(uint8_t* out, uint64_t v) {
  if (v && v < 0x80) {
    out[0] = (uint8_t)v;
    return 1;
  }
  unsigned n = put_be(out + 1, v);
  out[0] = (uint8_t)(0x80 + n);
  return 1 + n;
}

// Items of a transaction's list by type: how many, which one is the
// access list (-1: none) and which two are byte strings of any content
// (to, data); every other item is an integer.
struct TxLayout {
  int items, access_list, to, data;
};
const TxLayout LEGACY_TX = {9, -1, 3, 5};
const TxLayout ACCESS_LIST_TX = {11, 7, 4, 6};
const TxLayout DYNAMIC_FEE_TX = {12, 8, 5, 7};

// n / 2: the largest s of a canonical (low-s, EIP-2) signature
U256 order_half() {
  U256 h;
  for (int i = 0; i < 4; ++i)
    h.v[i] = (ORDER.v[i] >> 1) | (i < 3 ? ORDER.v[i + 1] << 63 : 0);
  return h;
}
const U256 HALF_ORDER = order_half();

// r or s, at most 32 bytes and not 0 (the walk has already refused a
// leading zero byte): as a 32-byte big-endian field and as a number.
bool sig_scalar(const RlpItem& it, uint8_t* out32, U256& x) {
  if (it.len == 0 || it.len > 32) return false;
  std::memset(out32, 0, 32);
  std::memcpy(out32 + 32 - it.len, it.payload, it.len);
  load_be(x, out32);
  return true;
}

// One transaction's signing hash, r, s and recovery id from its wire
// bytes [p, end), by the rules of LatestSigner(chain_id); false leaves
// the transaction to the per-transaction path.  `pre` is scratch for
// the hash's preimage, kept between transactions.
bool wire_sig(const uint8_t* p, const uint8_t* end, uint64_t chain_id,
              std::vector<uint8_t>& pre, uint8_t* hash32, uint8_t* r32,
              uint8_t* s32, uint8_t* recid) {
  if (p >= end) return false;
  const uint8_t type = *p;
  const TxLayout* lay;
  if (type >= 0xC0) {
    lay = &LEGACY_TX;
  } else if (type == 0x01 || type == 0x02) {
    lay = type == 0x01 ? &ACCESS_LIST_TX : &DYNAMIC_FEE_TX;
    ++p;
  } else {
    return false;
  }
  RlpItem outer, it[12];
  if (!rlp_item(p, end, outer) || !outer.list ||
      outer.payload + outer.len != end)
    return false;
  const uint8_t* q = outer.payload;
  for (int k = 0; k < lay->items; ++k) {
    if (!rlp_item(q, end, it[k])) return false;
    if (k == lay->access_list) {
      if (!it[k].list) return false;
    } else if (k == lay->to || k == lay->data) {
      if (it[k].list) return false;
    } else if (!rlp_is_uint(it[k])) {
      return false;
    }
    q = it[k].payload + it[k].len;
  }
  if (q != end) return false;
  const RlpItem& v_item = it[lay->items - 3];
  uint64_t v;
  if (!rlp_u64(v_item, v)) return false;
  uint8_t tail[11];  // hashed after the span: EIP-155's three items
  unsigned tail_len = 0;
  if (lay == &LEGACY_TX) {
    if (v == 27 || v == 28) {
      *recid = (uint8_t)(v - 27);
    } else {  // EIP-155: v = 35 + 2 x chain id + {0, 1}
      if (chain_id > (UINT64_MAX - 36) / 2) return false;
      const uint64_t v0 = 35 + 2 * chain_id;
      if (v != v0 && v != v0 + 1) return false;
      *recid = (uint8_t)(v - v0);
      tail_len = rlp_put_u64(tail, chain_id);
      tail[tail_len++] = 0x80;
      tail[tail_len++] = 0x80;
    }
  } else {
    uint64_t tx_chain;
    if (!rlp_u64(it[0], tx_chain) || tx_chain != chain_id || v > 1)
      return false;
    *recid = (uint8_t)v;
  }
  U256 r, s;
  if (!sig_scalar(it[lay->items - 2], r32, r) ||
      !sig_scalar(it[lay->items - 1], s32, s) ||
      cmp(r, ORDER) >= 0 || cmp(s, HALF_ORDER) > 0)
    return false;
  const uint64_t span = (uint64_t)(v_item.at - outer.payload);
  uint8_t head[10];
  unsigned head_len = 0;
  if (lay != &LEGACY_TX) head[head_len++] = type;
  head_len += rlp_put_list_header(head + head_len, span + tail_len);
  pre.clear();
  pre.insert(pre.end(), head, head + head_len);
  pre.insert(pre.end(), outer.payload, v_item.at);
  pre.insert(pre.end(), tail, tail + tail_len);
  coreth_keccak256(pre.data(), pre.size(), hash32);
  return true;
}

}  // namespace

extern "C" {

// Recover the 20-byte address from (msg_hash, r, s, recid).
// Returns 1 on success, 0 on invalid signature.
int coreth_ecrecover(const uint8_t* hash32, const uint8_t* r32,
                     const uint8_t* s32, int recid, uint8_t* out20) {
  if (recid < 0 || recid > 3) return 0;
  U256 r, s, z;
  load_be(r, r32);
  load_be(s, s32);
  load_be(z, hash32);
  if (is_zero(r) || is_zero(s)) return 0;
  if (cmp(r, ORDER) >= 0 || cmp(s, ORDER) >= 0) return 0;
  Point q;
  q.z = FE_ONE;
  if (!lift_x(r, recid, q.x, q.y)) return 0;  // non-residue: invalid r
  // u1 = -z/r mod n ; u2 = s/r mod n
  U256 rinv, u1, u2;
  reduce_hash(z);
  sc_inv(rinv, r);
  sc_mul(u1, z, rinv);
  if (!is_zero(u1)) mod_sub(u1, ORDER, u1, ORDER);
  sc_mul(u2, s, rinv);
  Point res;
  pt_shamir(res, u1, u2, q);
  if (pt_is_inf(res)) return 0;
  // to affine
  Fe zinv, zinv2, ax, ay, t;
  fe_inv(zinv, res.z);
  fe_sqr(zinv2, zinv);
  fe_mul(ax, res.x, zinv2);
  fe_mul(t, zinv2, zinv);
  fe_mul(ay, res.y, t);
  address_of(ax, ay, out20);
  return 1;
}

// Host-side prep for the DEVICE recovery kernel (crypto/secp_device):
// validates ranges, computes x = r (+n) and the scalars
// u1 = -z/r, u2 = s/r mod n with ONE Montgomery batch inversion.
// Outputs: xs 33-byte LE each, u1/u2 32-byte LE each, ok bytes.
// Keeps the Python driver off the critical path (bigint modmuls).
void coreth_recover_prep(const uint8_t* hashes, const uint8_t* rs,
                         const uint8_t* ss, const uint8_t* recids,
                         uint64_t n, uint8_t* xs_le33, uint8_t* u1_le32,
                         uint8_t* u2_le32, uint8_t* ok) {
  std::vector<U256> r_l(n), prefix(n);
  std::vector<uint64_t> live;
  live.reserve(n);
  U256 acc = ONE;
  std::memset(xs_le33, 0, 33 * n);
  std::memset(u1_le32, 0, 32 * n);
  std::memset(u2_le32, 0, 32 * n);
  for (uint64_t i = 0; i < n; ++i) {
    ok[i] = 0;
    U256 r, s;
    load_be(r, rs + 32 * i);
    load_be(s, ss + 32 * i);
    r_l[i] = r;
    if (recids[i] > 3 || is_zero(r) || is_zero(s)) continue;
    if (cmp(r, ORDER) >= 0 || cmp(s, ORDER) >= 0) continue;
    U256 x = r;
    if (recids[i] & 2) {
      if (add_raw(x, r, ORDER)) continue;
      if (cmp(x, PRIME) >= 0) continue;
    }
    // store x as 33-byte little-endian
    uint8_t be[32];
    store_be(be, x);
    for (int j = 0; j < 32; ++j) xs_le33[33 * i + j] = be[31 - j];
    ok[i] = 1;
    sc_mul(acc, acc, r);
    prefix[i] = acc;
    live.push_back(i);
  }
  if (live.empty()) return;
  U256 inv;
  sc_inv(inv, acc);
  for (size_t k = live.size(); k-- > 0;) {
    uint64_t i = live[k];
    U256 rinv;
    if (k == 0) {
      rinv = inv;
    } else {
      sc_mul(rinv, inv, prefix[live[k - 1]]);
    }
    sc_mul(inv, inv, r_l[i]);
    // u2 = s/r ; u1 = -(z/r)
    U256 s, z, u1, u2;
    load_be(s, ss + 32 * i);
    load_be(z, hashes + 32 * i);
    reduce_hash(z);
    sc_mul(u2, s, rinv);
    sc_mul(u1, z, rinv);
    if (!is_zero(u1)) sub_raw(u1, ORDER, u1);
    uint8_t be[32];
    store_be(be, u1);
    for (int j = 0; j < 32; ++j) u1_le32[32 * i + j] = be[31 - j];
    store_be(be, u2);
    for (int j = 0; j < 32; ++j) u2_le32[32 * i + j] = be[31 - j];
  }
}

// Finish for the device kernel: rows = X(33)||Y(33)||Z(33)||flags(3)
// little-endian Jacobian coordinates (102 bytes/row).  Batch-inverts Z
// mod p, converts to affine, keccaks to addresses.  Rows whose flags
// mark a ladder doubling-collision get ok=2 so the Python driver can
// re-run them on the exact path.
void coreth_recover_finish(const uint8_t* rows, uint64_t n,
                           const uint8_t* ok_in, uint8_t* out20,
                           uint8_t* ok) {
  auto load_le33 = [](Fe& v, const uint8_t* p) {
    uint8_t be[32];
    for (int j = 0; j < 32; ++j) be[j] = p[31 - j];
    U256 u;
    load_be(u, be);
    fe_from_u256(v, u);
  };
  std::vector<Point> pts;
  std::vector<uint64_t> fin;
  pts.reserve(n);
  fin.reserve(n);
  for (uint64_t i = 0; i < n; ++i) {
    ok[i] = 0;
    const uint8_t* row = rows + 102 * i;
    uint8_t inf = row[99], bad = row[100], residue = row[101];
    if (!ok_in[i] || !residue) continue;
    if (bad) {
      ok[i] = 2;  // caller re-runs on the exact host path
      continue;
    }
    if (inf) continue;
    Point p;
    load_le33(p.x, row);
    load_le33(p.y, row + 33);
    load_le33(p.z, row + 66);
    pts.push_back(p);
    fin.push_back(i);
  }
  std::vector<APoint> aff(pts.size());
  batch_to_affine(pts.data(), aff.data(), pts.size());
  for (size_t k = 0; k < fin.size(); ++k) {
    if (aff[k].inf) continue;
    address_of(aff[k].x, aff[k].y, out20 + 20 * fin[k]);
    ok[fin[k]] = 1;
  }
}

// Test hook over raw 5x52 limbs, so tests/test_secp_field.py (and
// test_crypto.py's carry-band regression) can hold every field operation
// to Python integers at the magnitudes the point formulas reach (no cell
// calls it).  op: 0 mul, 1 sqr, 2 add,
// 3 negate(a, k), 4 mul_int(a, k), 5 normalize_weak, 6 normalize,
// 7 normalizes_to_zero (out[0] = 0 / 1), 8 inv, 9 sqrt chain.
void coreth_test_fe_op(int op, const uint64_t* a5, const uint64_t* b5,
                       int k, uint64_t* out5) {
  Fe a, b, r = {{0, 0, 0, 0, 0}};
  std::memcpy(a.n, a5, sizeof a.n);
  std::memcpy(b.n, b5, sizeof b.n);
  switch (op) {
    case 0: fe_mul(r, a, b); break;
    case 1: fe_sqr(r, a); break;
    case 2: r = a; fe_add(r, b); break;
    case 3: fe_negate(r, a, k); break;
    case 4: r = a; fe_mul_int(r, k); break;
    case 5: r = a; fe_normalize_weak(r); break;
    case 6: r = a; fe_normalize(r); break;
    case 7: r.n[0] = fe_normalizes_to_zero(a); break;
    case 8: fe_inv(r, a); break;
    case 9: fe_sqrt_chain(r, a); break;
  }
  std::memcpy(out5, r.n, sizeof r.n);
}

// Test hook: a^-1 mod n over big-endian 32-byte operands (a < n).
void coreth_test_sc_inv(const uint8_t* a32, uint8_t* out32) {
  U256 a, r;
  load_be(a, a32);
  sc_inv(r, a);
  store_be(out32, r);
}

// Batched recovery: packed 32-byte hashes / r / s, recid bytes.
// out: packed 20-byte addresses; ok[i] = 1 on success, 2 when the
// sequential fallback recovered what the fast path could not (correct,
// and a fault of the fast path to be counted; 0 on every valid chain).
// Strided across hardware threads — the C++ twin of the reference's
// GOMAXPROCS sender cacher (core/sender_cacher.go:49-80).  Degenerates
// to the sequential loop on single-core hosts.
void coreth_ecrecover_batch(const uint8_t* hashes, const uint8_t* rs,
                            const uint8_t* ss, const uint8_t* recids,
                            uint64_t n, uint8_t* out, uint8_t* ok) {
  if (fast_recover_disabled()) {
    // A/B knob: the sequential per-signature loop (striding threads
    // kept for multi-core hosts — the pre-PR-13 shape)
    unsigned nthreads = std::thread::hardware_concurrency();
    if (nthreads < 2 || n < 2 * nthreads) {
      for (uint64_t i = 0; i < n; ++i)
        ok[i] = (uint8_t)coreth_ecrecover(hashes + 32 * i, rs + 32 * i,
                                          ss + 32 * i, recids[i],
                                          out + 20 * i);
      return;
    }
    std::vector<std::thread> workers;
    workers.reserve(nthreads);
    for (unsigned w = 0; w < nthreads; ++w) {
      workers.emplace_back([=]() {
        for (uint64_t i = w; i < n; i += nthreads)
          ok[i] = (uint8_t)coreth_ecrecover(hashes + 32 * i,
                                            rs + 32 * i, ss + 32 * i,
                                            recids[i], out + 20 * i);
      });
    }
    for (auto& t : workers) t.join();
    return;
  }
  in_chunks(n, [=](uint64_t lo, uint64_t hi) {
    fast_recover_range(hashes, rs, ss, recids, lo, hi, out, ok);
  });
}

// Batched recovery from the transactions' wire encodings, laid end to
// end in `wire` and cut by offsets[n + 1].  Each chunk's thread derives
// its lanes' signing hash, r, s and recovery id (wire_sig, by the rules
// of LatestSigner(chain_id)) and recovers them as the batch above does
// (ok = 2 as there).
// ok[i] = 0 says this transaction is not vouched for — malformed,
// truncated, cut outside [0, wire_len], foreign chain id, high s,
// recovery id past 1, r or s out of range, no such point — and is left
// to the per-transaction path; its neighbours are untouched.
void coreth_recover_wire(const uint8_t* wire, uint64_t wire_len,
                         const uint64_t* offsets, uint64_t n,
                         uint64_t chain_id, uint8_t* out, uint8_t* ok) {
  if (n == 0) return;
  std::vector<uint8_t> hashes(32 * n), rs(32 * n), ss(32 * n),
      recids(n, 255);  // 255: the ladder answers ok = 0
  uint8_t *h = hashes.data(), *r = rs.data(), *s = ss.data(),
          *rec = recids.data();
  const bool per_sig = fast_recover_disabled();
  in_chunks(n, [=](uint64_t lo, uint64_t hi) {
    std::vector<uint8_t> pre;
    for (uint64_t i = lo; i < hi; ++i) {
      const uint64_t at = offsets[i], end = offsets[i + 1];
      uint8_t recid;
      if (at <= end && end <= wire_len &&
          wire_sig(wire + at, wire + end, chain_id, pre, h + 32 * i,
                   r + 32 * i, s + 32 * i, &recid))
        rec[i] = recid;
    }
    if (!per_sig) {
      fast_recover_range(h, r, s, rec, lo, hi, out, ok);
      return;
    }
    for (uint64_t i = lo; i < hi; ++i)  // the A/B knob, as above
      ok[i] = (uint8_t)coreth_ecrecover(h + 32 * i, r + 32 * i,
                                        s + 32 * i, rec[i], out + 20 * i);
  });
}

}  // extern "C"

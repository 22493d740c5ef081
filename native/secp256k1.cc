// Native secp256k1 ECDSA public-key recovery for the coreth-tpu host runtime.
//
// Role parity with the reference's cgo libsecp256k1 binding (geth
// crypto/secp256k1), which coreth drives in parallel for every block via
// core/sender_cacher.go.  This implementation: 4x64-bit limbs with __int128
// products, fast reduction mod p = 2^256 - 0x1000003D1, Jacobian points,
// Shamir double-scalar multiplication for u1*G + u2*R, Fermat inversion.
// Keccak for the address derivation comes from keccak.cc.
//
// Correctness is anchored by the test suite: cross-checked against the
// pure-Python implementation, which is itself anchored by the well-known
// privkey=1 -> 0x7E5F4552091A69125d5DfCb7b8C2659029395Bdf vector.

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

struct U256 {
  uint64_t v[4];  // little-endian limbs
};

const U256 ZERO = {{0, 0, 0, 0}};
const U256 ONE = {{1, 0, 0, 0}};

// p = 2^256 - 2^32 - 977
const U256 PRIME = {{0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                     0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL}};
const uint64_t P_C = 0x1000003D1ULL;  // 2^256 - p

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

// ---- field arithmetic mod p (fast reduction using p = 2^256 - P_C) ----

void fe_mul(U256& r, const U256& a, const U256& b) {
  uint64_t w[8] = {0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a.v[i] * b.v[j] + w[i + j] + carry;
      w[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    w[i + 4] += (uint64_t)carry;
  }
  // fold hi*2^256 -> hi*P_C twice
  U256 lo = {{w[0], w[1], w[2], w[3]}};
  U256 hi = {{w[4], w[5], w[6], w[7]}};
  // acc = lo + hi * P_C  (result fits in 256 + ~33 bits)
  uint64_t w2[5] = {0};
  {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)hi.v[j] * P_C + lo.v[j] + carry;
      w2[j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    w2[4] = (uint64_t)carry;
  }
  // fold again: w2[4] * P_C.  A carry can still ripple out of limb 3
  // (acc + w2[4]*P_C may reach 2^256); the dropped 2^256 == P_C (mod p),
  // so a third conditional fold is required.
  U256 acc = {{w2[0], w2[1], w2[2], w2[3]}};
  {
    u128 cur = (u128)w2[4] * P_C + acc.v[0];
    acc.v[0] = (uint64_t)cur;
    uint64_t carry = (uint64_t)(cur >> 64);
    for (int j = 1; j < 4; ++j) {
      u128 c2 = (u128)acc.v[j] + carry;
      acc.v[j] = (uint64_t)c2;
      carry = (uint64_t)(c2 >> 64);
    }
    if (carry) {  // acc wrapped to a tiny value; adding P_C cannot overflow
      u128 c3 = (u128)acc.v[0] + P_C;
      acc.v[0] = (uint64_t)c3;
      uint64_t c = (uint64_t)(c3 >> 64);
      for (int j = 1; j < 4 && c; ++j) {
        u128 c4 = (u128)acc.v[j] + c;
        acc.v[j] = (uint64_t)c4;
        c = (uint64_t)(c4 >> 64);
      }
    }
  }
  while (cmp(acc, PRIME) >= 0) {
    U256 t;
    sub_raw(t, acc, PRIME);
    acc = t;
  }
  r = acc;
}

inline void fe_sqr(U256& r, const U256& a) { fe_mul(r, a, a); }

void fe_pow(U256& r, const U256& a, const U256& e) {
  U256 acc = ONE, base = a;
  for (int i = 0; i < 256; ++i) {
    if ((e.v[i / 64] >> (i % 64)) & 1) {
      U256 t;
      fe_mul(t, acc, base);
      acc = t;
    }
    U256 t;
    fe_sqr(t, base);
    base = t;
  }
  r = acc;
}

void fe_inv(U256& r, const U256& a) {
  U256 e;
  sub_raw(e, PRIME, {{2, 0, 0, 0}});
  fe_pow(r, a, e);
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

void sc_mul(U256& r, const U256& a, const U256& b, const U256& /*m*/) {
  uint64_t w[8];
  mul_wide(w, a, b);
  sc_reduce_wide(r, w);
}

void sc_pow(U256& r, const U256& a, const U256& e, const U256& m) {
  U256 acc = ONE, base = a;
  for (int i = 0; i < 256; ++i) {
    if ((e.v[i / 64] >> (i % 64)) & 1) {
      U256 t;
      sc_mul(t, acc, base, m);
      acc = t;
    }
    U256 t;
    sc_mul(t, base, base, m);
    base = t;
  }
  r = acc;
}

void sc_inv(U256& r, const U256& a) {
  U256 e;
  sub_raw(e, ORDER, {{2, 0, 0, 0}});
  sc_pow(r, a, e, ORDER);
}

// ---- Jacobian point arithmetic over fe ----

struct Point {
  U256 x, y, z;  // z == 0 => infinity
};

inline bool pt_is_inf(const Point& p) { return is_zero(p.z); }

void pt_double(Point& r, const Point& p) {
  if (pt_is_inf(p) || is_zero(p.y)) {
    r = {ZERO, ONE, ZERO};
    return;
  }
  U256 ysq, s, m, t;
  fe_sqr(ysq, p.y);
  fe_mul(s, p.x, ysq);
  mod_add(s, s, s, PRIME);
  mod_add(s, s, s, PRIME);  // s = 4*x*y^2
  fe_sqr(m, p.x);
  U256 m3;
  mod_add(m3, m, m, PRIME);
  mod_add(m, m3, m, PRIME);  // m = 3*x^2
  U256 nx;
  fe_sqr(nx, m);
  mod_sub(nx, nx, s, PRIME);
  mod_sub(nx, nx, s, PRIME);
  U256 ysq2, y4;
  fe_sqr(ysq2, ysq);  // y^4
  // 8*y^4
  mod_add(y4, ysq2, ysq2, PRIME);
  mod_add(y4, y4, y4, PRIME);
  mod_add(y4, y4, y4, PRIME);
  U256 ny;
  mod_sub(t, s, nx, PRIME);
  fe_mul(ny, m, t);
  mod_sub(ny, ny, y4, PRIME);
  U256 nz;
  fe_mul(nz, p.y, p.z);
  mod_add(nz, nz, nz, PRIME);
  r.x = nx;
  r.y = ny;
  r.z = nz;
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
  U256 z1sq, z2sq, u1, u2, s1, s2, t;
  fe_sqr(z1sq, p1.z);
  fe_sqr(z2sq, p2.z);
  fe_mul(u1, p1.x, z2sq);
  fe_mul(u2, p2.x, z1sq);
  fe_mul(t, z2sq, p2.z);
  fe_mul(s1, p1.y, t);
  fe_mul(t, z1sq, p1.z);
  fe_mul(s2, p2.y, t);
  if (cmp(u1, u2) == 0) {
    if (cmp(s1, s2) != 0) {
      r = {ZERO, ONE, ZERO};
      return;
    }
    pt_double(r, p1);
    return;
  }
  U256 h, rr, hsq, hcu, v;
  mod_sub(h, u2, u1, PRIME);
  mod_sub(rr, s2, s1, PRIME);
  fe_sqr(hsq, h);
  fe_mul(hcu, hsq, h);
  fe_mul(v, u1, hsq);
  U256 nx;
  fe_sqr(nx, rr);
  mod_sub(nx, nx, hcu, PRIME);
  mod_sub(nx, nx, v, PRIME);
  mod_sub(nx, nx, v, PRIME);
  U256 ny;
  mod_sub(t, v, nx, PRIME);
  fe_mul(ny, rr, t);
  U256 s1h;
  fe_mul(s1h, s1, hcu);
  mod_sub(ny, ny, s1h, PRIME);
  U256 nz;
  fe_mul(t, p1.z, p2.z);
  fe_mul(nz, t, h);
  r.x = nx;
  r.y = ny;
  r.z = nz;
}

// Shamir: k1*G + k2*Q in one double-and-add ladder.
void pt_shamir(Point& r, const U256& k1, const U256& k2, const Point& q) {
  Point g = {GX, GY, ONE};
  Point gq;
  pt_add(gq, g, q);
  Point acc = {ZERO, ONE, ZERO};
  for (int i = 255; i >= 0; --i) {
    Point t;
    pt_double(t, acc);
    acc = t;
    int b1 = (k1.v[i / 64] >> (i % 64)) & 1;
    int b2 = (k2.v[i / 64] >> (i % 64)) & 1;
    if (b1 && b2)
      pt_add(t, acc, gq);
    else if (b1)
      pt_add(t, acc, g);
    else if (b2)
      pt_add(t, acc, q);
    else
      continue;
    acc = t;
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

// ---- batch-only fast recovery (coreth_ecrecover_batch) ----
//
// The sequential coreth_ecrecover above is the native baseline's
// primitive (one Shamir ladder per call) and stays untouched.  The
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
// so a constant or carry bug degrades to the slow path, never to a
// wrong address.  CORETH_FAST_RECOVER=0 forces the per-signature
// fallback everywhere (the A/B and bisection knob).

// lambda/beta: the cube roots of 1 realizing the curve endomorphism
// (x, y) -> (beta*x, y) == lambda * P; lattice basis and the rounded
// 384-bit division constants g1/g2 are the standard secp256k1 values
// (verified exhaustively against the Python twin in tests).
const U256 GLV_LAMBDA = {{0xDF02967C1B23BD72ULL, 0x122E22EA20816678ULL,
                          0xA5261C028812645AULL, 0x5363AD4CC05C30E0ULL}};
const U256 GLV_BETA = {{0xC1396C28719501EEULL, 0x9CF0497512F58995ULL,
                        0x6E64479EAC3434E9ULL, 0x7AE96A2B657C0710ULL}};
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

// a^((p+1)/4) by addition chain (255 squarings + 13 multiplies vs
// ~506 multiplies for the generic bit-scan fe_pow — the exponent is
// almost all ones).  Chain verified against (p+1)/4 in tests.
void fe_sqrt_chain(U256& r, const U256& a) {
  auto sqr_n = [](U256& x, int n) {
    for (int i = 0; i < n; ++i) {
      U256 t;
      fe_sqr(t, x);
      x = t;
    }
  };
  U256 x2, x3, x6, x9, x11, x22, x44, x88, x176, x220, x223, t1, t;
  fe_sqr(x2, a);
  fe_mul(t, x2, a);
  x2 = t;                       // a^3
  fe_sqr(x3, x2);
  fe_mul(t, x3, a);
  x3 = t;                       // a^7
  x6 = x3;
  sqr_n(x6, 3);
  fe_mul(t, x6, x3);
  x6 = t;
  x9 = x6;
  sqr_n(x9, 3);
  fe_mul(t, x9, x3);
  x9 = t;
  x11 = x9;
  sqr_n(x11, 2);
  fe_mul(t, x11, x2);
  x11 = t;
  x22 = x11;
  sqr_n(x22, 11);
  fe_mul(t, x22, x11);
  x22 = t;
  x44 = x22;
  sqr_n(x44, 22);
  fe_mul(t, x44, x22);
  x44 = t;
  x88 = x44;
  sqr_n(x88, 44);
  fe_mul(t, x88, x44);
  x88 = t;
  x176 = x88;
  sqr_n(x176, 88);
  fe_mul(t, x176, x88);
  x176 = t;
  x220 = x176;
  sqr_n(x220, 44);
  fe_mul(t, x220, x44);
  x220 = t;
  x223 = x220;
  sqr_n(x223, 3);
  fe_mul(t, x223, x3);
  x223 = t;
  t1 = x223;
  sqr_n(t1, 23);
  fe_mul(t, t1, x22);
  t1 = t;
  sqr_n(t1, 6);
  fe_mul(t, t1, x2);
  t1 = t;
  sqr_n(t1, 2);
  r = t1;
}

struct APoint {
  U256 x, y;
  bool inf;
};

// p1 (Jacobian) + p2 (affine): the 8M+3S mixed addition every table
// hit uses.  Equal-x inputs degrade to pt_double / infinity exactly
// like pt_add.
void pt_add_mixed(Point& r, const Point& p1, const APoint& p2) {
  if (p2.inf) {
    r = p1;
    return;
  }
  if (pt_is_inf(p1)) {
    r = {p2.x, p2.y, ONE};
    return;
  }
  U256 z1sq, u2, s2, t;
  fe_sqr(z1sq, p1.z);
  fe_mul(u2, p2.x, z1sq);
  fe_mul(t, z1sq, p1.z);
  fe_mul(s2, p2.y, t);
  if (cmp(p1.x, u2) == 0) {
    if (cmp(p1.y, s2) != 0) {
      r = {ZERO, ONE, ZERO};
      return;
    }
    pt_double(r, p1);
    return;
  }
  U256 h, rr, hsq, hcu, v;
  mod_sub(h, u2, p1.x, PRIME);
  mod_sub(rr, s2, p1.y, PRIME);
  fe_sqr(hsq, h);
  fe_mul(hcu, hsq, h);
  fe_mul(v, p1.x, hsq);
  U256 nx;
  fe_sqr(nx, rr);
  mod_sub(nx, nx, hcu, PRIME);
  mod_sub(nx, nx, v, PRIME);
  mod_sub(nx, nx, v, PRIME);
  U256 ny;
  mod_sub(t, v, nx, PRIME);
  fe_mul(ny, rr, t);
  U256 yh;
  fe_mul(yh, p1.y, hcu);
  mod_sub(ny, ny, yh, PRIME);
  U256 nz;
  fe_mul(nz, p1.z, h);
  r.x = nx;
  r.y = ny;
  r.z = nz;
}

// Normalize Jacobian points to affine with ONE field inversion
// (Montgomery prefix products).  Infinity rows come back inf.
void batch_to_affine(const Point* pts, APoint* out, size_t n) {
  std::vector<U256> prefix(n);
  std::vector<size_t> live;
  live.reserve(n);
  U256 acc = ONE;
  for (size_t i = 0; i < n; ++i) {
    out[i].inf = pt_is_inf(pts[i]);
    if (out[i].inf) continue;
    U256 t;
    fe_mul(t, acc, pts[i].z);
    acc = t;
    prefix[i] = acc;
    live.push_back(i);
  }
  if (live.empty()) return;
  U256 inv;
  fe_inv(inv, acc);
  for (size_t k = live.size(); k-- > 0;) {
    size_t i = live[k];
    U256 zinv;
    if (k == 0) {
      zinv = inv;
    } else {
      fe_mul(zinv, inv, prefix[live[k - 1]]);
    }
    U256 t;
    fe_mul(t, inv, pts[i].z);
    inv = t;
    U256 zi2;
    fe_sqr(zi2, zinv);
    fe_mul(out[i].x, pts[i].x, zi2);
    fe_mul(t, zi2, zinv);
    fe_mul(out[i].y, pts[i].y, t);
  }
}

// u1*G comb: TBL[w][v-1] = v * 2^(8w) * G, affine.  522KB, built once
// under std::call_once on first batch call (the warm replay rep pays
// it, like an XLA compile).
constexpr int COMB_WINDOWS = 32;
constexpr int COMB_VALS = 255;
std::vector<APoint> g_comb;
std::once_flag g_comb_once;

void build_g_comb() {
  std::vector<Point> jac(COMB_WINDOWS * COMB_VALS);
  Point base = {GX, GY, ONE};
  for (int w = 0; w < COMB_WINDOWS; ++w) {
    jac[w * COMB_VALS] = base;
    for (int v = 2; v <= COMB_VALS; ++v)
      pt_add(jac[w * COMB_VALS + v - 1], jac[w * COMB_VALS + v - 2],
             base);
    for (int d = 0; d < 8; ++d) {
      Point t;
      pt_double(t, base);
      base = t;
    }
  }
  g_comb.resize(jac.size());
  batch_to_affine(jac.data(), g_comb.data(), jac.size());
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
  sc_mul(chk, k2m, GLV_LAMBDA, ORDER);
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
  bool ready;
};

// One signature's validation + R + scalars; rinv comes from the batch
// inversion.  Returns false -> caller routes index to the fallback.
bool fast_prep(const uint8_t* hash32, const uint8_t* s32, const U256& r,
               const U256& rinv, int recid, FastSig& fs) {
  U256 s, z;
  load_be(s, s32);
  load_be(z, hash32);
  U256 x = r;
  if (recid & 2) {
    if (add_raw(x, r, ORDER)) return false;
    if (cmp(x, PRIME) >= 0) return false;
  }
  U256 xsq, ysq, seven = {{7, 0, 0, 0}};
  fe_sqr(xsq, x);
  fe_mul(ysq, xsq, x);
  mod_add(ysq, ysq, seven, PRIME);
  U256 y;
  fe_sqrt_chain(y, ysq);
  U256 chk;
  fe_sqr(chk, y);
  if (cmp(chk, ysq) != 0) return false;
  if ((y.v[0] & 1) != (uint64_t)(recid & 1)) mod_sub(y, PRIME, y, PRIME);
  while (cmp(z, ORDER) >= 0) {
    U256 t;
    sub_raw(t, z, ORDER);
    z = t;
  }
  sc_mul(fs.u1, z, rinv, ORDER);
  if (!is_zero(fs.u1)) mod_sub(fs.u1, ORDER, fs.u1, ORDER);
  sc_mul(fs.u2, s, rinv, ORDER);
  if (!glv_split(fs.u2, fs.k1, fs.s1, fs.k2, fs.s2)) return false;
  // odd multiples of R
  Point rpt = {x, y, ONE};
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
  acc = {ZERO, ONE, ZERO};
  for (int i = len - 1; i >= 0; --i) {
    Point t;
    pt_double(t, acc);
    acc = t;
    if (i < l1 && d1[i]) {
      int8_t d = d1[i];
      bool neg = (d < 0) != (fs.s1 < 0);
      APoint p = tbl_aff[(d < 0 ? -d : d) >> 1];
      if (neg && !p.inf) mod_sub(p.y, PRIME, p.y, PRIME);
      pt_add_mixed(t, acc, p);
      acc = t;
    }
    if (i < l2 && d2[i]) {
      int8_t d = d2[i];
      bool neg = (d < 0) != (fs.s2 < 0);
      APoint p = tbl_aff[(d < 0 ? -d : d) >> 1];
      if (!p.inf) {
        U256 bx;
        fe_mul(bx, p.x, GLV_BETA);  // phi: (x,y) -> (beta x, y)
        p.x = bx;
        if (neg) mod_sub(p.y, PRIME, p.y, PRIME);
      }
      pt_add_mixed(t, acc, p);
      acc = t;
    }
  }
  for (int w = 0; w < COMB_WINDOWS; ++w) {
    int v = (int)((fs.u1.v[w / 8] >> (8 * (w % 8))) & 0xFF);
    if (!v) continue;
    Point t;
    pt_add_mixed(t, acc, g_comb[w * COMB_VALS + v - 1]);
    acc = t;
  }
}

// Fast batch over [lo, hi): shared r^-1 batch inversion, shared wNAF
// table normalization, per-signature ladders, shared final affine
// conversion.  Each index the fast path cannot carry falls back to
// the sequential coreth_ecrecover.
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
    U256 t;
    sc_mul(t, acc, r, ORDER);
    acc = t;
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
        sc_mul(rinv, inv, prefix[live[k - 1]], ORDER);
      }
      U256 t;
      sc_mul(t, inv, r_l[j], ORDER);
      inv = t;
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
  std::vector<Point> res(n);
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
      ok[i] = (uint8_t)coreth_ecrecover(hashes + 32 * i, rs + 32 * i,
                                        ss + 32 * i, recids[i],
                                        out + 20 * i);
      continue;
    }
    if (state[j] != 1 || res_aff[j].inf) continue;
    uint8_t pub[64], digest[32];
    store_be(pub, res_aff[j].x);
    store_be(pub + 32, res_aff[j].y);
    coreth_keccak256(pub, 64, digest);
    std::memcpy(out + 20 * i, digest + 12, 20);
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
  // x = r (+ n when recid & 2)
  U256 x = r;
  if (recid & 2) {
    if (add_raw(x, r, ORDER)) return 0;
    if (cmp(x, PRIME) >= 0) return 0;
  }
  // y^2 = x^3 + 7
  U256 xsq, ysq, seven = {{7, 0, 0, 0}};
  fe_sqr(xsq, x);
  fe_mul(ysq, xsq, x);
  mod_add(ysq, ysq, seven, PRIME);
  // y = ysq^((p+1)/4)
  U256 e = PRIME;
  {  // (p+1)/4: p+1 overflows 256 bits? p < 2^256-1 so p+1 fits.
    U256 p1;
    add_raw(p1, PRIME, ONE);
    // shift right by 2
    for (int i = 0; i < 4; ++i) {
      uint64_t hi = (i < 3) ? p1.v[i + 1] : 0;
      e.v[i] = (p1.v[i] >> 2) | (hi << 62);
    }
  }
  U256 y;
  fe_pow(y, ysq, e);
  U256 chk;
  fe_sqr(chk, y);
  if (cmp(chk, ysq) != 0) return 0;  // non-residue: invalid r
  if ((y.v[0] & 1) != (uint64_t)(recid & 1)) mod_sub(y, PRIME, y, PRIME);
  // u1 = -z/r mod n ; u2 = s/r mod n
  U256 rinv, u1, u2, zmod = z;
  while (cmp(zmod, ORDER) >= 0) {
    U256 t;
    sub_raw(t, zmod, ORDER);
    zmod = t;
  }
  sc_inv(rinv, r);
  sc_mul(u1, zmod, rinv, ORDER);
  if (!is_zero(u1)) mod_sub(u1, ORDER, u1, ORDER);
  sc_mul(u2, s, rinv, ORDER);
  Point q = {x, y, ONE}, res;
  pt_shamir(res, u1, u2, q);
  if (pt_is_inf(res)) return 0;
  // to affine
  U256 zinv, zinv2, ax, ay, t;
  fe_inv(zinv, res.z);
  fe_sqr(zinv2, zinv);
  fe_mul(ax, res.x, zinv2);
  fe_mul(t, zinv2, zinv);
  fe_mul(ay, res.y, t);
  uint8_t pub[64];
  store_be(pub, ax);
  store_be(pub + 32, ay);
  uint8_t digest[32];
  coreth_keccak256(pub, 64, digest);
  std::memcpy(out20, digest + 12, 20);
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
    U256 t;
    sc_mul(t, acc, r, ORDER);
    acc = t;
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
      sc_mul(rinv, inv, prefix[live[k - 1]], ORDER);
    }
    U256 t;
    sc_mul(t, inv, r_l[i], ORDER);
    inv = t;
    // u2 = s/r ; u1 = -(z/r)
    U256 s, z, u1, u2;
    load_be(s, ss + 32 * i);
    load_be(z, hashes + 32 * i);
    while (cmp(z, ORDER) >= 0) {
      U256 t2;
      sub_raw(t2, z, ORDER);
      z = t2;
    }
    sc_mul(u2, s, rinv, ORDER);
    sc_mul(u1, z, rinv, ORDER);
    if (!is_zero(u1)) {
      U256 t2;
      sub_raw(t2, ORDER, u1);
      u1 = t2;
    }
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
  auto load_le33 = [](U256& v, const uint8_t* p) {
    uint8_t be[32];
    for (int j = 0; j < 32; ++j) be[j] = p[31 - j];
    load_be(v, be);
  };
  std::vector<U256> z_l(n), prefix(n);
  std::vector<uint64_t> fin;
  fin.reserve(n);
  U256 acc = ONE;
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
    U256 z;
    load_le33(z, row + 66);
    if (is_zero(z)) continue;
    z_l[i] = z;
    U256 t;
    fe_mul(t, acc, z);
    acc = t;
    prefix[i] = acc;
    fin.push_back(i);
  }
  if (fin.empty()) return;
  U256 inv;
  fe_inv(inv, acc);
  for (size_t k = fin.size(); k-- > 0;) {
    uint64_t i = fin[k];
    U256 zinv;
    if (k == 0) {
      zinv = inv;
    } else {
      fe_mul(zinv, inv, prefix[fin[k - 1]]);
    }
    U256 t;
    fe_mul(t, inv, z_l[i]);
    inv = t;
    const uint8_t* row = rows + 102 * i;
    U256 xj, yj, zi2, ax, ay;
    load_le33(xj, row);
    load_le33(yj, row + 33);
    fe_sqr(zi2, zinv);
    fe_mul(ax, xj, zi2);
    fe_mul(t, zi2, zinv);
    fe_mul(ay, yj, t);
    uint8_t pub[64], digest[32];
    store_be(pub, ax);
    store_be(pub + 32, ay);
    coreth_keccak256(pub, 64, digest);
    std::memcpy(out20 + 20 * i, digest + 12, 20);
    ok[i] = 1;
  }
}

// Test hook: field multiplication mod p over big-endian 32-byte operands.
// Exists so the carry-fold edge cases of fe_mul stay regression-tested from
// Python (see tests/test_crypto.py).
void coreth_test_fe_mul(const uint8_t* a32, const uint8_t* b32,
                        uint8_t* out32) {
  U256 a, b, r;
  load_be(a, a32);
  load_be(b, b32);
  fe_mul(r, a, b);
  store_be(out32, r);
}

// Batched recovery: packed 32-byte hashes / r / s, recid bytes.
// out: packed 20-byte addresses; ok[i] = 1 on success.
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
// of LatestSigner(chain_id)) and recovers them as the batch above does.
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

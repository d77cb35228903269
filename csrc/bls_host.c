/* Native BLS12-381 field arithmetic for the host codec (ops/codec.py).
 *
 * The codec's host path (hash-to-G2, signature and pubkey decompression,
 * subgroup checks) as one C kernel over contiguous buffers. It runs the
 * same algorithms as the raw-int Python path and returns bit-identical
 * outputs: the same SSWU branch structure, the complex-method Fq2 square
 * root with the same root choice, the oracle's Jacobian formulas and
 * their doubling / cancellation branches, Montgomery batch inversion with
 * inv(0) == 0. Points leave in the repo's limb layout (ops/fq.py: 15
 * limbs of 28 bits, Montgomery radix 2^420), so no per-item conversion
 * is left to Python.
 *
 * Inside, Fp is 6 x 64-bit words in Montgomery form (radix 2^384, CIOS
 * multiplication over unsigned __int128), always fully reduced (< p), so
 * equality is word equality. There is no global mutable state: every
 * entry point works on its arguments and on scratch it allocates and
 * frees, so concurrent calls from several threads are safe.
 *
 * Build: gcc -O3 -fPIC -shared -o csrc/libbls_host.so csrc/bls_host.c
 * (utils/native_bls.py builds it on first import).
 */
#include <stddef.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>

typedef unsigned __int128 u128;
typedef struct { uint64_t l[6]; } fp;
typedef struct { fp c0, c1; } fp2;
typedef struct { fp x, y, z; } g1j;   /* Jacobian; z == 0 is infinity */
typedef struct { fp2 x, y, z; } g2j;

#define REPO_LIMBS 15
#define REPO_BITS 28
#define REPO_MASK ((1ULL << REPO_BITS) - 1)
#define X_ABS 0xD201000000010000ULL  /* |x|, the BLS parameter magnitude */

/* -- constants (Montgomery form unless marked plain) ---------------------- */

static const uint64_t INV = 0x89f3fffcfffcfffdULL;  /* -p^-1 mod 2^64 */
/* p, plain */
static const fp P_ = {{0xb9feffffffffaaabULL, 0x1eabfffeb153ffffULL, 0x6730d2a0f6b0f624ULL, 0x64774b84f38512bfULL, 0x4b1ba7b6434bacd7ULL, 0x1a0111ea397fe69aULL}};
static const fp R2 = {{0xf4df1f341c341746ULL, 0x0a76e6a609d104f1ULL, 0x8de5476c4c95b6d5ULL, 0x67eb88a9939d83c0ULL, 0x9a793e85b519952dULL, 0x11988fe592cae3aaULL}};  /* plain 2^768 mod p: mul(a, R2) = a in Montgomery form */
static const fp R3 = {{0xed48ac6bd94ca1e0ULL, 0x315f831e03a7adf8ULL, 0x9a53352a615e29ddULL, 0x34c04e5e921e1761ULL, 0x2512d43565724728ULL, 0x0aa6346091755d4dULL}};  /* plain 2^1152 mod p: the high 128 bits of a 512-bit draw */
static const fp ONE = {{0x760900000002fffdULL, 0xebf4000bc40c0002ULL, 0x5f48985753c758baULL, 0x77ce585370525745ULL, 0x5c071a97a256ec6dULL, 0x15f65ec3fa80e493ULL}};  /* 1 in Montgomery form */
static const fp TO_REPO = {{0x977080ea8e9f9aecULL, 0x26e7d66716d8fe47ULL, 0xaca5f496fb088639ULL, 0xbca6d4cf7416d1f5ULL, 0xdaab0ee4b5b6168dULL, 0x14820974403d5566ULL}};  /* plain 2^420 mod p: mul(a, TO_REPO) = a * 2^420, the repo's form */
static const fp FROM_REPO_LO = {{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000010000000ULL}};  /* plain 2^348 */
static const fp FROM_REPO_HI = {{0x3751dc677a6af52bULL, 0x177236b00773a87dULL, 0xe2b39c60431a86daULL, 0xd9f3e94d61e347ffULL, 0x2c9e3285a688f276ULL, 0x08c56932932a77e3ULL}};  /* plain 2^732 mod p */
static const fp HALF = {{0xdcff7fffffffd555ULL, 0x0f55ffff58a9ffffULL, 0xb39869507b587b12ULL, 0xb23ba5c279c2895fULL, 0x258dd3db21a5d66bULL, 0x0d0088f51cbff34dULL}};  /* (p-1)/2, plain */
static const fp EXP_SQRT_INV = {{0xee7fbfffffffeaaaULL, 0x07aaffffac54ffffULL, 0xd9cc34a83dac3d89ULL, 0xd91dd2e13ce144afULL, 0x92c6e9ed90d2eb35ULL, 0x0680447a8e5ff9a6ULL}};  /* plain exponent (p-3)/4 */
static const fp EXP_INV = {{0xb9feffffffffaaa9ULL, 0x1eabfffeb153ffffULL, 0x6730d2a0f6b0f624ULL, 0x64774b84f38512bfULL, 0x4b1ba7b6434bacd7ULL, 0x1a0111ea397fe69aULL}};  /* plain exponent p-2 */
static const fp INV2 = {{0x1804000000015554ULL, 0x855000053ab00001ULL, 0x633cb57c253c276fULL, 0x6e22d1ec31ebb502ULL, 0xd3916126f2d14ca2ULL, 0x17fbb8571a006596ULL}};  /* 1/2 */
static const fp FOUR = {{0xaa270000000cfff3ULL, 0x53cc0032fc34000aULL, 0x478fe97a6b0a807fULL, 0xb1d37ebee6ba24d7ULL, 0x8ec9733bbf78ab2fULL, 0x09d645513d83de7eULL}};  /* b of G1 */
static const fp BETA_G1 = {{0x30f1361b798a64e8ULL, 0xf3b8ddab7ece5a2aULL, 0x16a8ca3ac61577f7ULL, 0xc26a2ff874fd029bULL, 0x3636b76660701c6eULL, 0x051ba4ab241b6160ULL}};  /* GLV cube root of unity */
static const fp2 B_G2 = {{{0xaa270000000cfff3ULL, 0x53cc0032fc34000aULL, 0x478fe97a6b0a807fULL, 0xb1d37ebee6ba24d7ULL, 0x8ec9733bbf78ab2fULL, 0x09d645513d83de7eULL}}, {{0xaa270000000cfff3ULL, 0x53cc0032fc34000aULL, 0x478fe97a6b0a807fULL, 0xb1d37ebee6ba24d7ULL, 0x8ec9733bbf78ab2fULL, 0x09d645513d83de7eULL}}};  /* 4(1+u) */
static const fp2 SSWU_A = {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0xe53a000003135242ULL, 0x01080c0fdef80285ULL, 0xe7889edbe340f6bdULL, 0x0b51375126310601ULL, 0x02d6985717c744abULL, 0x1220b4e979ea5467ULL}}};
static const fp2 SSWU_B = {{{0x22ea00000cf89db2ULL, 0x6ec832df71380aa4ULL, 0x6e1b94403db5a66eULL, 0x75bf3c53a79473baULL, 0x3dd3a569412c0a34ULL, 0x125cdb5e74dc4fd1ULL}}, {{0x22ea00000cf89db2ULL, 0x6ec832df71380aa4ULL, 0x6e1b94403db5a66eULL, 0x75bf3c53a79473baULL, 0x3dd3a569412c0a34ULL, 0x125cdb5e74dc4fd1ULL}}};
static const fp2 SSWU_Z = {{{0x87ebfffffff9555cULL, 0x656fffe5da8ffffaULL, 0x0fd0749345d33ad2ULL, 0xd951e663066576f4ULL, 0xde291a3d41e980d3ULL, 0x0815664c7dfe040dULL}}, {{0x43f5fffffffcaaaeULL, 0x32b7fff2ed47fffdULL, 0x07e83a49a2e99d69ULL, 0xeca8f3318332bb7aULL, 0xef148d1ea0f4c069ULL, 0x040ab3263eff0206ULL}}};
static const fp2 NEG_B_OVER_A = {{{0x903c555555474fb3ULL, 0x5f98cc95ce451105ULL, 0x9f8e582eefe0fadeULL, 0xc68946b6aebbd062ULL, 0x467a4ad10ee6de53ULL, 0x0e7146f483e23a05ULL}}, {{0x29c2aaaaaab85af8ULL, 0xbf133368e30eeefaULL, 0xc7a27a7206cffb45ULL, 0x9dee04ce44c9425cULL, 0x04a15ce53464ce83ULL, 0x0b8fcaf5b59dac95ULL}}};  /* -B/A */
static const fp2 X1_EXC = {{{0xf2d8444444414324ULL, 0x2585c28393a69d00ULL, 0x5dd35cd05d972c42ULL, 0xfd963b744ea89b53ULL, 0x07f5d9fd91c1fa91ULL, 0x127db28a3ce062c4ULL}}, {{0x55743333333b3695ULL, 0xeb72b871590828fcULL, 0x1c186171cb4d5da5ULL, 0x34a33031ee956644ULL, 0xc971692a149d16d0ULL, 0x168a1e1ff5de8b82ULL}}};  /* B/(Z A), the tv2 == 0 case */
static const fp2 PSI_CX = {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0x890dc9e4867545c3ULL, 0x2af322533285a5d5ULL, 0x50880866309b7e2cULL, 0xa20d1b8c7e881024ULL, 0x14e4f04fe2db9068ULL, 0x14e56d3f1564853aULL}}};
static const fp2 PSI_CY = {{{0x3e2f585da55c9ad1ULL, 0x4294213d86c18183ULL, 0x382844c88b623732ULL, 0x92ad2afd19103e18ULL, 0x1d794e4fac7cf0b9ULL, 0x0bd592fc7d825ec8ULL}}, {{0x7bcfa7a25aa30fdaULL, 0xdc17dec12a927e7cULL, 0x2f088dd86b4ebef1ULL, 0xd1ca2087da74d4a7ULL, 0x2da2596696cebc1dULL, 0x0e2b7eedbbfd87d2ULL}}};
static const fp2 ISO_X_NUM[4] = {
  {{{0x47f671c71ce05e62ULL, 0x06dd57071206393eULL, 0x7c80cd2af3fd71a2ULL, 0x048103ea9e6cd062ULL, 0xc54516acc8d037f6ULL, 0x13808f550920ea41ULL}}, {{0x47f671c71ce05e62ULL, 0x06dd57071206393eULL, 0x7c80cd2af3fd71a2ULL, 0x048103ea9e6cd062ULL, 0xc54516acc8d037f6ULL, 0x13808f550920ea41ULL}}},
  {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0x5fe55555554c71d0ULL, 0x873fffdd236aaaa3ULL, 0x6a6b4619b26ef918ULL, 0x21c2888408874945ULL, 0x2836cda7028cabc5ULL, 0x0ac73310a7fd5abdULL}}},
  {{{0x0a0c5555555971c3ULL, 0xdb0c00101f9eaaaeULL, 0xb1fb2f941d797997ULL, 0xd3960742ef416e1cULL, 0xb70040e2c20556f4ULL, 0x149d7861e581393bULL}}, {{0xaff2aaaaaaa638e8ULL, 0x439fffee91b55551ULL, 0xb535a30cd9377c8cULL, 0x90e144420443a4a2ULL, 0x941b66d3814655e2ULL, 0x0563998853fead5eULL}}},
  {{{0x40aac71c71c725edULL, 0x190955557a84e38eULL, 0xd817050a8f41abc3ULL, 0xd86485d4c87f6fb1ULL, 0x696eb479f885d059ULL, 0x198e1a74328002d2ULL}}, {{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}},
};
static const fp2 ISO_X_DEN[3] = {
  {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0x1f3affffff13ab97ULL, 0xf25bfc611da3ff3eULL, 0xca3757cb3819b208ULL, 0x3e6427366f8cec18ULL, 0x03977bc86095b089ULL, 0x04f69db13f39a952ULL}}},
  {{{0x447600000027552eULL, 0xdcb8009a43480020ULL, 0x6f7ee9ce4a6e8b59ULL, 0xb10330b7c0a95bc6ULL, 0x6140b1fcfb1e54b7ULL, 0x0381be097f0bb4e1ULL}}, {{0x7588ffffffd8557dULL, 0x41f3ff646e0bffdfULL, 0xf7b1e8d2ac426acaULL, 0xb3741acd32dbb6f8ULL, 0xe9daf5b9482d581fULL, 0x167f53e0ba7431b8ULL}}},
  {{{0x760900000002fffdULL, 0xebf4000bc40c0002ULL, 0x5f48985753c758baULL, 0x77ce585370525745ULL, 0x5c071a97a256ec6dULL, 0x15f65ec3fa80e493ULL}}, {{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}},
};
static const fp2 ISO_Y_NUM[4] = {
  {{{0x96d8f684bdfc77beULL, 0xb530e4f43b66d0e2ULL, 0x184a88ff379652fdULL, 0x57cb23ecfae804e1ULL, 0x0fd2e39eada3eba9ULL, 0x08c8055e31c5d5c3ULL}}, {{0x96d8f684bdfc77beULL, 0xb530e4f43b66d0e2ULL, 0x184a88ff379652fdULL, 0x57cb23ecfae804e1ULL, 0x0fd2e39eada3eba9ULL, 0x08c8055e31c5d5c3ULL}}},
  {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0xbf0a71c71c91b406ULL, 0x4d6d55d28b7638fdULL, 0x9d82f98e5f205aeeULL, 0xa27aa27b1d1a18d5ULL, 0x02c3b2b2d2938e86ULL, 0x0c7d13420b09807fULL}}},
  {{{0xd7f9555555531c74ULL, 0x21cffff748daaaa8ULL, 0x5a9ad1866c9bbe46ULL, 0x4870a2210221d251ULL, 0x4a0db369c0a32af1ULL, 0x02b1ccc429ff56afULL}}, {{0xe205aaaaaaac8e37ULL, 0xfcdc000768795556ULL, 0x0c96011a8a1537ddULL, 0x1c06a963f163406eULL, 0x010df44c82a881e6ULL, 0x174f45260f808febULL}}},
  {{{0xa470bda12f67f35cULL, 0xc0fe38e23327b425ULL, 0xc9d3d0f2c6f0678dULL, 0x1c55c9935b5a982eULL, 0x27f6c0e2f0746764ULL, 0x117c5e6e28aa9054ULL}}, {{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}},
};
static const fp2 ISO_Y_DEN[4] = {
  {{{0x0162fffffa765adfULL, 0x8f7bea480083fb75ULL, 0x561b3c2259e93611ULL, 0x11e19fc1a9c875d5ULL, 0xca713efc00367660ULL, 0x03c6a03d41da1151ULL}}, {{0x0162fffffa765adfULL, 0x8f7bea480083fb75ULL, 0x561b3c2259e93611ULL, 0x11e19fc1a9c875d5ULL, 0xca713efc00367660ULL, 0x03c6a03d41da1151ULL}}},
  {{{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}, {{0x5db0fffffd3b02c5ULL, 0xd713f52358ebfdbaULL, 0x5ea60761a84d161aULL, 0xbb2c75a34ea6c44aULL, 0x0ac6735921c1119bULL, 0x0ee3d913bdacfbf6ULL}}},
  {{{0x66b10000003affc5ULL, 0xcb1400e764ec0030ULL, 0xa73e5eb56fa5d106ULL, 0x8984c913a0fe09a9ULL, 0x11e10afb78ad7f13ULL, 0x05429d0e3e918f52ULL}}, {{0x534dffffffc4aae6ULL, 0x5397ff174c67ffcfULL, 0xbff273eb870b251dULL, 0xdaf2827152870915ULL, 0x393a9cbaca9e2dc3ULL, 0x14be74dbfaee5748ULL}}},
  {{{0x760900000002fffdULL, 0xebf4000bc40c0002ULL, 0x5f48985753c758baULL, 0x77ce585370525745ULL, 0x5c071a97a256ec6dULL, 0x15f65ec3fa80e493ULL}}, {{0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL, 0x0000000000000000ULL}}},
};

static const fp FP_ZERO = {{0, 0, 0, 0, 0, 0}};
static const fp PLAIN_ONE = {{1, 0, 0, 0, 0, 0}};

/* -- Fp -------------------------------------------------------------------- */

static inline int fp_is_zero(const fp *a) {
    return (a->l[0] | a->l[1] | a->l[2] | a->l[3] | a->l[4] | a->l[5]) == 0;
}

static inline int fp_eq(const fp *a, const fp *b) {
    return ((a->l[0] ^ b->l[0]) | (a->l[1] ^ b->l[1]) | (a->l[2] ^ b->l[2])
            | (a->l[3] ^ b->l[3]) | (a->l[4] ^ b->l[4])
            | (a->l[5] ^ b->l[5])) == 0;
}

/* plain a < b, most significant word first */
static int fp_lt(const fp *a, const fp *b) {
    for (int i = 5; i >= 0; i--) {
        if (a->l[i] != b->l[i]) return a->l[i] < b->l[i];
    }
    return 0;
}

/* r = t - p if t >= p, else t; t < 2p */
static inline void fp_reduce_once(fp *r, const uint64_t t[6]) {
    uint64_t d[6], borrow = 0;
    for (int i = 0; i < 6; i++) {
        u128 s = (u128)t[i] - P_.l[i] - borrow;
        d[i] = (uint64_t)s;
        borrow = (uint64_t)(s >> 64) & 1;
    }
    const uint64_t *src = borrow ? t : d;
    for (int i = 0; i < 6; i++) r->l[i] = src[i];
}

static inline void fp_add(fp *r, const fp *a, const fp *b) {
    uint64_t t[6];
    u128 c = 0;
    for (int i = 0; i < 6; i++) {
        c += (u128)a->l[i] + b->l[i];
        t[i] = (uint64_t)c;
        c >>= 64;
    }
    fp_reduce_once(r, t);  /* a + b < 2p < 2^382: no carry out */
}

static inline void fp_sub(fp *r, const fp *a, const fp *b) {
    uint64_t t[6], borrow = 0;
    for (int i = 0; i < 6; i++) {
        u128 s = (u128)a->l[i] - b->l[i] - borrow;
        t[i] = (uint64_t)s;
        borrow = (uint64_t)(s >> 64) & 1;
    }
    if (borrow) {
        u128 c = 0;
        for (int i = 0; i < 6; i++) {
            c += (u128)t[i] + P_.l[i];
            t[i] = (uint64_t)c;
            c >>= 64;
        }
    }
    for (int i = 0; i < 6; i++) r->l[i] = t[i];
}

static inline void fp_neg(fp *r, const fp *a) {
    if (fp_is_zero(a)) {
        *r = FP_ZERO;
        return;
    }
    fp_sub(r, &P_, a);
}

/* Montgomery product a * b / 2^384 mod p (CIOS). Needs b < p and a < 2^384;
 * the result is fully reduced. Kept out of line: inlined at its hundreds of
 * call sites it costs a second of compile time and gains nothing. */
__attribute__((noinline)) static void fp_mul(fp *r, const fp *a,
                                             const fp *b) {
    uint64_t t[8] = {0, 0, 0, 0, 0, 0, 0, 0};
#pragma GCC unroll 6
    for (int i = 0; i < 6; i++) {
        u128 c = 0;
        uint64_t bi = b->l[i];
#pragma GCC unroll 6
        for (int j = 0; j < 6; j++) {
            c += (u128)a->l[j] * bi + t[j];
            t[j] = (uint64_t)c;
            c >>= 64;
        }
        u128 s = (u128)t[6] + c;
        t[6] = (uint64_t)s;
        t[7] = (uint64_t)(s >> 64);
        uint64_t m = t[0] * INV;
        c = ((u128)m * P_.l[0] + t[0]) >> 64;
#pragma GCC unroll 5
        for (int j = 1; j < 6; j++) {
            c += (u128)m * P_.l[j] + t[j];
            t[j - 1] = (uint64_t)c;
            c >>= 64;
        }
        s = (u128)t[6] + c;
        t[5] = (uint64_t)s;
        t[6] = t[7] + (uint64_t)(s >> 64);
    }
    fp_reduce_once(r, t);
}

static inline void fp_sqr(fp *r, const fp *a) { fp_mul(r, a, a); }

/* r = a^e, e plain, 4-bit fixed window */
static void fp_pow(fp *r, const fp *a, const fp *e) {
    fp table[16];
    table[0] = ONE;
    table[1] = *a;
    for (int i = 2; i < 16; i++) fp_mul(&table[i], &table[i - 1], a);
    fp acc = ONE;
    int started = 0;
    for (int i = 95; i >= 0; i--) {
        unsigned nib = (unsigned)(e->l[i / 16] >> (4 * (i % 16))) & 0xF;
        if (started) {
            fp_sqr(&acc, &acc);
            fp_sqr(&acc, &acc);
            fp_sqr(&acc, &acc);
            fp_sqr(&acc, &acc);
        }
        if (nib) {
            fp_mul(&acc, &acc, &table[nib]);
            started = 1;
        }
    }
    *r = acc;
}

/* Fermat inverse; inv(0) == 0 */
static inline void fp_inv(fp *r, const fp *a) { fp_pow(r, a, &EXP_INV); }

/* square root for p = 3 mod 4, the oracle fq_sqrt: the candidate
 * a^((p+1)/4), accepted iff it squares back to a. It is taken as s * a with
 * s = a^((p-3)/4), and s is then 1 / root (s * root = a^((p-1)/2) = 1), so
 * a caller that divides by the root needs no inversion; rinv may be NULL.
 * For a == 0 both are 0, as inv(0) == 0. */
static int fp_sqrt_inv(fp *r, fp *rinv, const fp *a) {
    fp s, c, c2;
    fp_pow(&s, a, &EXP_SQRT_INV);
    fp_mul(&c, &s, a);
    fp_sqr(&c2, &c);
    if (!fp_eq(&c2, a)) return 0;
    *r = c;
    if (rinv) *rinv = s;
    return 1;
}

static inline int fp_sqrt(fp *r, const fp *a) { return fp_sqrt_inv(r, NULL, a); }

static inline void fp_to_mont(fp *r, const fp *plain) { fp_mul(r, plain, &R2); }
static inline void fp_from_mont(fp *r, const fp *a) { fp_mul(r, a, &PLAIN_ONE); }

/* 48 big-endian bytes -> plain words (may be >= p) */
static void fp_from_be48(fp *r, const uint8_t *b) {
    for (int i = 0; i < 6; i++) {
        uint64_t w = 0;
        for (int k = 0; k < 8; k++) w = (w << 8) | b[8 * (5 - i) + k];
        r->l[i] = w;
    }
}

/* a 64-byte big-endian field draw, reduced mod p, in Montgomery form:
 * lo (384 bits) * R2 + hi (128 bits) * R3 */
static void fp_from_draw64(fp *r, const uint8_t *b) {
    fp hi = FP_ZERO, lo, t;
    for (int i = 0; i < 2; i++) {
        uint64_t w = 0;
        for (int k = 0; k < 8; k++) w = (w << 8) | b[8 * (1 - i) + k];
        hi.l[i] = w;
    }
    fp_from_be48(&lo, b + 16);
    fp_mul(&t, &lo, &R2);
    fp_mul(&hi, &hi, &R3);
    fp_add(r, &t, &hi);
}

/* Montgomery -> the repo's 15 x 28-bit limbs of a * 2^420 mod p */
static void fp_to_repo(uint64_t *out, const fp *a) {
    fp v;
    fp_mul(&v, a, &TO_REPO);
    uint64_t w[7];  /* 420 limb bits over 384 value bits: a zero top word */
    memcpy(w, v.l, sizeof v.l);
    w[6] = 0;
    for (int k = 0; k < REPO_LIMBS; k++) {
        int bit = REPO_BITS * k, i = bit / 64, off = bit % 64;
        uint64_t x = w[i] >> off;
        if (off > 64 - REPO_BITS) x |= w[i + 1] << (64 - off);
        out[k] = x & REPO_MASK;
    }
}

/* the repo's limbs (value v = a * 2^420 mod p, limbs < 2^29) -> Montgomery
 * a * 2^384 = v * 2^-36: lo * 2^348 + hi * 2^732, each over 2^384 */
static void fp_from_repo(fp *r, const uint64_t *in) {
    uint64_t w[8] = {0, 0, 0, 0, 0, 0, 0, 0};
    for (int k = 0; k < REPO_LIMBS; k++) {
        int bit = REPO_BITS * k, i = bit / 64, off = bit % 64;
        u128 add = (u128)in[k] << off;
        for (int j = i; j < 8 && add; j++) {
            u128 s = (u128)w[j] + (uint64_t)add;
            w[j] = (uint64_t)s;
            add = (add >> 64) + (s >> 64);
        }
    }
    fp lo, hi = FP_ZERO, t;
    for (int i = 0; i < 6; i++) lo.l[i] = w[i];
    hi.l[0] = w[6];
    hi.l[1] = w[7];
    fp_mul(&t, &lo, &FROM_REPO_LO);
    fp_mul(&hi, &hi, &FROM_REPO_HI);
    fp_add(r, &t, &hi);
}

/* Montgomery batch inversion (codec.int_batch_inverse): one inversion for
 * the batch, three products per element, zero lanes skipped (inv(0) == 0).
 * out may alias in; pref is scratch of n elements. */
static void fp_batch_inv(fp *out, const fp *in, size_t n, fp *pref) {
    fp acc = ONE, inv;
    for (size_t i = 0; i < n; i++) {
        pref[i] = acc;
        if (!fp_is_zero(&in[i])) fp_mul(&acc, &acc, &in[i]);
    }
    fp_inv(&inv, &acc);
    for (size_t i = n; i-- > 0;) {
        fp v = in[i];
        if (fp_is_zero(&v)) {
            out[i] = FP_ZERO;
            continue;
        }
        fp_mul(&out[i], &inv, &pref[i]);
        fp_mul(&inv, &inv, &v);
    }
}

/* -- Fp2 = Fp[u]/(u^2 + 1) ---------------------------------------------- */

static inline int fp2_is_zero(const fp2 *a) {
    return fp_is_zero(&a->c0) && fp_is_zero(&a->c1);
}

static inline int fp2_eq(const fp2 *a, const fp2 *b) {
    return fp_eq(&a->c0, &b->c0) && fp_eq(&a->c1, &b->c1);
}

static inline void fp2_add(fp2 *r, const fp2 *a, const fp2 *b) {
    fp_add(&r->c0, &a->c0, &b->c0);
    fp_add(&r->c1, &a->c1, &b->c1);
}

static inline void fp2_sub(fp2 *r, const fp2 *a, const fp2 *b) {
    fp_sub(&r->c0, &a->c0, &b->c0);
    fp_sub(&r->c1, &a->c1, &b->c1);
}

static inline void fp2_neg(fp2 *r, const fp2 *a) {
    fp_neg(&r->c0, &a->c0);
    fp_neg(&r->c1, &a->c1);
}

static inline void fp2_conj(fp2 *r, const fp2 *a) {
    r->c0 = a->c0;
    fp_neg(&r->c1, &a->c1);
}

static void fp2_mul(fp2 *r, const fp2 *a, const fp2 *b) {
    fp t0, t1, s0, s1, m;
    fp_mul(&t0, &a->c0, &b->c0);
    fp_mul(&t1, &a->c1, &b->c1);
    fp_add(&s0, &a->c0, &a->c1);
    fp_add(&s1, &b->c0, &b->c1);
    fp_mul(&m, &s0, &s1);
    fp_sub(&r->c0, &t0, &t1);
    fp_sub(&m, &m, &t0);
    fp_sub(&r->c1, &m, &t1);
}

static void fp2_sqr(fp2 *r, const fp2 *a) {
    fp s, d, p;
    fp_add(&s, &a->c0, &a->c1);
    fp_sub(&d, &a->c0, &a->c1);
    fp_mul(&p, &a->c0, &a->c1);
    fp_mul(&r->c0, &s, &d);
    fp_add(&r->c1, &p, &p);
}

static inline void fp2_norm(fp *r, const fp2 *a) {
    fp t;
    fp_sqr(r, &a->c0);
    fp_sqr(&t, &a->c1);
    fp_add(r, r, &t);
}

/* conj(a) / norm(a), given ninv = 1 / norm(a) */
static inline void fp2_inv_with(fp2 *r, const fp2 *a, const fp *ninv) {
    fp_mul(&r->c0, &a->c0, ninv);
    fp_mul(&r->c1, &a->c1, ninv);
    fp_neg(&r->c1, &r->c1);
}

/* the oracle Fq2.sqrt complex method, same root choice; 0 iff it returns
 * None */
static int fp2_sqrt(fp2 *r, const fp2 *v) {
    const fp *a = &v->c0, *b = &v->c1;
    fp s;
    if (fp_is_zero(b)) {
        if (fp_sqrt(&s, a)) {
            r->c0 = s;
            r->c1 = FP_ZERO;
            return 1;
        }
        fp na;
        fp_neg(&na, a);
        if (fp_sqrt(&s, &na)) {
            r->c0 = FP_ZERO;
            r->c1 = s;
            return 1;
        }
        return 0;
    }
    fp n, alpha, delta, x0, x0inv;
    fp2_norm(&n, v);
    if (!fp_sqrt(&alpha, &n)) return 0;
    fp_add(&delta, a, &alpha);
    fp_mul(&delta, &delta, &INV2);
    if (!fp_sqrt_inv(&x0, &x0inv, &delta)) {
        fp_sub(&delta, a, &alpha);
        fp_mul(&delta, &delta, &INV2);
        if (!fp_sqrt_inv(&x0, &x0inv, &delta)) return 0;
    }
    /* x1 = b / (2 x0) */
    fp2 cand, sq;
    cand.c0 = x0;
    fp_mul(&cand.c1, b, &x0inv);
    fp_mul(&cand.c1, &cand.c1, &INV2);
    fp2_sqr(&sq, &cand);
    if (!fp2_eq(&sq, v)) return 0;
    *r = cand;
    return 1;
}

/* RFC 9380 sgn0 on the value (not its Montgomery residue) */
static int fp2_sgn0(const fp2 *a) {
    fp c0, c1;
    fp_from_mont(&c0, &a->c0);
    fp_from_mont(&c1, &a->c1);
    return (int)(c0.l[0] & 1) | (fp_is_zero(&c0) & (int)(c1.l[0] & 1));
}

/* -- Jacobian points: the oracle's ec_double / ec_add branch structure --- */

static void g1_dbl(g1j *r, const g1j *p) {
    if (fp_is_zero(&p->z) || fp_is_zero(&p->y)) {
        memset(r, 0, sizeof *r);
        return;
    }
    fp A, B, C, t, D, E, F, X3, Y3, Z3, c8;
    fp_sqr(&A, &p->x);
    fp_sqr(&B, &p->y);
    fp_sqr(&C, &B);
    fp_add(&t, &p->x, &B);
    fp_sqr(&t, &t);
    fp_sub(&t, &t, &A);
    fp_sub(&t, &t, &C);
    fp_add(&D, &t, &t);
    fp_add(&E, &A, &A);
    fp_add(&E, &E, &A);
    fp_sqr(&F, &E);
    fp_add(&t, &D, &D);
    fp_sub(&X3, &F, &t);
    fp_add(&c8, &C, &C);
    fp_add(&c8, &c8, &c8);
    fp_add(&c8, &c8, &c8);
    fp_sub(&t, &D, &X3);
    fp_mul(&Y3, &E, &t);
    fp_sub(&Y3, &Y3, &c8);
    fp_mul(&Z3, &p->y, &p->z);
    fp_add(&Z3, &Z3, &Z3);
    r->x = X3;
    r->y = Y3;
    r->z = Z3;
}

static void g1_add(g1j *r, const g1j *p1, const g1j *p2) {
    if (fp_is_zero(&p1->z)) {
        *r = *p2;
        return;
    }
    if (fp_is_zero(&p2->z)) {
        *r = *p1;
        return;
    }
    fp Z1Z1, Z2Z2, U1, U2, S1, S2, H, I, J, rr, V, t, X3, Y3, Z3;
    fp_sqr(&Z1Z1, &p1->z);
    fp_sqr(&Z2Z2, &p2->z);
    fp_mul(&U1, &p1->x, &Z2Z2);
    fp_mul(&U2, &p2->x, &Z1Z1);
    fp_mul(&S1, &p1->y, &p2->z);
    fp_mul(&S1, &S1, &Z2Z2);
    fp_mul(&S2, &p2->y, &p1->z);
    fp_mul(&S2, &S2, &Z1Z1);
    if (fp_eq(&U1, &U2)) {
        if (fp_eq(&S1, &S2)) {
            g1_dbl(r, p1);
        } else {
            memset(r, 0, sizeof *r);
        }
        return;
    }
    fp_sub(&H, &U2, &U1);
    fp_add(&I, &H, &H);
    fp_sqr(&I, &I);
    fp_mul(&J, &H, &I);
    fp_sub(&rr, &S2, &S1);
    fp_add(&rr, &rr, &rr);
    fp_mul(&V, &U1, &I);
    fp_sqr(&X3, &rr);
    fp_sub(&X3, &X3, &J);
    fp_add(&t, &V, &V);
    fp_sub(&X3, &X3, &t);
    fp_sub(&t, &V, &X3);
    fp_mul(&Y3, &rr, &t);
    fp_mul(&t, &S1, &J);
    fp_add(&t, &t, &t);
    fp_sub(&Y3, &Y3, &t);
    fp_add(&t, &p1->z, &p2->z);
    fp_sqr(&t, &t);
    fp_sub(&t, &t, &Z1Z1);
    fp_sub(&t, &t, &Z2Z2);
    fp_mul(&Z3, &t, &H);
    r->x = X3;
    r->y = Y3;
    r->z = Z3;
}

/* [k]p, LSB-first double-and-add (the oracle ec_mul schedule) */
static void g1_mul(g1j *r, const g1j *p, uint64_t k) {
    g1j acc, addend = *p;
    memset(&acc, 0, sizeof acc);
    while (k) {
        if (k & 1) g1_add(&acc, &acc, &addend);
        k >>= 1;
        if (k) g1_dbl(&addend, &addend);
    }
    *r = acc;
}

static void g2_dbl(g2j *r, const g2j *p) {
    if (fp2_is_zero(&p->z) || fp2_is_zero(&p->y)) {
        memset(r, 0, sizeof *r);
        return;
    }
    fp2 A, B, C, t, D, E, F, X3, Y3, Z3, c8;
    fp2_sqr(&A, &p->x);
    fp2_sqr(&B, &p->y);
    fp2_sqr(&C, &B);
    fp2_add(&t, &p->x, &B);
    fp2_sqr(&t, &t);
    fp2_sub(&t, &t, &A);
    fp2_sub(&t, &t, &C);
    fp2_add(&D, &t, &t);
    fp2_add(&E, &A, &A);
    fp2_add(&E, &E, &A);
    fp2_sqr(&F, &E);
    fp2_add(&t, &D, &D);
    fp2_sub(&X3, &F, &t);
    fp2_add(&c8, &C, &C);
    fp2_add(&c8, &c8, &c8);
    fp2_add(&c8, &c8, &c8);
    fp2_sub(&t, &D, &X3);
    fp2_mul(&Y3, &E, &t);
    fp2_sub(&Y3, &Y3, &c8);
    fp2_mul(&Z3, &p->y, &p->z);
    fp2_add(&Z3, &Z3, &Z3);
    r->x = X3;
    r->y = Y3;
    r->z = Z3;
}

static void g2_add(g2j *r, const g2j *p1, const g2j *p2) {
    if (fp2_is_zero(&p1->z)) {
        *r = *p2;
        return;
    }
    if (fp2_is_zero(&p2->z)) {
        *r = *p1;
        return;
    }
    fp2 Z1Z1, Z2Z2, U1, U2, S1, S2, H, I, J, rr, V, t, X3, Y3, Z3;
    fp2_sqr(&Z1Z1, &p1->z);
    fp2_sqr(&Z2Z2, &p2->z);
    fp2_mul(&U1, &p1->x, &Z2Z2);
    fp2_mul(&U2, &p2->x, &Z1Z1);
    fp2_mul(&S1, &p1->y, &p2->z);
    fp2_mul(&S1, &S1, &Z2Z2);
    fp2_mul(&S2, &p2->y, &p1->z);
    fp2_mul(&S2, &S2, &Z1Z1);
    if (fp2_eq(&U1, &U2)) {
        if (fp2_eq(&S1, &S2)) {
            g2_dbl(r, p1);
        } else {
            memset(r, 0, sizeof *r);
        }
        return;
    }
    fp2_sub(&H, &U2, &U1);
    fp2_add(&I, &H, &H);
    fp2_sqr(&I, &I);
    fp2_mul(&J, &H, &I);
    fp2_sub(&rr, &S2, &S1);
    fp2_add(&rr, &rr, &rr);
    fp2_mul(&V, &U1, &I);
    fp2_sqr(&X3, &rr);
    fp2_sub(&X3, &X3, &J);
    fp2_add(&t, &V, &V);
    fp2_sub(&X3, &X3, &t);
    fp2_sub(&t, &V, &X3);
    fp2_mul(&Y3, &rr, &t);
    fp2_mul(&t, &S1, &J);
    fp2_add(&t, &t, &t);
    fp2_sub(&Y3, &Y3, &t);
    fp2_add(&t, &p1->z, &p2->z);
    fp2_sqr(&t, &t);
    fp2_sub(&t, &t, &Z1Z1);
    fp2_sub(&t, &t, &Z2Z2);
    fp2_mul(&Z3, &t, &H);
    r->x = X3;
    r->y = Y3;
    r->z = Z3;
}

static void g2_mul(g2j *r, const g2j *p, uint64_t k) {
    g2j acc, addend = *p;
    memset(&acc, 0, sizeof acc);
    while (k) {
        if (k & 1) g2_add(&acc, &acc, &addend);
        k >>= 1;
        if (k) g2_dbl(&addend, &addend);
    }
    *r = acc;
}

static inline void g2_neg(g2j *r, const g2j *p) {
    r->x = p->x;
    fp2_neg(&r->y, &p->y);
    r->z = p->z;
}

/* psi on Jacobian coordinates: (cx conj X : cy conj Y : conj Z) */
static void g2_psi(g2j *r, const g2j *p) {
    fp2 t;
    fp2_conj(&t, &p->x);
    fp2_mul(&r->x, &PSI_CX, &t);
    fp2_conj(&t, &p->y);
    fp2_mul(&r->y, &PSI_CY, &t);
    fp2_conj(&r->z, &p->z);
}

/* -- hash-to-G2 (RFC 9380 BLS12381G2_XMD:SHA-256_SSWU_RO_) --------------- */

static void gprime(fp2 *r, const fp2 *x) {
    fp2 x3, ax;
    fp2_sqr(&x3, x);
    fp2_mul(&x3, &x3, x);
    fp2_mul(&ax, &SSWU_A, x);
    fp2_add(r, &x3, &ax);
    fp2_add(r, r, &SSWU_B);
}

static void horner(fp2 *r, const fp2 *coeffs, int n, const fp2 *x) {
    fp2 acc = coeffs[n - 1];
    for (int i = n - 2; i >= 0; i--) {
        fp2_mul(&acc, &acc, x);
        fp2_add(&acc, &acc, &coeffs[i]);
    }
    *r = acc;
}

/* SSWU for one draw given 1/norm(tv2) (unused when tv2 == 0); 0 when
 * neither candidate has a square root (not reachable for these curve
 * constants) */
static int sswu(fp2 *qx, fp2 *qy, const fp2 *u, const fp2 *tv1,
                const fp2 *tv2, const fp *ninv) {
    fp2 x1, gx, y;
    if (fp2_is_zero(tv2)) {
        x1 = X1_EXC;
    } else {
        fp2 inv_tv2;
        fp2_inv_with(&inv_tv2, tv2, ninv);
        fp_add(&inv_tv2.c0, &inv_tv2.c0, &ONE);
        fp2_mul(&x1, &NEG_B_OVER_A, &inv_tv2);
    }
    gprime(&gx, &x1);
    if (fp2_sqrt(&y, &gx)) {
        *qx = x1;
    } else {
        fp2_mul(qx, tv1, &x1);
        gprime(&gx, qx);
        if (!fp2_sqrt(&y, &gx)) return 0;
    }
    if (fp2_sgn0(u) != fp2_sgn0(&y)) fp2_neg(&y, &y);
    *qy = y;
    return 1;
}

/* Batched hash-to-G2 from the field draws.
 * uniform: n x 256 bytes, each message's expand_message_xmd output (u0.c0,
 * u0.c1, u1.c0, u1.c1 as 64-byte big-endian draws).
 * out: n x 4 x 15 repo limbs [x.c0, x.c1, y.c0, y.c1] of the affine point.
 * Returns 0, 1 (SSWU found no square root), 2 (a point at infinity) or -1
 * (out of memory). */
int bls_hash_to_g2(const uint8_t *uniform, size_t n, uint64_t *out) {
    if (n == 0) return 0;
    size_t m = 2 * n;  /* draws, message-major */
    fp2 *us = malloc(m * sizeof(fp2));
    fp2 *tv1 = malloc(m * sizeof(fp2));
    fp2 *tv2 = malloc(m * sizeof(fp2));
    fp2 *qx = malloc(m * sizeof(fp2));
    fp2 *qy = malloc(m * sizeof(fp2));
    fp2 *dens = malloc(2 * m * sizeof(fp2));
    fp *norms = malloc(2 * m * sizeof(fp));
    fp *scratch = malloc(2 * m * sizeof(fp));
    g2j *accs = malloc(n * sizeof(g2j));
    int rc = 0;
    if (!us || !tv1 || !tv2 || !qx || !qy || !dens || !norms || !scratch
        || !accs) {
        rc = -1;
        goto done;
    }
    /* SSWU phase 1: tv1, tv2 per draw; 1/norm(tv2) through one ladder */
    for (size_t i = 0; i < m; i++) {
        fp_from_draw64(&us[i].c0, uniform + 128 * i);
        fp_from_draw64(&us[i].c1, uniform + 128 * i + 64);
        fp2 u2;
        fp2_sqr(&u2, &us[i]);
        fp2_mul(&tv1[i], &SSWU_Z, &u2);
        fp2_sqr(&tv2[i], &tv1[i]);
        fp2_add(&tv2[i], &tv2[i], &tv1[i]);
        fp2_norm(&norms[i], &tv2[i]);
    }
    fp_batch_inv(norms, norms, m, scratch);
    for (size_t i = 0; i < m; i++) {
        if (!sswu(&qx[i], &qy[i], &us[i], &tv1[i], &tv2[i], &norms[i])) {
            rc = 1;
            goto done;
        }
    }
    /* iso map: x_den and y_den of every draw through one ladder */
    for (size_t i = 0; i < m; i++) {
        horner(&dens[2 * i], ISO_X_DEN, 3, &qx[i]);
        horner(&dens[2 * i + 1], ISO_Y_DEN, 4, &qx[i]);
        fp2_norm(&norms[2 * i], &dens[2 * i]);
        fp2_norm(&norms[2 * i + 1], &dens[2 * i + 1]);
    }
    fp_batch_inv(norms, norms, 2 * m, scratch);
    for (size_t i = 0; i < m; i++) {
        fp2 xn, yn, di;
        horner(&xn, ISO_X_NUM, 4, &qx[i]);
        horner(&yn, ISO_Y_NUM, 4, &qx[i]);
        fp2_mul(&yn, &qy[i], &yn);
        fp2_inv_with(&di, &dens[2 * i], &norms[2 * i]);
        fp2_mul(&qx[i], &xn, &di);
        fp2_inv_with(&di, &dens[2 * i + 1], &norms[2 * i + 1]);
        fp2_mul(&qy[i], &yn, &di);
    }
    /* add, then clear the cofactor (Budroni-Pintore, the oracle's
     * clear_cofactor_g2): [x^2 - x - 1]P + [x - 1]psi(P) + psi^2(2P), with
     * [|x|]psi(P) taken as psi([|x|]P) (psi is a group endomorphism) */
    for (size_t i = 0; i < n; i++) {
        g2j q0 = {qx[2 * i], qy[2 * i], {ONE, FP_ZERO}};
        g2j q1 = {qx[2 * i + 1], qy[2 * i + 1], {ONE, FP_ZERO}};
        g2j r, t1, txx, psi_p, t2, psi2_2p, neg, acc;
        g2_add(&r, &q0, &q1);
        g2_mul(&t1, &r, X_ABS);         /* [-x]P */
        g2_mul(&txx, &t1, X_ABS);       /* [x^2]P */
        g2_psi(&psi_p, &r);
        g2_psi(&t2, &t1);               /* [-x]psi(P) */
        g2_dbl(&psi2_2p, &r);
        g2_psi(&psi2_2p, &psi2_2p);
        g2_psi(&psi2_2p, &psi2_2p);
        g2_add(&acc, &txx, &t1);
        g2_neg(&neg, &r);
        g2_add(&acc, &acc, &neg);
        g2_neg(&neg, &t2);
        g2_add(&acc, &acc, &neg);
        g2_neg(&neg, &psi_p);
        g2_add(&acc, &acc, &neg);
        g2_add(&acc, &acc, &psi2_2p);
        if (fp2_is_zero(&acc.z)) {
            rc = 2;
            goto done;
        }
        accs[i] = acc;
        fp2_norm(&norms[i], &acc.z);
    }
    /* Jacobian -> affine, every Z through one ladder */
    fp_batch_inv(norms, norms, n, scratch);
    for (size_t i = 0; i < n; i++) {
        fp2 zi, zi2, x, y;
        fp2_inv_with(&zi, &accs[i].z, &norms[i]);
        fp2_sqr(&zi2, &zi);
        fp2_mul(&x, &accs[i].x, &zi2);
        fp2_mul(&zi2, &zi2, &zi);
        fp2_mul(&y, &accs[i].y, &zi2);
        uint64_t *o = out + (size_t)4 * REPO_LIMBS * i;
        fp_to_repo(o, &x.c0);
        fp_to_repo(o + REPO_LIMBS, &x.c1);
        fp_to_repo(o + 2 * REPO_LIMBS, &y.c0);
        fp_to_repo(o + 3 * REPO_LIMBS, &y.c1);
    }
done:
    free(us);
    free(tv1);
    free(tv2);
    free(qx);
    free(qy);
    free(dens);
    free(norms);
    free(scratch);
    free(accs);
    return rc;
}

/* -- decompression (ZCash format) and subgroup checks -------------------- */

/* status per item: 0 ok, 1 x out of range, 2 x not on curve */

/* raw: n x 48 flag-stripped big-endian x; sign: n flags (1 = the larger y).
 * out: n x 2 x 15 repo limbs [x, y]. */
int bls_g1_decompress(const uint8_t *raw, const uint8_t *sign, size_t n,
                      uint64_t *out, int32_t *status) {
    for (size_t i = 0; i < n; i++) {
        fp xp, x, y2, y, yp;
        fp_from_be48(&xp, raw + 48 * i);
        if (!fp_lt(&xp, &P_)) {
            status[i] = 1;
            continue;
        }
        fp_to_mont(&x, &xp);
        fp_sqr(&y2, &x);
        fp_mul(&y2, &y2, &x);
        fp_add(&y2, &y2, &FOUR);
        if (!fp_sqrt(&y, &y2)) {
            status[i] = 2;
            continue;
        }
        fp_from_mont(&yp, &y);
        if ((sign[i] != 0) != fp_lt(&HALF, &yp)) fp_neg(&y, &y);
        fp_to_repo(out + 2 * REPO_LIMBS * i, &x);
        fp_to_repo(out + 2 * REPO_LIMBS * i + REPO_LIMBS, &y);
        status[i] = 0;
    }
    return 0;
}

/* raw: n x 96 flag-stripped big-endian (x.c1, then x.c0); sign as above.
 * out: n x 4 x 15 repo limbs [x.c0, x.c1, y.c0, y.c1]. */
int bls_g2_decompress(const uint8_t *raw, const uint8_t *sign, size_t n,
                      uint64_t *out, int32_t *status) {
    for (size_t i = 0; i < n; i++) {
        fp x1p, x0p;
        fp2 x, y2, y;
        fp_from_be48(&x1p, raw + 96 * i);
        fp_from_be48(&x0p, raw + 96 * i + 48);
        if (!fp_lt(&x0p, &P_) || !fp_lt(&x1p, &P_)) {
            status[i] = 1;
            continue;
        }
        fp_to_mont(&x.c0, &x0p);
        fp_to_mont(&x.c1, &x1p);
        fp2_sqr(&y2, &x);
        fp2_mul(&y2, &y2, &x);
        fp2_add(&y2, &y2, &B_G2);
        if (!fp2_sqrt(&y, &y2)) {
            status[i] = 2;
            continue;
        }
        /* lexicographic sign: c1 > (p-1)/2, or c1 == 0 and c0 > (p-1)/2 */
        fp y0p, y1p;
        fp_from_mont(&y0p, &y.c0);
        fp_from_mont(&y1p, &y.c1);
        int large = fp_lt(&HALF, &y1p)
                    || (fp_is_zero(&y1p) && fp_lt(&HALF, &y0p));
        if ((sign[i] != 0) != large) fp2_neg(&y, &y);
        uint64_t *o = out + (size_t)4 * REPO_LIMBS * i;
        fp_to_repo(o, &x.c0);
        fp_to_repo(o + REPO_LIMBS, &x.c1);
        fp_to_repo(o + 2 * REPO_LIMBS, &y.c0);
        fp_to_repo(o + 3 * REPO_LIMBS, &y.c1);
        status[i] = 0;
    }
    return 0;
}

/* G1 membership of on-curve affine points (n x 2 x 15 repo limbs), the
 * codec's GLV test: phi(P) == -[z^2]P, [z^2]P as two 64-bit ladders,
 * compared cross-multiplied. ok: n bytes. */
int bls_g1_subgroup_check(const uint64_t *pts, size_t n, uint8_t *ok) {
    for (size_t i = 0; i < n; i++) {
        g1j p, q;
        fp_from_repo(&p.x, pts + 2 * REPO_LIMBS * i);
        fp_from_repo(&p.y, pts + 2 * REPO_LIMBS * i + REPO_LIMBS);
        p.z = ONE;
        g1_mul(&q, &p, X_ABS);
        g1_mul(&q, &q, X_ABS);
        if (fp_is_zero(&q.z)) {
            ok[i] = 0;  /* ord(P) | z^2 and gcd(r, z^2) == 1: not in G1 */
            continue;
        }
        fp z2, z3, lhs, ny;
        fp_sqr(&z2, &q.z);
        fp_mul(&z3, &z2, &q.z);
        fp_mul(&lhs, &BETA_G1, &p.x);
        fp_mul(&lhs, &lhs, &z2);
        fp_neg(&ny, &p.y);
        fp_mul(&ny, &ny, &z3);
        ok[i] = fp_eq(&lhs, &q.x) && fp_eq(&ny, &q.y);
    }
    return 0;
}

/* G2 membership of on-curve affine points (n x 4 x 15 repo limbs), the
 * psi criterion psi(P) == -[|x|]P, compared cross-multiplied. */
int bls_g2_subgroup_check(const uint64_t *pts, size_t n, uint8_t *ok) {
    for (size_t i = 0; i < n; i++) {
        const uint64_t *o = pts + (size_t)4 * REPO_LIMBS * i;
        g2j p, q;
        fp_from_repo(&p.x.c0, o);
        fp_from_repo(&p.x.c1, o + REPO_LIMBS);
        fp_from_repo(&p.y.c0, o + 2 * REPO_LIMBS);
        fp_from_repo(&p.y.c1, o + 3 * REPO_LIMBS);
        p.z.c0 = ONE;
        p.z.c1 = FP_ZERO;
        g2_mul(&q, &p, X_ABS);
        if (fp2_is_zero(&q.z)) {
            ok[i] = 0;  /* psi of a finite point is finite */
            continue;
        }
        fp2 px, py, t, z2, z3, ny;
        fp2_conj(&t, &p.x);
        fp2_mul(&px, &PSI_CX, &t);
        fp2_conj(&t, &p.y);
        fp2_mul(&py, &PSI_CY, &t);
        fp2_sqr(&z2, &q.z);
        fp2_mul(&z3, &z2, &q.z);
        fp2_mul(&px, &px, &z2);
        fp2_mul(&py, &py, &z3);
        fp2_neg(&ny, &q.y);
        ok[i] = fp2_eq(&px, &q.x) && fp2_eq(&py, &ny);
    }
    return 0;
}

/* -- field entry points (plain values, 6 little-endian words each) -------- */

/* out[i] = 1 / in[i] mod p, inv(0) == 0; out may alias in */
int bls_fp_batch_inverse(const uint64_t *in, uint64_t *out, size_t n) {
    if (n == 0) return 0;
    fp *v = malloc(n * sizeof(fp));
    fp *scratch = malloc(n * sizeof(fp));
    if (!v || !scratch) {
        free(v);
        free(scratch);
        return -1;
    }
    for (size_t i = 0; i < n; i++) {
        fp p;
        memcpy(p.l, in + 6 * i, sizeof p.l);
        fp_to_mont(&v[i], &p);
    }
    fp_batch_inv(v, v, n, scratch);
    for (size_t i = 0; i < n; i++) {
        fp p;
        fp_from_mont(&p, &v[i]);
        memcpy(out + 6 * i, p.l, sizeof p.l);
    }
    free(v);
    free(scratch);
    return 0;
}

/* in: n x (c0, c1) plain; out: the oracle's root, ok[i] = 0 where it
 * returns None (out left zero there) */
int bls_fp2_sqrt_batch(const uint64_t *in, uint64_t *out, uint8_t *ok,
                       size_t n) {
    for (size_t i = 0; i < n; i++) {
        fp2 v, r;
        fp p;
        memcpy(p.l, in + 12 * i, sizeof p.l);
        fp_to_mont(&v.c0, &p);
        memcpy(p.l, in + 12 * i + 6, sizeof p.l);
        fp_to_mont(&v.c1, &p);
        ok[i] = (uint8_t)fp2_sqrt(&r, &v);
        if (!ok[i]) {
            memset(&r, 0, sizeof r);
        }
        fp_from_mont(&p, &r.c0);
        memcpy(out + 12 * i, p.l, sizeof p.l);
        fp_from_mont(&p, &r.c1);
        memcpy(out + 12 * i + 6, p.l, sizeof p.l);
    }
    return 0;
}

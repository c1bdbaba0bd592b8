/* Native ed25519 batch verification — CPU fallback hot loop.
 *
 * Why native: the reference's hot loop (types/validator_set.go:685-707)
 * is Go calling an assembly ed25519; our Python CPU path pays ~30%
 * interpreter overhead per signature AND the `cryptography` wheel holds
 * the GIL during verify, so Python threads cannot scale it across cores.
 * This file is the tpu-framework's native runtime answer: one call per
 * batch, GIL released by ctypes, pthreads inside chunk the batch across
 * cores, each thread looping OpenSSL EVP_DigestVerify.
 *
 * Semantics: identical accept/reject to OpenSSL's ed25519 verify
 * (cofactorless, rejects s >= L and non-canonical A), which is what the
 * Python path wraps too.
 *
 * Build: cc -O2 -shared -fPIC -o libcbft_ed25519.so ed25519_batch.c \
 *           -lcrypto -pthread
 */

#include <pthread.h>
#include <stddef.h>
#include <string.h>

/* The build image ships libcrypto.so.3 without dev headers; the EVP
 * functions used below have had a stable ABI since OpenSSL 1.1.1, so we
 * declare them directly. EVP_PKEY_ED25519 == NID_ED25519 == 1087. */
typedef struct evp_pkey_st EVP_PKEY;
typedef struct evp_md_ctx_st EVP_MD_CTX;
typedef struct evp_md_st EVP_MD;
typedef struct engine_st ENGINE;
typedef struct evp_pkey_ctx_st EVP_PKEY_CTX;
#define EVP_PKEY_ED25519 1087
EVP_PKEY *EVP_PKEY_new_raw_public_key(int type, ENGINE *e,
                                      const unsigned char *pub, size_t len);
void EVP_PKEY_free(EVP_PKEY *pkey);
EVP_MD_CTX *EVP_MD_CTX_new(void);
void EVP_MD_CTX_free(EVP_MD_CTX *ctx);
int EVP_DigestVerifyInit(EVP_MD_CTX *ctx, EVP_PKEY_CTX **pctx,
                         const EVP_MD *type, ENGINE *e, EVP_PKEY *pkey);
int EVP_DigestVerify(EVP_MD_CTX *ctx, const unsigned char *sig,
                     size_t siglen, const unsigned char *tbs, size_t tbslen);

EVP_PKEY *EVP_PKEY_new_raw_private_key(int type, ENGINE *e,
                                       const unsigned char *priv, size_t len);
int EVP_PKEY_get_raw_public_key(const EVP_PKEY *pkey, unsigned char *pub,
                                size_t *len);
int EVP_DigestSignInit(EVP_MD_CTX *ctx, EVP_PKEY_CTX **pctx,
                       const EVP_MD *type, ENGINE *e, EVP_PKEY *pkey);
int EVP_DigestSign(EVP_MD_CTX *ctx, unsigned char *sig, size_t *siglen,
                   const unsigned char *tbs, size_t tbslen);

typedef struct {
    const unsigned char *pubs;   /* n * 32 */
    const unsigned char *msgs;   /* concatenated */
    const size_t *msg_off;       /* n offsets into msgs */
    const size_t *msg_len;       /* n lengths */
    const unsigned char *sigs;   /* n * 64 */
    unsigned char *out;          /* n result bytes: 1 ok / 0 bad */
    size_t begin, end;
} chunk_t;

static void *verify_chunk(void *arg)
{
    chunk_t *c = (chunk_t *)arg;
    for (size_t i = c->begin; i < c->end; i++) {
        unsigned char ok = 0;
        EVP_PKEY *pk = EVP_PKEY_new_raw_public_key(
            EVP_PKEY_ED25519, NULL, c->pubs + 32 * i, 32);
        if (pk != NULL) {
            EVP_MD_CTX *ctx = EVP_MD_CTX_new();
            if (ctx != NULL) {
                if (EVP_DigestVerifyInit(ctx, NULL, NULL, NULL, pk) == 1 &&
                    EVP_DigestVerify(ctx, c->sigs + 64 * i, 64,
                                     c->msgs + c->msg_off[i],
                                     c->msg_len[i]) == 1)
                    ok = 1;
                EVP_MD_CTX_free(ctx);
            }
            EVP_PKEY_free(pk);
        }
        c->out[i] = ok;
    }
    return NULL;
}

/* Returns 0 on success. nthreads <= 1 runs inline (no thread spawn). */
int cbft_ed25519_verify_batch(const unsigned char *pubs,
                              const unsigned char *msgs,
                              const size_t *msg_off, const size_t *msg_len,
                              const unsigned char *sigs, unsigned char *out,
                              size_t n, int nthreads)
{
    if (n == 0)
        return 0;
    if (nthreads <= 1 || (size_t)nthreads > n) {
        chunk_t c = {pubs, msgs, msg_off, msg_len, sigs, out, 0, n};
        verify_chunk(&c);
        return 0;
    }
    enum { MAX_THREADS = 64 };
    if (nthreads > MAX_THREADS)
        nthreads = MAX_THREADS;
    pthread_t tids[MAX_THREADS];
    chunk_t chunks[MAX_THREADS];
    size_t per = n / nthreads, rem = n % nthreads, pos = 0;
    int spawned = 0;
    for (int t = 0; t < nthreads; t++) {
        size_t take = per + (t < (int)rem ? 1 : 0);
        chunks[t] = (chunk_t){pubs, msgs, msg_off, msg_len,
                              sigs, out, pos, pos + take};
        pos += take;
        if (t == nthreads - 1) {
            /* run the last chunk on the calling thread */
            verify_chunk(&chunks[t]);
        } else if (pthread_create(&tids[spawned], NULL, verify_chunk,
                                  &chunks[t]) == 0) {
            spawned++;
        } else {
            verify_chunk(&chunks[t]); /* spawn failed: run inline */
        }
    }
    for (int t = 0; t < spawned; t++)
        pthread_join(tids[t], NULL);
    return 0;
}

/* --- single-key sign / keygen ------------------------------------------
 *
 * The image may lack the Python `cryptography` wheel entirely; these two
 * entry points let crypto/ed25519.py keep OpenSSL semantics for signing
 * and seed→pubkey derivation through the same ctypes .so instead of
 * dropping to the (much slower) pure-Python scalar path. */

/* Returns 0 on success; sig_out receives 64 bytes. */
int cbft_ed25519_sign(const unsigned char *seed, const unsigned char *msg,
                      size_t msglen, unsigned char *sig_out)
{
    int rc = 1;
    EVP_PKEY *pk = EVP_PKEY_new_raw_private_key(
        EVP_PKEY_ED25519, NULL, seed, 32);
    if (pk != NULL) {
        EVP_MD_CTX *ctx = EVP_MD_CTX_new();
        if (ctx != NULL) {
            size_t siglen = 64;
            if (EVP_DigestSignInit(ctx, NULL, NULL, NULL, pk) == 1 &&
                EVP_DigestSign(ctx, sig_out, &siglen, msg, msglen) == 1 &&
                siglen == 64)
                rc = 0;
            EVP_MD_CTX_free(ctx);
        }
        EVP_PKEY_free(pk);
    }
    return rc;
}

/* Returns 0 on success; pub_out receives 32 bytes. */
int cbft_ed25519_pub_from_seed(const unsigned char *seed,
                               unsigned char *pub_out)
{
    int rc = 1;
    EVP_PKEY *pk = EVP_PKEY_new_raw_private_key(
        EVP_PKEY_ED25519, NULL, seed, 32);
    if (pk != NULL) {
        size_t publen = 32;
        if (EVP_PKEY_get_raw_public_key(pk, pub_out, &publen) == 1 &&
            publen == 32)
            rc = 0;
        EVP_PKEY_free(pk);
    }
    return rc;
}

/* --- batch challenge scalars: h = SHA-512(R ‖ A ‖ M) mod L ------------
 *
 * Host-side packing cost of the TPU batch/resident verify paths
 * (crypto/tpu/ed25519_batch.py _challenge_scalars): one call per launch,
 * chunked over the threads the caller asks for (native/__init__.py
 * challenge_threads). A lane is four plain SHA512_* calls on a context
 * on the thread's stack (no EVP dispatch, no provider fetch, no lock:
 * OpenSSL 3's EVP_DigestInit_ex(EVP_sha512()) fetched the digest anew
 * every lane) and the fixed 512 -> 253-bit reduction below (no BIGNUM).
 * Output is 32 little-endian bytes per lane; lanes with valid[i] == 0
 * are skipped (left as the caller's zeros). */

/* SHA512_CTX is 216 bytes on every libcrypto since 0.9.8 (8 + 2 + 16
 * u64 words, two ints); declared with room to spare, as the image has
 * no headers. */
typedef union {
    unsigned long long words[32];
} cbft_sha512_ctx;
int SHA512_Init(cbft_sha512_ctx *c);
int SHA512_Update(cbft_sha512_ctx *c, const void *data, size_t len);
int SHA512_Final(unsigned char *md, cbft_sha512_ctx *c);

static long long load21(const unsigned char *in, unsigned bit)
{
    const unsigned char *p = in + (bit >> 3);
    unsigned long long w = (unsigned long long)p[0] |
                           ((unsigned long long)p[1] << 8) |
                           ((unsigned long long)p[2] << 16) |
                           ((unsigned long long)p[3] << 24);
    return (long long)(w >> (bit & 7));
}

/* 2^252 = L - c == -c (mod L) in signed radix-2^21 digits: ref10's
 * sc_reduce constants. */
static const long long CBFT_MINUS_C[6] = {
    666643, 470296, 654183, -997805, 136657, -683901,
};

/* s[hi] * 2^(21 hi) folded into s[hi - 12 .. hi - 7], for hi from
 * ``top`` down to ``bottom``. */
static void fold(long long *s, int top, int bottom)
{
    for (int hi = top; hi >= bottom; hi--) {
        for (int j = 0; j < 6; j++)
            s[hi - 12 + j] += s[hi] * CBFT_MINUS_C[j];
        s[hi] = 0;
    }
}

/* One carry from s[i] into s[i + 1]: ``round`` to the nearest (the
 * limb lands in [-2^20, 2^20)), else the floor (in [0, 2^21)). */
static void carry(long long *s, int i, int round)
{
    long long c = (s[i] + (round ? 1LL << 20 : 0)) >> 21;
    s[i + 1] += c;
    s[i] -= c * (1LL << 21);
}

/* out = in mod L, in: 64 little-endian bytes (a SHA-512 digest), out:
 * 32. ref10's sc_reduce in loops: 24 limbs of 21 bits (the top one 29),
 * folded down twice with carries between, then twice more by the one
 * limb the carries push past 2^252. */
static void sc_reduce64(unsigned char out[32], const unsigned char in[64])
{
    long long s[24];
    for (int i = 0; i < 23; i++)
        s[i] = load21(in, 21 * i) & 2097151;
    s[23] = load21(in, 483);

    fold(s, 23, 18);
    for (int i = 6; i <= 16; i += 2)
        carry(s, i, 1);
    for (int i = 7; i <= 15; i += 2)
        carry(s, i, 1);
    fold(s, 17, 12);
    for (int i = 0; i <= 10; i += 2)
        carry(s, i, 1);
    for (int i = 1; i <= 11; i += 2)
        carry(s, i, 1);
    fold(s, 12, 12);
    for (int i = 0; i <= 11; i++)
        carry(s, i, 0);
    fold(s, 12, 12);
    for (int i = 0; i <= 10; i++)
        carry(s, i, 0);

    unsigned long long acc = 0;
    int bits = 0, pos = 0;
    for (int i = 0; i < 12; i++) {
        acc |= (unsigned long long)s[i] << bits;
        for (bits += 21; bits >= 8; bits -= 8) {
            out[pos++] = (unsigned char)acc;
            acc >>= 8;
        }
    }
    out[pos] = (unsigned char)acc;
}

/* Test entry: the reduction alone, n digests of 64 bytes -> n * 32. */
void cbft_sc_reduce64(const unsigned char *in, unsigned char *out, size_t n)
{
    for (size_t i = 0; i < n; i++)
        sc_reduce64(out + 32 * i, in + 64 * i);
}

typedef struct {
    const unsigned char *pubs;   /* n * 32 (A) */
    const unsigned char *rs;     /* n * 32 (R) */
    const unsigned char *msgs;   /* concatenated */
    const size_t *msg_off;
    const size_t *msg_len;
    const unsigned char *valid;  /* n: 0 = skip lane */
    unsigned char *out;          /* n * 32 LE */
    size_t begin, end;
    int rc;
} hchunk_t;

static void *challenge_chunk(void *arg)
{
    hchunk_t *c = (hchunk_t *)arg;
    cbft_sha512_ctx ctx;
    unsigned char digest[64];
    for (size_t i = c->begin; i < c->end; i++) {
        if (!c->valid[i])
            continue;
        if (SHA512_Init(&ctx) != 1 ||
            SHA512_Update(&ctx, c->rs + 32 * i, 32) != 1 ||
            SHA512_Update(&ctx, c->pubs + 32 * i, 32) != 1 ||
            SHA512_Update(&ctx, c->msgs + c->msg_off[i],
                          c->msg_len[i]) != 1 ||
            SHA512_Final(digest, &ctx) != 1) {
            c->rc = 1;
            return NULL;
        }
        sc_reduce64(c->out + 32 * i, digest);
    }
    return NULL;
}

/* Returns 0 on success (any lane failure poisons the call — callers
 * fall back to the Python path rather than trust partial output).
 * nthreads <= 1 runs on the caller's thread. */
int cbft_ed25519_challenges(const unsigned char *pubs,
                            const unsigned char *rs,
                            const unsigned char *msgs,
                            const size_t *msg_off, const size_t *msg_len,
                            const unsigned char *valid, unsigned char *out,
                            size_t n, int nthreads)
{
    if (n == 0)
        return 0;
    if (nthreads <= 1 || (size_t)nthreads > n) {
        hchunk_t c = {pubs, rs, msgs, msg_off, msg_len,
                      valid, out, 0, n, 0};
        challenge_chunk(&c);
        return c.rc;
    }
    enum { MAX_THREADS = 64 };
    if (nthreads > MAX_THREADS)
        nthreads = MAX_THREADS;
    pthread_t tids[MAX_THREADS];
    hchunk_t chunks[MAX_THREADS];
    size_t per = n / nthreads, rem = n % nthreads, pos = 0;
    int spawned = 0;
    for (int t = 0; t < nthreads; t++) {
        size_t take = per + (t < (int)rem ? 1 : 0);
        chunks[t] = (hchunk_t){pubs, rs, msgs, msg_off, msg_len,
                               valid, out, pos, pos + take, 0};
        pos += take;
        if (t == nthreads - 1) {
            challenge_chunk(&chunks[t]);
        } else if (pthread_create(&tids[spawned], NULL, challenge_chunk,
                                  &chunks[t]) == 0) {
            spawned++;
        } else {
            challenge_chunk(&chunks[t]);
        }
    }
    for (int t = 0; t < spawned; t++)
        pthread_join(tids[t], NULL);
    int rc = 0;
    for (int t = 0; t < nthreads; t++)
        rc |= chunks[t].rc;
    return rc;
}

/* ------------------------------------------------------------------------
 * secp256k1 ECDSA scalars of a launch (crypto/tpu/secp256k1_batch.py
 * _scalars): per valid lane e = SHA-256(msg) mod n, w = s^-1 mod n,
 * u1 = e*w, u2 = r*w mod n, out as 32 little-endian bytes each. The
 * launch's inversions are one (Montgomery's trick: prefix products, one
 * Fermat power, three products a lane back). Arithmetic mod n in four
 * 64-bit limbs: a product of 512 bits folds twice through
 * 2^256 = c (mod n), c = 2^256 - n (129 bits), then at most two
 * subtractions. Lanes with valid[i] == 0 are skipped (the caller's
 * zeros); r and s are the caller's, range-checked (1 <= r, s < n). */

/* SHA256_CTX is 112 bytes on every libcrypto since 0.9.8; declared with
 * room to spare, as the image has no headers (cbft_sha512_ctx above). */
typedef union {
    unsigned long long words[32];
} cbft_sha256_ctx;
int SHA256_Init(cbft_sha256_ctx *c);
int SHA256_Update(cbft_sha256_ctx *c, const void *data, size_t len);
int SHA256_Final(unsigned char *md, cbft_sha256_ctx *c);

typedef unsigned long long u64;
typedef unsigned __int128 u128;

static const u64 SECP_N[4] = {0xBFD25E8CD0364141ULL, 0xBAAEDCE6AF48A03BULL,
                              0xFFFFFFFFFFFFFFFEULL, 0xFFFFFFFFFFFFFFFFULL};
static const u64 SECP_C[3] = {0x402DA1732FC9BEBFULL, 0x4551231950B75FC4ULL,
                              1ULL};

static void sn_load_be(u64 out[4], const unsigned char *b)
{
    for (int i = 0; i < 4; i++) {
        u64 w = 0;
        for (int k = 0; k < 8; k++)
            w = (w << 8) | b[8 * (3 - i) + k];
        out[i] = w;
    }
}

static void sn_store_le(unsigned char *b, const u64 a[4])
{
    for (int i = 0; i < 4; i++)
        for (int k = 0; k < 8; k++)
            b[8 * i + k] = (unsigned char)(a[i] >> (8 * k));
}

static int sn_geq_n(const u64 a[4])
{
    for (int i = 3; i >= 0; i--)
        if (a[i] != SECP_N[i])
            return a[i] > SECP_N[i];
    return 1;
}

static void sn_sub_n(u64 a[4])
{
    u64 borrow = 0;
    for (int i = 0; i < 4; i++) {
        u128 d = (u128)a[i] - SECP_N[i] - borrow;
        a[i] = (u64)d;
        borrow = (u64)(d >> 64) & 1;
    }
}

/* x (len <= 8 limbs, little-endian) mod n -> out */
static void sn_reduce(const u64 *x, int len, u64 out[4])
{
    u64 t[8] = {0};
    for (int i = 0; i < len; i++)
        t[i] = x[i];
    while (len > 4) {
        u64 r[8] = {t[0], t[1], t[2], t[3], 0, 0, 0, 0};
        for (int i = 0; i < len - 4; i++) {
            u64 carry = 0;
            for (int j = 0; j < 3; j++) {
                u128 p = (u128)t[4 + i] * SECP_C[j] + r[i + j] + carry;
                r[i + j] = (u64)p;
                carry = (u64)(p >> 64);
            }
            for (int k = i + 3; carry && k < 8; k++) {
                u128 s = (u128)r[k] + carry;
                r[k] = (u64)s;
                carry = (u64)(s >> 64);
            }
        }
        len = 8;
        while (len > 4 && r[len - 1] == 0)
            len--;
        for (int i = 0; i < 8; i++)
            t[i] = r[i];
    }
    for (int i = 0; i < 4; i++)
        out[i] = t[i];
    while (sn_geq_n(out))
        sn_sub_n(out);
}

static void sn_mul(u64 out[4], const u64 a[4], const u64 b[4])
{
    u64 p[8] = {0};
    for (int i = 0; i < 4; i++) {
        u64 carry = 0;
        for (int j = 0; j < 4; j++) {
            u128 m = (u128)a[i] * b[j] + p[i + j] + carry;
            p[i + j] = (u64)m;
            carry = (u64)(m >> 64);
        }
        p[i + 4] = carry;
    }
    sn_reduce(p, 8, out);
}

/* a^(n-2) mod n: a^-1 for a != 0 */
static void sn_inv(u64 out[4], const u64 a[4])
{
    u64 e[4] = {SECP_N[0] - 2, SECP_N[1], SECP_N[2], SECP_N[3]};
    u64 acc[4] = {1, 0, 0, 0};
    for (int i = 255; i >= 0; i--) {
        sn_mul(acc, acc, acc);
        if ((e[i / 64] >> (i % 64)) & 1)
            sn_mul(acc, acc, a);
    }
    for (int i = 0; i < 4; i++)
        out[i] = acc[i];
}

/* Returns 0 on success. scratch: 4 * n u64 of the caller's. */
int cbft_secp256k1_scalars(const unsigned char *sigs,
                           const unsigned char *msgs,
                           const size_t *msg_off, const size_t *msg_len,
                           const unsigned char *valid, unsigned char *u1,
                           unsigned char *u2, u64 *prefix, size_t n)
{
    u64 acc[4] = {1, 0, 0, 0};
    for (size_t i = 0; i < n; i++) {
        if (!valid[i])
            continue;
        u64 s[4];
        sn_load_be(s, sigs + 64 * i + 32);
        for (int k = 0; k < 4; k++)
            prefix[4 * i + k] = acc[k];
        sn_mul(acc, acc, s);
    }
    u64 inv[4];
    sn_inv(inv, acc);
    for (size_t i = n; i-- > 0;) {
        if (!valid[i])
            continue;
        unsigned char digest[32];
        cbft_sha256_ctx ctx;
        if (SHA256_Init(&ctx) != 1 ||
            SHA256_Update(&ctx, msgs + msg_off[i], msg_len[i]) != 1 ||
            SHA256_Final(digest, &ctx) != 1)
            return 1;
        u64 s[4], r[4], e[4], w[4], u[4];
        sn_load_be(s, sigs + 64 * i + 32);
        sn_load_be(r, sigs + 64 * i);
        sn_load_be(e, digest);
        while (sn_geq_n(e))
            sn_sub_n(e);
        sn_mul(w, inv, prefix + 4 * i);
        sn_mul(inv, inv, s);
        sn_mul(u, e, w);
        sn_store_le(u1 + 32 * i, u);
        sn_mul(u, r, w);
        sn_store_le(u2 + 32 * i, u);
    }
    return 0;
}

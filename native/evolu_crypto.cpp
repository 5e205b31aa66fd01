// libevolu_crypto.so — batched OpenPGP symmetric crypto for the sync
// hot loop (SURVEY.md hot loop #3; reference
// packages/evolu/src/sync.worker.ts:50-91,135-173).
//
// The Python implementation (evolu_tpu/sync/crypto.py) is the
// semantic oracle: correct for every wire shape, but per-message
// Python (~35us/msg, measured r4 — S2K + EVP context churn + packet
// assembly dominate). This layer batches the common path into ONE C
// call per sync leg: protobuf CrdtMessageContent encode, S2K
// (iterated+salted SHA-256), AES-256-CFB, SHA-1 MDC, and packet
// assembly all run in C++ over packed buffers (NUL-safe by
// construction — wire fields may contain NUL, so nothing here is
// char*-terminated). Decrypt handles the canonical shapes this
// framework and OpenPGP.js v5 emit (new-format definite lengths,
// SKESK v4 AES-256 S2K type 0/1/3 SHA-256, SEIPD v1, uncompressed
// literal, canonical content wire types); ANYTHING else — old-format
// headers, partial lengths, compression, legacy SED, wrong password,
// MDC failure, non-canonical protobuf — sets that message's status to
// 1 and the Python oracle re-runs it, preserving the exact error
// surface (PgpError/ValueError) byte for byte.
//
// OpenSSL: the image ships libcrypto.so.3 without dev headers, so the
// needed EVP/RAND prototypes are declared here (stable ABI) and the
// Makefile links the versioned soname directly, mirroring its
// libsqlite3 pattern. The four functions that exist only in OpenSSL 3
// (EVP_MD_fetch / EVP_MD_free / EVP_CIPHER_fetch / EVP_CIPHER_free) are
// looked up with dlsym, so the same source links against 1.1 too.
//
// What a v1 message costs (ISSUE 32). Under OpenSSL 3 an `*_Init_ex` on
// a legacy handle (`EVP_sha256()`, `EVP_aes_256_cfb128()`) fetches the
// algorithm again through the library's global method store, under its
// lock, and decrypt_one did three a message: a third of the "3 us of
// S2K" that earlier notes called the format's floor was that lookup,
// and it is why threads contended. `Ctxs` now resolves its algorithms
// once a call and nothing per message touches shared OpenSSL state: not
// the store, and not the reference count of a shared EVP_MD / EVP_CIPHER
// either (SHA-256 and SHA-1 each keep their own EVP_MD_CTX; a cipher
// context is told its cipher once). What is left of a message is the
// format's: 1 KiB of SHA-256 (S2K count byte 0), an AES-256 key
// schedule, ~120 bytes of CFB and ~100 of SHA-1.

#include <dlfcn.h>
#include <sched.h>

#include <algorithm>
#include <array>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <memory>
#include <new>
#include <string>
#include <system_error>
#include <thread>
#include <unordered_map>
#include <vector>

#include "pyabi.h"
#include "wire.h"

// ---- OpenSSL 3 ABI (self-declared; no headers in the image) ----

extern "C" {
typedef struct evp_cipher_ctx_st EVP_CIPHER_CTX;
typedef struct evp_cipher_st EVP_CIPHER;
typedef struct evp_md_ctx_st EVP_MD_CTX;
typedef struct evp_md_st EVP_MD;
typedef struct engine_st ENGINE;

EVP_CIPHER_CTX *EVP_CIPHER_CTX_new(void);
void EVP_CIPHER_CTX_free(EVP_CIPHER_CTX *);
const EVP_CIPHER *EVP_aes_256_cfb128(void);
int EVP_EncryptInit_ex(EVP_CIPHER_CTX *, const EVP_CIPHER *, ENGINE *,
                       const unsigned char *, const unsigned char *);
int EVP_EncryptUpdate(EVP_CIPHER_CTX *, unsigned char *, int *,
                      const unsigned char *, int);
int EVP_DecryptInit_ex(EVP_CIPHER_CTX *, const EVP_CIPHER *, ENGINE *,
                       const unsigned char *, const unsigned char *);
int EVP_DecryptUpdate(EVP_CIPHER_CTX *, unsigned char *, int *,
                      const unsigned char *, int);
// AEAD leg (aead-batch-v1, sync/aead.py): AES-256-GCM + ctrl/final.
const EVP_CIPHER *EVP_aes_256_gcm(void);
int EVP_CIPHER_CTX_ctrl(EVP_CIPHER_CTX *, int, int, void *);
int EVP_EncryptFinal_ex(EVP_CIPHER_CTX *, unsigned char *, int *);
int EVP_DecryptFinal_ex(EVP_CIPHER_CTX *, unsigned char *, int *);

EVP_MD_CTX *EVP_MD_CTX_new(void);
void EVP_MD_CTX_free(EVP_MD_CTX *);
const EVP_MD *EVP_sha256(void);
const EVP_MD *EVP_sha1(void);
int EVP_DigestInit_ex(EVP_MD_CTX *, const EVP_MD *, ENGINE *);
int EVP_DigestUpdate(EVP_MD_CTX *, const void *, size_t);
int EVP_DigestFinal_ex(EVP_MD_CTX *, unsigned char *, unsigned int *);

int RAND_bytes(unsigned char *, int);
}

namespace {

// ---- small helpers ----

// proto3 varint of a (two's-complement) 64-bit value; negatives emit
// the 10-byte form — bit-exact with crypto.py's _varint. ONE shared
// implementation with libevolu_host (wire.h).
using ::wire_varint_size;

// New-format OpenPGP packet header length octets (RFC 4880 §4.2.2).
inline size_t pkt_len_size(size_t n) { return n < 192 ? 1 : (n < 8384 ? 2 : 5); }
inline uint8_t *put_pkt_hdr(uint8_t *p, int tag, size_t n) {
  *p++ = uint8_t(0xC0 | tag);
  if (n < 192) {
    *p++ = uint8_t(n);
  } else if (n < 8384) {
    size_t m = n - 192;
    *p++ = uint8_t(192 + (m >> 8));
    *p++ = uint8_t(m & 0xFF);
  } else {
    *p++ = 0xFF;
    *p++ = uint8_t(n >> 24); *p++ = uint8_t(n >> 16);
    *p++ = uint8_t(n >> 8);  *p++ = uint8_t(n);
  }
  return p;
}

// EVP_CIPHER_CTX_ctrl codes (stable across OpenSSL 1.1 / 3.x; the
// AEAD aliases share the GCM values).
constexpr int CTRL_GCM_GET_TAG = 0x10, CTRL_GCM_SET_TAG = 0x11;

// OpenSSL 3's explicit fetch, absent from 1.1 and therefore not
// declared above: resolved once a process from the very libcrypto the
// declared symbols bind to (all four or none). 1.1 has no implicit
// fetch to avoid, so there the legacy handles stay.
struct FetchApi {
  EVP_MD *(*md_fetch)(void *, const char *, const char *) = nullptr;
  void (*md_free)(EVP_MD *) = nullptr;
  EVP_CIPHER *(*cipher_fetch)(void *, const char *, const char *) = nullptr;
  void (*cipher_free)(EVP_CIPHER *) = nullptr;
  FetchApi() {
    Dl_info info;
    if (!dladdr(reinterpret_cast<void *>(&EVP_sha256), &info) || !info.dli_fname) return;
    // NOLOAD: a handle on the library that is already mapped, never a
    // second copy; kept for the life of the process like the library.
    void *lib = dlopen(info.dli_fname, RTLD_LAZY | RTLD_NOLOAD);
    if (!lib) return;
    auto mf = reinterpret_cast<decltype(md_fetch)>(dlsym(lib, "EVP_MD_fetch"));
    auto mr = reinterpret_cast<decltype(md_free)>(dlsym(lib, "EVP_MD_free"));
    auto cf = reinterpret_cast<decltype(cipher_fetch)>(dlsym(lib, "EVP_CIPHER_fetch"));
    auto cr = reinterpret_cast<decltype(cipher_free)>(dlsym(lib, "EVP_CIPHER_free"));
    if (mf && mr && cf && cr) { md_fetch = mf; md_free = mr; cipher_fetch = cf; cipher_free = cr; }
  }
};

const FetchApi &fetch_api() {
  static const FetchApi api;  // thread-safe once (C++11), read-only after
  return api;
}

struct Ctxs {
  EVP_CIPHER_CTX *cipher = nullptr;
  // One digest context an algorithm: a context that alternates between
  // two frees and reallocates its algorithm state and takes and drops a
  // reference on the shared EVP_MD at every switch.
  EVP_MD_CTX *md = nullptr;       // SHA-256: S2K, HKDF
  EVP_MD_CTX *md_sha1 = nullptr;  // SHA-1: the MDC
  const EVP_CIPHER *aes = nullptr;
  const EVP_MD *sha256 = nullptr;
  const EVP_MD *sha1 = nullptr;
  // What this Ctxs fetched and must free (null under OpenSSL 1.1).
  EVP_MD *own_sha256 = nullptr, *own_sha1 = nullptr;
  EVP_CIPHER *own_aes = nullptr, *own_gcm = nullptr;
  // `cipher` has been told its cipher: inits pass nullptr (cfb_init).
  bool cfb_named = false;
  // salt ‖ password repeated, for s2k_iterated.
  std::vector<uint8_t> s2k_tile;
  // aead-batch-v1 state: a dedicated GCM context so the CFB context's
  // reuse pattern is untouched. `gcm_keyed` tracks whether gcm_ctx
  // currently holds `gcm_key` with its AES key schedule expanded — a
  // leg under ONE session key then pays the schedule once and each
  // record re-inits with the nonce alone (the whole point of the
  // per-session key schedule).
  EVP_CIPHER_CTX *gcm_ctx = nullptr;
  const EVP_CIPHER *gcm = nullptr;
  bool gcm_keyed = false;
  uint8_t gcm_key[32] = {0};
  // Per-call HKDF cache: one derivation per distinct session salt per
  // leg (the Python side keeps the cross-call cache). `last_salt` is
  // the hot lane: a leg's records virtually always share ONE session
  // salt, so the per-record cost is a 16-byte compare, not a string
  // key + map probe.
  std::unordered_map<std::string, std::array<uint8_t, 32>> aead_keys;
  uint8_t last_salt[16] = {0};
  uint8_t last_key[32] = {0};
  bool has_last_salt = false;
  bool ok() const {
    return cipher && md && md_sha1 && aes && sha256 && sha1 && gcm_ctx && gcm;
  }
  Ctxs() {
    cipher = EVP_CIPHER_CTX_new();
    md = EVP_MD_CTX_new();
    md_sha1 = EVP_MD_CTX_new();
    gcm_ctx = EVP_CIPHER_CTX_new();
    const FetchApi &api = fetch_api();
    if (api.md_fetch) {
      own_sha256 = api.md_fetch(nullptr, "SHA256", nullptr);
      own_sha1 = api.md_fetch(nullptr, "SHA1", nullptr);
      own_aes = api.cipher_fetch(nullptr, "AES-256-CFB", nullptr);
      own_gcm = api.cipher_fetch(nullptr, "AES-256-GCM", nullptr);
    }
    // A provider that lacks one keeps the legacy handle's behaviour.
    sha256 = own_sha256 ? own_sha256 : EVP_sha256();
    sha1 = own_sha1 ? own_sha1 : EVP_sha1();
    aes = own_aes ? own_aes : EVP_aes_256_cfb128();
    gcm = own_gcm ? own_gcm : EVP_aes_256_gcm();
    // Let each context allocate its algorithm state here, on the thread
    // that builds the Ctxs, not at the first message on the thread that
    // uses it (a lane's: see ehc_decrypt_response_columns). A failure
    // shows again, and is handled, at the first message.
    if (ok()) {
      EVP_DigestInit_ex(md, sha256, nullptr);
      EVP_DigestInit_ex(md_sha1, sha1, nullptr);
      cfb_named = EVP_DecryptInit_ex(cipher, aes, nullptr, nullptr, nullptr) != 0;
    }
  }
  Ctxs(const Ctxs &) = delete;
  Ctxs &operator=(const Ctxs &) = delete;
  ~Ctxs() {
    // Contexts first: each holds a reference on what it was given.
    if (cipher) EVP_CIPHER_CTX_free(cipher);
    if (md) EVP_MD_CTX_free(md);
    if (md_sha1) EVP_MD_CTX_free(md_sha1);
    if (gcm_ctx) EVP_CIPHER_CTX_free(gcm_ctx);
    const FetchApi &api = fetch_api();
    if (own_sha256) api.md_free(own_sha256);
    if (own_sha1) api.md_free(own_sha1);
    if (own_aes) api.cipher_free(own_aes);
    if (own_gcm) api.cipher_free(own_gcm);
  }
};

// (Re)key the CFB context with the zero IV of SEIPD v1. The cipher is
// named once a context (in Ctxs(), here only after a failure): an
// `*_Init_ex` that names it again resets the context (frees and
// reallocates its state, takes and drops a reference on the shared
// EVP_CIPHER); one that passes nullptr keeps the state and runs the key
// schedule alone.
bool cfb_init(Ctxs &cx, const uint8_t key[32], bool enc) {
  static const uint8_t zero_iv[16] = {0};
  const EVP_CIPHER *named = cx.cfb_named ? nullptr : cx.aes;
  cx.cfb_named = (enc ? EVP_EncryptInit_ex(cx.cipher, named, nullptr, key, zero_iv)
                      : EVP_DecryptInit_ex(cx.cipher, named, nullptr, key, zero_iv)) != 0;
  return cx.cfb_named;
}

// RFC 4880 §3.7.1.3 iterated+salted S2K (SHA-256 → exactly the 32-byte
// AES-256 key, single context). Incremental so an adversarial wire
// count byte (up to ~65MB of hashing) never materializes a buffer.
bool s2k_iterated(Ctxs &cx, const uint8_t *pw, size_t pw_len,
                  const uint8_t *salt, int count_byte, uint8_t key_out[32]) {
  uint64_t count = uint64_t(16 + (count_byte & 15)) << ((count_byte >> 4) + 6);
  // The hashed stream is salt ‖ password repeated to `total` bytes. It
  // is fed from a tile of whole repetitions that lives in `cx` (one
  // allocation a call, not one a message) and covers the usual count
  // (1,024: count byte 0) in ONE DigestUpdate where a repetition a call
  // made 14.
  size_t unit = 8 + pw_len;
  size_t reps = unit >= 1024 ? 1 : (1024 + unit - 1) / unit;
  size_t tile = unit * reps;
  if (cx.s2k_tile.size() != tile) cx.s2k_tile.resize(tile);
  uint8_t *t = cx.s2k_tile.data();
  memcpy(t, salt, 8);
  memcpy(t + 8, pw, pw_len);
  for (size_t r = 1; r < reps; r++) memcpy(t + r * unit, t, unit);
  uint64_t total = count > unit ? count : unit;
  if (!EVP_DigestInit_ex(cx.md, cx.sha256, nullptr)) return false;
  // A whole tile is a whole number of repetitions, so every update
  // starts at phase 0 of the stream.
  for (; total >= tile; total -= tile)
    if (!EVP_DigestUpdate(cx.md, t, tile)) return false;
  if (total && !EVP_DigestUpdate(cx.md, t, size_t(total))) return false;
  unsigned int out_len = 0;
  uint8_t digest[32];
  if (!EVP_DigestFinal_ex(cx.md, digest, &out_len) || out_len != 32) return false;
  memcpy(key_out, digest, 32);
  return true;
}

// §3.7.1.2 salted / §3.7.1.1 simple (accepted on decrypt, never produced).
bool s2k_salted(Ctxs &cx, const uint8_t *pw, size_t pw_len,
                const uint8_t *salt /* null = simple */, uint8_t key_out[32]) {
  if (!EVP_DigestInit_ex(cx.md, cx.sha256, nullptr)) return false;
  if (salt && !EVP_DigestUpdate(cx.md, salt, 8)) return false;
  if (!EVP_DigestUpdate(cx.md, pw, pw_len)) return false;
  unsigned int out_len = 0;
  uint8_t digest[32];
  if (!EVP_DigestFinal_ex(cx.md, digest, &out_len) || out_len != 32) return false;
  memcpy(key_out, digest, 32);
  return true;
}

bool sha1_oneshot(Ctxs &cx, const uint8_t *data, size_t n, uint8_t out[20]) {
  if (!EVP_DigestInit_ex(cx.md_sha1, cx.sha1, nullptr)) return false;
  if (!EVP_DigestUpdate(cx.md_sha1, data, n)) return false;
  unsigned int out_len = 0;
  if (!EVP_DigestFinal_ex(cx.md_sha1, out, &out_len) || out_len != 20) return false;
  return true;
}

// ---- aead-batch-v1 (sync/aead.py — the v2 record format) ----
//
//   [0]  magic 0x45 0x32 0x01 ("E2" + version; bit 7 of byte 0 is
//        clear, so v2 records and OpenPGP packet streams are
//        structurally disjoint — decrypt_one dispatches on it)
//   [3]  salt[16] (HKDF session salt)  [19] nonce[12]
//   [31] AES-256-GCM ciphertext ‖ tag[16]
// Plaintext = the CrdtMessageContent protobuf (same bytes the v1
// literal packet carries).

constexpr size_t AEAD_SALT = 16, AEAD_NONCE = 12, AEAD_TAG = 16;
constexpr size_t AEAD_OVERHEAD = 3 + AEAD_SALT + AEAD_NONCE + AEAD_TAG;  // 47
// MUST match sync/aead.py::HKDF_INFO byte for byte.
constexpr char AEAD_HKDF_INFO[] = "evolu-tpu aead-batch-v1 key";

inline bool is_aead_record(const uint8_t *d, size_t n) {
  return n >= 3 && d[0] == 0x45 && d[1] == 0x32 && d[2] == 0x01;
}

// HMAC-SHA-256 over (m1 ‖ m2), hand-rolled on the digest ABI (the
// legacy HMAC() one-shot is deprecated in OpenSSL 3 and the EVP_MAC
// API does not exist in 1.1 — the block construction is version-proof).
bool hmac_sha256(Ctxs &cx, const uint8_t *key, size_t key_len,
                 const uint8_t *m1, size_t l1, const uint8_t *m2, size_t l2,
                 uint8_t out[32]) {
  uint8_t k0[64] = {0};
  if (key_len > 64) {
    unsigned int dl = 0;
    if (!EVP_DigestInit_ex(cx.md, cx.sha256, nullptr) ||
        !EVP_DigestUpdate(cx.md, key, key_len) ||
        !EVP_DigestFinal_ex(cx.md, k0, &dl) || dl != 32)
      return false;
  } else {
    memcpy(k0, key, key_len);
  }
  uint8_t ipad[64], opad[64];
  for (int i = 0; i < 64; i++) { ipad[i] = k0[i] ^ 0x36; opad[i] = k0[i] ^ 0x5C; }
  uint8_t inner[32];
  unsigned int dl = 0;
  if (!EVP_DigestInit_ex(cx.md, cx.sha256, nullptr) ||
      !EVP_DigestUpdate(cx.md, ipad, 64) ||
      (l1 && !EVP_DigestUpdate(cx.md, m1, l1)) ||
      (l2 && !EVP_DigestUpdate(cx.md, m2, l2)) ||
      !EVP_DigestFinal_ex(cx.md, inner, &dl) || dl != 32)
    return false;
  if (!EVP_DigestInit_ex(cx.md, cx.sha256, nullptr) ||
      !EVP_DigestUpdate(cx.md, opad, 64) ||
      !EVP_DigestUpdate(cx.md, inner, 32) ||
      !EVP_DigestFinal_ex(cx.md, out, &dl) || dl != 32)
    return false;
  return true;
}

// RFC 5869, one 32-byte block: PRK = HMAC(salt, secret);
// OKM = HMAC(PRK, info ‖ 0x01). Bit-identical to aead.hkdf_sha256.
bool hkdf_sha256(Ctxs &cx, const uint8_t *secret, size_t secret_len,
                 const uint8_t *salt16, uint8_t out[32]) {
  uint8_t prk[32];
  if (!hmac_sha256(cx, salt16, AEAD_SALT, secret, secret_len, nullptr, 0, prk))
    return false;
  static const uint8_t one = 1;
  return hmac_sha256(cx, prk, 32,
                     reinterpret_cast<const uint8_t *>(AEAD_HKDF_INFO),
                     sizeof(AEAD_HKDF_INFO) - 1, &one, 1, out);
}

// Session key for a record's salt, HKDF'd once per distinct salt per
// call (the cross-call cache lives in Python, keyed the same way).
bool aead_key_for(Ctxs &cx, const uint8_t *pw, size_t pw_len,
                  const uint8_t *salt16, uint8_t out[32]) {
  if (cx.has_last_salt && memcmp(cx.last_salt, salt16, AEAD_SALT) == 0) {
    memcpy(out, cx.last_key, 32);
    return true;
  }
  std::string k(reinterpret_cast<const char *>(salt16), AEAD_SALT);
  auto it = cx.aead_keys.find(k);
  if (it == cx.aead_keys.end()) {
    std::array<uint8_t, 32> key;
    if (!hkdf_sha256(cx, pw, pw_len, salt16, key.data())) return false;
    it = cx.aead_keys.emplace(std::move(k), key).first;
  }
  memcpy(cx.last_salt, salt16, AEAD_SALT);
  memcpy(cx.last_key, it->second.data(), 32);
  cx.has_last_salt = true;
  memcpy(out, it->second.data(), 32);
  return true;
}

// (Re)key the GCM context only when the session key changes; records
// under the current key re-init with the nonce alone (no AES key
// schedule). `enc` selects direction — a call only ever runs one.
bool gcm_ready(Ctxs &cx, const uint8_t key[32], const uint8_t *nonce, bool enc) {
  if (!cx.gcm_keyed || memcmp(cx.gcm_key, key, 32) != 0) {
    int ok = enc ? EVP_EncryptInit_ex(cx.gcm_ctx, cx.gcm, nullptr, key, nonce)
                 : EVP_DecryptInit_ex(cx.gcm_ctx, cx.gcm, nullptr, key, nonce);
    if (!ok) return false;
    memcpy(cx.gcm_key, key, 32);
    cx.gcm_keyed = true;
    return true;
  }
  return enc ? EVP_EncryptInit_ex(cx.gcm_ctx, nullptr, nullptr, nullptr, nonce)
             : EVP_DecryptInit_ex(cx.gcm_ctx, nullptr, nullptr, nullptr, nonce);
}

// Decrypt + verify ONE v2 record into `plain` (room for `clen` bytes;
// the content's length, clen − 47, comes back in `plain_len`). false =
// demote to the Python oracle (which owns the exact PgpError surface
// for truncation/auth failure).
bool aead_open_record(Ctxs &cx, const uint8_t *msg, size_t clen,
                      const uint8_t *password, size_t pw_len,
                      uint8_t *plain, size_t &plain_len) {
  if (clen < AEAD_OVERHEAD) return false;
  const uint8_t *salt = msg + 3, *nonce = msg + 3 + AEAD_SALT;
  const uint8_t *ct = msg + 3 + AEAD_SALT + AEAD_NONCE;
  size_t ct_len = clen - AEAD_OVERHEAD;
  uint8_t key[32], tag[AEAD_TAG];
  memcpy(tag, msg + clen - AEAD_TAG, AEAD_TAG);
  if (!aead_key_for(cx, password, pw_len, salt, key)) return false;
  if (!gcm_ready(cx, key, nonce, /*enc=*/false)) { cx.gcm_keyed = false; return false; }
  int len = 0, fl = 0;
  if (ct_len && !EVP_DecryptUpdate(cx.gcm_ctx, plain, &len, ct, int(ct_len))) {
    cx.gcm_keyed = false;
    return false;
  }
  if (EVP_CIPHER_CTX_ctrl(cx.gcm_ctx, CTRL_GCM_SET_TAG, AEAD_TAG, tag) != 1 ||
      EVP_DecryptFinal_ex(cx.gcm_ctx, plain + len, &fl) != 1 ||
      size_t(len + fl) != ct_len) {
    // A failed final leaves ctx state undefined enough that the next
    // record must re-run the full keyed init.
    cx.gcm_keyed = false;
    return false;
  }
  plain_len = ct_len;
  return true;
}

// Seal ONE content plaintext as a v2 record into dst (sized c + 47).
bool aead_seal_record(Ctxs &cx, const uint8_t key[32], const uint8_t *salt16,
                      const uint8_t *nonce12, const uint8_t *pt, size_t c,
                      uint8_t *dst) {
  dst[0] = 0x45; dst[1] = 0x32; dst[2] = 0x01;
  memcpy(dst + 3, salt16, AEAD_SALT);
  memcpy(dst + 3 + AEAD_SALT, nonce12, AEAD_NONCE);
  uint8_t *ct = dst + 3 + AEAD_SALT + AEAD_NONCE;
  if (!gcm_ready(cx, key, nonce12, /*enc=*/true)) { cx.gcm_keyed = false; return false; }
  int len = 0, fl = 0;
  if (c && !EVP_EncryptUpdate(cx.gcm_ctx, ct, &len, pt, int(c))) {
    cx.gcm_keyed = false;
    return false;
  }
  if (EVP_EncryptFinal_ex(cx.gcm_ctx, ct + len, &fl) != 1 ||
      size_t(len + fl) != c ||
      EVP_CIPHER_CTX_ctrl(cx.gcm_ctx, CTRL_GCM_GET_TAG, AEAD_TAG, ct + c) != 1) {
    cx.gcm_keyed = false;
    return false;
  }
  return true;
}

// ---- CrdtMessageContent protobuf encode (protocol.py:139-172) ----

// vkind: 0 = None, 1 = str (in blob), 2 = int/bool (ival), 3 = double.
constexpr int64_t INT32_LO = -(int64_t(1) << 31), INT32_HI = (int64_t(1) << 31) - 1;

size_t content_size(const int32_t lens[4], int8_t vkind, int64_t ival) {
  size_t n = 0;
  for (int f = 0; f < 3; f++)
    n += 1 + wire_varint_size(uint64_t(lens[f])) + size_t(lens[f]);
  if (vkind == 1) {
    n += 1 + wire_varint_size(uint64_t(lens[3])) + size_t(lens[3]);
  } else if (vkind == 2) {
    n += 1 + wire_varint_size(uint64_t(ival));  // field 5 or 7, same wire size
  } else if (vkind == 3) {
    n += 1 + 8;
  }
  return n;
}

uint8_t *put_content(uint8_t *p, const uint8_t *strs, const int32_t lens[4],
                     int8_t vkind, int64_t ival, double dval) {
  const uint8_t *s = strs;
  for (int f = 0; f < 3; f++) {
    *p++ = uint8_t(((f + 1) << 3) | 2);
    p = wire_put_varint(p, uint64_t(lens[f]));
    memcpy(p, s, size_t(lens[f]));
    p += lens[f]; s += lens[f];
  }
  if (vkind == 1) {
    *p++ = uint8_t((4 << 3) | 2);
    p = wire_put_varint(p, uint64_t(lens[3]));
    memcpy(p, s, size_t(lens[3]));
    p += lens[3];
  } else if (vkind == 2) {
    *p++ = uint8_t(ival >= INT32_LO && ival <= INT32_HI ? (5 << 3) : (7 << 3));
    p = wire_put_varint(p, uint64_t(ival));
  } else if (vkind == 3) {
    *p++ = uint8_t((6 << 3) | 1);
    uint64_t bits;
    memcpy(&bits, &dval, 8);
    for (int i = 0; i < 8; i++) *p++ = uint8_t(bits >> (8 * i));
  }
  return p;
}

// Per-column twins of content_size/put_content for the aead wire leg
// (its Python packer ships one blob per column — b"".join of per-field
// comprehensions is measurably cheaper than interleaving in a Python
// loop, and the per-message Python share is the binding cost there).
size_t content_size_cols(int32_t tl, int32_t rl, int32_t cl, int32_t sl,
                         int8_t vkind, int64_t ival) {
  size_t n = 1 + wire_varint_size(uint64_t(tl)) + size_t(tl) +
             1 + wire_varint_size(uint64_t(rl)) + size_t(rl) +
             1 + wire_varint_size(uint64_t(cl)) + size_t(cl);
  if (vkind == 1) {
    n += 1 + wire_varint_size(uint64_t(sl)) + size_t(sl);
  } else if (vkind == 2) {
    n += 1 + wire_varint_size(uint64_t(ival));
  } else if (vkind == 3) {
    n += 1 + 8;
  }
  return n;
}

uint8_t *put_str_field(uint8_t *p, int field, const uint8_t *s, int32_t len) {
  *p++ = uint8_t((field << 3) | 2);
  p = wire_put_varint(p, uint64_t(len));
  memcpy(p, s, size_t(len));
  return p + len;
}

uint8_t *put_content_cols(uint8_t *p, const uint8_t *t, int32_t tl,
                          const uint8_t *r, int32_t rl, const uint8_t *c,
                          int32_t cl, const uint8_t *s, int32_t sl,
                          int8_t vkind, int64_t ival, double dval) {
  p = put_str_field(p, 1, t, tl);
  p = put_str_field(p, 2, r, rl);
  p = put_str_field(p, 3, c, cl);
  if (vkind == 1) {
    p = put_str_field(p, 4, s, sl);
  } else if (vkind == 2) {
    *p++ = uint8_t(ival >= INT32_LO && ival <= INT32_HI ? (5 << 3) : (7 << 3));
    p = wire_put_varint(p, uint64_t(ival));
  } else if (vkind == 3) {
    *p++ = uint8_t((6 << 3) | 1);
    uint64_t bits;
    memcpy(&bits, &dval, 8);
    for (int i = 0; i < 8; i++) *p++ = uint8_t(bits >> (8 * i));
  }
  return p;
}

// Exact SKESK‖SEIPD size for a content of `c` bytes.
size_t message_size(size_t c) {
  size_t lit_body = 6 + c;
  size_t lit_pkt = 1 + pkt_len_size(lit_body) + lit_body;
  size_t plain = 18 + lit_pkt + 22;
  size_t seipd_body = 1 + plain;
  return 15 + 1 + pkt_len_size(seipd_body) + seipd_body;
}

// Encrypt ONE CrdtMessageContent into dst (must hold message_size(c)).
// rnd24 = 8 salt + 16 prefix bytes. Returns false on OpenSSL failure.
bool emit_message(Ctxs &cx, const uint8_t *password, size_t pw_len,
                  const uint8_t *rnd24, const uint8_t *strs,
                  const int32_t L[4], int8_t vkind, int64_t ival, double dval,
                  size_t c, std::vector<uint8_t> &plainbuf, uint8_t *dst) {
  const uint8_t *salt = rnd24, *prefix = rnd24 + 8;
  uint8_t key[32];
  if (!s2k_iterated(cx, password, pw_len, salt, 0, key)) return false;

  uint8_t *q = dst;
  // SKESK (tag 3): v4, AES-256, iterated+salted SHA-256, count 0.
  *q++ = 0xC3; *q++ = 13; *q++ = 4; *q++ = 9; *q++ = 3; *q++ = 8;
  memcpy(q, salt, 8); q += 8;
  *q++ = 0;

  // Plaintext body: prefix ‖ repeat ‖ literal ‖ d3 14 ‖ SHA1(MDC).
  size_t lit_body = 6 + c;
  size_t plain = 18 + (1 + pkt_len_size(lit_body) + lit_body) + 22;
  plainbuf.resize(plain);
  uint8_t *b = plainbuf.data();
  memcpy(b, prefix, 16); b += 16;
  b[0] = prefix[14]; b[1] = prefix[15]; b += 2;
  b = put_pkt_hdr(b, 11, lit_body);
  *b++ = 'b'; *b++ = 0; memset(b, 0, 4); b += 4;
  b = put_content(b, strs, L, vkind, ival, dval);
  *b++ = 0xD3; *b++ = 0x14;
  uint8_t mdc[20];
  if (!sha1_oneshot(cx, plainbuf.data(), size_t(b - plainbuf.data()), mdc))
    return false;
  memcpy(b, mdc, 20);

  // SEIPD (tag 18): 0x01 ‖ AES-256-CFB(zero IV) of the body.
  size_t seipd_body = 1 + plain;
  q = put_pkt_hdr(q, 18, seipd_body);
  *q++ = 0x01;
  int enc_len = 0;
  if (!cfb_init(cx, key, /*enc=*/true) ||
      !EVP_EncryptUpdate(cx.cipher, q, &enc_len, plainbuf.data(), int(plain)) ||
      size_t(enc_len) != plain)
    return false;
  // Size accounting must be EXACT: the caller sized this slot with
  // message_size(c); any drift between the two is heap corruption,
  // not a recoverable condition — fail the batch cleanly instead.
  return size_t(q + plain - dst) == message_size(c);
}

}  // namespace

// ---- public ABI ----

extern "C" {

void ehc_free(void *p) { free(p); }

// Probe: 1 if OpenSSL primitives are usable in this process.
int ehc_available(void) {
  Ctxs cx;
  return cx.ok() ? 1 : 0;
}

// Encrypt a batch of CrdtMessageContents into OpenPGP SKESK‖SEIPD
// streams (crypto.py:70-83, bit-compatible modulo the random salt and
// prefix). Inputs are packed columns; output is one malloc'd blob of
// per-message records [u32 ct_len][ct bytes], freed with ehc_free.
// Returns 0 on success, nonzero on any failure (caller falls back to
// the Python path wholesale).
int ehc_encrypt_batch(int64_t n, const uint8_t *str_blob, const int32_t *lens4,
                      const int8_t *vkinds, const int64_t *ivals,
                      const double *dvals, const uint8_t *password,
                      int32_t pw_len, uint8_t **out_blob, int64_t *out_len) {
  Ctxs cx;
  if (!cx.ok() || n < 0 || pw_len < 0) return 1;

  // Sizes are exactly computable: SKESK is 15 bytes; the SEIPD body is
  // 1 + 18 + literal_packet + 22.
  std::vector<size_t> clen(static_cast<size_t>(n)), total(static_cast<size_t>(n));
  size_t out_total = 0;
  for (int64_t i = 0; i < n; i++) {
    const int32_t *L = lens4 + 4 * i;
    if (L[0] < 0 || L[1] < 0 || L[2] < 0 || (vkinds[i] == 1 && L[3] < 0)) return 1;
    size_t c = content_size(L, vkinds[i], ivals[i]);
    clen[size_t(i)] = c;
    total[size_t(i)] = message_size(c);
    out_total += 4 + total[size_t(i)];
  }

  uint8_t *out = static_cast<uint8_t *>(malloc(out_total ? out_total : 1));
  if (!out) return 1;
  // One RNG call for the whole batch: 8 salt + 16 prefix per message.
  std::vector<uint8_t> rnd(size_t(n) * 24);
  if (n && !RAND_bytes(rnd.data(), int(rnd.size()))) { free(out); return 1; }

  std::vector<uint8_t> plainbuf;
  const uint8_t *strs = str_blob;
  uint8_t *p = out;
  for (int64_t i = 0; i < n; i++) {
    const int32_t *L = lens4 + 4 * i;
    size_t msg = total[size_t(i)];
    *p++ = uint8_t(msg); *p++ = uint8_t(msg >> 8);
    *p++ = uint8_t(msg >> 16); *p++ = uint8_t(msg >> 24);
    if (!emit_message(cx, password, size_t(pw_len), rnd.data() + 24 * i, strs,
                      L, vkinds[i], ivals[i], dvals[i], clen[size_t(i)], plainbuf,
                      p)) {
      free(out);
      return 1;
    }
    p += msg;
    strs += L[0] + L[1] + L[2] + (vkinds[i] == 1 ? L[3] : 0);
  }
  *out_blob = out;
  *out_len = int64_t(out_total);
  return 0;
}

// Encrypt a batch STRAIGHT INTO SyncRequest wire form: the output is
// the concatenated `messages` field-1 stream of protobuf.proto's
// SyncRequest — per message `0x0A varint(inner)` wrapping
// `EncryptedCrdtMessage{ timestamp=1, content=2 }` — byte-identical
// to protocol.encode_sync_request's messages section. The caller
// appends the userId/nodeId/merkleTree fields (2/3/4) and has the
// whole request body with ZERO per-message Python (sync hot path;
// ts_blob/ts_lens carry the plaintext timestamps).
int ehc_encrypt_wire_batch(int64_t n, const uint8_t *ts_blob,
                           const int32_t *ts_lens, const uint8_t *str_blob,
                           const int32_t *lens4, const int8_t *vkinds,
                           const int64_t *ivals, const double *dvals,
                           const uint8_t *password, int32_t pw_len,
                           uint8_t **out_blob, int64_t *out_len) {
  Ctxs cx;
  if (!cx.ok() || n < 0 || pw_len < 0) return 1;
  std::vector<size_t> clen(static_cast<size_t>(n)), ctsz(static_cast<size_t>(n)),
      inner(static_cast<size_t>(n));
  size_t out_total = 0;
  for (int64_t i = 0; i < n; i++) {
    const int32_t *L = lens4 + 4 * i;
    if (L[0] < 0 || L[1] < 0 || L[2] < 0 || ts_lens[i] < 0 ||
        (vkinds[i] == 1 && L[3] < 0))
      return 1;
    size_t c = content_size(L, vkinds[i], ivals[i]);
    size_t ct = message_size(c);
    size_t in = 1 + wire_varint_size(uint64_t(ts_lens[i])) + size_t(ts_lens[i]) +
                1 + wire_varint_size(ct) + ct;
    clen[size_t(i)] = c;
    ctsz[size_t(i)] = ct;
    inner[size_t(i)] = in;
    out_total += 1 + wire_varint_size(in) + in;
  }
  uint8_t *out = static_cast<uint8_t *>(malloc(out_total ? out_total : 1));
  if (!out) return 1;
  std::vector<uint8_t> rnd(size_t(n) * 24);
  if (n && !RAND_bytes(rnd.data(), int(rnd.size()))) { free(out); return 1; }

  std::vector<uint8_t> plainbuf;
  const uint8_t *strs = str_blob;
  const uint8_t *ts = ts_blob;
  uint8_t *p = out;
  for (int64_t i = 0; i < n; i++) {
    const int32_t *L = lens4 + 4 * i;
    *p++ = 0x0A;  // SyncRequest.messages, field 1, wt 2
    p = wire_put_varint(p, uint64_t(inner[size_t(i)]));
    *p++ = 0x0A;  // EncryptedCrdtMessage.timestamp
    p = wire_put_varint(p, uint64_t(ts_lens[i]));
    memcpy(p, ts, size_t(ts_lens[i]));
    p += ts_lens[i];
    ts += ts_lens[i];
    *p++ = 0x12;  // EncryptedCrdtMessage.content, field 2, wt 2
    p = wire_put_varint(p, uint64_t(ctsz[size_t(i)]));
    if (!emit_message(cx, password, size_t(pw_len), rnd.data() + 24 * i, strs,
                      L, vkinds[i], ivals[i], dvals[i], clen[size_t(i)], plainbuf,
                      p)) {
      free(out);
      return 1;
    }
    p += ctsz[size_t(i)];
    strs += L[0] + L[1] + L[2] + (vkinds[i] == 1 ? L[3] : 0);
  }
  if (size_t(p - out) != out_total) { free(out); return 1; }
  *out_blob = out;
  *out_len = int64_t(out_total);
  return 0;
}

// aead-batch-v1 push leg: encrypt a batch STRAIGHT INTO SyncRequest
// wire form under ONE session key — the v2 twin of
// ehc_encrypt_wire_batch. The key schedule runs once (key32/salt16
// come from the Python-side AeadSession, HKDF'd once per owner per
// session); each message costs one nonce + one small GCM. Inputs are
// per-column blobs (timestamps, tables, rows, columns, string values)
// with per-column length arrays; vkinds/ivals/dvals as in the v1 ABI
// (s_lens[i] is only read when vkinds[i]==1). Output: the concatenated
// `messages` field-1 stream, caller appends fields 2/3/4 (+5).
// Returns 0 on success, nonzero on any failure (→ pure Python path).
int ehc_aead_encrypt_wire_batch(
    int64_t n, const uint8_t *ts_blob, const int32_t *ts_lens,
    const uint8_t *t_blob, const int32_t *t_lens, const uint8_t *r_blob,
    const int32_t *r_lens, const uint8_t *c_blob, const int32_t *c_lens,
    const uint8_t *s_blob, const int32_t *s_lens, const int8_t *vkinds,
    const int64_t *ivals, const double *dvals, const uint8_t *key32,
    const uint8_t *salt16, uint8_t **out_blob, int64_t *out_len) {
  Ctxs cx;
  if (!cx.ok() || n < 0) return 1;
  std::vector<size_t> clen(static_cast<size_t>(n)), inner(static_cast<size_t>(n));
  size_t out_total = 0;
  for (int64_t i = 0; i < n; i++) {
    if (t_lens[i] < 0 || r_lens[i] < 0 || c_lens[i] < 0 || ts_lens[i] < 0 ||
        (vkinds[i] == 1 && s_lens[i] < 0))
      return 1;
    size_t c = content_size_cols(t_lens[i], r_lens[i], c_lens[i],
                                 vkinds[i] == 1 ? s_lens[i] : 0, vkinds[i],
                                 ivals[i]);
    size_t ct = c + AEAD_OVERHEAD;
    size_t in = 1 + wire_varint_size(uint64_t(ts_lens[i])) + size_t(ts_lens[i]) +
                1 + wire_varint_size(ct) + ct;
    clen[size_t(i)] = c;
    inner[size_t(i)] = in;
    out_total += 1 + wire_varint_size(in) + in;
  }
  uint8_t *out = static_cast<uint8_t *>(malloc(out_total ? out_total : 1));
  if (!out) return 1;
  // One RNG call for the whole batch: a 12-byte nonce per record.
  std::vector<uint8_t> rnd(size_t(n) * AEAD_NONCE);
  if (n && !RAND_bytes(rnd.data(), int(rnd.size()))) { free(out); return 1; }

  std::vector<uint8_t> plainbuf;
  const uint8_t *ts = ts_blob, *t = t_blob, *r = r_blob, *cc = c_blob,
                *s = s_blob;
  uint8_t *p = out;
  for (int64_t i = 0; i < n; i++) {
    size_t c = clen[size_t(i)];
    *p++ = 0x0A;  // SyncRequest.messages, field 1, wt 2
    p = wire_put_varint(p, uint64_t(inner[size_t(i)]));
    *p++ = 0x0A;  // EncryptedCrdtMessage.timestamp
    p = wire_put_varint(p, uint64_t(ts_lens[i]));
    memcpy(p, ts, size_t(ts_lens[i]));
    p += ts_lens[i];
    ts += ts_lens[i];
    *p++ = 0x12;  // EncryptedCrdtMessage.content, field 2, wt 2
    p = wire_put_varint(p, uint64_t(c + AEAD_OVERHEAD));
    int32_t sl = vkinds[i] == 1 ? s_lens[i] : 0;
    plainbuf.resize(c ? c : 1);
    uint8_t *end = put_content_cols(plainbuf.data(), t, t_lens[i], r, r_lens[i],
                                    cc, c_lens[i], s, sl, vkinds[i], ivals[i],
                                    dvals[i]);
    if (size_t(end - plainbuf.data()) != c ||
        !aead_seal_record(cx, key32, salt16, rnd.data() + AEAD_NONCE * i,
                          plainbuf.data(), c, p)) {
      free(out);
      return 1;
    }
    p += c + AEAD_OVERHEAD;
    t += t_lens[i]; r += r_lens[i]; cc += c_lens[i];
    if (vkinds[i] == 1) s += s_lens[i];
  }
  if (size_t(p - out) != out_total) { free(out); return 1; }
  *out_blob = out;
  *out_len = int64_t(out_total);
  return 0;
}

}  // extern "C"

// ---- CPython ABI fast lane (aead push encode) ----
//
// The ABI declarations, `PyRefs`, `GilScope`, `py_str` and the probe
// live in pyabi.h, shared with libevolu_host's request pack. The
// binding side (sync/native_crypto.py) calls through ctypes.PyDLL so
// the GIL is HELD for the whole call — mandatory for every function
// below.
// Why: the Python-side columnar packer costs ~0.9µs/msg (attr access,
// per-string encode, length arrays — more than the ENTIRE C crypto
// leg after the S2K removal). Extracting fields here instead reads
// each str's cached UTF-8 in place (zero-copy for ASCII), turning the
// residual Python share into ~5 C-API calls per message.
// Safety: `ehc_py_abi_probe` gates the lane; exact types only — a
// str/int subclass or any error demotes the whole batch (return 2) to
// the Python packer, which owns the canonical error surface.

extern "C" {

// pyabi.h's layout gate. MUST be called via PyDLL (GIL held).
int ehc_py_abi_probe(PyObj *sample) { return py_abi_probe(sample); }

// aead-batch-v1 push leg over the message OBJECTS: extraction +
// content assembly + seal in one GIL-held call. `messages` is the
// CrdtMessage sequence; key32/salt16 from the Python AeadSession.
// Output: the SyncRequest field-1 stream (caller appends fields
// 2/3/4). Returns 0 ok; 2 = shape demotion (any non-exact type,
// int64 overflow, surrogate) → caller falls back to the blob packer.
int ehc_aead_encrypt_push_py(PyObj *messages, int64_t n,
                             const uint8_t *key32, const uint8_t *salt16,
                             uint8_t **out_blob, int64_t *out_len) {
  Ctxs cx;
  if (!cx.ok() || n < 0 || !messages) return 1;
  PyRefs names;
  PyObj *a_ts = names.keep(PyUnicode_FromString("timestamp"));
  PyObj *a_t = names.keep(PyUnicode_FromString("table"));
  PyObj *a_r = names.keep(PyUnicode_FromString("row"));
  PyObj *a_c = names.keep(PyUnicode_FromString("column"));
  PyObj *a_v = names.keep(PyUnicode_FromString("value"));
  if (!a_ts || !a_t || !a_r || !a_c || !a_v) { PyErr_Clear(); return 1; }

  struct Row {
    const char *ts, *t, *r, *c, *s;
    long long tsl, tl, rl, cl, sl;
    int8_t vkind;
    int64_t ival;
    double dval;
  };
  std::vector<Row> rows(static_cast<size_t>(n));
  PyRefs held;  // every attr value stays alive until assembly is done
  held.refs.reserve(static_cast<size_t>(n) * 5 + 1);
  size_t out_total = 0;
  std::vector<size_t> clen(static_cast<size_t>(n)), inner(static_cast<size_t>(n));
  for (int64_t i = 0; i < n; i++) {
    PyObj *m = held.keep(PySequence_GetItem(messages, i));
    if (!m) { PyErr_Clear(); return 2; }
    Row &w = rows[size_t(i)];
    if (!py_str(held.keep(PyObject_GetAttr(m, a_ts)), &w.ts, &w.tsl) ||
        !py_str(held.keep(PyObject_GetAttr(m, a_t)), &w.t, &w.tl) ||
        !py_str(held.keep(PyObject_GetAttr(m, a_r)), &w.r, &w.rl) ||
        !py_str(held.keep(PyObject_GetAttr(m, a_c)), &w.c, &w.cl)) {
      PyErr_Clear();
      return 2;
    }
    PyObj *v = held.keep(PyObject_GetAttr(m, a_v));
    if (!v) { PyErr_Clear(); return 2; }
    void *vt = v->ob_type;
    w.s = nullptr; w.sl = 0; w.ival = 0; w.dval = 0.0;
    if (static_cast<void *>(v) == static_cast<void *>(&_Py_NoneStruct)) {
      w.vkind = 0;
    } else if (vt == static_cast<void *>(&PyUnicode_Type)) {
      if (!py_str(v, &w.s, &w.sl)) return 2;
      w.vkind = 1;
    } else if (vt == static_cast<void *>(&PyLong_Type) ||
               vt == static_cast<void *>(&PyBool_Type)) {
      w.ival = PyLong_AsLongLong(v);
      if (w.ival == -1 && PyErr_Occurred()) { PyErr_Clear(); return 2; }
      w.vkind = 2;
    } else if (vt == static_cast<void *>(&PyFloat_Type)) {
      w.dval = PyFloat_AsDouble(v);
      w.vkind = 3;
    } else {
      return 2;  // exotic value → the Python packer/oracle decides
    }
    size_t c = content_size_cols(int32_t(w.tl), int32_t(w.rl), int32_t(w.cl),
                                 int32_t(w.sl), w.vkind, w.ival);
    size_t ct = c + AEAD_OVERHEAD;
    size_t in = 1 + wire_varint_size(uint64_t(w.tsl)) + size_t(w.tsl) +
                1 + wire_varint_size(ct) + ct;
    clen[size_t(i)] = c;
    inner[size_t(i)] = in;
    out_total += 1 + wire_varint_size(in) + in;
  }

  uint8_t *out = static_cast<uint8_t *>(malloc(out_total ? out_total : 1));
  if (!out) return 1;
  // Extraction is done: the seal loop below is pure C (the Rows point
  // into strs PyRefs keeps alive), so other Python threads may run.
  GilScope gil;
  std::vector<uint8_t> rnd(size_t(n) * AEAD_NONCE);
  if (n && !RAND_bytes(rnd.data(), int(rnd.size()))) { free(out); return 1; }
  std::vector<uint8_t> plainbuf;
  uint8_t *p = out;
  for (int64_t i = 0; i < n; i++) {
    const Row &w = rows[size_t(i)];
    size_t c = clen[size_t(i)];
    *p++ = 0x0A;  // SyncRequest.messages, field 1, wt 2
    p = wire_put_varint(p, uint64_t(inner[size_t(i)]));
    *p++ = 0x0A;  // EncryptedCrdtMessage.timestamp
    p = wire_put_varint(p, uint64_t(w.tsl));
    memcpy(p, w.ts, size_t(w.tsl));
    p += w.tsl;
    *p++ = 0x12;  // EncryptedCrdtMessage.content, field 2, wt 2
    p = wire_put_varint(p, uint64_t(c + AEAD_OVERHEAD));
    plainbuf.resize(c ? c : 1);
    uint8_t *end = put_content_cols(
        plainbuf.data(), reinterpret_cast<const uint8_t *>(w.t), int32_t(w.tl),
        reinterpret_cast<const uint8_t *>(w.r), int32_t(w.rl),
        reinterpret_cast<const uint8_t *>(w.c), int32_t(w.cl),
        reinterpret_cast<const uint8_t *>(w.s), int32_t(w.sl), w.vkind,
        w.ival, w.dval);
    if (size_t(end - plainbuf.data()) != c ||
        !aead_seal_record(cx, key32, salt16, rnd.data() + AEAD_NONCE * i,
                          plainbuf.data(), c, p)) {
      free(out);
      return 1;
    }
    p += c + AEAD_OVERHEAD;
  }
  if (size_t(p - out) != out_total) { free(out); return 1; }
  *out_blob = out;
  *out_len = int64_t(out_total);
  return 0;
}

}  // extern "C"

extern "C" {

namespace {

// New-format definite-length packet walk. Returns false on anything
// the fast path doesn't cover (old format, partial lengths, bounds).
struct Pkt { int tag; const uint8_t *body; size_t len; };

bool read_packets(const uint8_t *d, size_t n, std::vector<Pkt> &out) {
  size_t pos = 0;
  while (pos < n) {
    uint8_t ctb = d[pos++];
    if (!(ctb & 0x80) || !(ctb & 0x40)) return false;
    int tag = ctb & 0x3F;
    if (pos >= n) return false;
    uint8_t first = d[pos++];
    size_t len;
    if (first < 192) {
      len = first;
    } else if (first < 224) {
      if (pos >= n) return false;
      len = (size_t(first - 192) << 8) + d[pos++] + 192;
    } else if (first == 255) {
      if (pos + 4 > n) return false;
      len = (size_t(d[pos]) << 24) | (size_t(d[pos + 1]) << 16) |
            (size_t(d[pos + 2]) << 8) | size_t(d[pos + 3]);
      pos += 4;
    } else {
      return false;  // partial length → Python oracle
    }
    if (len > n - pos) return false;  // overflow-safe: pos <= n
    out.push_back({tag, d + pos, len});
    pos += len;
  }
  return true;
}

// Strict UTF-8 validation matching CPython's decoder: rejects bare
// continuations, overlong encodings, surrogates (U+D800..U+DFFF), and
// code points above U+10FFFF. The columnar receive path commits these
// bytes to SQLite with explicit lengths; anything Python's .decode()
// would reject must bounce the batch to the object path instead.
static bool utf8_ok(const uint8_t *s, size_t n) {
  size_t i = 0;
  while (i < n) {
    uint8_t b = s[i];
    if (b < 0x80) { i++; continue; }
    if (b < 0xC2) return false;  // continuation byte or overlong 2-byte
    if (b < 0xE0) {
      if (i + 1 >= n || (s[i + 1] & 0xC0) != 0x80) return false;
      i += 2;
    } else if (b < 0xF0) {
      if (i + 2 >= n) return false;
      uint8_t b1 = s[i + 1], b2 = s[i + 2];
      if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80) return false;
      if (b == 0xE0 && b1 < 0xA0) return false;   // overlong
      if (b == 0xED && b1 >= 0xA0) return false;  // surrogate
      i += 3;
    } else if (b < 0xF5) {
      if (i + 3 >= n) return false;
      uint8_t b1 = s[i + 1], b2 = s[i + 2], b3 = s[i + 3];
      if ((b1 & 0xC0) != 0x80 || (b2 & 0xC0) != 0x80 || (b3 & 0xC0) != 0x80)
        return false;
      if (b == 0xF0 && b1 < 0x90) return false;   // overlong
      if (b == 0xF4 && b1 >= 0x90) return false;  // > U+10FFFF
      i += 4;
    } else {
      return false;
    }
  }
  return true;
}

// Top-level SyncResponse field-3 (capability) validation, shared by
// both fused response walkers. The pure decoder (_decode_capability)
// decodes every capability entry as strict UTF-8 and raises past 64
// entries; the C walkers used to SKIP field 3 entirely — so a
// response whose capability bytes the pure path rejects decoded
// "successfully" on the fused path (the pinned
// tests/fixtures/fuzz_divergent_response.bin divergence). Returns
// false on exactly the shapes the pure decoder raises for; the caller
// demotes the whole response to the pure decoder, which owns the
// exact ValueError surface. Well-formed capabilities stay skipped
// (the client scans them separately, pre-decrypt).
static bool capability_ok(const uint8_t *body, size_t blen, int &n_caps) {
  if (n_caps >= 64) return false;  // protocol._MAX_CAPABILITIES
  n_caps++;
  return utf8_ok(body, blen);
}

// Canonical-wire-type CrdtMessageContent decode (protocol.py:194-217).
// Any deviation (unexpected wire type on a known field, truncation)
// → false → Python oracle reproduces the exact lenient/strict result.
struct Content {
  const uint8_t *t = nullptr, *r = nullptr, *c = nullptr, *s = nullptr;
  size_t tl = 0, rl = 0, cl = 0, sl = 0;
  int8_t vkind = 0;  // 0 none, 1 str, 2 int, 3 double
  int64_t ival = 0;
  double dval = 0;
};

bool read_varint64(const uint8_t *d, size_t n, size_t &pos, uint64_t &v) {
  v = 0;
  int shift = 0;
  while (true) {
    if (pos >= n) return false;
    uint8_t b = d[pos++];
    // The Python oracle (_read_varint) keeps UNBOUNDED precision: a
    // 10th byte may carry bits ≥ 2^64 into the decoded int, or a
    // continuation that raises "varint too long". Wrapping mod 2^64
    // here would silently diverge (overflowed field keys remapping to
    // real fields, overflowed lengths decoding "successfully") — any
    // 10th byte beyond the single value bit 63 demotes to the oracle.
    if (shift == 63 && (b & 0xFE)) return false;
    v |= uint64_t(b & 0x7F) << shift;
    if (!(b & 0x80)) return true;
    shift += 7;
    if (shift > 63) return false;
  }
}

bool decode_content(const uint8_t *d, size_t n, Content &out) {
  size_t pos = 0;
  while (pos < n) {
    uint64_t key;
    if (!read_varint64(d, n, pos, key)) return false;
    uint64_t field = key >> 3;
    int wt = int(key & 7);
    uint64_t iv = 0;
    const uint8_t *bytes = nullptr;
    size_t blen = 0;
    if (wt == 0) {
      if (!read_varint64(d, n, pos, iv)) return false;
    } else if (wt == 1) {
      if (pos + 8 > n) return false;
      for (int i = 7; i >= 0; i--) iv = (iv << 8) | d[pos + i];
      pos += 8;
    } else if (wt == 2) {
      uint64_t len;
      if (!read_varint64(d, n, pos, len)) return false;
      // Overflow-safe (pos <= n): a 10-byte varint can carry bit 63,
      // and `pos + len` would wrap past the check (r4 review finding —
      // heap over-read on untrusted input).
      if (len > n - pos) return false;
      bytes = d + pos; blen = size_t(len); pos += size_t(len);
    } else if (wt == 5) {
      if (pos + 4 > n) return false;
      pos += 4;
    } else {
      return false;
    }
    switch (field) {
      case 1: if (wt != 2) return false; out.t = bytes; out.tl = blen; break;
      case 2: if (wt != 2) return false; out.r = bytes; out.rl = blen; break;
      case 3: if (wt != 2) return false; out.c = bytes; out.cl = blen; break;
      case 4: if (wt != 2) return false;
        out.vkind = 1; out.s = bytes; out.sl = blen; break;
      case 5: if (wt != 0) return false;
        // int32 truncation exactly as decode_content: low 32 bits,
        // sign-extended.
        out.vkind = 2; out.ival = int64_t(int32_t(uint32_t(iv))); break;
      case 6: if (wt != 1) return false; {
        out.vkind = 3;
        uint64_t bits = iv;
        memcpy(&out.dval, &bits, 8);
        break;
      }
      case 7: if (wt != 0) return false;
        out.vkind = 2; out.ival = int64_t(iv); break;
      default: break;  // unknown fields skipped, any wire type
    }
  }
  return true;
}

// What decrypt_one needs besides the message: the contexts and two
// packet lists, reused from message to message. One a call, or one a
// lane (ehc_decrypt_response_columns); nothing in it is shared.
struct Scratch {
  Ctxs cx;
  std::vector<Pkt> pkts, inner;
  Scratch() {
    pkts.reserve(8);  // a canonical message has two packets and one inside
    inner.reserve(8);
  }
};

// Decrypt ONE canonical SKESK‖SEIPD stream (or one v2 record) into
// `plain` and decode its content; `c` points into `plain`, which must
// have room for `clen` bytes (a plaintext is no longer than its
// ciphertext) and outlive `c`. false = demote this message to the
// Python oracle.
bool decrypt_one(Scratch &sc, const uint8_t *msg, size_t clen,
                 const uint8_t *password, size_t pw_len, uint8_t *plain,
                 Content &c) {
  Ctxs &cx = sc.cx;
  std::vector<Pkt> &pkts = sc.pkts, &inner = sc.inner;
  if (is_aead_record(msg, clen)) {
    // aead-batch-v1 record: session-keyed GCM instead of per-message
    // S2K. Every decrypt entry point (batch, fused response, fused
    // columns) gains v2 through this one dispatch; any failure —
    // truncation, bad tag — demotes to the Python oracle, which owns
    // the exact PgpError surface.
    size_t plain_len = 0;
    if (!aead_open_record(cx, msg, clen, password, pw_len, plain, plain_len))
      return false;
    return decode_content(plain, plain_len, c);
  }
  pkts.clear();
  if (!read_packets(msg, clen, pkts)) return false;
  const Pkt *skesk = nullptr, *seipd = nullptr;
  bool sed = false;
  for (const Pkt &p : pkts) {
    if (p.tag == 3 && !skesk) skesk = &p;
    else if (p.tag == 18 && !seipd) seipd = &p;
    else if (p.tag == 9) sed = true;
  }
  if (!skesk || !seipd || sed) return false;  // legacy SED → oracle

  const uint8_t *sk = skesk->body;
  if (skesk->len < 4 || sk[0] != 4 || sk[1] != 9) return false;
  uint8_t key[32];
  if (sk[2] == 3) {
    if (skesk->len < 13 || sk[3] != 8) return false;
    if (!s2k_iterated(cx, password, pw_len, sk + 4, sk[12], key)) return false;
  } else if (sk[2] == 1) {
    if (skesk->len < 12 || sk[3] != 8) return false;
    if (!s2k_salted(cx, password, pw_len, sk + 4, key)) return false;
  } else if (sk[2] == 0) {
    if (sk[3] != 8) return false;
    if (!s2k_salted(cx, password, pw_len, nullptr, key)) return false;
  } else {
    return false;
  }

  if (seipd->len < 1 + 18 + 22 || seipd->body[0] != 1) return false;
  size_t blen = seipd->len - 1;
  int dec_len = 0;
  if (!cfb_init(cx, key, /*enc=*/false) ||
      !EVP_DecryptUpdate(cx.cipher, plain, &dec_len, seipd->body + 1,
                         int(blen)) ||
      size_t(dec_len) != blen)
    return false;
  const uint8_t *b = plain;
  if (b[16] != b[14] || b[17] != b[15]) return false;  // wrong password → oracle
  if (b[blen - 22] != 0xD3 || b[blen - 21] != 0x14) return false;
  uint8_t mdc[20];
  if (!sha1_oneshot(cx, b, blen - 20, mdc)) return false;
  if (memcmp(mdc, b + blen - 20, 20) != 0) return false;

  inner.clear();
  if (!read_packets(b + 18, blen - 18 - 22, inner)) return false;
  const Pkt *lit = nullptr;
  for (const Pkt &p : inner) {
    if (p.tag == 11) { lit = &p; break; }
    if (p.tag == 8) return false;  // compressed → oracle
  }
  if (!lit || lit->len < 2) return false;
  size_t name_len = lit->body[1];
  if (2 + name_len + 4 > lit->len) return false;
  return decode_content(lit->body + 2 + name_len + 4,
                        lit->len - 2 - name_len - 4, c);
}

// Append a decoded-content record to `out` (the decrypt_batch record
// layout — the Python side shares one parser for both entry points).
void append_content_record(std::string &out, const Content &c) {
  auto put_i32 = [&out](int64_t v) {
    for (int k = 0; k < 4; k++) out.push_back(char(uint64_t(v) >> (8 * k)));
  };
  put_i32(int64_t(c.tl)); put_i32(int64_t(c.rl)); put_i32(int64_t(c.cl));
  put_i32(c.vkind == 1 ? int64_t(c.sl) : -1);
  out.push_back(char(c.vkind));
  for (int k = 0; k < 8; k++) out.push_back(char(uint64_t(c.ival) >> (8 * k)));
  uint64_t dbits;
  memcpy(&dbits, &c.dval, 8);
  for (int k = 0; k < 8; k++) out.push_back(char(dbits >> (8 * k)));
  if (c.tl) out.append(reinterpret_cast<const char *>(c.t), c.tl);
  if (c.rl) out.append(reinterpret_cast<const char *>(c.r), c.rl);
  if (c.cl) out.append(reinterpret_cast<const char *>(c.c), c.cl);
  if (c.vkind == 1 && c.sl) out.append(reinterpret_cast<const char *>(c.s), c.sl);
}

}  // namespace

// Decrypt a batch of OpenPGP streams (packed [len]+bytes via ct_lens)
// on the canonical fast path. statuses[i]: 0 = decoded (record
// appended to out_blob), 1 = fall back to the Python oracle for this
// message. Record layout (unaligned, little-endian):
//   [i32 tlen][i32 rlen][i32 clen][i32 vlen][i8 vkind][i64 ival]
//   [f64 dval][table bytes][row bytes][column bytes][str value bytes]
// vkind: 0 none, 1 str, 2 int, 3 double. Returns 0 unless allocation
// or OpenSSL setup fails entirely (→ caller falls back wholesale).
int ehc_decrypt_batch(int64_t n, const uint8_t *ct_blob, const int32_t *ct_lens,
                      const uint8_t *password, int32_t pw_len,
                      uint8_t *statuses, uint8_t **out_blob, int64_t *out_len) {
  Scratch sc;
  if (!sc.cx.ok() || n < 0 || pw_len < 0) return 1;
  std::string out;
  out.reserve(size_t(n) * 128);
  std::vector<uint8_t> plain;
  const uint8_t *ct = ct_blob;

  for (int64_t i = 0; i < n; i++) {
    size_t clen = size_t(ct_lens[i]);
    const uint8_t *msg = ct;
    ct += clen;
    Content c;
    if (plain.size() < clen) plain.resize(clen);
    if (decrypt_one(sc, msg, clen, password, size_t(pw_len), plain.data(), c)) {
      append_content_record(out, c);
      statuses[i] = 0;
    } else {
      statuses[i] = 1;  // → Python oracle at this position
    }
  }

  uint8_t *blob = static_cast<uint8_t *>(malloc(out.size() ? out.size() : 1));
  if (!blob) return 1;
  if (!out.empty()) memcpy(blob, out.data(), out.size());
  *out_blob = blob;
  *out_len = int64_t(out.size());
  return 0;
}

// Parse a whole SyncResponse protobuf AND decrypt its messages in one
// call (the client receive leg: decode_sync_response +
// decrypt_messages fused — per-message Python eliminated for
// canonical rows). Output blob:
//   [i64 n_messages][u32 tree_len]
//   per message: [u8 status][u32 ts_len][ts bytes] then
//     status 0: a decoded-content record (decrypt_batch layout)
//     status 1: [i64 ct_off][u32 ct_len] — the ciphertext span inside
//       `resp` for the Python oracle to re-do at this position.
//   then the merkleTree bytes (tree_len of them) at the TAIL.
// Returns 0 ok; 2 = non-canonical WIRE shape (unknown/unexpected wire
// types, truncation — the caller falls back to the pure decoder
// wholesale, preserving its exact ValueError surface); 1 = internal.
int ehc_decrypt_response(const uint8_t *resp, int64_t resp_len,
                         const uint8_t *password, int32_t pw_len,
                         uint8_t **out_blob, int64_t *out_len) {
  Scratch sc;
  if (!sc.cx.ok() || resp_len < 0 || pw_len < 0) return 1;
  size_t n_ = size_t(resp_len);
  std::string out(12, '\0');  // n + tree_len placeholders
  int64_t n_msgs = 0;
  const uint8_t *tree = nullptr;
  size_t tree_len = 0;
  std::vector<uint8_t> plain;

  int n_caps = 0;
  size_t pos = 0;
  while (pos < n_) {
    uint64_t key;
    if (!read_varint64(resp, n_, pos, key)) return 2;
    uint64_t field = key >> 3;
    int wt = int(key & 7);
    if (wt != 2) return 2;  // canonical SyncResponse is all wt-2
    uint64_t len;
    if (!read_varint64(resp, n_, pos, len)) return 2;
    // Overflow-safe (pos <= n_): see decode_content — a 10-byte varint
    // can carry bit 63 and wrap `pos + len` past a naive check,
    // spanning reads beyond the response buffer (r4 review finding).
    if (len > n_ - pos) return 2;
    const uint8_t *body = resp + pos;
    size_t blen = size_t(len);
    pos += blen;
    if (field == 2) {
      tree = body;  // last wins, like the Python decoder
      tree_len = blen;
      continue;
    }
    if (field == 3) {
      // Capabilities: the pure decoder PARSES these (raising on bad
      // UTF-8 / >64 entries); skipping them unvalidated is the pinned
      // fused/pure divergence — reject exactly what it rejects.
      if (!capability_ok(body, blen, n_caps)) return 2;
      continue;
    }
    if (field != 1) continue;  // unknown length-delimited field: skip

    // EncryptedCrdtMessage { timestamp=1, content=2 } — last wins.
    const uint8_t *ts = nullptr, *ct = nullptr;
    size_t ts_len = 0, ct_len = 0;
    size_t mp = 0;
    while (mp < blen) {
      uint64_t mkey;
      if (!read_varint64(body, blen, mp, mkey)) return 2;
      uint64_t mf = mkey >> 3;
      int mwt = int(mkey & 7);
      if (mwt != 2) return 2;  // incl. the varint-content DoS shape
      uint64_t mlen;
      if (!read_varint64(body, blen, mp, mlen)) return 2;
      if (mlen > blen - mp) return 2;  // overflow-safe: mp <= blen
      if (mf == 1) { ts = body + mp; ts_len = size_t(mlen); }
      else if (mf == 2) { ct = body + mp; ct_len = size_t(mlen); }
      mp += size_t(mlen);
    }
    n_msgs++;
    out.push_back('\0');  // status placeholder
    size_t status_at = out.size() - 1;
    uint32_t tl32 = uint32_t(ts_len);
    out.append(reinterpret_cast<const char *>(&tl32), 4);
    if (ts_len) out.append(reinterpret_cast<const char *>(ts), ts_len);
    Content c;
    if (plain.size() < ct_len) plain.resize(ct_len);
    if (ct && decrypt_one(sc, ct, ct_len, password, size_t(pw_len),
                          plain.data(), c)) {
      append_content_record(out, c);
    } else {
      out[status_at] = 1;
      int64_t off = ct ? int64_t(ct - resp) : 0;
      uint32_t cl32 = uint32_t(ct_len);
      out.append(reinterpret_cast<const char *>(&off), 8);
      out.append(reinterpret_cast<const char *>(&cl32), 4);
    }
  }
  memcpy(&out[0], &n_msgs, 8);
  uint32_t tl = uint32_t(tree_len);
  memcpy(&out[8], &tl, 4);
  if (tree_len) out.append(reinterpret_cast<const char *>(tree), tree_len);

  uint8_t *blob = static_cast<uint8_t *>(malloc(out.size() ? out.size() : 1));
  if (!blob) return 1;
  memcpy(blob, out.data(), out.size());
  *out_blob = blob;
  *out_len = int64_t(out.size());
  return 0;
}

// (utf8_ok lives in the anonymous namespace above, next to the
// response walkers' shared capability validation.)

// ehc_decrypt_response_columns in three phases (ISSUE 32; the entry and
// its blob are described where it is defined, below). WALK, on the
// caller's thread: the protobuf walk, which keeps for each message its
// timestamp and its ciphertext slice. DECRYPT, in L lanes: lane j runs decrypt_one over a contiguous
// range of the messages with a Scratch of its own and writes each
// plaintext into its slice of one buffer (a plaintext is no longer than
// its ciphertext, so the messages' summed ct_len bounds it), leaving a
// Content a message that points there. COLUMNARIZE, on the caller's
// thread, in wire order: interning, the value columns, the UTF-8
// checks, the blob. Interning is by first appearance in wire order, so
// the blob does not depend on L, and the return code is that of the
// first message in wire order that fails, as one loop over the messages
// gave it.
//
// L is what the call can observe: min(cores this process may run on,
// kMaxLanes, n / kLaneMessages), at least 1; a live sync's small
// response takes one lane and starts no thread. Lane 0 is the calling
// thread, lanes 1..L-1 are std::threads started and joined inside the
// call: no pool, no global, nothing outlives the call.
//
// **A lane's thread never grows a heap.** Everything a lane touches is
// allocated on the caller's thread before the first thread starts: the
// Scratches (OpenSSL's contexts with them), the plaintext buffer, the
// Contents. A thread's first malloc draws an arena and grows it a page
// an `mprotect`, which costs 112-136 us under the chip machine's kernel
// (evolu_host.cpp, reserve_heap; the eight-thread insert of PERF.md §6,
// PR 27, lost to exactly that). A v1 message allocates nothing at all
// (Ctxs names every algorithm to its context when it is built); a v2
// record allocates one small map node the first time its lane sees a
// session salt (aead_key_for) and OpenSSL the GCM state once a lane.

namespace {

constexpr int64_t kLaneMessages = 4096;  // messages that pay for one more lane
constexpr int kMaxLanes = 8;

struct MsgRef { const uint8_t *ts, *ct; size_t ct_len; };

struct Lane {
  Scratch sc;
  int64_t begin = 0, end = 0;  // its messages
  size_t plain_off = 0;        // where their plaintexts start in the call's buffer
  bool failed = false;         // one of them needs the object path
};

int cores_granted() {
  cpu_set_t set;
  if (sched_getaffinity(0, sizeof(set), &set) != 0) return 1;
  int n = CPU_COUNT(&set);
  return n > 0 ? n : 1;
}

// `contents` is raw storage: a lane constructs its messages' Contents,
// so the pages are first touched where they are filled.
void run_lane(Lane &lane, const MsgRef *refs, Content *contents, uint8_t *plain,
              const uint8_t *password, size_t pw_len) {
  plain += lane.plain_off;
  for (int64_t i = lane.begin; i < lane.end; i++) {
    const MsgRef &m = refs[i];
    Content *c = new (contents + i) Content();
    if (!m.ct || !decrypt_one(lane.sc, m.ct, m.ct_len, password, pw_len, plain, *c)) {
      lane.failed = true;  // the whole batch takes the object path: stop here
      return;
    }
    plain += m.ct_len;
  }
}

// `lanes` > 0 sets L (the tests'); 0 lets the call choose it.
int decrypt_response_columns(const uint8_t *resp, int64_t resp_len,
                             const uint8_t *password, int32_t pw_len, int lanes,
                             uint8_t **out_blob, int64_t *out_len,
                             int32_t *out_lanes) {
  if (out_lanes) *out_lanes = 0;
  if (resp_len < 0 || pw_len < 0) return 1;
  size_t n_ = size_t(resp_len);
  const uint8_t *tree = nullptr;
  size_t tree_len = 0;

  // ---- walk ----
  std::vector<MsgRef> refs;
  refs.reserve(size_t(resp_len / 90) + 8);  // a v2 record is >= 90 wire bytes
  int n_caps = 0;
  size_t pos = 0;
  auto walk = [&]() -> int {
    while (pos < n_) {
      uint64_t key;
      if (!read_varint64(resp, n_, pos, key)) return 2;
      uint64_t field = key >> 3;
      int wt = int(key & 7);
      if (wt != 2) return 2;  // canonical SyncResponse is all wt-2
      uint64_t len;
      if (!read_varint64(resp, n_, pos, len)) return 2;
      if (len > n_ - pos) return 2;  // overflow-safe: pos <= n_
      const uint8_t *body = resp + pos;
      size_t blen = size_t(len);
      pos += blen;
      if (field == 2) {
        tree = body;  // last wins, like the Python decoder
        tree_len = blen;
        continue;
      }
      if (field == 3) {
        // Same capability validation as ehc_decrypt_response — the pure
        // decoder raises on bad UTF-8 / >64 entries, so the fused path
        // must never succeed on those shapes.
        if (!capability_ok(body, blen, n_caps)) return 2;
        continue;
      }
      if (field != 1) continue;  // unknown length-delimited field: skip

      // EncryptedCrdtMessage { timestamp=1, content=2 } — last wins.
      MsgRef m{nullptr, nullptr, 0};
      size_t ts_len = 0;
      size_t mp = 0;
      while (mp < blen) {
        uint64_t mkey;
        if (!read_varint64(body, blen, mp, mkey)) return 2;
        uint64_t mf = mkey >> 3;
        int mwt = int(mkey & 7);
        if (mwt != 2) return 2;
        uint64_t mlen;
        if (!read_varint64(body, blen, mp, mlen)) return 2;
        if (mlen > blen - mp) return 2;  // overflow-safe: mp <= blen
        if (mf == 1) { m.ts = body + mp; ts_len = size_t(mlen); }
        else if (mf == 2) { m.ct = body + mp; m.ct_len = size_t(mlen); }
        mp += size_t(mlen);
      }
      // The packed apply path assumes fixed-width canonical timestamps;
      // ASCII also guarantees the (rare) later string materialization
      // decodes losslessly.
      if (ts_len != 46) return 3;
      for (size_t j = 0; j < 46; j++)
        if (m.ts[j] >= 0x80) return 3;
      refs.push_back(m);
    }
    return 0;
  };
  int walk_rc = walk();
  // Every message before a bad timestamp walked clean, so whichever of
  // them fails first fails with 3 as well.
  if (walk_rc == 3) return 3;
  // Before a non-canonical shape (2) a message may still need the object
  // path (3), and the first failure in wire order names the code: go on
  // over the messages walked so far, answer 2 only if they all pass.
  const bool wire_bad = walk_rc != 0;

  // ---- decrypt, in lanes ----
  int64_t n = int64_t(refs.size());
  int64_t L = lanes > 0 ? lanes
                        : std::min<int64_t>({cores_granted(), kMaxLanes, n / kLaneMessages});
  L = std::max<int64_t>(1, std::min(L, n));  // never an empty lane
  std::vector<Lane> lane(static_cast<size_t>(L));
  size_t plain_total = 0;
  for (int64_t j = 0; j < L; j++) {
    Lane &ln = lane[size_t(j)];
    if (!ln.sc.cx.ok()) return 1;
    ln.begin = n * j / L;
    ln.end = n * (j + 1) / L;
    ln.plain_off = plain_total;
    for (int64_t i = ln.begin; i < ln.end; i++) plain_total += refs[size_t(i)].ct_len;
  }
  // Uninitialised on purpose, both: the lanes write every byte that is
  // read afterwards.
  std::unique_ptr<uint8_t[]> plain(new (std::nothrow) uint8_t[plain_total ? plain_total : 1]);
  std::unique_ptr<void, decltype(&free)> contents_mem(
      malloc(n ? size_t(n) * sizeof(Content) : 1), &free);
  Content *contents = static_cast<Content *>(contents_mem.get());
  if (!plain || !contents) return 1;
  std::vector<std::thread> threads;
  threads.reserve(size_t(L - 1));
  for (int64_t j = 1; j < L; j++) {
    try {
      threads.emplace_back(run_lane, std::ref(lane[size_t(j)]), refs.data(),
                           contents, plain.get(), password, size_t(pw_len));
    } catch (const std::system_error &) {
      break;  // no thread to be had: the caller's thread takes the rest
    }
  }
  int64_t started = int64_t(threads.size());
  run_lane(lane[0], refs.data(), contents, plain.get(), password, size_t(pw_len));
  for (int64_t j = started + 1; j < L; j++)
    run_lane(lane[size_t(j)], refs.data(), contents, plain.get(), password, size_t(pw_len));
  for (std::thread &t : threads) t.join();
  if (out_lanes) *out_lanes = int32_t(started + 1);
  for (const Lane &ln : lane)
    if (ln.failed) return 3;  // any demoted row → whole batch takes the object path

  // ---- columnarize, in wire order ----
  std::vector<int64_t> ivals;
  std::vector<double> dvals;
  std::vector<int32_t> cell_ids, vlens, cell_lens;
  std::string vkinds, ts_slab, vblob, cell_blob;
  ivals.reserve(size_t(n)); dvals.reserve(size_t(n));
  cell_ids.reserve(size_t(n)); vlens.reserve(size_t(n));
  vkinds.reserve(size_t(n)); ts_slab.reserve(size_t(n) * 46);
  std::unordered_map<std::string, int32_t> intern;
  // Cold syncs intern ~one cell per row: pre-size for the worst case so
  // the map never rehashes mid-batch — rehash churn measured as a
  // visible share of the unique-cell decode.
  intern.reserve(size_t(n) + 8);
  std::string keybuf;
  for (int64_t i = 0; i < n; i++) {
    const Content &c = contents[i];
    // Intern the cell; validate UTF-8 once per unique triple.
    keybuf.clear();
    uint32_t tl32 = uint32_t(c.tl), rl32 = uint32_t(c.rl);
    keybuf.append(reinterpret_cast<const char *>(&tl32), 4);
    keybuf.append(reinterpret_cast<const char *>(&rl32), 4);
    if (c.tl) keybuf.append(reinterpret_cast<const char *>(c.t), c.tl);
    if (c.rl) keybuf.append(reinterpret_cast<const char *>(c.r), c.rl);
    if (c.cl) keybuf.append(reinterpret_cast<const char *>(c.c), c.cl);
    // try_emplace hashes once for both the hit and the miss lane
    // (find+emplace double-hashed every unique cell).
    auto ins = intern.try_emplace(keybuf, int32_t(intern.size()));
    int32_t cid = ins.first->second;
    if (ins.second) {  // newly interned triple
      if (!utf8_ok(c.t, c.tl) || !utf8_ok(c.r, c.rl) || !utf8_ok(c.c, c.cl))
        return 3;  // whole batch → object path; the map dies with us
      cell_lens.push_back(int32_t(c.tl));
      cell_lens.push_back(int32_t(c.rl));
      cell_lens.push_back(int32_t(c.cl));
      if (c.tl) cell_blob.append(reinterpret_cast<const char *>(c.t), c.tl);
      if (c.rl) cell_blob.append(reinterpret_cast<const char *>(c.r), c.rl);
      if (c.cl) cell_blob.append(reinterpret_cast<const char *>(c.c), c.cl);
    }
    cell_ids.push_back(cid);
    ts_slab.append(reinterpret_cast<const char *>(refs[size_t(i)].ts), 46);
    // Content vkind (0 none, 1 str, 2 int, 3 double) → the SQLite bind
    // encoding shared with eh_apply_planned_packed (0 null, 1 int,
    // 2 double, 3 text).
    switch (c.vkind) {
      case 1:
        if (!utf8_ok(c.s, c.sl)) return 3;
        vkinds.push_back(char(3));
        vlens.push_back(int32_t(c.sl));
        if (c.sl) vblob.append(reinterpret_cast<const char *>(c.s), c.sl);
        break;
      case 2: vkinds.push_back(char(1)); vlens.push_back(0); break;
      case 3: vkinds.push_back(char(2)); vlens.push_back(0); break;
      default: vkinds.push_back(char(0)); vlens.push_back(0); break;
    }
    ivals.push_back(c.ival);
    dvals.push_back(c.dval);
  }
  if (wire_bad) return 2;
  if (tree_len && !utf8_ok(tree, tree_len)) return 3;

  int64_t k = int64_t(intern.size());
  int64_t header[5] = {n, k, int64_t(tree_len), int64_t(vblob.size()),
                       int64_t(cell_blob.size())};
  size_t total = sizeof(header) + size_t(n) * (8 + 8 + 4 + 4 + 1) +
                 size_t(k) * 12 + ts_slab.size() + vblob.size() +
                 cell_blob.size() + tree_len;
  uint8_t *blob = static_cast<uint8_t *>(malloc(total ? total : 1));
  if (!blob) return 1;
  uint8_t *p = blob;
  auto put = [&p](const void *src, size_t len) {
    if (len) memcpy(p, src, len);
    p += len;
  };
  put(header, sizeof(header));
  put(ivals.data(), size_t(n) * 8);
  put(dvals.data(), size_t(n) * 8);
  put(cell_ids.data(), size_t(n) * 4);
  put(vlens.data(), size_t(n) * 4);
  put(cell_lens.data(), size_t(k) * 12);
  put(vkinds.data(), vkinds.size());
  put(ts_slab.data(), ts_slab.size());
  put(vblob.data(), vblob.size());
  put(cell_blob.data(), cell_blob.size());
  put(tree, tree_len);
  *out_blob = blob;
  *out_len = int64_t(total);
  return 0;
}

}  // namespace

// Columnar twin of ehc_decrypt_response for the fused receive→apply
// path (reference sync.worker.ts:135-173 → receive.ts:144 →
// applyMessages.ts:78 as ONE leg). Succeeds ONLY when every message
// decrypts on the canonical fast path, every timestamp is exactly 46
// ASCII bytes, and every string field (incl. the tree) is strict
// UTF-8 — the Python side then feeds the batch straight into the
// planner and the packed SQLite apply with ZERO per-row objects.
// Cells (table,row,column) are interned in first-appearance order
// (parity with host_parse.intern_cells) so only k unique triples ever
// become Python strings.
// Returns 0 ok; 2 non-canonical wire; 3 some row needs the object
// path (the caller falls back to ehc_decrypt_response, whose per-row
// oracle demotion owns the exact error surface); 1 internal.
// Output blob layout (little-endian, naturally aligned):
//   [i64 n][i64 k][i64 tree_len][i64 vblob_len][i64 cell_blob_len]
//   ivals i64[n]; dvals f64[n];
//   cell_id i32[n]; vlens i32[n]; cell_lens i32[3k];
//   vkinds u8[n] (SQLite bind encoding: 0 null, 1 int, 2 double, 3 text)
//   ts_slab u8[46*n]; vblob; cell_blob; tree
// `out_lanes` (nullable) receives the lanes that ran at once: 1 + the
// threads the call started, 1 when it started none.
int ehc_decrypt_response_columns(const uint8_t *resp, int64_t resp_len,
                                 const uint8_t *password, int32_t pw_len,
                                 uint8_t **out_blob, int64_t *out_len,
                                 int32_t *out_lanes) {
  return decrypt_response_columns(resp, resp_len, password, pw_len, /*lanes=*/0,
                                  out_blob, out_len, out_lanes);
}

// The same with L given (1..64), for the tests: as eh_reserve_heap is
// for the heap.
int ehc_decrypt_response_columns_lanes(const uint8_t *resp, int64_t resp_len,
                                       const uint8_t *password, int32_t pw_len,
                                       int32_t lanes, uint8_t **out_blob,
                                       int64_t *out_len, int32_t *out_lanes) {
  if (lanes < 1 || lanes > 64) return 1;
  return decrypt_response_columns(resp, resp_len, password, pw_len, lanes,
                                  out_blob, out_len, out_lanes);
}

}  // extern "C"

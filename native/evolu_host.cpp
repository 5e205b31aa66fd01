// evolu_host — C++ SQLite host layer for the TPU framework.
//
// The reference's only native code is SQLite itself (vendored twice:
// wa-sqlite in the browser, better-sqlite3 on the server — SURVEY.md
// §2.14). This library plays the same role for the Python runtime: the
// storage engine is the real SQLite C library driven from C++, and the
// merge hot path — the reference's per-message applyMessages loop
// (packages/evolu/src/applyMessages.ts:26-131) — runs entirely inside
// one C call per batch, with prepared-statement caching like the
// reference's per-SQL cache (applyMessages.ts:46-73).
//
// The image ships libsqlite3.so.0 but no sqlite3.h, so the handful of
// C-API entry points used here are declared directly; the SQLite C ABI
// is stable and these signatures match https://sqlite.org/c3ref.
//
// Exported surface (C ABI, driven from Python via ctypes):
//   eh_open/eh_close/eh_errmsg/eh_exec/eh_changes/eh_total_changes
//   eh_prepare/eh_finalize/eh_bind_*/eh_step/eh_reset/eh_column_*
//   eh_fetch_winners   — batched per-cell winner lookup
//   eh_apply_sequential — the reference loop (winner check + app-table
//                         upsert + __message insert), masks out
//   eh_apply_planned_packed — apply a device-computed plan (upsert mask)
//
// Value passing: each message value arrives as (kind, int64, double,
// text, blob_len) where kind ∈ {0:null, 1:int64, 2:double, 3:text,
// 4:blob} — no string round-trip for numerics, preserving SQLite
// storage classes byte-for-byte vs the Python backend.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <map>
#include <string>
#include <vector>

#include "pyabi.h"
#include "wire.h"

extern "C" {

// --- SQLite C ABI (subset) ---
typedef struct sqlite3 sqlite3;
typedef struct sqlite3_stmt sqlite3_stmt;
typedef int64_t sqlite3_int64;

int sqlite3_open_v2(const char *filename, sqlite3 **db, int flags, const char *vfs);
int sqlite3_close_v2(sqlite3 *);
int sqlite3_exec(sqlite3 *, const char *sql, int (*cb)(void *, int, char **, char **),
                 void *, char **errmsg);
void sqlite3_free(void *);
int sqlite3_prepare_v2(sqlite3 *, const char *sql, int nbyte, sqlite3_stmt **, const char **tail);
int sqlite3_finalize(sqlite3_stmt *);
int sqlite3_step(sqlite3_stmt *);
int sqlite3_reset(sqlite3_stmt *);
int sqlite3_clear_bindings(sqlite3_stmt *);
int sqlite3_bind_null(sqlite3_stmt *, int);
int sqlite3_bind_int64(sqlite3_stmt *, int, sqlite3_int64);
int sqlite3_bind_double(sqlite3_stmt *, int, double);
int sqlite3_bind_text(sqlite3_stmt *, int, const char *, int n, void (*)(void *));
int sqlite3_bind_blob(sqlite3_stmt *, int, const void *, int n, void (*)(void *));
int sqlite3_column_count(sqlite3_stmt *);
const char *sqlite3_column_name(sqlite3_stmt *, int);
int sqlite3_column_type(sqlite3_stmt *, int);
sqlite3_int64 sqlite3_column_int64(sqlite3_stmt *, int);
double sqlite3_column_double(sqlite3_stmt *, int);
const unsigned char *sqlite3_column_text(sqlite3_stmt *, int);
const void *sqlite3_column_blob(sqlite3_stmt *, int);
int sqlite3_column_bytes(sqlite3_stmt *, int);
int sqlite3_changes(sqlite3 *);
int sqlite3_total_changes(sqlite3 *);
const char *sqlite3_errmsg(sqlite3 *);

}  // extern "C"

#define SQLITE_OK 0
#define SQLITE_ROW 100
#define SQLITE_DONE 101
#define SQLITE_OPEN_READWRITE 0x00000002
#define SQLITE_OPEN_CREATE 0x00000004
#define SQLITE_OPEN_URI 0x00000040
#define SQLITE_TRANSIENT ((void (*)(void *))(intptr_t)-1)
#define SQLITE_INTEGER 1
#define SQLITE_FLOAT 2
#define SQLITE_TEXT 3
#define SQLITE_BLOB 4
#define SQLITE_NULL 5
// For the batched entry points the caller's buffers outlive the whole
// C call (ctypes arrays hold them), so SQLITE_STATIC avoids a copy per
// bind; each row is stepped and reset before buffers change.
#define SQLITE_STATIC ((void (*)(void *))0)

namespace {

// Bind one (kind, int, real, text/blob bytes, byte_len) value at `pos`.
// TEXT uses the explicit byte length too — values may contain NUL
// bytes, which must round-trip identically to the Python backend.
int bind_value(sqlite3_stmt *st, int pos, int kind, int64_t iv, double dv,
               const char *sv, int byte_len) {
  switch (kind) {
    case 1: return sqlite3_bind_int64(st, pos, iv);
    case 2: return sqlite3_bind_double(st, pos, dv);
    case 3: return sqlite3_bind_text(st, pos, sv, byte_len, SQLITE_TRANSIENT);
    case 4: return sqlite3_bind_blob(st, pos, sv, byte_len, SQLITE_TRANSIENT);
    default: return sqlite3_bind_null(st, pos);
  }
}

// Like bind_value, but the caller's buffers outlive the statement step
// (packed batch entry points), so SQLITE_STATIC skips the copy.
int bind_value_static(sqlite3_stmt *st, int pos, int kind, int64_t iv, double dv,
                      const char *sv, int byte_len) {
  switch (kind) {
    case 1: return sqlite3_bind_int64(st, pos, iv);
    case 2: return sqlite3_bind_double(st, pos, dv);
    case 3: return sqlite3_bind_text(st, pos, sv, byte_len, SQLITE_STATIC);
    case 4: return sqlite3_bind_blob(st, pos, sv, byte_len, SQLITE_STATIC);
    default: return sqlite3_bind_null(st, pos);
  }
}

// Per-batch prepared-statement cache keyed by SQL — the reference's
// cacheGet/cacheRelease (applyMessages.ts:46-73), scoped to one call.
struct StmtCache {
  sqlite3 *db;
  std::map<std::string, sqlite3_stmt *> cache;
  explicit StmtCache(sqlite3 *d) : db(d) {}
  ~StmtCache() {
    for (auto &kv : cache) sqlite3_finalize(kv.second);
  }
  sqlite3_stmt *get(const std::string &sql) {
    auto it = cache.find(sql);
    if (it != cache.end()) return it->second;
    sqlite3_stmt *st = nullptr;
    if (sqlite3_prepare_v2(db, sql.c_str(), -1, &st, nullptr) != SQLITE_OK) return nullptr;
    cache.emplace(sql, st);
    return st;
  }
};

std::string quote_ident(const char *name) {
  // "name" with embedded quotes doubled (identifiers come from the
  // app schema; quoting matches the Python backend's _upsert_sql).
  std::string out = "\"";
  for (const char *p = name; *p; ++p) {
    out += *p;
    if (*p == '"') out += '"';
  }
  out += '"';
  return out;
}

std::string upsert_sql(const char *table, const char *column) {
  // applyMessages.ts:92-103
  std::string t = quote_ident(table), c = quote_ident(column);
  // Explicit conflict target: targetless DO UPDATE needs SQLite >=
  // 3.35; ON CONFLICT("id") works on every 3.24+. Same text in
  // storage/apply.py::_upsert_sql.
  return "INSERT INTO " + t + " (\"id\", " + c + ") VALUES (?, ?) "
         "ON CONFLICT(\"id\") DO UPDATE SET " + c + " = ?";
}

constexpr const char *kSelectWinner =
    "SELECT \"timestamp\" FROM \"__message\" "
    "WHERE \"table\" = ? AND \"row\" = ? AND \"column\" = ? "
    "ORDER BY \"timestamp\" DESC LIMIT 1";

constexpr const char *kInsertMessage =
    "INSERT INTO \"__message\" (\"timestamp\", \"table\", \"row\", \"column\", \"value\") "
    "VALUES (?, ?, ?, ?, ?) ON CONFLICT DO NOTHING";

int step_done(sqlite3_stmt *st) {
  int rc = sqlite3_step(st);
  sqlite3_reset(st);
  sqlite3_clear_bindings(st);
  return rc == SQLITE_DONE || rc == SQLITE_ROW ? SQLITE_OK : rc;
}

// --- heap reservation per native batch ---
//
// glibc gives every thread but the main one an arena whose heaps are
// 64 MiB mappings (HEAP_MAX_SIZE), PROT_NONE beyond what has been used,
// and `sysmalloc` makes them writable through `grow_heap` by exactly
// what the failing request lacks: no top pad applies there, so a batch
// that stores pages of 4 KiB and journal chunks of 1 KiB pays one
// `mprotect` for every page of heap it gains. Under the chip machine's
// sandboxed kernel that call costs 112-136 us whatever it covers (3 us
// in the CPU sandbox), and four fifths of a restoring client's
// `eh_apply_planned_cells` was that (PERF.md §6, PR 29 and PR 30).
// `grow_heap` calls `mprotect` only beyond the heap's high-water mark
// (`mprotect_size`), and `shrink_heap` gives pages back with `madvise`
// and leaves the mark where it was. So before a batch steps its first
// statement, reserve_heap takes blocks under the default mmap threshold
// (128 KiB) from malloc until they cover what the batch is about to
// store, one `mprotect` a block, and frees them again: the pages the
// statements then ask for lie under the mark.
//
// The blocks are freed in the order they were taken. Each but the last
// has a live block between it and the top of the heap, so it only
// coalesces with the ones freed before it; the last joins the top, and
// the allocator trims once (`heap_trim`: one `madvise`), not once a
// block. Nothing is kept: no block outlives the call, the blocks are
// never written to, and what the trim leaves writable is address space
// whose pages the kernel has taken back. On an allocator that is not
// glibc's, and in glibc's main arena (`brk`, which already grows by a
// pad), this is a few hundred malloc/free pairs and no gain.
//
// The estimate is the batch's own input bytes times a constant measured
// once (CHANGES.md, PR 30: `sqlite3_memory_used()` and the heap's
// extent in /proc/self/maps over one call). A reservation that is too
// large costs one `mprotect` a block once, since the mark stays for the
// next batch on the thread; one that is too small leaves the remainder
// at one a page. Nothing here touches SQLite, and nothing is global:
// the threading contract above eh_relay_insert_packed holds.

// A block is three quarters of the default M_MMAP_THRESHOLD (128 KiB; at
// or above it malloc maps the block by itself and the heap gains
// nothing); the call's cost does not grow with it, so fewer and larger
// is cheaper. The cap is the whole blocks in half of one heap.
constexpr int64_t kReserveBlock = 96 * 1024;
constexpr int64_t kReserveCap = 32 * 1024 * 1024 / kReserveBlock * kReserveBlock;
// Heap bytes a stored input byte asks for inside one transaction:
// `__message` is a row, its primary key's index and the covering index
// (3.3 bytes of pages a byte) plus the in-memory journal of every page
// the batch rewrites (up to 3.8 more once the table holds 75,000 rows).
constexpr int64_t kHeapPerByteIndexed = 8;
// One B-tree and no second copy of the row: the relay's `message`
// (WITHOUT ROWID), a temp table, an app table.
constexpr int64_t kHeapPerByteRow = 4;

// Makes `input_bytes * per_byte` of the calling thread's heap writable
// (at most kReserveCap, whole blocks only: under one block, nothing);
// returns the bytes it held, all freed again. Every batched entry point
// ends its argument list with a nullable `out_reserved` that receives
// them too.
int64_t reserve_heap(int64_t input_bytes, int64_t per_byte, int64_t *out_reserved = nullptr) {
  if (out_reserved) *out_reserved = 0;
  if (input_bytes <= 0) return 0;
  int64_t want = input_bytes > kReserveCap / per_byte ? kReserveCap : input_bytes * per_byte;
  size_t blocks = size_t(want / kReserveBlock), got = 0;
  void *held[kReserveCap / kReserveBlock];
  while (got < blocks && (held[got] = malloc(size_t(kReserveBlock))) != nullptr) ++got;
  for (size_t i = 0; i < got; ++i) free(held[i]);
  int64_t reserved = int64_t(got) * kReserveBlock;
  if (out_reserved) *out_reserved = reserved;
  return reserved;
}

int64_t text_bytes(const char *const *items, int64_t n) {
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) total += int64_t(strlen(items[i]));
  return total;
}

template <class T>
int64_t sum_of(const T *items, int64_t n) {
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) total += items[i];
  return total;
}

}  // namespace

extern "C" {

// reserve_heap itself, for the tests.
int64_t eh_reserve_heap(int64_t input_bytes, int64_t per_byte) {
  return per_byte > 0 ? reserve_heap(input_bytes, per_byte) : 0;
}

sqlite3 *eh_open(const char *path) {
  sqlite3 *db = nullptr;
  int flags = SQLITE_OPEN_READWRITE | SQLITE_OPEN_CREATE | SQLITE_OPEN_URI;
  if (sqlite3_open_v2(path, &db, flags, nullptr) != SQLITE_OK) {
    if (db) sqlite3_close_v2(db);
    return nullptr;
  }
  return db;
}

int eh_close(sqlite3 *db) { return sqlite3_close_v2(db); }

const char *eh_errmsg(sqlite3 *db) { return sqlite3_errmsg(db); }

int eh_exec(sqlite3 *db, const char *sql) {
  return sqlite3_exec(db, sql, nullptr, nullptr, nullptr);
}

int eh_changes(sqlite3 *db) { return sqlite3_changes(db); }
int eh_total_changes(sqlite3 *db) { return sqlite3_total_changes(db); }

// --- generic prepared-statement surface (cold paths, driven from Python) ---

sqlite3_stmt *eh_prepare(sqlite3 *db, const char *sql) {
  sqlite3_stmt *st = nullptr;
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return nullptr;
  return st;
}

// Like eh_prepare but rejects trailing statements: *tail_nonempty is
// set when anything but whitespace/semicolons follows the first
// statement (PySqliteDatabase's execute raises there too).
sqlite3_stmt *eh_prepare_single(sqlite3 *db, const char *sql, int *tail_nonempty) {
  sqlite3_stmt *st = nullptr;
  const char *tail = nullptr;
  *tail_nonempty = 0;
  if (sqlite3_prepare_v2(db, sql, -1, &st, &tail) != SQLITE_OK) return nullptr;
  // Skip whitespace, ';', and SQL comments ("--...\n", "/*...*/") —
  // Python's sqlite3.execute accepts those after the statement too.
  const char *p = tail ? tail : "";
  while (*p) {
    if (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r' || *p == ';') {
      ++p;
    } else if (p[0] == '-' && p[1] == '-') {
      while (*p && *p != '\n') ++p;
    } else if (p[0] == '/' && p[1] == '*') {
      p += 2;
      while (*p && !(p[0] == '*' && p[1] == '/')) ++p;
      if (*p) p += 2;
    } else {
      *tail_nonempty = 1;
      break;
    }
  }
  return st;
}

int eh_finalize(sqlite3_stmt *st) { return sqlite3_finalize(st); }
int eh_step(sqlite3_stmt *st) { return sqlite3_step(st); }
int eh_reset(sqlite3_stmt *st) {
  int rc = sqlite3_reset(st);
  sqlite3_clear_bindings(st);
  return rc;
}

int eh_bind(sqlite3_stmt *st, int pos, int kind, int64_t iv, double dv,
            const char *sv, int blob_len) {
  return bind_value(st, pos, kind, iv, dv, sv, blob_len);
}

int eh_column_count(sqlite3_stmt *st) { return sqlite3_column_count(st); }
const char *eh_column_name(sqlite3_stmt *st, int i) { return sqlite3_column_name(st, i); }
int eh_column_type(sqlite3_stmt *st, int i) { return sqlite3_column_type(st, i); }
int64_t eh_column_int64(sqlite3_stmt *st, int i) { return sqlite3_column_int64(st, i); }
double eh_column_double(sqlite3_stmt *st, int i) { return sqlite3_column_double(st, i); }
const unsigned char *eh_column_text(sqlite3_stmt *st, int i) { return sqlite3_column_text(st, i); }
const void *eh_column_blob(sqlite3_stmt *st, int i) { return sqlite3_column_blob(st, i); }
int eh_column_bytes(sqlite3_stmt *st, int i) { return sqlite3_column_bytes(st, i); }

// --- hot path 1: batched winner lookup ---
//
// For each distinct cell i, writes the current winner timestamp into
// out[i] (caller-provided buffer of size out_cap, 0-terminated; empty
// string = no winner). Timestamps are 46 ASCII chars, so out_cap=47.
int eh_fetch_winners(sqlite3 *db, int64_t n, const char *const *tables,
                     const char *const *rows, const char *const *cols,
                     char *out, int64_t out_cap) {
  sqlite3_stmt *st = nullptr;
  if (sqlite3_prepare_v2(db, kSelectWinner, -1, &st, nullptr) != SQLITE_OK) return 1;
  for (int64_t i = 0; i < n; ++i) {
    sqlite3_bind_text(st, 1, tables[i], -1, SQLITE_TRANSIENT);
    sqlite3_bind_text(st, 2, rows[i], -1, SQLITE_TRANSIENT);
    sqlite3_bind_text(st, 3, cols[i], -1, SQLITE_TRANSIENT);
    int rc = sqlite3_step(st);
    char *dst = out + i * out_cap;
    if (rc == SQLITE_ROW) {
      // NULL is possible despite the PK (SQLite's legacy non-INTEGER
      // BLOB PRIMARY KEY quirk allows NULL in tampered/corrupt DBs);
      // treat it as no-winner rather than reading a null pointer.
      const unsigned char *t = sqlite3_column_text(st, 0);
      if (t == nullptr) {
        dst[0] = '\0';
      } else {
        std::strncpy(dst, reinterpret_cast<const char *>(t), out_cap - 1);
        dst[out_cap - 1] = '\0';
      }
    } else if (rc == SQLITE_DONE) {
      dst[0] = '\0';
    } else {
      sqlite3_finalize(st);
      return 1;
    }
    sqlite3_reset(st);
    sqlite3_clear_bindings(st);
  }
  sqlite3_finalize(st);
  return 0;
}

// --- hot path 2: the reference loop, one C call per batch ---
//
// Exactly applyMessages.ts:78-124 per message, inside the caller's
// transaction: winner SELECT; upsert the app table when the message
// beats it; INSERT OR NOTHING into __message and flag the Merkle XOR
// when the winner differs. out_xor[i]=1 marks messages whose hash the
// caller XORs into the tree (host-side sparse trie update).
int eh_apply_sequential(sqlite3 *db, int64_t n, const char *const *timestamps,
                        const char *const *tables, const char *const *rows,
                        const char *const *cols, const int32_t *kinds,
                        const int64_t *ivals, const double *dvals,
                        const char *const *svals, const int32_t *blob_lens,
                        uint8_t *out_xor, int64_t *out_reserved) {
  reserve_heap(text_bytes(timestamps, n) + text_bytes(tables, n) + text_bytes(rows, n) +
                   text_bytes(cols, n) + sum_of(blob_lens, n),
               kHeapPerByteIndexed, out_reserved);
  StmtCache cache(db);
  sqlite3_stmt *sel = cache.get(kSelectWinner);
  sqlite3_stmt *ins = cache.get(kInsertMessage);
  if (!sel || !ins) return 1;

  for (int64_t i = 0; i < n; ++i) {
    sqlite3_bind_text(sel, 1, tables[i], -1, SQLITE_TRANSIENT);
    sqlite3_bind_text(sel, 2, rows[i], -1, SQLITE_TRANSIENT);
    sqlite3_bind_text(sel, 3, cols[i], -1, SQLITE_TRANSIENT);
    int rc = sqlite3_step(sel);
    bool has_winner = rc == SQLITE_ROW;
    if (!has_winner && rc != SQLITE_DONE) return 1;
    std::string winner;
    if (has_winner) {
      const unsigned char *w = sqlite3_column_text(sel, 0);
      if (w == nullptr)  // tampered DB: NULL in the BLOB PK column
        has_winner = false;
      else
        winner = reinterpret_cast<const char *>(w);
    }
    sqlite3_reset(sel);
    sqlite3_clear_bindings(sel);

    bool newer = !has_winner || winner.compare(timestamps[i]) < 0;
    if (newer) {  // applyMessages.ts:92-103
      sqlite3_stmt *up = cache.get(upsert_sql(tables[i], cols[i]));
      if (!up) return 1;
      sqlite3_bind_text(up, 1, rows[i], -1, SQLITE_TRANSIENT);
      bind_value(up, 2, kinds[i], ivals[i], dvals[i], svals[i], blob_lens[i]);
      bind_value(up, 3, kinds[i], ivals[i], dvals[i], svals[i], blob_lens[i]);
      if (step_done(up) != SQLITE_OK) return 1;
    }
    bool differs = !has_winner || winner.compare(timestamps[i]) != 0;
    out_xor[i] = differs ? 1 : 0;
    if (differs) {  // applyMessages.ts:104-122
      sqlite3_bind_text(ins, 1, timestamps[i], -1, SQLITE_TRANSIENT);
      sqlite3_bind_text(ins, 2, tables[i], -1, SQLITE_TRANSIENT);
      sqlite3_bind_text(ins, 3, rows[i], -1, SQLITE_TRANSIENT);
      sqlite3_bind_text(ins, 4, cols[i], -1, SQLITE_TRANSIENT);
      bind_value(ins, 5, kinds[i], ivals[i], dvals[i], svals[i], blob_lens[i]);
      if (step_done(ins) != SQLITE_OK) return 1;
    }
  }
  return 0;
}

// --- hot path 3: apply a device-computed plan ---
//
// The TPU planner already decided the final winner per cell
// (upsert_mask) and the Merkle XOR set; this applies the SQL side —
// upserts for flagged rows, then the bulk __message insert for ALL
// rows (PK dedup) — inside the caller's transaction.
// Packed variant: each string column arrives as ONE contiguous buffer
// plus per-row byte lengths — no per-row pointer marshalling on the
// Python side, and every bind carries its explicit byte length, so
// embedded NUL bytes in table/row/column round-trip exactly like the
// Python backend (the pointer variant above truncates at NUL).
// Returns 0 ok, 1 SQLite error, 3 NUL inside an upserted identifier
// (the Python backend's quote_ident raises there; whole batch aborts).
int eh_apply_planned_packed(sqlite3 *db, int64_t n,
                            const char *ts_buf, const int32_t *ts_lens,
                            const char *tbl_buf, const int32_t *tbl_lens,
                            const char *row_buf, const int32_t *row_lens,
                            const char *col_buf, const int32_t *col_lens,
                            const int32_t *kinds, const int64_t *ivals,
                            const double *dvals, const char *val_buf,
                            const int32_t *val_lens,
                            const uint8_t *upsert_mask, int64_t *out_reserved) {
  reserve_heap(sum_of(ts_lens, n) + sum_of(tbl_lens, n) + sum_of(row_lens, n) +
                   sum_of(col_lens, n) + sum_of(val_lens, n),
               kHeapPerByteIndexed, out_reserved);
  StmtCache cache(db);
  sqlite3_stmt *ins = cache.get(kInsertMessage);
  if (!ins) return 1;
  int64_t ts_o = 0, tbl_o = 0, row_o = 0, col_o = 0, val_o = 0;
  for (int64_t i = 0; i < n; ++i) {
    const char *ts = ts_buf + ts_o;
    const char *tbl = tbl_buf + tbl_o;
    const char *row = row_buf + row_o;
    const char *col = col_buf + col_o;
    const char *val = val_buf + val_o;
    const int tsl = ts_lens[i], tbll = tbl_lens[i], rowl = row_lens[i],
              coll = col_lens[i], vall = val_lens[i];
    ts_o += tsl; tbl_o += tbll; row_o += rowl; col_o += coll;
    if (kinds[i] == 3 || kinds[i] == 4) val_o += vall;
    if (upsert_mask[i]) {
      if (memchr(tbl, 0, tbll) || memchr(col, 0, coll)) return 3;
      std::string tname(tbl, tbll), cname(col, coll);
      sqlite3_stmt *up = cache.get(upsert_sql(tname.c_str(), cname.c_str()));
      if (!up) return 1;
      sqlite3_bind_text(up, 1, row, rowl, SQLITE_STATIC);
      bind_value_static(up, 2, kinds[i], ivals[i], dvals[i], val, vall);
      bind_value_static(up, 3, kinds[i], ivals[i], dvals[i], val, vall);
      if (step_done(up) != SQLITE_OK) return 1;
    }
    sqlite3_bind_text(ins, 1, ts, tsl, SQLITE_STATIC);
    sqlite3_bind_text(ins, 2, tbl, tbll, SQLITE_STATIC);
    sqlite3_bind_text(ins, 3, row, rowl, SQLITE_STATIC);
    sqlite3_bind_text(ins, 4, col, coll, SQLITE_STATIC);
    bind_value_static(ins, 5, kinds[i], ivals[i], dvals[i], val, vall);
    if (step_done(ins) != SQLITE_OK) return 1;
  }
  return 0;
}


// --- hot path 3b: apply a device-computed plan from INTERNED columns ---
//
// The fused receive leg (ehc_decrypt_response_columns) emits the batch
// as a fixed-width 46-byte timestamp slab plus k unique
// (table,row,column) cells and per-row cell indices; this applies the
// plan straight from those buffers — no per-row string expansion on
// the Python side at all. Semantics are identical to
// eh_apply_planned_packed (upserts for masked rows, bulk __message
// insert for all rows, explicit byte lengths everywhere so embedded
// NULs round-trip). kinds use the bind encoding (0 null, 1 int,
// 2 double, 3 text). Returns 0 ok, 1 SQLite error, 2 bad cell index,
// 3 NUL inside an upserted identifier.
int eh_apply_planned_cells(sqlite3 *db, int64_t n, const char *ts_slab,
                           int64_t k, const char *cell_blob,
                           const int32_t *cell_lens, const int32_t *cell_ids,
                           const uint8_t *kinds, const int64_t *ivals,
                           const double *dvals, const char *val_blob,
                           const int32_t *val_lens,
                           const uint8_t *upsert_mask, int64_t *out_reserved) {
  // Per-cell field offsets into cell_blob (k is small: unique cells).
  std::vector<int64_t> coff(size_t(k) * 3 + 1);
  int64_t o = 0;
  for (int64_t j = 0; j < k * 3; ++j) {
    coff[size_t(j)] = o;
    o += cell_lens[j];
  }
  coff[size_t(k) * 3] = o;
  int64_t input_bytes = n * 46 + sum_of(val_lens, n);
  for (int64_t i = 0; i < n; ++i) {
    int64_t cid = cell_ids[i];
    if (cid >= 0 && cid < k) input_bytes += coff[size_t(cid) * 3 + 3] - coff[size_t(cid) * 3];
  }
  reserve_heap(input_bytes, kHeapPerByteIndexed, out_reserved);
  StmtCache cache(db);
  sqlite3_stmt *ins = cache.get(kInsertMessage);
  if (!ins) return 1;
  // Upsert statements resolved once per cell, not per row (lazy: most
  // cells in a steady-state batch never win).
  std::vector<sqlite3_stmt *> up_stmt(size_t(k), nullptr);
  std::vector<int8_t> up_state(size_t(k), 0);  // 0 unresolved, 1 ok, 3 NUL

  int64_t val_o = 0;
  for (int64_t i = 0; i < n; ++i) {
    int32_t cid = cell_ids[i];
    if (cid < 0 || int64_t(cid) >= k) return 2;
    const char *tbl = cell_blob + coff[size_t(cid) * 3];
    const char *row = cell_blob + coff[size_t(cid) * 3 + 1];
    const char *col = cell_blob + coff[size_t(cid) * 3 + 2];
    const int tbll = cell_lens[cid * 3], rowl = cell_lens[cid * 3 + 1],
              coll = cell_lens[cid * 3 + 2];
    const char *val = val_blob + val_o;
    const int vall = val_lens[i];
    if (kinds[i] == 3) val_o += vall;
    if (upsert_mask[i]) {
      if (up_state[size_t(cid)] == 0) {
        if (memchr(tbl, 0, tbll) || memchr(col, 0, coll)) {
          up_state[size_t(cid)] = 3;
        } else {
          std::string tname(tbl, tbll), cname(col, coll);
          up_stmt[size_t(cid)] = cache.get(upsert_sql(tname.c_str(), cname.c_str()));
          up_state[size_t(cid)] = up_stmt[size_t(cid)] ? 1 : 2;
        }
      }
      if (up_state[size_t(cid)] == 3) return 3;
      if (up_state[size_t(cid)] != 1) return 1;
      sqlite3_stmt *up = up_stmt[size_t(cid)];
      sqlite3_bind_text(up, 1, row, rowl, SQLITE_STATIC);
      bind_value_static(up, 2, kinds[i], ivals[i], dvals[i], val, vall);
      bind_value_static(up, 3, kinds[i], ivals[i], dvals[i], val, vall);
      if (step_done(up) != SQLITE_OK) return 1;
    }
    sqlite3_bind_text(ins, 1, ts_slab + i * 46, 46, SQLITE_STATIC);
    sqlite3_bind_text(ins, 2, tbl, tbll, SQLITE_STATIC);
    sqlite3_bind_text(ins, 3, row, rowl, SQLITE_STATIC);
    sqlite3_bind_text(ins, 4, col, coll, SQLITE_STATIC);
    bind_value_static(ins, 5, kinds[i], ivals[i], dvals[i], val, vall);
    if (step_done(ins) != SQLITE_OK) return 1;
  }
  return 0;
}

// --- relay hot path: bulk (timestamp, userId, content) insert with
// per-row "was new" flags (INSERT OR IGNORE changes()==1 semantics,
// apps/server/src/index.ts:148-159). content is a blob. ---
// --- packed fixed-width timestamp parse ---
//
// The host-side batch columnarization (ops/host_parse.py) is the same
// loop in numpy; this is its native twin for the hot server/client
// paths (one pass over the packed 46-byte records instead of ~40
// vectorized passes). Validation is identical: exact separators,
// digit ranges with real calendar rules, hex fields accepting both
// cases. out_case_ok[i] = 1 iff the row uses the canonical encoder's
// case (UPPERCASE counter / lowercase node). Returns 0, or 1 on any
// malformed row (callers abort the batch, like the numpy path).

static inline int64_t days_from_civil(int64_t y, int m, int d) {
  y -= m <= 2;
  int64_t era = (y >= 0 ? y : y - 399) / 400;
  int yoe = (int)(y - era * 400);
  int doy = (153 * (m + (m > 2 ? -3 : 9)) + 2) / 5 + d - 1;
  int doe = yoe * 365 + yoe / 4 - yoe / 100 + doy;
  return era * 146097 + doe - 719468;
}

static inline bool is_leap(int64_t y) {
  return (y % 4 == 0 && y % 100 != 0) || y % 400 == 0;
}

int eh_parse_timestamps(const char *ts_packed, int64_t n, int64_t *out_millis,
                        int32_t *out_counter, uint64_t *out_node,
                        uint8_t *out_case_ok) {
  static const int month_days[13] = {0, 31, 28, 31, 30, 31, 30,
                                     31, 31, 30, 31, 30, 31};
  for (int64_t i = 0; i < n; ++i) {
    const unsigned char *t =
        reinterpret_cast<const unsigned char *>(ts_packed) + i * 46;
    if (t[4] != '-' || t[7] != '-' || t[10] != 'T' || t[13] != ':' ||
        t[16] != ':' || t[19] != '.' || t[23] != 'Z' || t[24] != '-' ||
        t[29] != '-')
      return 1;
    int64_t nums[7];  // y, mo, d, hh, mi, ss, ms
    static const int spans[7][2] = {{0, 4},   {5, 7},   {8, 10},  {11, 13},
                                    {14, 16}, {17, 19}, {20, 23}};
    for (int f = 0; f < 7; ++f) {
      int64_t v = 0;
      for (int j = spans[f][0]; j < spans[f][1]; ++j) {
        if (t[j] < '0' || t[j] > '9') return 1;
        v = v * 10 + (t[j] - '0');
      }
      nums[f] = v;
    }
    int64_t y = nums[0];
    int mo = (int)nums[1], d = (int)nums[2];
    if (y < 1 || mo < 1 || mo > 12 || d < 1) return 1;
    int dim = month_days[mo] + ((mo == 2 && is_leap(y)) ? 1 : 0);
    if (d > dim || nums[3] > 23 || nums[4] > 59 || nums[5] > 59) return 1;
    out_millis[i] =
        ((days_from_civil(y, mo, d) * 86400 + nums[3] * 3600 + nums[4] * 60 +
          nums[5]) *
         1000) +
        nums[6];
    bool canonical = true;
    uint32_t counter = 0;
    for (int j = 25; j < 29; ++j) {
      unsigned char c = t[j];
      uint32_t nib;
      if (c >= '0' && c <= '9') nib = c - '0';
      else if (c >= 'A' && c <= 'F') nib = c - 'A' + 10;
      else if (c >= 'a' && c <= 'f') { nib = c - 'a' + 10; canonical = false; }
      else return 1;
      counter = (counter << 4) | nib;
    }
    out_counter[i] = (int32_t)counter;
    uint64_t node = 0;
    for (int j = 30; j < 46; ++j) {
      unsigned char c = t[j];
      uint64_t nib;
      if (c >= '0' && c <= '9') nib = c - '0';
      else if (c >= 'a' && c <= 'f') nib = c - 'a' + 10;
      else if (c >= 'A' && c <= 'F') { nib = c - 'A' + 10; canonical = false; }
      else return 1;
      node = (node << 4) | nib;
    }
    out_node[i] = node;
    out_case_ok[i] = canonical ? 1 : 0;
  }
  return 0;
}

// --- the relay pass's request pack (CPython ABI, pyabi.h) ---
//
// `BatchReconciler._pack_batch`'s per-message half in one walk: the
// in-batch dedup on (timestamp, owner), and per shard the packed
// 46-byte timestamps, the packed contents and their lengths — the
// buffers eh_parse_timestamps and eh_relay_insert_packed_shards read.
// The Python body took ~1.1 us a message for this (a set probe and a
// tuple, two list.extends of generators, two map(len)s, two joins);
// here a message is an item read, two attribute reads, a hash probe
// and two copies. Called through ctypes.PyDLL (ops/host_parse.py):
// the GIL is HELD for the whole call, which touches Python objects
// from its first line to its last.
//
// `groups` is a sequence of the requests' `messages` sequences, shard
// after shard in the order the caller will store them; group g has
// group_sizes[g] messages of owner group_owner[g] (a dense id: equal
// ids are one owner), and shard s takes the next shard_groups[s]
// groups. Dedup is `_pack_batch`'s: one table over the whole batch,
// rows in walk order, the first occurrence kept — the row the primary
// key would keep — and the same timestamp under two owners both kept.
//
// Out: out_kept[g] rows of group g survived; out_lens holds the kept
// rows' content lengths, all shards back to back (capacity: the sum of
// group_sizes); for every shard with a kept row, in shard order, `out`
// (a list) gains two `bytes`: rows x 46 timestamp bytes and the
// contents. Those and `scratch` (eh_pack_scratch_words(...) words, the
// caller's, reusable from pass to pass) are all the memory the call
// uses: it calls no malloc of its own, so it grows no thread arena.
//
// Returns 0; 2 = demotion: a timestamp that is not an exact str of 46
// ASCII bytes, a content that is not exact bytes, or any CPython error
// (cleared) — the caller runs the Python body, which owns the error
// surface; 1 = bad arguments or no memory (the caller demotes too).
// On a non-zero return `out` may hold buffers of earlier shards: drop
// it.

namespace {

constexpr int64_t kTsWidth = 46;

inline uint64_t load64(const char *p) {
  uint64_t v;
  memcpy(&v, p, 8);
  return v;
}

// 64 bits over the 46 bytes and the owner. A batch's timestamps share
// their first words and differ in the last, so every word is folded
// through a multiply; low bits index the table, high bits tag a slot.
inline uint64_t pack_key_hash(const char *t, uint32_t owner) {
  uint64_t h = (uint64_t(owner) + 1) * 0x9E3779B97F4A7C15ull;
  for (int i = 0; i < 40; i += 8) {
    h = (h ^ load64(t + i)) * 0xFF51AFD7ED558CCDull;
    h ^= h >> 32;
  }
  h = (h ^ load64(t + kTsWidth - 8)) * 0xC4CEB9FE1A85EC53ull;
  return h ^ (h >> 29);
}

int64_t pack_table_slots(int64_t n_rows) {
  int64_t slots = 16;
  while (slots < 2 * n_rows) slots <<= 1;
  return slots;
}

// The messages the walk has in hand: their objects and timestamp strs
// (owned references, released at scope exit), the strs' bytes, the key
// hashes.
struct PackBlock {
  static constexpr int64_t kRows = 32;
  PyObj *msg[kRows], *ts[kRows];
  const char *text[kRows];
  uint64_t hash[kRows];
  int n = 0;
  ~PackBlock() {
    for (int j = 0; j < n; ++j) {
      Py_DecRef(msg[j]);
      if (ts[j]) Py_DecRef(ts[j]);
    }
  }
};

// Owned references in caller-provided storage, released at scope exit.
struct HeldRefs {
  PyObj **items;
  int64_t n = 0;
  explicit HeldRefs(PyObj **storage) : items(storage) {}
  ~HeldRefs() { release(); }
  void release() {
    for (int64_t i = 0; i < n; ++i)
      if (items[i]) Py_DecRef(items[i]);
    n = 0;
  }
};

}  // namespace

// Words of `scratch` eh_pack_requests needs for n_rows messages over
// n_shards shards: the dedup table, a kept row's timestamp pointer,
// content object and owner, a shard's two buffers.
int64_t eh_pack_scratch_words(int64_t n_rows, int64_t n_shards) {
  if (n_rows < 0 || n_shards < 0) return -1;
  return pack_table_slots(n_rows) + 2 * n_rows + (n_rows + 1) / 2 + 2 * n_shards;
}

int eh_py_abi_probe(PyObj *sample) { return py_abi_probe(sample); }

int eh_pack_requests(PyObj *groups, int64_t n_groups, const int64_t *group_sizes,
                     const int32_t *group_owner, const int64_t *shard_groups,
                     int64_t n_shards, int64_t *out_kept, int32_t *out_lens,
                     uint64_t *scratch, int64_t scratch_words, PyObj *out) {
  if (!groups || !out || n_groups < 0 || n_shards < 0) return 1;
  int64_t n_rows = 0;
  for (int64_t g = 0; g < n_groups; ++g) {
    if (group_sizes[g] < 0) return 1;
    n_rows += group_sizes[g];
  }
  for (int64_t s = 0; s < n_shards; ++s)
    if (shard_groups[s] < 0) return 1;
  // A slot holds a row's index + 1 in its low 32 bits.
  if (n_rows >= INT32_MAX || sum_of(shard_groups, n_shards) != n_groups ||
      scratch_words < eh_pack_scratch_words(n_rows, n_shards))
    return 1;
  const int64_t slots = pack_table_slots(n_rows);
  const uint64_t mask = uint64_t(slots) - 1;
  uint64_t *table = scratch;
  uint64_t *rest = scratch + slots;
  const char **row_ts = reinterpret_cast<const char **>(rest);
  PyObj **row_content = reinterpret_cast<PyObj **>(rest + n_rows);
  PyObj **shard_bufs = reinterpret_cast<PyObj **>(rest + 2 * n_rows);  // ts, content a shard
  int32_t *row_owner = reinterpret_cast<int32_t *>(rest + 2 * n_rows + 2 * n_shards);
  memset(table, 0, size_t(slots) * sizeof(uint64_t));

  PyRef a_ts(PyUnicode_InternFromString("timestamp"));
  PyRef a_content(PyUnicode_InternFromString("content"));
  if (!a_ts.o || !a_content.o) { PyErr_Clear(); return 1; }

  HeldRefs bufs(shard_bufs);       // finished shards' buffers
  HeldRefs contents(row_content);  // the running shard's kept contents
  int64_t row = 0, g = 0;          // kept rows, groups so far
  for (int64_t s = 0; s < n_shards; ++s) {
    const int64_t g_end = g + shard_groups[s];
    const int64_t capacity = sum_of(group_sizes + g, shard_groups[s]);
    PyObj *&ts_buf = shard_bufs[2 * s];
    PyObj *&content_buf = shard_bufs[2 * s + 1];
    ts_buf = content_buf = nullptr;
    bufs.n = 2 * s + 2;
    if (capacity == 0) {
      for (; g < g_end; ++g) out_kept[g] = 0;
      continue;
    }
    // Sized for no duplicate; cut to the kept rows after the walk (a
    // copy, only where the dedup dropped a row of this shard).
    ts_buf = PyBytes_FromStringAndSize(nullptr, capacity * kTsWidth);
    if (!ts_buf) { PyErr_Clear(); return 1; }
    char *ts_dst = PyBytes_AsString(ts_buf);
    const int64_t shard_row0 = row;
    int64_t content_bytes = 0;
    for (; g < g_end; ++g) {
      PyRef msgs(PySequence_GetItem(groups, g));
      if (!msgs.o) { PyErr_Clear(); return 2; }
      const uint32_t owner = uint32_t(group_owner[g]);
      const int64_t group_row0 = row;
      // A block of messages at a time, in four sweeps, so that the
      // cache misses of a block overlap instead of queueing: a
      // message, its timestamp str and its table slot are wherever
      // the allocator and the hash put them.
      for (int64_t i0 = 0; i0 < group_sizes[g]; i0 += PackBlock::kRows) {
        PackBlock b;
        const int64_t i1 = std::min(i0 + PackBlock::kRows, group_sizes[g]);
        for (int64_t i = i0; i < i1; ++i) {
          PyObj *m = PySequence_GetItem(msgs.o, i);
          if (!m) { PyErr_Clear(); return 2; }
          b.msg[b.n] = m;
          b.ts[b.n++] = nullptr;
          // the object's head and the words before it, where CPython
          // keeps an instance's attribute storage: a hint, never read
          __builtin_prefetch(reinterpret_cast<const char *>(m) - 24);
          __builtin_prefetch(reinterpret_cast<const char *>(m) + 8);
        }
        for (int j = 0; j < b.n; ++j) {
          PyObj *ts = b.ts[j] = PyObject_GetAttr(b.msg[j], a_ts.o);
          if (!ts) { PyErr_Clear(); return 2; }
          __builtin_prefetch(ts);
          __builtin_prefetch(reinterpret_cast<const char *>(ts) + 64);
        }
        for (int j = 0; j < b.n; ++j) {
          const char *t;
          long long t_len;
          if (!py_str(b.ts[j], &t, &t_len) || t_len != kTsWidth) return 2;
          uint64_t high = 0;
          for (int w = 0; w < 40; w += 8) high |= load64(t + w);
          if ((high | load64(t + kTsWidth - 8)) & 0x8080808080808080ull) return 2;
          b.text[j] = t;
          b.hash[j] = pack_key_hash(t, owner);
          __builtin_prefetch(&table[b.hash[j] & mask]);
        }
        for (int j = 0; j < b.n; ++j) {
          const char *t = b.text[j];
          const uint64_t tag = b.hash[j] & 0xFFFFFFFF00000000ull;
          uint64_t slot = b.hash[j] & mask;
          bool duplicate = false;
          for (uint64_t e; (e = table[slot]) != 0; slot = (slot + 1) & mask) {
            if ((e & 0xFFFFFFFF00000000ull) != tag) continue;
            const int64_t k = int64_t(e & 0xFFFFFFFFull) - 1;
            if (uint32_t(row_owner[k]) == owner && memcmp(row_ts[k], t, kTsWidth) == 0) {
              duplicate = true;
              break;
            }
          }
          if (duplicate) continue;
          PyObj *c = PyObject_GetAttr(b.msg[j], a_content.o);
          if (!c) { PyErr_Clear(); return 2; }
          row_content[contents.n++] = c;
          const long long c_len = py_exact(c, PyBytes_Type) ? PyBytes_Size(c) : -1;
          if (c_len < 0 || c_len > INT32_MAX) return 2;
          char *dst = ts_dst + (row - shard_row0) * kTsWidth;
          memcpy(dst, t, kTsWidth);
          row_ts[row] = dst;
          row_owner[row] = int32_t(owner);
          out_lens[row] = int32_t(c_len);
          content_bytes += c_len;
          table[slot] = tag | uint64_t(row + 1);
          ++row;
        }
      }
      out_kept[g] = row - group_row0;
    }
    const int64_t kept = row - shard_row0;
    if (kept == 0) continue;
    content_buf = PyBytes_FromStringAndSize(nullptr, content_bytes);
    if (!content_buf) { PyErr_Clear(); return 1; }
    char *dst = PyBytes_AsString(content_buf);
    for (int64_t k = 0; k < kept; ++k) {
      memcpy(dst, PyBytes_AsString(row_content[k]), size_t(out_lens[shard_row0 + k]));
      dst += out_lens[shard_row0 + k];
    }
    contents.release();
  }
  // Every probe is done: nothing reads a shard's timestamp buffer
  // through row_ts any more, so one the dedup left short may move.
  g = 0;
  for (int64_t s = 0; s < n_shards; ++s) {
    int64_t kept = 0, capacity = 0;
    for (const int64_t g_end = g + shard_groups[s]; g < g_end; ++g) {
      kept += out_kept[g];
      capacity += group_sizes[g];
    }
    if (kept == 0) continue;
    PyObj *&ts_buf = shard_bufs[2 * s];
    if (kept < capacity) {
      PyObj *cut = PyBytes_FromStringAndSize(PyBytes_AsString(ts_buf), kept * kTsWidth);
      if (!cut) { PyErr_Clear(); return 1; }
      Py_DecRef(ts_buf);
      ts_buf = cut;
    }
    if (PyList_Append(out, ts_buf) != 0 || PyList_Append(out, shard_bufs[2 * s + 1]) != 0) {
      PyErr_Clear();
      return 1;
    }
  }
  return 0;
}

// Packed, grouped variant of eh_relay_insert: the batch reconciler's
// one-call ingest. Timestamps arrive as ONE fixed-width 46-byte
// buffer and contents as ONE packed blob buffer with per-row lengths;
// rows are grouped per requesting user (group_users/group_counts), so
// the host passes n_groups pointers instead of n. In-batch duplicates
// dedup through the PK exactly like sequential INSERT OR IGNORE: the
// first occurrence reports was-new, later ones don't (index.ts:148-159
// changes()==1 semantics).
//
// Threading contract (PR-19 parallel sharded drain): this function
// touches only its `db` handle and caller-owned buffers — no globals,
// no Python API — so ctypes calls it with the GIL RELEASED and the
// write-behind queue runs one drain worker PER SHARD concurrently,
// each on its own sqlite3 handle (SQLite objects are never shared
// across the workers; serialization is per shard via the shard lock).
// Keep it that way: any global/static state added here would race the
// parallel drain.
static int relay_insert_packed_rows(sqlite3 *db, int64_t n_groups,
                                    const char *const *group_users,
                                    const int64_t *group_counts,
                                    const char *ts_packed,
                                    const unsigned char *content_packed,
                                    const int32_t *content_lens, uint8_t *out_new) {
  sqlite3_stmt *st = nullptr;
  const char *sql =
      "INSERT OR IGNORE INTO \"message\" (\"timestamp\", \"userId\", \"content\") "
      "VALUES (?, ?, ?)";
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  int64_t i = 0;
  int64_t content_off = 0;
  for (int64_t g = 0; g < n_groups; ++g) {
    const char *user = group_users[g];
    for (int64_t k = 0; k < group_counts[g]; ++k, ++i) {
      sqlite3_bind_text(st, 1, ts_packed + i * 46, 46, SQLITE_STATIC);
      sqlite3_bind_text(st, 2, user, -1, SQLITE_STATIC);
      sqlite3_bind_blob(st, 3, content_packed + content_off, content_lens[i],
                        SQLITE_STATIC);
      content_off += content_lens[i];
      int rc = sqlite3_step(st);
      sqlite3_reset(st);
      if (rc != SQLITE_DONE) {
        sqlite3_finalize(st);
        return 1;
      }
      out_new[i] = sqlite3_changes(db) == 1 ? 1 : 0;
    }
  }
  sqlite3_finalize(st);
  return 0;
}

int eh_relay_insert_packed(sqlite3 *db, int64_t n_groups,
                           const char *const *group_users,
                           const int64_t *group_counts,
                           const char *ts_packed,
                           const unsigned char *content_packed,
                           const int32_t *content_lens, uint8_t *out_new,
                           int64_t *out_reserved) {
  int64_t n = sum_of(group_counts, n_groups), input_bytes = n * 46 + sum_of(content_lens, n);
  for (int64_t g = 0; g < n_groups; ++g)
    input_bytes += group_counts[g] * int64_t(strlen(group_users[g]));
  reserve_heap(input_bytes, kHeapPerByteRow, out_reserved);
  return relay_insert_packed_rows(db, n_groups, group_users, group_counts, ts_packed,
                                  content_packed, content_lens, out_new);
}

int eh_relay_insert(sqlite3 *db, int64_t n, const char *const *timestamps,
                    const char *const *user_ids, const char *const *contents,
                    const int32_t *content_lens, uint8_t *out_new,
                    int64_t *out_reserved) {
  reserve_heap(text_bytes(timestamps, n) + text_bytes(user_ids, n) + sum_of(content_lens, n),
               kHeapPerByteRow, out_reserved);
  sqlite3_stmt *st = nullptr;
  const char *sql =
      "INSERT OR IGNORE INTO \"message\" (\"timestamp\", \"userId\", \"content\") "
      "VALUES (?, ?, ?)";
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  for (int64_t i = 0; i < n; ++i) {
    sqlite3_bind_text(st, 1, timestamps[i], -1, SQLITE_STATIC);
    sqlite3_bind_text(st, 2, user_ids[i], -1, SQLITE_STATIC);
    sqlite3_bind_blob(st, 3, contents[i], content_lens[i], SQLITE_STATIC);
    int rc = sqlite3_step(st);
    sqlite3_reset(st);
    sqlite3_clear_bindings(st);
    if (rc != SQLITE_DONE) {
      sqlite3_finalize(st);
      return 1;
    }
    out_new[i] = sqlite3_changes(db) == 1 ? 1 : 0;
  }
  sqlite3_finalize(st);
  return 0;
}

}  // extern "C"

// --- shard-set calls: the storage leg of an engine pass in two C calls ---
//
// `BatchReconciler` lands one pass on every live shard of a
// ShardedRelayStore: BEGIN + the packed insert + the owners' stored
// trees per shard (eh_relay_insert_packed_shards), then, after the host
// has folded the pass's Merkle deltas, the merkleTree upserts + COMMIT
// per shard (eh_relay_commit_shards). Driven shard by shard from Python
// that was ~45 ctypes calls and a thread pool a pass; here ctypes drops
// the interpreter lock once per call. Statements and their order on
// each handle are exactly the per-shard path's.
//
// The shards run one after the other on the calling thread, at every
// size. On the chip's host, one storage pass through one std::thread
// per shard took 4.6 to 7.7 times as long as through this loop at every
// size from 256 to 250,000 rows a call (1,250 rows over 8 shards: 20.4
// ms against 2.65 ms; 250,000: 3,036 ms against 633 ms; PERF.md §6, PR
// 27), which is also what the Python thread pool this replaced had
// cost. The cause (PR 38's builder's chip runs; the ledger's PR 38
// line): the image's libsqlite3 (3.40.1) is built with OMIT_LOOKASIDE
// and SYSTEM_MALLOC and keeps allocation statistics, so every malloc
// and free inside a step takes the ONE process-wide
// SQLITE_MUTEX_STATIC_MEM, 5.4 times an inserted row: 250,000 rows over
// 8 shards, this loop 593 ms against 1,246 / 1,269 / 1,399 ms in 2 / 4
// / 8 lanes of threads, each with its heap reserved, at 17x the CPU.
// With sqlite3_config(SQLITE_CONFIG_MEMSTATUS, 0) before SQLite
// initialises the same lanes win (534 -> 319 -> 200 ms at 1 / 2 / 8),
// but that setting is the process's, must precede the first
// sqlite3_open (and Python's `import sqlite3`), and belongs to whoever
// owns the process, not to this library. Until a relay owns its
// process that way, no two threads of it step SQLite at once
// (`reconcile_stream`'s helper stages the next pass and never comes
// here), and run_shards is the one place to start threads then; the
// threading contract above eh_relay_insert_packed holds either way:
// only the given handles and caller-owned buffers are touched, no
// globals, no Python API.

namespace {

struct ShardRun {
  bool begun = false;  // this call's BEGIN succeeded on the handle
  std::string err;     // sqlite3_errmsg at the point of failure
};

// fn(s) for every shard s, all of them whatever fails (a failed set is
// rolled back whole by the caller); returns the first shard whose fn
// returned non-zero, or -1.
template <class F>
int64_t run_shards(int64_t k, F fn) {
  int64_t failed = -1;
  for (int64_t s = 0; s < k; ++s) {
    int rc;
    try {
      rc = fn(s);
    } catch (...) {  // bad_alloc must not cross the C ABI
      rc = 1;
    }
    if (rc != 0 && failed < 0) failed = s;
  }
  return failed;
}

int fail(ShardRun &run, sqlite3 *db) {
  if (run.err.empty()) run.err = sqlite3_errmsg(db);
  return 1;
}

void report(const std::string &msg, char *err, int32_t err_cap) {
  if (err_cap <= 0) return;
  size_t n = msg.size() < size_t(err_cap - 1) ? msg.size() : size_t(err_cap - 1);
  memcpy(err, msg.data(), n);
  err[n] = 0;
}

}  // namespace

extern "C" {

// One reserve_heap for the rows of every shard, before the first BEGIN
// (the shards' pages all come from the calling thread's heap). Then,
// per shard s of k, on dbs[s]: begin_sql[s], then eh_relay_insert_packed's
// rows with the shard's slice of the arguments, then the stored "merkleTree"
// TEXT of each of the shard's group users, read inside the transaction.
// The per-group and per-row arrays are FLAT over the shards in order
// (n_groups[s] groups each; a shard's rows are the sum of its
// group_counts); ts_packed and content_packed are one buffer per shard.
// out_new receives the was-new flag of every row. *out_trees (free with
// eh_free) holds, per group in flat order, an int32 byte length (-1: no
// stored tree) followed by the text. Returns -1 with every shard's
// transaction OPEN; on failure rolls back every transaction it began,
// writes the failing handle's message to err, and returns the failing
// shard's index (k: out of memory outside any shard).
int64_t eh_relay_insert_packed_shards(
    int64_t k, sqlite3 *const *dbs, const char *const *begin_sql,
    const int64_t *n_groups, const char *const *group_users,
    const int32_t *group_user_lens, const int64_t *group_counts,
    const char *const *ts_packed, const unsigned char *const *content_packed,
    const int32_t *content_lens, uint8_t *out_new, unsigned char **out_trees,
    int64_t *out_trees_len, char *err, int32_t err_cap, int64_t *out_reserved) {
  *out_trees = nullptr;
  *out_trees_len = 0;
  std::vector<int64_t> group_off(k + 1, 0), row_off(k + 1, 0);
  int64_t input_bytes = 0;
  for (int64_t s = 0; s < k; ++s) {
    group_off[s + 1] = group_off[s] + n_groups[s];
    int64_t rows = 0;
    for (int64_t g = group_off[s]; g < group_off[s + 1]; ++g) {
      rows += group_counts[g];
      input_bytes += group_counts[g] * (46 + group_user_lens[g]);
    }
    row_off[s + 1] = row_off[s] + rows;
  }
  input_bytes += sum_of(content_lens, row_off[k]);
  reserve_heap(input_bytes, kHeapPerByteRow, out_reserved);
  std::vector<ShardRun> runs(k);
  std::string trees;  // framed stored trees, one per group, flat order
  auto shard = [&](int64_t s) -> int {
    sqlite3 *db = dbs[s];
    ShardRun &run = runs[s];
    if (sqlite3_exec(db, begin_sql[s], nullptr, nullptr, nullptr) != SQLITE_OK)
      return fail(run, db);
    run.begun = true;
    const int64_t g0 = group_off[s];
    if (relay_insert_packed_rows(db, n_groups[s], group_users + g0, group_counts + g0,
                                 ts_packed[s], content_packed[s],
                                 content_lens + row_off[s], out_new + row_off[s]) != 0)
      return fail(run, db);
    sqlite3_stmt *st = nullptr;
    if (sqlite3_prepare_v2(db, "SELECT \"merkleTree\" FROM \"merkleTree\" WHERE \"userId\" = ?",
                           -1, &st, nullptr) != SQLITE_OK)
      return fail(run, db);
    for (int64_t g = g0; g < group_off[s + 1]; ++g) {
      sqlite3_bind_text(st, 1, group_users[g], group_user_lens[g], SQLITE_STATIC);
      int rc = sqlite3_step(st);
      if (rc != SQLITE_ROW && rc != SQLITE_DONE) {
        sqlite3_finalize(st);
        return fail(run, db);
      }
      const unsigned char *text = rc == SQLITE_ROW ? sqlite3_column_text(st, 0) : nullptr;
      int32_t n = rc == SQLITE_ROW ? sqlite3_column_bytes(st, 0) : -1;
      trees.append(reinterpret_cast<const char *>(&n), sizeof n);
      if (n > 0) trees.append(reinterpret_cast<const char *>(text), size_t(n));
      sqlite3_reset(st);
    }
    sqlite3_finalize(st);
    return 0;
  };
  int64_t failed = run_shards(k, shard);
  if (failed < 0) {
    unsigned char *buf = static_cast<unsigned char *>(malloc(trees.size() + 1));
    if (buf) {
      memcpy(buf, trees.data(), trees.size());
      *out_trees = buf;
      *out_trees_len = int64_t(trees.size());
      return -1;
    }
    failed = k;
  }
  for (int64_t s = 0; s < k; ++s)
    if (runs[s].begun) sqlite3_exec(dbs[s], "ROLLBACK", nullptr, nullptr, nullptr);
  report(failed < k ? runs[failed].err : "out of memory", err, err_cap);
  return failed;
}

// Per shard s of k, on dbs[s] (each inside the transaction the insert
// call opened): INSERT OR REPLACE the shard's n_trees[s] (userId,
// merkleTree) rows, flat over the shards in order, and once EVERY
// shard's upserts are done, COMMIT each. Returns -1, or the first
// failing shard's index with its message in err. A failed upsert rolls
// every shard back; a failed COMMIT rolls that shard back while the
// others commit (the per-shard path's contract: first commit error
// wins). Either way no handle is left inside a transaction.
int64_t eh_relay_commit_shards(
    int64_t k, sqlite3 *const *dbs, const int64_t *n_trees,
    const char *const *users, const int32_t *user_lens,
    const char *const *trees, const int32_t *tree_lens, char *err,
    int32_t err_cap) {
  std::vector<int64_t> off(k + 1, 0);
  for (int64_t s = 0; s < k; ++s) off[s + 1] = off[s] + n_trees[s];
  std::vector<ShardRun> runs(k);
  auto upsert = [&](int64_t s) -> int {
    if (off[s] == off[s + 1]) return 0;
    sqlite3 *db = dbs[s];
    sqlite3_stmt *st = nullptr;
    if (sqlite3_prepare_v2(db,
                           "INSERT OR REPLACE INTO \"merkleTree\" (\"userId\", \"merkleTree\") "
                           "VALUES (?, ?)",
                           -1, &st, nullptr) != SQLITE_OK)
      return fail(runs[s], db);
    for (int64_t i = off[s]; i < off[s + 1]; ++i) {
      sqlite3_bind_text(st, 1, users[i], user_lens[i], SQLITE_STATIC);
      sqlite3_bind_text(st, 2, trees[i], tree_lens[i], SQLITE_STATIC);
      int rc = sqlite3_step(st);
      sqlite3_reset(st);
      if (rc != SQLITE_DONE) {
        sqlite3_finalize(st);
        return fail(runs[s], db);
      }
    }
    sqlite3_finalize(st);
    return 0;
  };
  int64_t failed = run_shards(k, upsert);
  if (failed >= 0) {
    for (int64_t s = 0; s < k; ++s)
      sqlite3_exec(dbs[s], "ROLLBACK", nullptr, nullptr, nullptr);
    report(runs[failed].err, err, err_cap);
    return failed;
  }
  auto commit = [&](int64_t s) -> int {
    if (sqlite3_exec(dbs[s], "COMMIT", nullptr, nullptr, nullptr) == SQLITE_OK) return 0;
    fail(runs[s], dbs[s]);
    sqlite3_exec(dbs[s], "ROLLBACK", nullptr, nullptr, nullptr);
    return 1;
  };
  failed = run_shards(k, commit);
  if (failed >= 0) report(runs[failed].err, err, err_cap);
  return failed;
}

}  // extern "C"

extern "C" {

// --- generic bulk insert for text/blob/null rows ---
//
// One C call per statement batch: `kinds` is per CELL (nrows * ncols),
// 0 = null, 3 = text, 4 = blob; `vals`/`lens` are the flat cell
// buffers. Covers the relay's temp-table joins and message inserts
// (the ctypes per-bind path costs ~3us/bind; this is one call).
int eh_run_many_tb(sqlite3 *db, const char *sql, int64_t nrows, int32_t ncols,
                   const char *const *vals, const int32_t *lens,
                   const int32_t *kinds, int64_t *out_reserved) {
  reserve_heap(sum_of(lens, nrows * ncols), kHeapPerByteRow, out_reserved);
  sqlite3_stmt *st = nullptr;
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  for (int64_t r = 0; r < nrows; ++r) {
    for (int32_t c = 0; c < ncols; ++c) {
      int64_t i = r * ncols + c;
      int rc;
      if (kinds[i] == 3)
        rc = sqlite3_bind_text(st, c + 1, vals[i], lens[i], SQLITE_STATIC);
      else if (kinds[i] == 4)
        rc = sqlite3_bind_blob(st, c + 1, vals[i], lens[i], SQLITE_STATIC);
      else
        rc = sqlite3_bind_null(st, c + 1);
      if (rc != SQLITE_OK) {
        sqlite3_finalize(st);
        return 1;
      }
    }
    int rc = sqlite3_step(st);
    sqlite3_reset(st);
    sqlite3_clear_bindings(st);
    if (rc != SQLITE_DONE && rc != SQLITE_ROW) {
      sqlite3_finalize(st);
      return 1;
    }
  }
  sqlite3_finalize(st);
  return 0;
}

// --- relay hot path: fetch a user's messages after `since`, excluding
// the requester's node (index.ts:173-202), packed into three buffers
// the caller frees with eh_free: fixed-width 46-byte timestamps,
// concatenated contents, and per-row content lengths. Avoids the
// per-row ctypes column reads (~10us/row) of the generic path. ---
int eh_get_messages(sqlite3 *db, const char *user, int32_t user_len,
                    const char *since, const char *node, int32_t node_len,
                    char **out_ts, unsigned char **out_content,
                    int32_t **out_lens, int64_t *out_n) {
  const char *sql =
      "SELECT \"timestamp\", \"content\" FROM \"message\" "
      "WHERE \"userId\" = ? AND \"timestamp\" > ? AND \"timestamp\" NOT LIKE '%' || ? "
      "ORDER BY \"timestamp\"";
  sqlite3_stmt *st = nullptr;
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  // Wire-derived user/node may contain NUL: explicit lengths (r4 —
  // the char* form truncated and could serve divergent rows vs the
  // Python backend).
  sqlite3_bind_text(st, 1, user, user_len, SQLITE_TRANSIENT);
  sqlite3_bind_text(st, 2, since, -1, SQLITE_TRANSIENT);
  sqlite3_bind_text(st, 3, node, node_len, SQLITE_TRANSIENT);

  std::string ts_buf;
  std::string content_buf;
  std::vector<int32_t> lens;
  int rc;
  while ((rc = sqlite3_step(st)) == SQLITE_ROW) {
    const unsigned char *ts = sqlite3_column_text(st, 0);
    int ts_len = sqlite3_column_bytes(st, 0);
    // Timestamps are the fixed 46-char encoding; anything else would
    // desync the fixed-width unpacking — fail loudly.
    if (ts_len != 46) {
      sqlite3_finalize(st);
      return 2;
    }
    ts_buf.append(reinterpret_cast<const char *>(ts), 46);
    const void *blob = sqlite3_column_blob(st, 1);
    int blen = sqlite3_column_bytes(st, 1);
    if (blen > 0) content_buf.append(static_cast<const char *>(blob), blen);
    lens.push_back(blen);
  }
  sqlite3_finalize(st);
  if (rc != SQLITE_DONE) return 1;

  *out_n = static_cast<int64_t>(lens.size());
  char *ts_out = static_cast<char *>(malloc(ts_buf.size() ? ts_buf.size() : 1));
  unsigned char *content_out =
      static_cast<unsigned char *>(malloc(content_buf.size() ? content_buf.size() : 1));
  int32_t *lens_out = static_cast<int32_t *>(malloc(lens.size() ? lens.size() * 4 : 4));
  if (!ts_out || !content_out || !lens_out) {
    free(ts_out);
    free(content_out);
    free(lens_out);
    return 3;  // allocation failure: surfaced, never a segfault
  }
  memcpy(ts_out, ts_buf.data(), ts_buf.size());
  memcpy(content_out, content_buf.data(), content_buf.size());
  memcpy(lens_out, lens.data(), lens.size() * 4);
  *out_ts = ts_out;
  *out_content = content_out;
  *out_lens = lens_out;
  return 0;
}

// --- relay response fast path: the same query as eh_get_messages,
// emitted DIRECTLY as the SyncResponse `messages` field-1 protobuf
// stream (per row: 0x0A varint(inner) ‖ 0x0A 0x2E ts46 ‖ 0x12
// varint(clen) content) — byte-identical to
// protocol.encode_sync_response's messages section, with zero per-row
// Python objects. The caller appends the merkleTree field 2. ---

int eh_get_messages_wire(sqlite3 *db, const char *user, int32_t user_len,
                         const char *since, const char *node,
                         int32_t node_len, unsigned char **out,
                         int64_t *out_len, int64_t *out_n) {
  const char *sql =
      "SELECT \"timestamp\", \"content\" FROM \"message\" "
      "WHERE \"userId\" = ? AND \"timestamp\" > ? AND \"timestamp\" NOT LIKE '%' || ? "
      "ORDER BY \"timestamp\"";
  sqlite3_stmt *st = nullptr;
  if (sqlite3_prepare_v2(db, sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  // user/node come off the WIRE and may contain NUL — explicit lengths
  // (the char* convention would truncate and serve divergent rows vs
  // the Python backend; CLAUDE.md NUL invariant). `since` is a
  // canonical 46-char timestamp, NUL-free by construction.
  sqlite3_bind_text(st, 1, user, user_len, SQLITE_TRANSIENT);
  sqlite3_bind_text(st, 2, since, -1, SQLITE_TRANSIENT);
  sqlite3_bind_text(st, 3, node, node_len, SQLITE_TRANSIENT);

  std::string buf;
  int64_t rows = 0;
  int rc;
  while ((rc = sqlite3_step(st)) == SQLITE_ROW) {
    const unsigned char *ts = sqlite3_column_text(st, 0);
    if (sqlite3_column_bytes(st, 0) != 46) {  // fixed-width invariant
      sqlite3_finalize(st);
      return 2;
    }
    const void *blob = sqlite3_column_blob(st, 1);
    size_t clen = size_t(sqlite3_column_bytes(st, 1));
    size_t inner = 2 + 46 + 1 + wire_varint_size(clen) + clen;
    buf.push_back(char(0x0A));
    wire_put_varint(buf, inner);
    buf.push_back(char(0x0A));
    buf.push_back(char(46));
    buf.append(reinterpret_cast<const char *>(ts), 46);
    buf.push_back(char(0x12));
    wire_put_varint(buf, clen);
    if (clen) buf.append(static_cast<const char *>(blob), clen);
    rows++;
  }
  sqlite3_finalize(st);
  if (rc != SQLITE_DONE) return 1;
  unsigned char *p =
      static_cast<unsigned char *>(malloc(buf.size() ? buf.size() : 1));
  if (!p) return 3;
  memcpy(p, buf.data(), buf.size());
  *out = p;
  *out_len = static_cast<int64_t>(buf.size());
  *out_n = rows;
  return 0;
}

// --- snapshot capture (server/snapshot.py) ---
//
// Every `message` row and `merkleTree` row of one shard, packed into
// ONE malloc'd buffer of framed records the caller frees with eh_free:
//   'M' (0x4D): u32 ts_len‖ts ‖ u32 uid_len‖uid ‖ u32 len‖content
//   'T' (0x54): u32 uid_len‖uid ‖ u32 tree_len‖tree
// (little-endian lengths, explicit everywhere — timestamps/ids may be
// any width, contents are ciphertext blobs with possible NULs). Rows
// stream in PK order (userId, timestamp) and trees by userId, exactly
// matching the stdlib oracle `snapshot._capture_shard_py`, so the two
// paths are byte-identical (parity-pinned). The caller wraps this in
// a read transaction — the two SELECTs must see one consistent state.
int eh_snapshot_rows(sqlite3 *db, unsigned char **out, int64_t *out_len,
                     int64_t *out_msgs, int64_t *out_trees) {
  std::string buf;
  auto put_u32 = [&buf](uint32_t v) {
    buf.append(reinterpret_cast<const char *>(&v), 4);
  };
  sqlite3_stmt *st = nullptr;
  const char *msg_sql =
      "SELECT \"timestamp\", \"userId\", \"content\" FROM \"message\" "
      "ORDER BY \"userId\", \"timestamp\"";
  if (sqlite3_prepare_v2(db, msg_sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  int64_t msgs = 0;
  int rc;
  while ((rc = sqlite3_step(st)) == SQLITE_ROW) {
    const unsigned char *ts = sqlite3_column_text(st, 0);
    uint32_t ts_len = uint32_t(sqlite3_column_bytes(st, 0));
    const unsigned char *uid = sqlite3_column_text(st, 1);
    uint32_t uid_len = uint32_t(sqlite3_column_bytes(st, 1));
    const void *blob = sqlite3_column_blob(st, 2);
    uint32_t blen = uint32_t(sqlite3_column_bytes(st, 2));
    buf.push_back(char(0x4D));
    put_u32(ts_len);
    if (ts_len) buf.append(reinterpret_cast<const char *>(ts), ts_len);
    put_u32(uid_len);
    if (uid_len) buf.append(reinterpret_cast<const char *>(uid), uid_len);
    put_u32(blen);
    if (blen) buf.append(static_cast<const char *>(blob), blen);
    msgs++;
  }
  sqlite3_finalize(st);
  if (rc != SQLITE_DONE) return 1;

  const char *tree_sql =
      "SELECT \"userId\", \"merkleTree\" FROM \"merkleTree\" "
      "ORDER BY \"userId\"";
  if (sqlite3_prepare_v2(db, tree_sql, -1, &st, nullptr) != SQLITE_OK) return 1;
  int64_t trees = 0;
  while ((rc = sqlite3_step(st)) == SQLITE_ROW) {
    const unsigned char *uid = sqlite3_column_text(st, 0);
    uint32_t uid_len = uint32_t(sqlite3_column_bytes(st, 0));
    const unsigned char *tr = sqlite3_column_text(st, 1);
    uint32_t tr_len = uint32_t(sqlite3_column_bytes(st, 1));
    buf.push_back(char(0x54));
    put_u32(uid_len);
    if (uid_len) buf.append(reinterpret_cast<const char *>(uid), uid_len);
    put_u32(tr_len);
    if (tr_len) buf.append(reinterpret_cast<const char *>(tr), tr_len);
    trees++;
  }
  sqlite3_finalize(st);
  if (rc != SQLITE_DONE) return 1;

  unsigned char *p =
      static_cast<unsigned char *>(malloc(buf.size() ? buf.size() : 1));
  if (!p) return 3;
  memcpy(p, buf.data(), buf.size());
  *out = p;
  *out_len = static_cast<int64_t>(buf.size());
  *out_msgs = msgs;
  *out_trees = trees;
  return 0;
}

// --- packed query reader (SURVEY hot loop #4) ---
//
// Step an already-bound statement to completion and pack every row
// into ONE malloc'd buffer the caller frees with eh_free. The generic
// per-cell path costs ~4 ctypes calls per cell (~65 ms for a 10k-row
// 3-column subscribed query, measured r4); this is one call, and the
// raw bytes double as a cache key — identical bytes mean the
// subscribed query did not change, so the worker skips dict
// materialization and diffing entirely.
//
// Buffer layout (little-endian, unaligned):
//   [i32 ncols][ncols x (i32 name_len, name bytes)]
//   per row: ncols x ([u8 type] + payload) where type/payload is
//     1 int (i64), 2 float (f64), 3 text (u32 len + bytes),
//     4 blob (u32 len + bytes), 5 null (no payload)
// `out_offsets` (nullable): malloc'd int64[rows+1] — byte offset of each
// row's start within `out`, with offsets[0] = header size and
// offsets[rows] = total length. The worker's row-granular change
// detection diffs consecutive result sets per ROW span and unpacks only
// changed rows (runtime/worker.py::_query, r5).
int eh_exec_packed(sqlite3_stmt *st, unsigned char **out, int64_t *out_len,
                   int64_t *out_rows, int64_t **out_offsets) {
  std::string buf;
  std::vector<int64_t> offsets;
  int ncols = sqlite3_column_count(st);
  auto put_i32 = [&buf](int32_t v) {
    buf.append(reinterpret_cast<const char *>(&v), 4);
  };
  put_i32(ncols);
  for (int c = 0; c < ncols; ++c) {
    const char *name = sqlite3_column_name(st, c);
    int32_t n = name ? static_cast<int32_t>(strlen(name)) : 0;
    put_i32(n);
    if (n) buf.append(name, n);
  }
  int64_t rows = 0;
  int rc;
  while ((rc = sqlite3_step(st)) == SQLITE_ROW) {
    rows++;
    if (out_offsets) offsets.push_back(int64_t(buf.size()));
    for (int c = 0; c < ncols; ++c) {
      int t = sqlite3_column_type(st, c);
      if (t == SQLITE_INTEGER) {
        buf.push_back(1);
        int64_t v = sqlite3_column_int64(st, c);
        buf.append(reinterpret_cast<const char *>(&v), 8);
      } else if (t == SQLITE_FLOAT) {
        buf.push_back(2);
        double v = sqlite3_column_double(st, c);
        buf.append(reinterpret_cast<const char *>(&v), 8);
      } else if (t == SQLITE_TEXT) {
        buf.push_back(3);
        const unsigned char *v = sqlite3_column_text(st, c);
        uint32_t n = static_cast<uint32_t>(sqlite3_column_bytes(st, c));
        buf.append(reinterpret_cast<const char *>(&n), 4);
        if (n) buf.append(reinterpret_cast<const char *>(v), n);
      } else if (t == SQLITE_BLOB) {
        buf.push_back(4);
        const void *v = sqlite3_column_blob(st, c);
        uint32_t n = static_cast<uint32_t>(sqlite3_column_bytes(st, c));
        buf.append(reinterpret_cast<const char *>(&n), 4);
        if (n) buf.append(static_cast<const char *>(v), n);
      } else {
        buf.push_back(5);
      }
    }
  }
  if (rc != SQLITE_DONE) return 1;
  unsigned char *p =
      static_cast<unsigned char *>(malloc(buf.size() ? buf.size() : 1));
  if (!p) return 3;
  memcpy(p, buf.data(), buf.size());
  if (out_offsets) {
    offsets.push_back(int64_t(buf.size()));  // [rows] = total length
    int64_t *op = static_cast<int64_t *>(malloc(offsets.size() * 8));
    if (!op) {
      free(p);
      return 3;
    }
    memcpy(op, offsets.data(), offsets.size() * 8);
    *out_offsets = op;
  }
  *out = p;
  *out_len = static_cast<int64_t>(buf.size());
  *out_rows = rows;
  return 0;
}

void eh_free(void *p) { free(p); }

}  // extern "C"

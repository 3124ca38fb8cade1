// Host-side record combiner: RLE of identical flow descriptors.
//
// The C++ twin of retina_tpu/parallel/combine.py (see that module for the
// losslessness contract and the eBPF-map analogy). One pass, open
// addressing: hash the 12 descriptor columns, probe, and either claim an
// output row or accumulate PACKETS/BYTES (saturating) and take the later
// timestamp. Order of first appearance is preserved, which the Python
// fallback does NOT guarantee (it sorts); consumers treat row order as
// arbitrary.
//
// Must stay semantically identical to combine_records_numpy — the test
// suite cross-checks the two on random batches.

#include <cstdint>
#include <cstdlib>
#include <cstring>

namespace {

constexpr int NUM_FIELDS = 16;
// Field indices (retina_tpu/events/schema.py).
constexpr int F_TS_LO = 0, F_TS_HI = 1, F_BYTES = 6, F_PACKETS = 7;
// Descriptor columns: everything except TS_LO/TS_HI/BYTES/PACKETS.
constexpr int KEY_COLS[12] = {2, 3, 4, 5, 8, 9, 10, 11, 12, 13, 14, 15};

// The 12 key columns form two contiguous spans (2..5 and 8..15):
// hashing/comparing them as six unaligned u64 words halves the per-row
// mix rounds vs the per-column loop — this pass is the host feed path's
// single largest cost at production quanta.
inline uint64_t hash_row(const uint32_t* row) {
  uint64_t h = 0x9E3779B97F4A7C15ull, v;
  const char* p = (const char*)(row + 2);
  for (int i = 0; i < 2; i++) {
    memcpy(&v, p + 8 * i, 8);
    h ^= v;
    h *= 0xFF51AFD7ED558CCDull;
    h ^= h >> 33;
  }
  p = (const char*)(row + 8);
  for (int i = 0; i < 4; i++) {
    memcpy(&v, p + 8 * i, 8);
    h ^= v;
    h *= 0xFF51AFD7ED558CCDull;
    h ^= h >> 33;
  }
  return h;
}

inline bool keys_equal(const uint32_t* a, const uint32_t* b) {
  return memcmp(a + 2, b + 2, 4 * sizeof(uint32_t)) == 0 &&
         memcmp(a + 8, b + 8, 8 * sizeof(uint32_t)) == 0;
}

inline uint32_t sat_add_u32(uint32_t a, uint32_t b) {
  uint64_t s = (uint64_t)a + b;
  return s > 0xFFFFFFFFull ? 0xFFFFFFFFu : (uint32_t)s;
}

}  // namespace

extern "C" {

// rows: (n, 16) u32 row-major. out: caller buffer with room for n rows.
// Returns the number of combined rows written to out, or -1 on alloc
// failure. out may alias nothing (distinct buffer required).
//
// hint_slots (rt_combine_hint): expected table size from the caller's
// previous quantum — distinct-flow counts are stable flush over flush,
// and a table sized to the distinct count stays cache-resident where
// the worst-case 2n sizing (16 MB at production quanta) probes cold
// RAM. 0 means no hint (worst-case sizing, the old behavior). When a
// hint undershoots, the table doubles and re-inserts the g combined
// rows seen so far (cheap: g << n), so results are identical for any
// hint.
long rt_combine_multi(const uint32_t* const* blocks, const size_t* ns,
                      size_t nblocks, uint32_t* out, size_t hint_slots);

long rt_combine_hint(const uint32_t* rows, size_t n, uint32_t* out,
                     size_t hint_slots) {
  // One-block view of the multi-block core (single table body lives
  // in rt_combine_multi so a fix can never diverge between them).
  const uint32_t* blocks[1] = {rows};
  size_t ns[1] = {n};
  return rt_combine_multi(blocks, ns, 1, out, hint_slots);
}

long rt_combine(const uint32_t* rows, size_t n, uint32_t* out) {
  return rt_combine_hint(rows, n, out, 0);
}

// Multi-block combine: the one table body behind every combine entry
// point (single-block, multi-block) — a fix can never diverge between
// them. The feed loop's flush quantum is a list of sink blocks, and
// concatenating them first costs a full row-copy pass (~40% of the
// combine stage at production quanta). First-appearance output order
// matches exactly what rt_combine_hint would produce on the
// concatenation, so results are bit-identical (cross-checked by the
// test suite). Single-threaded by design: the caller's feed workers
// each combine their own flush, which is where the parallelism is.
long rt_combine_multi(const uint32_t* const* blocks, const size_t* ns,
                      size_t nblocks, uint32_t* out, size_t hint_slots) {
  size_t n = 0;
  for (size_t b = 0; b < nblocks; b++) n += ns[b];
  if (n == 0) return 0;
  size_t worst = 16;
  while (worst < 2 * n) worst <<= 1;
  size_t slots = worst;
  if (hint_slots) {
    slots = 1024;
    while (slots < hint_slots && slots < worst) slots <<= 1;
    if (slots > worst) slots = worst;
  }
  uint32_t* table = (uint32_t*)malloc(slots * sizeof(uint32_t));
  if (!table) return -1;
  memset(table, 0xFF, slots * sizeof(uint32_t));
  size_t mask = slots - 1;
  size_t g = 0;
  for (size_t b = 0; b < nblocks; b++) {
    const uint32_t* rows = blocks[b];
    size_t nb = ns[b];
    // Per-block prefetch pipeline (blocks are thousands of rows; the
    // ~kAhead ramp cost per boundary is noise).
    constexpr size_t kAhead = 8;
    size_t next_hashes[kAhead];
    for (size_t i = 0; i < nb && i < kAhead; i++) {
      next_hashes[i] = hash_row(rows + i * NUM_FIELDS);
      __builtin_prefetch(&table[next_hashes[i] & mask]);
    }
    for (size_t i = 0; i < nb; i++) {
      const uint32_t* row = rows + i * NUM_FIELDS;
      size_t h_i = next_hashes[i % kAhead];
      size_t slot = h_i & mask;
      if (i + kAhead < nb) {
        size_t h = hash_row(rows + (i + kAhead) * NUM_FIELDS);
        next_hashes[(i + kAhead) % kAhead] = h;
        __builtin_prefetch(&table[h & mask]);
      }
      if (2 * g >= slots && slots < worst) {
        size_t nslots = slots << 1;
        uint32_t* ntable = (uint32_t*)malloc(nslots * sizeof(uint32_t));
        if (!ntable) {
          free(table);
          return -1;
        }
        memset(ntable, 0xFF, nslots * sizeof(uint32_t));
        size_t nmask = nslots - 1;
        for (size_t j = 0; j < g; j++) {
          size_t s = hash_row(out + j * NUM_FIELDS) & nmask;
          while (ntable[s] != 0xFFFFFFFFu) s = (s + 1) & nmask;
          ntable[s] = (uint32_t)j;
        }
        free(table);
        table = ntable;
        slots = nslots;
        mask = nmask;
        slot = hash_row(row) & mask;
      }
      for (;;) {
        uint32_t gid = table[slot];
        if (gid == 0xFFFFFFFFu) {
          table[slot] = (uint32_t)g;
          memcpy(out + g * NUM_FIELDS, row,
                 NUM_FIELDS * sizeof(uint32_t));
          g++;
          break;
        }
        uint32_t* orow = out + (size_t)gid * NUM_FIELDS;
        if (keys_equal(orow, row)) {
          orow[F_PACKETS] = sat_add_u32(orow[F_PACKETS], row[F_PACKETS]);
          orow[F_BYTES] = sat_add_u32(orow[F_BYTES], row[F_BYTES]);
          uint64_t ots =
              ((uint64_t)orow[F_TS_HI] << 32) | orow[F_TS_LO];
          uint64_t nts = ((uint64_t)row[F_TS_HI] << 32) | row[F_TS_LO];
          if (nts > ots) {
            orow[F_TS_LO] = row[F_TS_LO];
            orow[F_TS_HI] = row[F_TS_HI];
          }
          break;
        }
        slot = (slot + 1) & mask;
      }
    }
  }
  free(table);
  return (long)g;
}

}  // extern "C"

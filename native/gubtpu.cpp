// gubernator-tpu native host runtime.
//
// The device step is sub-millisecond; at batch_limit-scale traffic the
// host-side request packing (per-key string hashing + duplicate-round
// assignment) dominates when done in Python.  This library provides the two
// hot host ops over raw buffers, exposed via a C ABI for ctypes
// (gubernator_tpu/native/__init__.py):
//
//   gub_xxh64_batch    — XXH64 of N length-prefixed keys (the device
//                        fingerprint; matches python-xxhash seed 0)
//   gub_assign_rounds  — the packer's (round, lane) assignment with
//                        per-(round, shard) lane counters and hash-level
//                        duplicate detection (ops/batch.py's contract:
//                        occurrence k of a key lands in a strictly later
//                        round than occurrence k-1)
//   gub_pack_rounds    — a coalescer drain's `lane.pack` in one pass: one
//                        hash map over the drain's key hashes gives the
//                        duplicate groups the host cascade may take, the
//                        plain and the read-lane assignments (the rule of
//                        gub_assign_rounds, twice over one map), which of
//                        the two saves a device launch, and the rounds of
//                        the one chosen, written once into the device's
//                        own layout: int64[12, t] a round on one chip,
//                        int64[12, n_shards, t] on a mesh, rows in
//                        DeviceBatch field order (key_hash, hits, limit,
//                        duration, algo, burst, reset_remaining, is_greg,
//                        greg_expire, greg_duration, active, use_cached),
//                        t the smallest compiled tier that holds the
//                        round's fullest shard, unused lanes zero
//   gub_gather_rounds  — `lane.unpack`: the fetched responses (int64[9, t]
//                        a round, or [n_shards, 9, t]; rows status, limit,
//                        remaining, reset_time, persisted, found, stored,
//                        cached, stored_status) walked back through that
//                        assignment into int64[k, n] per-check columns,
//                        with the sums the tallies take
//   gub_hotkey_observe — the hot-key detector's per-batch sketch update
//                        (runtime/hotkey.py): the adds, the estimates
//                        after them and the candidates, one pass
//
// ctypes releases the GIL for the length of a call: a drain's pack and
// unpack run beside the other lanes' Python, not in turn with it.  (The
// one exception is gub_hotkey_observe for a small batch, bound a second
// time through ctypes.PyDLL: a pass of a microsecond on the event loop,
// shorter than taking the GIL back.)
//
// Build: make -C native  (g++ -O3 -shared; no external dependencies —
// XXH64 is implemented from its public spec below).

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

extern "C" {

// The build stamps the SHA-256 of this file (native/Makefile); the loader
// (gubernator_tpu/native/__init__.py) scans the .so for the marker before
// dlopen and rebuilds on a mismatch, so a library never outlives the
// source it was built from.
#ifndef GUB_SRC_HASH
#define GUB_SRC_HASH ""
#endif
const char* gub_src_hash() { return "GUBSRCHASH:" GUB_SRC_HASH; }

// ---------------------------------------------------------------------------
// XXH64 (from the xxHash spec; seed fixed to 0 like core/hashing.py)
// ---------------------------------------------------------------------------

static const uint64_t P1 = 11400714785074694791ULL;
static const uint64_t P2 = 14029467366897019727ULL;
static const uint64_t P3 = 1609587929392839161ULL;
static const uint64_t P4 = 9650029242287828579ULL;
static const uint64_t P5 = 2870177450012600261ULL;

static inline uint64_t rotl64(uint64_t x, int r) {
  return (x << r) | (x >> (64 - r));
}

static inline uint64_t read64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);
  return v;  // little-endian hosts only (x86/arm64)
}

static inline uint32_t read32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

static inline uint64_t xxh64_round(uint64_t acc, uint64_t input) {
  acc += input * P2;
  acc = rotl64(acc, 31);
  acc *= P1;
  return acc;
}

static inline uint64_t xxh64_merge(uint64_t acc, uint64_t val) {
  val = xxh64_round(0, val);
  acc ^= val;
  acc = acc * P1 + P4;
  return acc;
}

static uint64_t xxh64(const uint8_t* p, size_t len) {
  const uint8_t* end = p + len;
  uint64_t h;
  if (len >= 32) {
    uint64_t v1 = P1 + P2, v2 = P2, v3 = 0, v4 = 0 - P1;
    const uint8_t* limit = end - 32;
    do {
      v1 = xxh64_round(v1, read64(p));
      v2 = xxh64_round(v2, read64(p + 8));
      v3 = xxh64_round(v3, read64(p + 16));
      v4 = xxh64_round(v4, read64(p + 24));
      p += 32;
    } while (p <= limit);
    h = rotl64(v1, 1) + rotl64(v2, 7) + rotl64(v3, 12) + rotl64(v4, 18);
    h = xxh64_merge(h, v1);
    h = xxh64_merge(h, v2);
    h = xxh64_merge(h, v3);
    h = xxh64_merge(h, v4);
  } else {
    h = P5;
  }
  h += (uint64_t)len;
  while (p + 8 <= end) {
    h ^= xxh64_round(0, read64(p));
    h = rotl64(h, 27) * P1 + P4;
    p += 8;
  }
  if (p + 4 <= end) {
    h ^= (uint64_t)read32(p) * P1;
    h = rotl64(h, 23) * P2 + P3;
    p += 4;
  }
  while (p < end) {
    h ^= (*p) * P5;
    h = rotl64(h, 11) * P1;
    p++;
  }
  h ^= h >> 33;
  h *= P2;
  h ^= h >> 29;
  h *= P3;
  h ^= h >> 32;
  return h;
}

// Hash n keys packed as a concatenated blob with (n+1) byte offsets.
// out[i] = xxh64(blob[offsets[i]:offsets[i+1]]), remapped 0 -> 1 (the
// empty-slot sentinel rule, core/hashing.py key_hash64).
void gub_xxh64_batch(const uint8_t* blob, const int64_t* offsets, int64_t n,
                     int64_t* out) {
  for (int64_t i = 0; i < n; i++) {
    uint64_t h =
        xxh64(blob + offsets[i], (size_t)(offsets[i + 1] - offsets[i]));
    if (h == 0) h = 1;
    out[i] = (int64_t)h;
  }
}

// ---------------------------------------------------------------------------
// Round/lane assignment (ops/batch.py pack_requests_grid inner loop)
// ---------------------------------------------------------------------------

// Open-addressing map from key hash -> last assigned round (linear probe).
struct RoundMap {
  std::vector<uint64_t> keys;
  std::vector<int32_t> last_round;
  uint64_t mask;
  explicit RoundMap(int64_t n) {
    uint64_t cap = 16;
    while (cap < (uint64_t)n * 2) cap <<= 1;
    keys.assign(cap, 0);
    last_round.assign(cap, -1);
    mask = cap - 1;
  }
  int32_t* slot(uint64_t h) {
    uint64_t i = (h * P1) & mask;
    while (keys[i] != 0 && keys[i] != h) i = (i + 1) & mask;
    keys[i] = h;
    return &last_round[i];
  }
};

// The lane counters of an assignment: counters[r * n_shards + s] = lanes
// used.  The keysets per round for the "key not in round" check are
// implied by last_round tracking: a key's next occurrence starts probing
// at last_round+1, and WITHIN one probe sequence only capacity can force
// extra rounds, never the same key.
struct LaneCounters {
  std::vector<int32_t> used;
  int64_t n_rounds = 0;
  int32_t n_shards, batch_size;
  LaneCounters(int32_t n_shards, int32_t batch_size)
      : n_shards(n_shards), batch_size(batch_size) {}
  // Place a key's next occurrence on shard s: the first round after
  // *last_round with a free lane there.
  inline void place(int32_t* last_round, int32_t s, int32_t* out_round,
                    int32_t* out_lane) {
    int32_t r = *last_round + 1;
    for (;;) {
      if (r >= n_rounds) {
        used.resize((size_t)(r + 1) * n_shards, 0);
        n_rounds = r + 1;
      }
      int32_t& c = used[(size_t)r * n_shards + s];
      if (c < batch_size) {
        *out_round = r;
        *out_lane = c;
        c++;
        *last_round = r;
        return;
      }
      r++;
    }
  }
};

// Assign each request a (round, lane) such that:
//  - a key hash appears at most once per round,
//  - occurrence k of a key lands in a strictly later round than k-1,
//  - each (round, shard) holds at most batch_size lanes.
// hashes[i] == 0 marks an errored request (skipped; round=-1).
// Returns the number of rounds.
int64_t gub_assign_rounds(const int64_t* hashes, const int32_t* shards,
                          int64_t n, int32_t n_shards, int32_t batch_size,
                          int32_t* out_round, int32_t* out_lane) {
  RoundMap seen(n);
  LaneCounters lanes(n_shards, batch_size);
  for (int64_t i = 0; i < n; i++) {
    uint64_t h = (uint64_t)hashes[i];
    if (h == 0) {
      out_round[i] = -1;
      out_lane[i] = -1;
      continue;
    }
    lanes.place(seen.slot(h), shards ? shards[i] : 0, &out_round[i],
                &out_lane[i]);
  }
  return lanes.n_rounds;
}

// ---------------------------------------------------------------------------
// A drain's pack and unpack (runtime/fastpath.py _process_packed,
// _engine_process_packed)
// ---------------------------------------------------------------------------

// One distinct key of a drain, in order of first appearance.
struct DrainKey {
  int64_t first, last;  // first and last occurrence
  int32_t count;
  int32_t last_round;   // the running assignment's cursor
  int32_t group;        // cascade group number, -1: keeps its rounds
  bool same;            // one limit / duration / algo / burst / use_cached
  bool bad;             // negative hits, RESET_REMAINING or Gregorian met
};

enum {
  GUB_META_ROUNDS = 0,    // rounds of the assignment chosen
  GUB_META_WORDS = 1,     // arena words they take
  GUB_META_CASCADES = 2,  // 1: the read-lane assignment was chosen
  GUB_META_GROUPS = 3,    // eligible duplicate groups
  GUB_META_OCC = 4,       // their occurrences
  GUB_META_PEEKS = 5,     // hits == 0 among those
  GUB_META_LANES = 6,     // lanes assigned
  GUB_META_VALID = 7,     // checks with hash != 0
  GUB_META_HEAD = 8,      // then (tier, lanes, arena offset) a round
};

// mode 0: the plain assignment (gub_assign_rounds' own).
// mode 1: the host cascade where it saves a launch: eligible groups keep
//         one READ lane (their first occurrence, hits 0) iff the plain
//         assignment takes more rounds than the read lanes' plus one for
//         the write-back, which groups that are all use_cached never send.
// mode 2: the cascade wherever a group is eligible.
//
// A group is eligible as fastpath._plan_cascade has it: more than one
// occurrence, one limit / duration / algorithm / burst (burst 0 reads as
// limit) / use_cached, no occurrence with negative hits, RESET_REMAINING
// (behavior & reset_bit) or a Gregorian duration.  behavior, is_greg,
// greg_expire, greg_duration, use_cached may be null: all zero.
//
// shard of a hash: ((uint64)h >> shard_shift) % n_shards.
//
// arena/meta: the caller's; nothing is written to arena unless the rounds
// fit (arena_words, max_rounds), and the return is then 1 with
// meta[ROUNDS] and meta[WORDS] saying what they take.  Round r is
// arena[off : off + 12 * n_shards * t], meta[HEAD + 3r ..] = (t, lanes, off).
//
// Where groups are eligible (meta[GROUPS] > 0; modes 1, 2): occ[i] = 1 on
// their occurrences, and the groups, numbered in ascending order of the
// signed hash as np.unique numbers them (the write-back's lanes go out in
// that order), each with its first occurrence firsts[g] and its
// occurrences in arrival order order[bounds[g] : bounds[g + 1]].
// cap_ok[i] = 1 where i is the last occurrence of its key (may be null).
int64_t gub_pack_rounds(
    int64_t n, const int64_t* hash, const int64_t* hits,
    const int64_t* limit, const int64_t* duration, const int32_t* algo,
    const int64_t* burst, const int64_t* behavior, const uint8_t* is_greg,
    const int64_t* greg_expire, const int64_t* greg_duration,
    const uint8_t* use_cached, int64_t reset_bit, int32_t n_shards,
    int32_t shard_shift, int32_t batch_size, const int32_t* tiers,
    int32_t n_tiers, int32_t mode, int64_t* arena, int64_t arena_words,
    int64_t* meta, int64_t max_rounds, int32_t* out_round,
    int32_t* out_lane, uint8_t* cap_ok, uint8_t* occ, int64_t* firsts,
    int64_t* order, int64_t* bounds) {
  RoundMap seen(n);  // hash -> number of the key (its last_round slot)
  std::vector<DrainKey> keys;
  std::vector<int32_t> key_of((size_t)n), shard((size_t)n);
  auto burst_of = [&](int64_t i) { return burst[i] ? burst[i] : limit[i]; };
  auto cached_of = [&](int64_t i) {
    return use_cached ? use_cached[i] != 0 : false;
  };
  int64_t valid = 0;
  for (int64_t i = 0; i < n; i++) {
    uint64_t h = (uint64_t)hash[i];
    if (h == 0) {
      key_of[i] = -1;
      continue;
    }
    valid++;
    shard[i] = n_shards > 1 ? (int32_t)((h >> shard_shift) % n_shards) : 0;
    int32_t* k = seen.slot(h);
    if (*k < 0) {
      *k = (int32_t)keys.size();
      keys.push_back({i, i, 0, -1, -1, true, false});
    }
    key_of[i] = *k;
    DrainKey& key = keys[*k];
    key.count++;
    key.last = i;
    if (mode == 0) continue;
    int64_t f = key.first;
    key.same = key.same && limit[i] == limit[f] &&
               duration[i] == duration[f] && algo[i] == algo[f] &&
               burst_of(i) == burst_of(f) && cached_of(i) == cached_of(f);
    key.bad = key.bad || hits[i] < 0 ||
              (behavior && (behavior[i] & reset_bit)) ||
              (is_greg && is_greg[i]);
  }
  if (cap_ok) {
    std::memset(cap_ok, 0, (size_t)n);
    for (const DrainKey& key : keys) cap_ok[key.last] = 1;
  }

  // The eligible groups, numbered in ascending order of the signed hash.
  std::vector<int32_t> groups;
  if (mode != 0) {
    for (size_t k = 0; k < keys.size(); k++)
      if (keys[k].count > 1 && keys[k].same && !keys[k].bad)
        groups.push_back((int32_t)k);
    std::sort(groups.begin(), groups.end(), [&](int32_t a, int32_t b) {
      return hash[keys[a].first] < hash[keys[b].first];
    });
  }
  int64_t occ_total = 0, peeks = 0;
  bool write_back = false;
  for (size_t g = 0; g < groups.size(); g++) {
    DrainKey& key = keys[groups[g]];
    key.group = (int32_t)g;
    occ_total += key.count;
    write_back = write_back || !cached_of(key.first);
  }

  // The assignment: of every check (plain), or with each group's later
  // occurrences diverted (reads).
  auto assign = [&](bool reads, LaneCounters& lanes, int32_t* rnd,
                    int32_t* lane) {
    for (DrainKey& key : keys) key.last_round = -1;
    for (int64_t i = 0; i < n; i++) {
      int32_t k = key_of[i];
      if (k < 0 || (reads && keys[k].group >= 0 && keys[k].first != i)) {
        rnd[i] = -1;
        lane[i] = -1;
        continue;
      }
      lanes.place(&keys[k].last_round, shard[i], &rnd[i], &lane[i]);
    }
  };
  LaneCounters lanes(n_shards, batch_size);
  bool cascades = false;
  if (groups.empty()) {
    assign(false, lanes, out_round, out_lane);
  } else if (mode == 2) {
    cascades = true;
    assign(true, lanes, out_round, out_lane);
  } else {
    LaneCounters read_lanes(n_shards, batch_size);
    std::vector<int32_t> read_rnd((size_t)n), read_lane((size_t)n);
    assign(false, lanes, out_round, out_lane);
    assign(true, read_lanes, read_rnd.data(), read_lane.data());
    if (lanes.n_rounds > read_lanes.n_rounds + (write_back ? 1 : 0)) {
      cascades = true;
      lanes = std::move(read_lanes);
      std::memcpy(out_round, read_rnd.data(), (size_t)n * sizeof(int32_t));
      std::memcpy(out_lane, read_lane.data(), (size_t)n * sizeof(int32_t));
    }
  }

  if (!groups.empty()) {
    std::memset(occ, 0, (size_t)n);
    bounds[0] = 0;
    for (size_t g = 0; g < groups.size(); g++) {
      firsts[g] = keys[groups[g]].first;
      bounds[g + 1] = bounds[g] + keys[groups[g]].count;
    }
    std::vector<int64_t> at(bounds, bounds + groups.size());
    for (int64_t i = 0; i < n; i++) {
      int32_t g = key_of[i] < 0 ? -1 : keys[key_of[i]].group;
      if (g < 0) continue;
      occ[i] = 1;
      order[at[g]++] = i;
      if (hits[i] == 0) peeks++;
    }
  }

  // Each round's tier and its place in the arena.
  int64_t n_rounds = lanes.n_rounds, words = 0, n_lanes = 0;
  meta[GUB_META_ROUNDS] = n_rounds;
  meta[GUB_META_CASCADES] = cascades ? 1 : 0;
  meta[GUB_META_GROUPS] = (int64_t)groups.size();
  meta[GUB_META_OCC] = occ_total;
  meta[GUB_META_PEEKS] = peeks;
  meta[GUB_META_VALID] = valid;
  std::vector<int64_t> tier((size_t)n_rounds), off((size_t)n_rounds);
  for (int64_t r = 0; r < n_rounds; r++) {
    int32_t fullest = 0;
    int64_t in_round = 0;
    for (int32_t s = 0; s < n_shards; s++) {
      int32_t c = lanes.used[(size_t)r * n_shards + s];
      fullest = c > fullest ? c : fullest;
      in_round += c;
    }
    int64_t t = tiers[n_tiers - 1];
    for (int32_t j = 0; j < n_tiers; j++)
      if (fullest <= tiers[j]) {
        t = tiers[j];
        break;
      }
    tier[r] = t;
    off[r] = words;
    words += 12 * (int64_t)n_shards * t;
    n_lanes += in_round;
    if (r < max_rounds) {
      meta[GUB_META_HEAD + 3 * r] = t;
      meta[GUB_META_HEAD + 3 * r + 1] = in_round;
      meta[GUB_META_HEAD + 3 * r + 2] = off[r];
    }
  }
  meta[GUB_META_WORDS] = words;
  meta[GUB_META_LANES] = n_lanes;
  if (n_rounds > max_rounds || words > arena_words) return 1;

  // The rounds, a field at a time: each field's row of a round is filled
  // in rising lane order, a shard after another.
  std::memset(arena, 0, (size_t)words * sizeof(int64_t));
  std::vector<int64_t> at0((size_t)n), row((size_t)n);
  for (int64_t i = 0; i < n; i++) {
    int32_t r = out_round[i];
    if (r < 0) {
      at0[i] = -1;
      continue;
    }
    at0[i] = off[r] + (int64_t)shard[i] * tier[r] + out_lane[i];
    row[i] = (int64_t)n_shards * tier[r];
  }
  auto fill = [&](int field, auto value) {
    for (int64_t i = 0; i < n; i++)
      if (at0[i] >= 0) arena[at0[i] + field * row[i]] = (int64_t)value(i);
  };
  fill(0, [&](int64_t i) { return hash[i]; });
  // A read lane spends nothing: the replay serves its group.
  fill(1, [&](int64_t i) {
    return cascades && keys[key_of[i]].group >= 0 ? 0 : hits[i];
  });
  fill(2, [&](int64_t i) { return limit[i]; });
  fill(3, [&](int64_t i) { return duration[i]; });
  fill(4, [&](int64_t i) { return algo[i]; });
  fill(5, burst_of);
  if (behavior)
    fill(6, [&](int64_t i) { return (behavior[i] & reset_bit) != 0; });
  if (is_greg) fill(7, [&](int64_t i) { return is_greg[i] != 0; });
  if (greg_expire) fill(8, [&](int64_t i) { return greg_expire[i]; });
  if (greg_duration) fill(9, [&](int64_t i) { return greg_duration[i]; });
  fill(10, [&](int64_t) { return 1; });
  if (use_cached) fill(11, [&](int64_t i) { return use_cached[i] != 0; });
  return 0;
}

// The responses of a drain's rounds back in the order of its checks.
// resp[r] is round r's fetched buffer, int64[9, tier[r]] (n_shards 1) or
// int64[n_shards, 9, tier[r]]; check i sits at (round[i], shard of
// hash[i], lane[i]), nowhere where round[i] < 0 (its columns read 0).
// out is int64[n_cols, n]: the first n_cols response rows (9: all; 4:
// status, limit, remaining, reset_time).  sums[0..3]: over the lanes
// read, status == 1, persisted == 0, found != 0, and their number.
void gub_gather_rounds(int64_t n, const int64_t* hash, const int32_t* round,
                       const int32_t* lane, int32_t n_shards,
                       int32_t shard_shift, const int64_t* const* resp,
                       const int64_t* tier, int32_t n_cols, int64_t* out,
                       int64_t* sums) {
  std::vector<const int64_t*> at((size_t)n);
  std::vector<int64_t> row((size_t)n);
  int64_t over = 0, not_persisted = 0, found = 0, lanes = 0;
  for (int64_t i = 0; i < n; i++) {
    int32_t r = round[i];
    if (r < 0) {
      at[i] = nullptr;
      continue;
    }
    int64_t t = tier[r];
    int64_t s =
        n_shards > 1 ? (int64_t)(((uint64_t)hash[i] >> shard_shift) % n_shards)
                     : 0;
    const int64_t* p = resp[r] + s * 9 * t + lane[i];
    at[i] = p;
    row[i] = t;
    lanes++;
    over += p[0] == 1;
    not_persisted += p[4 * t] == 0;
    found += p[5 * t] != 0;
  }
  for (int32_t f = 0; f < n_cols; f++) {
    int64_t* col = out + (int64_t)f * n;
    for (int64_t i = 0; i < n; i++) col[i] = at[i] ? at[i][f * row[i]] : 0;
  }
  sums[0] = over;
  sums[1] = not_persisted;
  sums[2] = found;
  sums[3] = lanes;
}

// ---------------------------------------------------------------------------
// Protobuf wire codec for the GetRateLimits hot path.
//
// The python-protobuf parse/build of a 1000-item batch costs ~1ms each way —
// more than the device step itself.  These two functions move the whole
// request->columns and columns->response conversion to compiled code, the
// analog of the reference's generated Go marshalers: the daemon's fast lane
// hands the raw gRPC payload here and gets numpy columns back, and the
// response bytes are emitted directly from the packed device output arrays.
//
// Wire schema (proto/gubernator.proto): GetRateLimitsReq{repeated
// RateLimitReq requests = 1} with RateLimitReq fields name=1 unique_key=2
// hits=3 limit=4 duration=5 algorithm=6 behavior=7 burst=8;
// GetRateLimitsResp{repeated RateLimitResp responses = 1} with
// status=1 limit=2 remaining=3 reset_time=4 error=5.  (peers.proto's
// GetPeerRateLimits pair uses field 1 for the same item types, so the same
// codec serves the peer-to-peer hot path.)
// ---------------------------------------------------------------------------

static inline bool get_varint(const uint8_t*& p, const uint8_t* end,
                              uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  while (p < end && shift < 64) {
    uint8_t b = *p++;
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) {
      *out = v;
      return true;
    }
    shift += 7;
  }
  return false;
}

static inline bool skip_field(const uint8_t*& p, const uint8_t* end,
                              uint32_t wire) {
  uint64_t tmp;
  switch (wire) {
    case 0:
      return get_varint(p, end, &tmp);
    case 1:
      if (end - p < 8) return false;
      p += 8;
      return true;
    case 2:
      if (!get_varint(p, end, &tmp) || (uint64_t)(end - p) < tmp)
        return false;
      p += tmp;
      return true;
    case 5:
      if (end - p < 4) return false;
      p += 4;
      return true;
    default:
      return false;
  }
}

// The cold store (runtime/coldtier.py ColdTier): an open-addressed,
// linear-probed table of mask + 1 slots.  A slot's row is GUB_COLD_W
// int64 words in COLD_FIELDS order — key_hash, algo, limit, duration,
// remaining, remaining_f (a binary64's bits), t0, status, burst,
// expire_at — and `state` says what the slot holds: 0 empty (a chain
// ends), 1 full, 2 tombstone (a chain passes through).  Fingerprint 0 is
// padding, never resident.  The numpy forms in coldtier.py are the
// reference; tests hold these passes to them slot for slot.
static const int GUB_COLD_W = 10;

// (slot of fp, or -1; the first tombstone on the way, or -1; where the
// chain ended, or -1 where the table has no empty slot)
static inline int64_t cold_find(int64_t fp, const int64_t* rows,
                                const uint8_t* state, int64_t mask,
                                int64_t* tomb, int64_t* end) {
  int64_t pos = (int64_t)((uint64_t)fp & (uint64_t)mask);
  *tomb = -1;
  *end = -1;
  for (int64_t step = 0; step <= mask; ++step) {
    const uint8_t st = state[pos];
    if (st == 0) {
      *end = pos;
      return -1;
    }
    if (st == 2) {
      if (*tomb < 0) *tomb = pos;
    } else if (rows[pos * GUB_COLD_W] == fp) {
      return pos;
    }
    pos = (pos + 1) & mask;
  }
  return -1;
}

// slot[i]: where fps[i] is resident, -1 where it is not.
void gub_cold_probe(int64_t n, const int64_t* fps, const int64_t* rows,
                    const uint8_t* state, int64_t mask, int64_t* slot) {
  int64_t tomb, end;
  for (int64_t i = 0; i < n; ++i)
    slot[i] = fps[i] == 0 ? -1
                          : cold_find(fps[i], rows, state, mask, &tomb, &end);
}

// Rows in[n, W] into the store, one after another: a row whose key is
// resident MERGES into the waiting row — the new row at the least
// budget, max(r_new - consumed_old, 0), a token row's `remaining`, a
// leaky row's `remaining_f`: ops/state.py migrate_inject_impl's algebra
// — one whose key is not takes the first tombstone of its chain, or its
// end, while `room` lasts, and is dropped and counted after.  Returns
// the rows that are resident after the call and came from this batch;
// counts[0..2]: merges, drops, tombstones reused.
int64_t gub_cold_put(int64_t n, const int64_t* in, int64_t* rows,
                     uint8_t* state, int64_t mask, int64_t room,
                     int64_t* counts) {
  int64_t put = 0, merges = 0, drops = 0, reused = 0, tomb, end;
  for (int64_t i = 0; i < n; ++i) {
    const int64_t* r = in + i * GUB_COLD_W;
    if (r[0] == 0) continue;
    int64_t at = cold_find(r[0], rows, state, mask, &tomb, &end);
    if (at >= 0) {
      int64_t* old = rows + at * GUB_COLD_W;
      const bool leaky = old[1] == 1;
      int64_t used_i = old[2] - old[4];
      if (used_i < 0 || leaky) used_i = 0;
      double old_f, new_f;
      memcpy(&old_f, &old[5], 8);
      memcpy(&new_f, &r[5], 8);
      double used_f = (double)old[2] - old_f;
      if (!(used_f > 0.0) || !leaky) used_f = 0.0;
      int64_t rem = r[4] - used_i;
      if (rem < 0) rem = 0;
      new_f -= used_f;
      if (!(new_f > 0.0)) new_f = 0.0;
      memcpy(old, r, GUB_COLD_W * 8);
      old[4] = rem;
      memcpy(&old[5], &new_f, 8);
      ++merges;
      ++put;
      continue;
    }
    at = tomb >= 0 ? tomb : end;
    if (room <= 0 || at < 0) {
      ++drops;
      continue;
    }
    if (state[at] == 2) ++reused;
    memcpy(rows + at * GUB_COLD_W, r, GUB_COLD_W * 8);
    state[at] = 1;
    --room;
    ++put;
  }
  counts[0] = merges;
  counts[1] = drops;
  counts[2] = reused;
  return put;
}

// The rows of the fingerprints that are resident out of the store, in
// the order asked (one asked twice leaves once): out[k, W] the rows,
// which[k] the entries of fps they answer.  A vacated slot is a
// tombstone, or empty again where its successor is empty (no chain goes
// on from there).  Returns k; *tombs the tombstones it left.
int64_t gub_cold_pop(int64_t n, const int64_t* fps, const int64_t* rows,
                     uint8_t* state, int64_t mask, int64_t* out,
                     int64_t* which, int64_t* tombs) {
  int64_t k = 0, left = 0, tomb, end;
  for (int64_t i = 0; i < n; ++i) {
    if (fps[i] == 0) continue;
    const int64_t at = cold_find(fps[i], rows, state, mask, &tomb, &end);
    if (at < 0) continue;
    memcpy(out + k * GUB_COLD_W, rows + at * GUB_COLD_W, GUB_COLD_W * 8);
    which[k++] = i;
    if (state[(at + 1) & mask] == 0) {
      state[at] = 0;
    } else {
      state[at] = 2;
      ++left;
    }
  }
  *tombs = left;
  return k;
}

// The hot-key detector's update for one served batch (runtime/hotkey.py
// HotKeyTracker.observe, on the event loop inside wire.ingress), over the
// count-min sketch of runtime/sketch_backend.py HostCMS, whose `update`
// and `estimate` in numpy are the reference the tests hold this pass to
// bit for bit.  `table` is the sketch's int64[depth, width] in place; row
// d's index of a fingerprint is the top log2(width) bits of its uint64
// view times mults[d] (`shift` = 64 - log2(width)).  A zero fingerprint
// (the parser's error sentinel) is skipped; every other one adds
// max(hits, 1) in each row, duplicates accumulating (and wrapping as
// int64 does in numpy).  Then, where `room` > 0, each fingerprint's
// min-over-rows estimate is taken AFTER the whole batch's adds, and those
// whose estimate as a binary64 reaches `floor` are written to `out`, in
// batch order, repeats included, `room` of them at most.  Returns how
// many were written, or -1 where every fingerprint was zero.
static inline int64_t cms_idx(uint64_t u, uint64_t mult, int32_t shift) {
  return shift >= 64 ? 0 : (int64_t)((u * mult) >> shift);
}

int64_t gub_hotkey_observe(int64_t* table, int32_t depth, int64_t width,
                           const uint64_t* mults, int32_t shift, int64_t n,
                           const int64_t* hashes, const int64_t* hits,
                           double floor, int64_t room, int64_t* out) {
  bool any = false;
  for (int64_t i = 0; i < n; ++i) {
    const uint64_t u = (uint64_t)hashes[i];
    if (u == 0) continue;
    any = true;
    const uint64_t w = hits[i] > 1 ? (uint64_t)hits[i] : 1;
    for (int32_t d = 0; d < depth; ++d) {
      int64_t* cell = table + d * width + cms_idx(u, mults[d], shift);
      *cell = (int64_t)((uint64_t)*cell + w);
    }
  }
  int64_t k = 0;
  for (int64_t i = 0; i < n && k < room; ++i) {
    const uint64_t u = (uint64_t)hashes[i];
    if (u == 0) continue;
    int64_t est = table[cms_idx(u, mults[0], shift)];
    for (int32_t d = 1; d < depth; ++d) {
      const int64_t c = table[d * width + cms_idx(u, mults[d], shift)];
      if (c < est) est = c;
    }
    if ((double)est >= floor) out[k++] = hashes[i];
  }
  return any ? k : -1;
}

// Count the repeated field-1 submessages of a GetRateLimitsReq (or
// GetPeerRateLimitsReq) payload.  Returns -1 on malformed input.
int64_t gub_count_reqs(const uint8_t* buf, int64_t len) {
  const uint8_t* p = buf;
  const uint8_t* end = buf + len;
  int64_t n = 0;
  while (p < end) {
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return -1;
    if ((tag >> 3) == 1 && (tag & 7) == 2) {
      uint64_t sz;
      if (!get_varint(p, end, &sz) || (uint64_t)(end - p) < sz) return -1;
      p += sz;
      n++;
    } else {
      if (!skip_field(p, end, (uint32_t)(tag & 7))) return -1;
    }
  }
  return n;
}

// FNV-1 / FNV-1a (core/hashing.py fnv1_64 / fnv1a_64; the reference ring's
// key hash, replicated_hash.go:33) of each request's hash key
// (name + "_" + unique_key), re-walked from the spliced request frames
// (msg_off/msg_len from gub_parse_reqs2).  variant: 0 = fnv1
// (multiply-then-xor), 1 = fnv1a (xor-then-multiply).  out[i] = 0 when the
// frame has no name or key (errored lanes; the router masks them anyway).
// Keeps the columnar router serving under placement-interop rings in mixed
// reference/tpu clusters instead of falling back to per-request routing.
void gub_fnv_hashkey_batch(const uint8_t* buf, const int64_t* msg_off,
                           const int64_t* msg_len, int64_t n,
                           int32_t variant, int64_t* out) {
  const uint64_t PRIME = 1099511628211ULL;
  const uint64_t OFFSET = 14695981039346656037ULL;
  for (int64_t i = 0; i < n; i++) {
    const uint8_t* p = buf + msg_off[i];
    const uint8_t* fend = p + msg_len[i];
    out[i] = 0;
    uint64_t tag, sz;
    if (!get_varint(p, fend, &tag)) continue;
    if (!get_varint(p, fend, &sz) || (uint64_t)(fend - p) < sz) continue;
    const uint8_t* q = p;
    const uint8_t* qend = p + sz;
    const uint8_t* name = nullptr;
    uint64_t name_len = 0;
    const uint8_t* key = nullptr;
    uint64_t key_len = 0;
    bool ok = true;
    while (q < qend) {
      uint64_t t;
      if (!get_varint(q, qend, &t)) { ok = false; break; }
      uint32_t field = (uint32_t)(t >> 3);
      uint32_t wire = (uint32_t)(t & 7);
      if (wire == 2 && (field == 1 || field == 2)) {
        uint64_t l;
        if (!get_varint(q, qend, &l) || (uint64_t)(qend - q) < l) {
          ok = false;
          break;
        }
        if (field == 1) {
          name = q;
          name_len = l;
        } else {
          key = q;
          key_len = l;
        }
        q += l;
      } else if (!skip_field(q, qend, wire)) {
        ok = false;
        break;
      }
    }
    if (!ok || name_len == 0 || key_len == 0) continue;
    uint64_t h = OFFSET;
    const uint8_t us = '_';
    const uint8_t* parts[3] = {name, &us, key};
    const uint64_t lens[3] = {name_len, 1, key_len};
    if (variant == 0) {
      for (int s = 0; s < 3; s++)
        for (uint64_t j = 0; j < lens[s]; j++) {
          h = h * PRIME;
          h ^= parts[s][j];
        }
    } else {
      for (int s = 0; s < 3; s++)
        for (uint64_t j = 0; j < lens[s]; j++) {
          h ^= parts[s][j];
          h = h * PRIME;
        }
    }
    out[i] = (int64_t)h;
  }
}

// Parse the payload into per-request columns.  err[i]: 0 ok, 1 empty
// unique_key, 2 empty name (matching the service's validation order and
// messages).  hash[i] = XXH64(name + "_" + unique_key) with 0 remapped to 1;
// 0 on errored requests.  name_hash[i] = XXH64(name) with 0 remapped to 1
// (0 when the name is empty) — the columnar route key for name-scoped
// tiers (the sketch tier routes by this the same way the slot table keys
// by the 64-bit request fingerprint).  msg_off/msg_len give each
// RateLimitReq's frame (tag byte + length varint + body) within the
// payload, so a router can splice request bytes verbatim into a
// peer-forward payload without re-encoding.  Returns the parsed count, or
// -1 on malformed input (callers fall back to the python-protobuf path
// for the real error).
int64_t gub_parse_reqs2(const uint8_t* buf, int64_t len, int64_t cap,
                        int64_t* hash, int32_t* err, int64_t* hits,
                        int64_t* limit, int64_t* duration, int32_t* algo,
                        int64_t* behavior, int64_t* burst,
                        int64_t* msg_off, int64_t* msg_len,
                        int64_t* name_hash) {
  const uint8_t* p = buf;
  const uint8_t* end = buf + len;
  int64_t n = 0;
  std::vector<uint8_t> scratch;
  while (p < end) {
    const uint8_t* frame_start = p;
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return -1;
    if ((tag >> 3) != 1 || (tag & 7) != 2) {
      if (!skip_field(p, end, (uint32_t)(tag & 7))) return -1;
      continue;
    }
    uint64_t sz;
    if (!get_varint(p, end, &sz) || (uint64_t)(end - p) < sz) return -1;
    if (n >= cap) return -1;
    const uint8_t* q = p;
    const uint8_t* qend = p + sz;
    p = qend;
    msg_off[n] = (int64_t)(frame_start - buf);
    msg_len[n] = (int64_t)(qend - frame_start);

    const uint8_t* name = nullptr;
    uint64_t name_len = 0;
    const uint8_t* key = nullptr;
    uint64_t key_len = 0;
    int64_t f_hits = 0, f_limit = 0, f_duration = 0, f_behavior = 0,
            f_burst = 0;
    int32_t f_algo = 0;
    while (q < qend) {
      uint64_t t;
      if (!get_varint(q, qend, &t)) return -1;
      uint32_t field = (uint32_t)(t >> 3);
      uint32_t wire = (uint32_t)(t & 7);
      if (wire == 2 && (field == 1 || field == 2)) {
        uint64_t l;
        if (!get_varint(q, qend, &l) || (uint64_t)(qend - q) < l) return -1;
        if (field == 1) {
          name = q;
          name_len = l;
        } else {
          key = q;
          key_len = l;
        }
        q += l;
      } else if (wire == 0 && field >= 3 && field <= 8) {
        uint64_t v;
        if (!get_varint(q, qend, &v)) return -1;
        switch (field) {
          case 3: f_hits = (int64_t)v; break;
          case 4: f_limit = (int64_t)v; break;
          case 5: f_duration = (int64_t)v; break;
          case 6: f_algo = (int32_t)v; break;
          case 7: f_behavior = (int64_t)v; break;
          case 8: f_burst = (int64_t)v; break;
        }
      } else {
        if (!skip_field(q, qend, wire)) return -1;
      }
    }
    hits[n] = f_hits;
    limit[n] = f_limit;
    duration[n] = f_duration;
    algo[n] = f_algo;
    behavior[n] = f_behavior;
    burst[n] = f_burst;
    if (name_len == 0) {
      name_hash[n] = 0;
    } else {
      uint64_t nh = xxh64(name, name_len);
      if (nh == 0) nh = 1;
      name_hash[n] = (int64_t)nh;
    }
    if (key_len == 0) {
      err[n] = 1;
      hash[n] = 0;
    } else if (name_len == 0) {
      err[n] = 2;
      hash[n] = 0;
    } else {
      err[n] = 0;
      scratch.resize(name_len + 1 + key_len);
      std::memcpy(scratch.data(), name, name_len);
      scratch[name_len] = '_';
      std::memcpy(scratch.data() + name_len + 1, key, key_len);
      uint64_t h = xxh64(scratch.data(), scratch.size());
      if (h == 0) h = 1;
      hash[n] = (int64_t)h;
    }
    n++;
  }
  return n;
}

// Parse a GetRateLimitsResp / GetPeerRateLimitsResp payload into response
// columns (status=1 limit=2 remaining=3 reset_time=4 error=5); the router
// uses this to merge peer-forwarded responses back into its output
// columns.  err_off/err_len index INTO the payload (zero len = no error).
// meta_off/meta_len cover the item's metadata map entries (field 6) as
// raw wire frames — tag + length + body — so a forwarder can splice the
// owner's metadata verbatim into its own response.  Serializers write
// map entries contiguously; if an item's entries are fragmented by an
// interleaved field, meta_len is -1 (caller drops the metadata rather
// than splicing unrelated bytes).  Returns the item count, or -1 on
// malformed input.
int64_t gub_parse_resps2(const uint8_t* buf, int64_t len, int64_t cap,
                         int64_t* status, int64_t* limit, int64_t* remaining,
                         int64_t* reset_time, int64_t* err_off,
                         int64_t* err_len, int64_t* meta_off,
                         int64_t* meta_len) {
  const uint8_t* p = buf;
  const uint8_t* end = buf + len;
  int64_t n = 0;
  while (p < end) {
    uint64_t tag;
    if (!get_varint(p, end, &tag)) return -1;
    if ((tag >> 3) != 1 || (tag & 7) != 2) {
      if (!skip_field(p, end, (uint32_t)(tag & 7))) return -1;
      continue;
    }
    uint64_t sz;
    if (!get_varint(p, end, &sz) || (uint64_t)(end - p) < sz) return -1;
    if (n >= cap) return -1;
    const uint8_t* q = p;
    const uint8_t* qend = p + sz;
    p = qend;
    status[n] = limit[n] = remaining[n] = reset_time[n] = 0;
    err_off[n] = err_len[n] = 0;
    meta_off[n] = meta_len[n] = 0;
    const uint8_t* meta_end = nullptr;
    while (q < qend) {
      const uint8_t* field_start = q;
      uint64_t t;
      if (!get_varint(q, qend, &t)) return -1;
      uint32_t field = (uint32_t)(t >> 3);
      uint32_t wire = (uint32_t)(t & 7);
      if (wire == 0 && field >= 1 && field <= 4) {
        uint64_t v;
        if (!get_varint(q, qend, &v)) return -1;
        switch (field) {
          case 1: status[n] = (int64_t)v; break;
          case 2: limit[n] = (int64_t)v; break;
          case 3: remaining[n] = (int64_t)v; break;
          case 4: reset_time[n] = (int64_t)v; break;
        }
      } else if (wire == 2 && field == 5) {
        uint64_t l;
        if (!get_varint(q, qend, &l) || (uint64_t)(qend - q) < l) return -1;
        err_off[n] = (int64_t)(q - buf);
        err_len[n] = (int64_t)l;
        q += l;
      } else if (wire == 2 && field == 6) {
        uint64_t l;
        if (!get_varint(q, qend, &l) || (uint64_t)(qend - q) < l) return -1;
        q += l;
        if (meta_len[n] == 0) {
          meta_off[n] = (int64_t)(field_start - buf);
          meta_len[n] = (int64_t)(q - field_start);
        } else if (meta_len[n] > 0 && field_start == meta_end) {
          meta_len[n] += (int64_t)(q - field_start);
        } else {
          meta_len[n] = -1;  // fragmented — caller drops
        }
        meta_end = q;
      } else {
        if (!skip_field(q, qend, wire)) return -1;
      }
    }
    n++;
  }
  return n;
}

static inline int varint_size(uint64_t v) {
  int s = 1;
  while (v >= 0x80) {
    v >>= 7;
    s++;
  }
  return s;
}

static inline void put_varint(uint8_t*& w, uint64_t v) {
  while (v >= 0x80) {
    *w++ = (uint8_t)(v | 0x80);
    v >>= 7;
  }
  *w++ = (uint8_t)v;
}

// Emit GetRateLimitsResp (or GetPeerRateLimitsResp) bytes from packed
// response columns.  err_blob/err_off carry per-request error strings
// (err_off[i]..err_off[i+1]); zero-length means no error.  meta_blob/
// meta_off (may be null) carry per-request PRE-ENCODED metadata map
// entries — complete field-6 wire frames (tag + length + body), one or
// more per item, copied into the body verbatim.  Callers build frames
// with the python helper (meta_frame) or splice them from a parsed
// response's meta span — this covers the forwarded-response "owner"
// annotation (gubernator.go asyncRequests) and the sketch tier's
// "tier" tag with one mechanism.  Zero-valued fields are omitted like
// proto3 requires.  Returns bytes written, or -1 if `cap` is too small.
int64_t gub_serialize_resps2(int64_t n, const int64_t* status,
                             const int64_t* limit, const int64_t* remaining,
                             const int64_t* reset_time,
                             const uint8_t* err_blob, const int64_t* err_off,
                             const uint8_t* meta_blob,
                             const int64_t* meta_off,
                             uint8_t* out, int64_t cap) {
  uint8_t* w = out;
  uint8_t* wend = out + cap;
  for (int64_t i = 0; i < n; i++) {
    uint64_t elen = (uint64_t)(err_off[i + 1] - err_off[i]);
    uint64_t mlen =
        meta_off ? (uint64_t)(meta_off[i + 1] - meta_off[i]) : 0;
    size_t body = 0;
    if (status[i]) body += 1 + varint_size((uint64_t)status[i]);
    if (limit[i]) body += 1 + varint_size((uint64_t)limit[i]);
    if (remaining[i]) body += 1 + varint_size((uint64_t)remaining[i]);
    if (reset_time[i]) body += 1 + varint_size((uint64_t)reset_time[i]);
    if (elen) body += 1 + varint_size(elen) + elen;
    body += mlen;
    size_t total = 1 + varint_size(body) + body;
    if ((size_t)(wend - w) < total) return -1;
    *w++ = 0x0A;  // field 1, wire 2
    put_varint(w, body);
    if (status[i]) {
      *w++ = 0x08;
      put_varint(w, (uint64_t)status[i]);
    }
    if (limit[i]) {
      *w++ = 0x10;
      put_varint(w, (uint64_t)limit[i]);
    }
    if (remaining[i]) {
      *w++ = 0x18;
      put_varint(w, (uint64_t)remaining[i]);
    }
    if (reset_time[i]) {
      *w++ = 0x20;
      put_varint(w, (uint64_t)reset_time[i]);
    }
    if (elen) {
      *w++ = 0x2A;
      put_varint(w, elen);
      std::memcpy(w, err_blob + err_off[i], elen);
      w += elen;
    }
    if (mlen) {
      std::memcpy(w, meta_blob + meta_off[i], mlen);
      w += mlen;
    }
  }
  return (int64_t)(w - out);
}

// Emit GetRateLimitsReq (or GetPeerRateLimitsReq / LeaseReq.requests —
// all use repeated field 1... field numbering below is the RateLimitReq
// schema) wire bytes from packed request columns — the CLIENT half of
// the codec: a compiled SDK (client.py FastV1Client) serializes a whole
// batch without constructing a single python protobuf object, attacking
// the ~1.3ms of python client machinery the E2E artifacts measure.
//
// name_blob/name_off and key_blob/key_off carry the n strings as
// concatenated bytes with (n+1) offsets (the gub_xxh64_batch layout).
// Numeric columns are int64 (algo included — widened by the caller);
// negative values (hit refunds) encode as 10-byte two's-complement
// varints exactly like protobuf's int64.  Zero-valued fields are
// omitted per proto3.  Returns bytes written, or -1 if `cap` is too
// small.
int64_t gub_serialize_reqs(int64_t n, const uint8_t* name_blob,
                           const int64_t* name_off,
                           const uint8_t* key_blob,
                           const int64_t* key_off, const int64_t* hits,
                           const int64_t* limit, const int64_t* duration,
                           const int64_t* algo, const int64_t* behavior,
                           const int64_t* burst, uint8_t* out,
                           int64_t cap) {
  uint8_t* w = out;
  uint8_t* wend = out + cap;
  for (int64_t i = 0; i < n; i++) {
    uint64_t nlen = (uint64_t)(name_off[i + 1] - name_off[i]);
    uint64_t klen = (uint64_t)(key_off[i + 1] - key_off[i]);
    size_t body = 0;
    if (nlen) body += 1 + varint_size(nlen) + nlen;
    if (klen) body += 1 + varint_size(klen) + klen;
    if (hits[i]) body += 1 + varint_size((uint64_t)hits[i]);
    if (limit[i]) body += 1 + varint_size((uint64_t)limit[i]);
    if (duration[i]) body += 1 + varint_size((uint64_t)duration[i]);
    if (algo[i]) body += 1 + varint_size((uint64_t)algo[i]);
    if (behavior[i]) body += 1 + varint_size((uint64_t)behavior[i]);
    if (burst[i]) body += 1 + varint_size((uint64_t)burst[i]);
    size_t total = 1 + varint_size(body) + body;
    if ((size_t)(wend - w) < total) return -1;
    *w++ = 0x0A;  // field 1 (requests), wire 2
    put_varint(w, body);
    if (nlen) {
      *w++ = 0x0A;  // name = 1
      put_varint(w, nlen);
      std::memcpy(w, name_blob + name_off[i], nlen);
      w += nlen;
    }
    if (klen) {
      *w++ = 0x12;  // unique_key = 2
      put_varint(w, klen);
      std::memcpy(w, key_blob + key_off[i], klen);
      w += klen;
    }
    if (hits[i]) {
      *w++ = 0x18;  // hits = 3
      put_varint(w, (uint64_t)hits[i]);
    }
    if (limit[i]) {
      *w++ = 0x20;  // limit = 4
      put_varint(w, (uint64_t)limit[i]);
    }
    if (duration[i]) {
      *w++ = 0x28;  // duration = 5
      put_varint(w, (uint64_t)duration[i]);
    }
    if (algo[i]) {
      *w++ = 0x30;  // algorithm = 6
      put_varint(w, (uint64_t)algo[i]);
    }
    if (behavior[i]) {
      *w++ = 0x38;  // behavior = 7
      put_varint(w, (uint64_t)behavior[i]);
    }
    if (burst[i]) {
      *w++ = 0x40;  // burst = 8
      put_varint(w, (uint64_t)burst[i]);
    }
  }
  return (int64_t)(w - out);
}

}  // extern "C"

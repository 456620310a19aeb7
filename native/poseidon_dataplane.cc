// Native data plane: LMDB scan + Datum decode + augmentation, multithreaded.
//
// The reference's ingest path is C++ end to end: DataLayer +
// BasePrefetchingDataLayer's InternalThread decode Datum protobufs from
// LMDB/LevelDB and run DataTransformer augmentation off the training thread
// (src/caffe/layers/data_layer.cpp, src/caffe/data_transformer.cpp). This
// file is the TPU-native equivalent: a dependency-free C library (mmap'd
// LMDB B+tree walk, hand-rolled protobuf wire decode, crop/mirror/mean/scale
// in a std::thread pool) exposed through a flat C ABI consumed via ctypes
// (poseidon_tpu/data/native.py). Releasing the GIL for the whole batch makes
// host-side prefetch overlap device steps for real.
//
// Build: make -C native   (g++ -O3 -shared -fPIC -pthread)

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <random>
#include <string>
#include <thread>
#include <vector>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr uint32_t kMdbMagic = 0xBEEFC0DE;
constexpr uint16_t kPBranch = 0x01;
constexpr uint16_t kPLeaf = 0x02;
constexpr uint16_t kPMeta = 0x08;
constexpr uint16_t kFBigData = 0x01;

struct Slice {
  const uint8_t* data = nullptr;
  size_t size = 0;
};

struct Db {
  int fd = -1;
  const uint8_t* map = nullptr;
  size_t map_size = 0;
  size_t page_size = 4096;
  int64_t root = -1;
  uint64_t entries = 0;
  // Index of value locations: (leaf page number, node index).
  std::vector<std::pair<uint64_t, uint32_t>> index;
  int channels = 0, height = 0, width = 0;  // from first record
  std::string error;
};

inline uint16_t rd16(const uint8_t* p) { uint16_t v; memcpy(&v, p, 2); return v; }
inline uint32_t rd32(const uint8_t* p) { uint32_t v; memcpy(&v, p, 4); return v; }
inline uint64_t rd64(const uint8_t* p) { uint64_t v; memcpy(&v, p, 8); return v; }

const uint8_t* page(const Db& db, uint64_t pgno) {
  return db.map + pgno * db.page_size;
}

bool parse_meta(Db* db) {
  for (size_t psize : {4096u, 8192u, 16384u, 32768u}) {
    if (db->map_size < 2 * psize) continue;
    uint64_t best_txn = 0;
    int64_t root = -2;
    uint64_t entries = 0;
    bool found = false;
    for (int m = 0; m < 2; ++m) {
      const uint8_t* p = db->map + m * psize;
      if (!(rd16(p + 10) & kPMeta)) continue;
      if (rd32(p + 16) != kMdbMagic) continue;
      // MDB_meta layout after magic+version+address+mapsize (offset 40):
      // free db (48 bytes), main db (48 bytes), last_pg, txnid.
      const uint8_t* main_db = p + 40 + 48;
      uint64_t txn = rd64(p + 40 + 96 + 8);
      if (!found || txn >= best_txn) {
        best_txn = txn;
        entries = rd64(main_db + 32);
        root = (int64_t)rd64(main_db + 40);
        found = true;
      }
    }
    if (found) {
      db->page_size = psize;
      db->root = root;
      db->entries = entries;
      return true;
    }
  }
  db->error = "not an LMDB file";
  return false;
}

uint32_t node_count(const uint8_t* p) {
  uint16_t lower = rd16(p + 12);
  return lower >= 16 ? (lower - 16) / 2 : 0;
}

bool walk(Db* db, uint64_t pgno, int depth) {
  if (depth > 64) { db->error = "B+tree too deep"; return false; }
  const uint8_t* p = page(*db, pgno);
  uint16_t flags = rd16(p + 10);
  uint32_t n = node_count(p);
  if (flags & kPLeaf) {
    for (uint32_t i = 0; i < n; ++i) db->index.emplace_back(pgno, i);
    return true;
  }
  if (!(flags & kPBranch)) { db->error = "unexpected page flags"; return false; }
  for (uint32_t i = 0; i < n; ++i) {
    uint16_t off = rd16(p + 16 + 2 * i);
    const uint8_t* node = p + off;
    uint64_t child = (uint64_t)rd16(node) | ((uint64_t)rd16(node + 2) << 16) |
                     ((uint64_t)rd16(node + 4) << 32);
    if (!walk(db, child, depth + 1)) return false;
  }
  return true;
}

Slice leaf_value(const Db& db, uint64_t pgno, uint32_t idx) {
  const uint8_t* p = page(db, pgno);
  uint16_t off = rd16(p + 16 + 2 * idx);
  const uint8_t* node = p + off;
  uint32_t datasize = (uint32_t)rd16(node) | ((uint32_t)rd16(node + 2) << 16);
  uint16_t flags = rd16(node + 4);
  uint16_t ksize = rd16(node + 6);
  if (flags & kFBigData) {
    uint64_t ovpg = rd64(node + 8 + ksize);
    return {page(db, ovpg) + 16, datasize};
  }
  return {node + 8 + ksize, datasize};
}

// ----------------------------------------------------------------------- //
// Protobuf wire decode for Datum (caffe.proto: channels=1 height=2 width=3
// data=4 label=5 float_data=6).
struct DatumView {
  int32_t channels = 0, height = 0, width = 0, label = 0;
  Slice bytes;        // field 4
  Slice packed_float; // field 6 packed
  bool ok = false;
};

bool read_varint(const uint8_t*& p, const uint8_t* end, uint64_t* out) {
  uint64_t v = 0;
  int shift = 0;
  while (p < end && shift <= 63) {
    uint8_t b = *p++;
    v |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) { *out = v; return true; }
    shift += 7;
  }
  return false;
}

DatumView parse_datum(Slice s) {
  DatumView d;
  const uint8_t* p = s.data;
  const uint8_t* end = s.data + s.size;
  while (p < end) {
    uint64_t key;
    if (!read_varint(p, end, &key)) return d;
    uint32_t fnum = key >> 3, wtype = key & 7;
    if (wtype == 0) {
      uint64_t v;
      if (!read_varint(p, end, &v)) return d;
      switch (fnum) {
        case 1: d.channels = (int32_t)v; break;
        case 2: d.height = (int32_t)v; break;
        case 3: d.width = (int32_t)v; break;
        case 5: d.label = (int32_t)v; break;
        default: break;
      }
    } else if (wtype == 2) {
      uint64_t len;
      if (!read_varint(p, end, &len) || len > (uint64_t)(end - p)) return d;
      if (fnum == 4) d.bytes = {p, (size_t)len};
      else if (fnum == 6) d.packed_float = {p, (size_t)len};
      p += len;
    } else if (wtype == 5) {
      p += 4;
    } else if (wtype == 1) {
      p += 8;
    } else {
      return d;
    }
  }
  const uint64_t pixels =
      (uint64_t)d.channels * (uint64_t)d.height * (uint64_t)d.width;
  d.ok = d.channels > 0 && d.height > 0 && d.width > 0 &&
         ((d.bytes.size >= pixels) ||
          (d.packed_float.size >= 4 * pixels));
  return d;
}

// ----------------------------------------------------------------------- //
struct TransformSpec {
  int32_t crop_size;     // 0 = none
  int32_t mirror;        // bool
  int32_t train;         // bool: random crop/mirror vs center/no-mirror
  float scale;
  int32_t mean_mode;     // 0 none, 1 per-channel values, 2 full mean array
  const float* mean;     // values[C] or array[C*H*W]
};

// splitmix64: cheap deterministic per-record rng
inline uint64_t mix(uint64_t x) {
  x += 0x9E3779B97F4A7C15ull;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
  return x ^ (x >> 31);
}

// Crop offsets + mirror decision for one record. BOTH batch paths (f32
// host-transform and u8 device-transform) derive augmentation from this one
// function, so the two pipelines see identical pixels for a given seed —
// the parity contract tests/test_native.py::test_native_u8_matches_f32_pixels
// checks.
struct Aug { int h_off, w_off; bool do_mirror; };

Aug compute_aug(uint64_t seed, int H, int W, int crop, bool train,
                bool mirror) {
  Aug a{0, 0, false};
  if (crop) {
    if (train) {
      uint64_t r = mix(seed);
      a.h_off = (int)(r % (uint64_t)(H - crop + 1));
      a.w_off = (int)(mix(r) % (uint64_t)(W - crop + 1));
    } else {
      a.h_off = (H - crop) / 2;
      a.w_off = (W - crop) / 2;
    }
  }
  if (mirror && train) a.do_mirror = (mix(seed ^ 0xABCDu) & 1) != 0;
  return a;
}

void transform_one(const DatumView& d, const TransformSpec& t, uint64_t seed,
                   float* out) {
  const int C = d.channels, H = d.height, W = d.width;
  const int crop = t.crop_size ? t.crop_size : 0;
  const int oh = crop ? crop : H, ow = crop ? crop : W;
  Aug a = compute_aug(seed, H, W, crop, t.train != 0, t.mirror != 0);
  const int h_off = a.h_off, w_off = a.w_off;
  const bool do_mirror = a.do_mirror;

  // One output row at a time, every decision hoisted out of the pixel
  // loop, so the compiler vectorizes the common case (byte pixels, a mean
  // array): the arithmetic per pixel is the same (v - mean) * scale.
  const uint8_t* bytes = d.bytes.size ? d.bytes.data : nullptr;
  const float scale = t.scale;
  for (int c = 0; c < C; ++c) {
    for (int h = 0; h < oh; ++h) {
      const int src0 = (c * H + h + h_off) * W + w_off;
      float* row = out + (size_t)(c * oh + h) * ow;
      if (bytes) {
        const uint8_t* s = bytes + src0;
        for (int w = 0; w < ow; ++w) row[w] = (float)s[w];
      } else {
        memcpy(row, d.packed_float.data + 4 * (size_t)src0, 4 * (size_t)ow);
      }
      if (t.mean_mode == 1) {
        const float m = t.mean[c];
        for (int w = 0; w < ow; ++w) row[w] = (row[w] - m) * scale;
      } else if (t.mean_mode == 2) {
        const float* m = t.mean + src0;
        for (int w = 0; w < ow; ++w) row[w] = (row[w] - m[w]) * scale;
      } else {
        for (int w = 0; w < ow; ++w) row[w] *= scale;
      }
      if (do_mirror) std::reverse(row, row + ow);
    }
  }
}

}  // namespace

extern "C" {

void* pdp_open(const char* path) {
  auto* db = new Db();
  std::string p(path);
  struct stat st;
  if (stat(p.c_str(), &st) == 0 && S_ISDIR(st.st_mode)) p += "/data.mdb";
  db->fd = open(p.c_str(), O_RDONLY);
  if (db->fd < 0) { db->error = "cannot open " + p; return db; }
  if (fstat(db->fd, &st) != 0) { db->error = "fstat failed"; return db; }
  db->map_size = (size_t)st.st_size;
  db->map = (const uint8_t*)mmap(nullptr, db->map_size, PROT_READ, MAP_SHARED,
                                 db->fd, 0);
  if (db->map == MAP_FAILED) { db->map = nullptr; db->error = "mmap failed"; return db; }
  if (!parse_meta(db)) return db;
  if (db->root >= 0 && !walk(db, (uint64_t)db->root, 0)) return db;
  if (!db->index.empty()) {
    DatumView d = parse_datum(leaf_value(*db, db->index[0].first,
                                         db->index[0].second));
    if (d.ok) { db->channels = d.channels; db->height = d.height; db->width = d.width; }
  }
  return db;
}

const char* pdp_error(void* h) {
  auto* db = (Db*)h;
  return db->error.empty() ? nullptr : db->error.c_str();
}

int64_t pdp_count(void* h) { return (int64_t)((Db*)h)->index.size(); }

void pdp_shape(void* h, int32_t* c, int32_t* hh, int32_t* w) {
  auto* db = (Db*)h;
  *c = db->channels; *hh = db->height; *w = db->width;
}

// Fill a batch: indices[n] records -> out_data (n,C,oh,ow) + out_labels[n].
// Returns 0 on success, <0 on error (bad record).
int32_t pdp_batch(void* h, const int64_t* indices, int32_t n,
                  const TransformSpec* spec, uint64_t seed,
                  float* out_data, int32_t* out_labels, int32_t n_threads) {
  auto* db = (Db*)h;
  const int C = db->channels;
  if (spec->crop_size &&
      (spec->crop_size > db->height || spec->crop_size > db->width))
    return -3;  // crop larger than record (ValueError on the Python path)
  const int oh = spec->crop_size ? spec->crop_size : db->height;
  const int ow = spec->crop_size ? spec->crop_size : db->width;
  const size_t rec = (size_t)C * oh * ow;
  const int64_t n_records = (int64_t)db->index.size();
  std::atomic<int32_t> status{0};
  int workers = std::max(1, std::min<int>(n_threads, n));
  std::vector<std::thread> threads;
  std::atomic<int32_t> next{0};
  auto work = [&]() {
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n) return;
      if (indices[i] < 0 || indices[i] >= n_records) { status.store(-2); return; }
      auto loc = db->index[(size_t)indices[i]];
      DatumView d = parse_datum(leaf_value(*db, loc.first, loc.second));
      if (!d.ok || d.channels != C || d.height != db->height ||
          d.width != db->width) { status.store(-1); return; }
      out_labels[i] = d.label;
      transform_one(d, *spec, mix(seed ^ (uint64_t)indices[i]),
                    out_data + (size_t)i * rec);
    }
  };
  for (int t = 0; t < workers; ++t) threads.emplace_back(work);
  for (auto& t : threads) t.join();
  return status.load();
}

// uint8 batch: decode + crop + mirror ONLY — mean/scale move onto the
// accelerator (fused into the first conv by XLA), and the host ships 4x
// fewer bytes. Only byte-backed Datums qualify (float_data records return
// -4 so the caller can fall back to the f32 path). Same crop/mirror RNG
// stream as transform_one, so u8-on-device == f32-on-host exactly.
int32_t pdp_batch_u8(void* h, const int64_t* indices, int32_t n,
                     int32_t crop_size, int32_t mirror, int32_t train,
                     uint64_t seed, uint8_t* out_data, int32_t* out_labels,
                     int32_t n_threads) {
  auto* db = (Db*)h;
  const int C = db->channels;
  if (crop_size && (crop_size > db->height || crop_size > db->width))
    return -3;
  const int H = db->height, W = db->width;
  const int oh = crop_size ? crop_size : H;
  const int ow = crop_size ? crop_size : W;
  const size_t rec = (size_t)C * oh * ow;
  const int64_t n_records = (int64_t)db->index.size();
  std::atomic<int32_t> status{0};
  int workers = std::max(1, std::min<int>(n_threads, n));
  std::vector<std::thread> threads;
  std::atomic<int32_t> next{0};
  auto work = [&]() {
    for (;;) {
      int32_t i = next.fetch_add(1);
      if (i >= n) return;
      if (indices[i] < 0 || indices[i] >= n_records) { status.store(-2); return; }
      auto loc = db->index[(size_t)indices[i]];
      DatumView d = parse_datum(leaf_value(*db, loc.first, loc.second));
      if (!d.ok || d.channels != C || d.height != H || d.width != W) {
        status.store(-1); return;
      }
      if (!d.bytes.size) { status.store(-4); return; }  // float_data record
      out_labels[i] = d.label;
      Aug a = compute_aug(mix(seed ^ (uint64_t)indices[i]), H, W, crop_size,
                          train != 0, mirror != 0);
      const int h_off = a.h_off, w_off = a.w_off;
      const bool do_mirror = a.do_mirror;
      uint8_t* out = out_data + (size_t)i * rec;
      for (int c = 0; c < C; ++c) {
        for (int hh = 0; hh < oh; ++hh) {
          const uint8_t* src_row =
              d.bytes.data + ((size_t)c * H + hh + h_off) * W + w_off;
          uint8_t* dst_row = out + ((size_t)c * oh + hh) * ow;
          if (!do_mirror) {
            memcpy(dst_row, src_row, (size_t)ow);
          } else {
            for (int w = 0; w < ow; ++w) dst_row[ow - 1 - w] = src_row[w];
          }
        }
      }
    }
  };
  for (int t = 0; t < workers; ++t) threads.emplace_back(work);
  for (auto& t : threads) t.join();
  return status.load();
}

void pdp_close(void* h) {
  auto* db = (Db*)h;
  if (db->map) munmap((void*)db->map, db->map_size);
  if (db->fd >= 0) close(db->fd);
  delete db;
}

// Snappy block-format decompressor (public format spec: varint32 length,
// then literal / copy-1/2/4 elements). The fast path behind the Python
// codec in poseidon_tpu/data/snappy.py — LevelDB SSTable blocks decompress
// through this when the library is built.
//
// Returns the uncompressed length, or -1 (malformed), or -2 (dst_cap too
// small; call with dst=null to query the needed size).
int64_t pdp_snappy_uncompress(const uint8_t* src, int64_t src_len,
                              uint8_t* dst, int64_t dst_cap) {
  int64_t pos = 0;
  uint64_t expected = 0;
  int shift = 0;
  for (;;) {  // varint32 uncompressed length
    if (pos >= src_len || shift > 32) return -1;
    uint8_t b = src[pos++];
    expected |= (uint64_t)(b & 0x7F) << shift;
    if (!(b & 0x80)) break;
    shift += 7;
  }
  if (dst == nullptr) return (int64_t)expected;
  if ((int64_t)expected > dst_cap) return -2;
  int64_t out = 0;
  while (pos < src_len) {
    uint8_t tag = src[pos++];
    uint32_t elem = tag & 3;
    if (elem == 0) {  // literal
      int64_t len = tag >> 2;
      if (len >= 60) {
        int extra = (int)len - 59;
        if (pos + extra > src_len) return -1;
        len = 0;
        for (int i = 0; i < extra; ++i) len |= (int64_t)src[pos + i] << (8 * i);
        pos += extra;
      }
      len += 1;
      if (pos + len > src_len || out + len > (int64_t)expected) return -1;
      memcpy(dst + out, src + pos, (size_t)len);
      pos += len;
      out += len;
      continue;
    }
    int64_t len, offset;
    if (elem == 1) {  // copy, 1-byte offset
      len = 4 + ((tag >> 2) & 0x7);
      if (pos >= src_len) return -1;
      offset = ((int64_t)(tag >> 5) << 8) | src[pos];
      pos += 1;
    } else if (elem == 2) {  // copy, 2-byte offset
      len = (tag >> 2) + 1;
      if (pos + 2 > src_len) return -1;
      offset = (int64_t)src[pos] | ((int64_t)src[pos + 1] << 8);
      pos += 2;
    } else {  // copy, 4-byte offset
      len = (tag >> 2) + 1;
      if (pos + 4 > src_len) return -1;
      offset = 0;
      for (int i = 0; i < 4; ++i) offset |= (int64_t)src[pos + i] << (8 * i);
      pos += 4;
    }
    if (offset <= 0 || offset > out || out + len > (int64_t)expected)
      return -1;
    // overlapping copies are byte-serial by definition (RLE-style refs)
    for (int64_t i = 0; i < len; ++i) dst[out + i] = dst[out - offset + i];
    out += len;
  }
  return out == (int64_t)expected ? out : -1;
}

}  // extern "C"

// Shared-memory MPMC ring buffer for request/tensor staging.
//
// Role: the native data-plane piece of the TPU serving runtime. The reference
// delegates its native performance path to external C++ servers
// (integrations/tfserving, nvidia-inference-server — SURVEY.md §2 native-code
// note); here the native component is in-repo: transport worker processes
// (REST/gRPC frontends) stage decoded tensor payloads into a shared-memory
// ring, and the single device-owning engine process drains them in batches —
// no pickling, no socket hop, one memcpy each way.
//
// Design: Vyukov bounded MPMC queue. Each cell carries an atomic sequence
// number; producers claim cells with fetch_add on enqueue_pos, consumers with
// fetch_add on dequeue_pos. Lock-free, FIFO per producer, safe across
// processes (std::atomic<uint64_t> on x86-64/aarch64 over shared mmap).
//
// Layout in the mapped file (v2):
//   [Header][CellHeader 0..capacity-1 (64B each, contiguous)][slot 0..capacity-1]
// Cell headers are packed together rather than strided through the data
// region: creation then touches capacity*64B instead of one page per slot —
// on block storage where a fresh MAP_SHARED page fault costs ~10ms, the old
// strided layout took ~16s to create a 1GB ring (measured; see git history).
// Polling also scans a compact array instead of page-sized strides.

#include <atomic>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

namespace {

constexpr uint64_t kMagic = 0x53454c52494e4732ull;  // "SELRING2"

struct Header {
  std::atomic<uint64_t> magic;  // written last (release) so attachers see a
                                // fully initialised header (acquire)
  uint64_t capacity;   // power of two
  uint64_t slot_size;  // payload bytes per cell
  uint64_t slot_stride;  // slot_size rounded to 64B
  alignas(64) std::atomic<uint64_t> enqueue_pos;
  alignas(64) std::atomic<uint64_t> dequeue_pos;
};

struct alignas(64) CellHeader {  // one cache line per cell, packed array
  std::atomic<uint64_t> seq;
  uint32_t len;
};

struct Ring {
  Header* header;
  CellHeader* cells;  // contiguous array [capacity]
  uint8_t* slots;     // data region, slot_stride apart
  size_t map_len;
};

inline CellHeader* cell_at(const Ring* r, uint64_t idx) {
  return r->cells + (idx & (r->header->capacity - 1));
}

inline uint8_t* cell_data(const Ring* r, uint64_t idx) {
  return r->slots + (idx & (r->header->capacity - 1)) * r->header->slot_stride;
}

size_t total_size(uint64_t capacity, uint64_t slot_stride) {
  return sizeof(Header) + capacity * sizeof(CellHeader) + capacity * slot_stride;
}

// nullptr with errno = err: the clean-up calls between a refusal and the
// return would otherwise overwrite the one fact the caller can report
void* fail(int err) {
  errno = err;
  return nullptr;
}

}  // namespace

extern "C" {

// Create (or replace) a ring file. capacity must be a power of two.
// The ring is initialised in a temp file and atomically renamed over the
// target, so re-creating a ring never truncates the inode that still-attached
// workers have mapped (they keep the old ring; new attachers get the new one).
// Returns an opaque handle, or nullptr with errno saying which call refused
// (EFBIG: a file-size limit; ENOSPC/ENOMEM: the directory or the mapping).
void* scr_create(const char* path, uint64_t capacity, uint64_t slot_size) {
  if (capacity == 0 || (capacity & (capacity - 1)) != 0) return fail(EINVAL);
  uint64_t stride = (slot_size + 63) & ~63ull;  // 64B-align slots
  size_t len = total_size(capacity, stride);

  char tmp[4096];
  int n = ::snprintf(tmp, sizeof(tmp), "%s.tmp.%d", path, ::getpid());
  if (n < 0 || static_cast<size_t>(n) >= sizeof(tmp)) return fail(ENAMETOOLONG);
  int fd = ::open(tmp, O_RDWR | O_CREAT | O_TRUNC, 0600);
  if (fd < 0) return nullptr;
  if (::ftruncate(fd, static_cast<off_t>(len)) != 0) {
    int err = errno;
    ::close(fd);
    ::unlink(tmp);
    return fail(err);
  }
  void* mem = ::mmap(nullptr, len, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  int map_err = errno;
  ::close(fd);
  if (mem == MAP_FAILED) {
    ::unlink(tmp);
    return fail(map_err);
  }

  auto* h = static_cast<Header*>(mem);
  h->capacity = capacity;
  h->slot_size = slot_size;
  h->slot_stride = stride;
  h->enqueue_pos.store(0, std::memory_order_relaxed);
  h->dequeue_pos.store(0, std::memory_order_relaxed);

  auto* cells = reinterpret_cast<CellHeader*>(static_cast<uint8_t*>(mem) + sizeof(Header));
  auto* ring = new Ring{h, cells,
                        reinterpret_cast<uint8_t*>(cells + capacity), len};
  for (uint64_t i = 0; i < capacity; ++i) {
    cell_at(ring, i)->seq.store(i, std::memory_order_relaxed);
    cell_at(ring, i)->len = 0;
  }
  h->magic.store(kMagic, std::memory_order_release);
  if (::rename(tmp, path) != 0) {
    int err = errno;
    ::munmap(mem, len);
    ::unlink(tmp);
    delete ring;
    return fail(err);
  }
  return ring;
}

// Attach to an existing ring file. Returns nullptr on mismatch.
void* scr_attach(const char* path) {
  int fd = ::open(path, O_RDWR);
  if (fd < 0) return nullptr;
  struct stat st;
  if (::fstat(fd, &st) != 0 || st.st_size < static_cast<off_t>(sizeof(Header))) {
    ::close(fd);
    return nullptr;
  }
  void* mem = ::mmap(nullptr, static_cast<size_t>(st.st_size), PROT_READ | PROT_WRITE,
                     MAP_SHARED, fd, 0);
  ::close(fd);
  if (mem == MAP_FAILED) return nullptr;
  auto* h = static_cast<Header*>(mem);
  if (h->magic.load(std::memory_order_acquire) != kMagic ||
      static_cast<size_t>(st.st_size) < total_size(h->capacity, h->slot_stride)) {
    ::munmap(mem, static_cast<size_t>(st.st_size));
    return nullptr;
  }
  auto* cells = reinterpret_cast<CellHeader*>(static_cast<uint8_t*>(mem) + sizeof(Header));
  return new Ring{h, cells, reinterpret_cast<uint8_t*>(cells + h->capacity),
                  static_cast<size_t>(st.st_size)};
}

void scr_detach(void* handle) {
  auto* r = static_cast<Ring*>(handle);
  if (!r) return;
  ::munmap(r->header, r->map_len);
  delete r;
}

uint64_t scr_capacity(void* handle) { return static_cast<Ring*>(handle)->header->capacity; }
uint64_t scr_slot_size(void* handle) { return static_cast<Ring*>(handle)->header->slot_size; }

// Approximate occupancy (racy by nature; exact when quiescent).
uint64_t scr_size(void* handle) {
  auto* h = static_cast<Ring*>(handle)->header;
  uint64_t e = h->enqueue_pos.load(std::memory_order_acquire);
  uint64_t d = h->dequeue_pos.load(std::memory_order_acquire);
  return e > d ? e - d : 0;
}

// 0 = ok, -1 = full, -2 = payload too large.
int scr_push(void* handle, const void* data, uint32_t len) {
  auto* r = static_cast<Ring*>(handle);
  Header* h = r->header;
  if (len > h->slot_size) return -2;

  uint64_t pos = h->enqueue_pos.load(std::memory_order_relaxed);
  CellHeader* cell;
  for (;;) {
    cell = cell_at(r, pos);
    uint64_t seq = cell->seq.load(std::memory_order_acquire);
    intptr_t dif = static_cast<intptr_t>(seq) - static_cast<intptr_t>(pos);
    if (dif == 0) {
      if (h->enqueue_pos.compare_exchange_weak(pos, pos + 1, std::memory_order_relaxed))
        break;
    } else if (dif < 0) {
      return -1;  // full
    } else {
      pos = h->enqueue_pos.load(std::memory_order_relaxed);
    }
  }
  cell->len = len;
  std::memcpy(cell_data(r, pos), data, len);
  cell->seq.store(pos + 1, std::memory_order_release);
  return 0;
}

// Returns payload length (>=0) or -1 = empty, -3 = out buffer too small
// (item left in place).
int scr_pop(void* handle, void* out, uint32_t out_cap) {
  auto* r = static_cast<Ring*>(handle);
  Header* h = r->header;

  uint64_t pos = h->dequeue_pos.load(std::memory_order_relaxed);
  CellHeader* cell;
  for (;;) {
    cell = cell_at(r, pos);
    uint64_t seq = cell->seq.load(std::memory_order_acquire);
    intptr_t dif =
        static_cast<intptr_t>(seq) - static_cast<intptr_t>(pos + 1);
    if (dif == 0) {
      if (cell->len > out_cap) return -3;
      if (h->dequeue_pos.compare_exchange_weak(pos, pos + 1, std::memory_order_relaxed))
        break;
    } else if (dif < 0) {
      return -1;  // empty
    } else {
      pos = h->dequeue_pos.load(std::memory_order_relaxed);
    }
  }
  uint32_t len = cell->len;
  std::memcpy(out, cell_data(r, pos), len);
  cell->seq.store(pos + h->capacity, std::memory_order_release);
  return static_cast<int>(len);
}

// Batched drain: pops up to max_items payloads into out, packed as
// [u32 len][payload]... back to back. Returns the number of frames popped
// (0 when empty), or -3 when the ring is non-empty but the FIRST pending
// frame exceeds out_cap (matching scr_pop) — without the distinct code an
// undersized caller would spin forever on "0 popped" with no way to tell
// it from empty. *bytes_used receives the total packed size. Stops early
// when the next payload would not fit in out_cap (item left in place).
// One FFI round-trip replaces max_items ctypes calls on the Python side —
// at ~1.5us per ctypes crossing that is most of the per-frame drain cost
// at 20k+ rps.
int scr_pop_many(void* handle, void* out, uint32_t out_cap, uint32_t max_items,
                 uint32_t* bytes_used) {
  auto* r = static_cast<Ring*>(handle);
  Header* h = r->header;
  uint8_t* dst = static_cast<uint8_t*>(out);
  uint32_t off = 0;
  uint32_t count = 0;
  bool first_too_big = false;
  while (count < max_items) {
    uint64_t pos = h->dequeue_pos.load(std::memory_order_relaxed);
    CellHeader* cell;
    bool got = false;
    for (;;) {
      cell = cell_at(r, pos);
      uint64_t seq = cell->seq.load(std::memory_order_acquire);
      intptr_t dif = static_cast<intptr_t>(seq) - static_cast<intptr_t>(pos + 1);
      if (dif == 0) {
        if (off + 4 + cell->len > out_cap) {  // no room: leave in place
          if (count == 0) first_too_big = true;
          break;
        }
        if (h->dequeue_pos.compare_exchange_weak(pos, pos + 1,
                                                 std::memory_order_relaxed)) {
          got = true;
          break;
        }
      } else if (dif < 0) {
        break;  // empty
      } else {
        pos = h->dequeue_pos.load(std::memory_order_relaxed);
      }
    }
    if (!got) break;
    uint32_t len = cell->len;
    std::memcpy(dst + off, &len, 4);
    std::memcpy(dst + off + 4, cell_data(r, pos), len);
    cell->seq.store(pos + r->header->capacity, std::memory_order_release);
    off += 4 + len;
    ++count;
  }
  if (bytes_used) *bytes_used = off;
  if (count == 0 && first_too_big) return -3;
  return static_cast<int>(count);
}

// Model-executor response fast path: builds and pushes n kind-2 OK
// responses straight into ring slots — zero intermediate buffers, one FFI
// crossing for a whole micro-batch chunk. Frame layout must mirror
// ModelExecutor._ok_response (transport/ipc.py):
//   [u32 req_id][u8 status=0][u8 dtype_code][u8 ndim]
//   [u32 dims x ndim][u32 frag_len][frag][rows * row_nvals f8]
// data holds stacked result rows; response i takes row_counts[i] rows
// starting at row_offsets[i]; dims = (row_counts[i], tail_dims...). All
// responses share the fragment (static-fragment chunks only; dynamic-tag
// components never take this path).
// Returns count actually pushed (< n when the ring filled; caller retries
// the tail) or -2 when a response exceeds slot_size.
int scr_push_model_resps(void* handle, const uint32_t* req_ids,
                         const uint64_t* row_offsets, const uint32_t* row_counts,
                         uint32_t n, const double* data, uint64_t row_nvals,
                         const uint32_t* tail_dims, uint32_t n_tail,
                         const char* frag, uint32_t frag_len, uint32_t dtype_code) {
  auto* r = static_cast<Ring*>(handle);
  Header* h = r->header;
  for (uint32_t i = 0; i < n; ++i) {
    uint32_t ndim = 1 + n_tail;
    uint64_t payload = static_cast<uint64_t>(row_counts[i]) * row_nvals * 8;
    uint64_t total = 4 + 1 + 1 + 1 + 4ull * ndim + 4 + frag_len + payload;
    if (total > h->slot_size) return -2;

    uint64_t pos = h->enqueue_pos.load(std::memory_order_relaxed);
    CellHeader* cell;
    for (;;) {
      cell = cell_at(r, pos);
      uint64_t seq = cell->seq.load(std::memory_order_acquire);
      intptr_t dif = static_cast<intptr_t>(seq) - static_cast<intptr_t>(pos);
      if (dif == 0) {
        if (h->enqueue_pos.compare_exchange_weak(pos, pos + 1,
                                                 std::memory_order_relaxed))
          break;
      } else if (dif < 0) {
        return static_cast<int>(i);  // full: caller retries the tail
      } else {
        pos = h->enqueue_pos.load(std::memory_order_relaxed);
      }
    }
    uint8_t* dst = cell_data(r, pos);
    std::memcpy(dst, &req_ids[i], 4);
    dst[4] = 0;  // status ok
    dst[5] = static_cast<uint8_t>(dtype_code);  // MATH dtype (0=f32, 1=f64):
    // payload bytes are always f8, but combiner averaging parity tracks the
    // model's original output dtype (edge.cc resolve_dval promotion)
    dst[6] = static_cast<uint8_t>(ndim);
    uint32_t off = 7;
    std::memcpy(dst + off, &row_counts[i], 4);
    off += 4;
    for (uint32_t d = 0; d < n_tail; ++d) {
      std::memcpy(dst + off, &tail_dims[d], 4);
      off += 4;
    }
    std::memcpy(dst + off, &frag_len, 4);
    off += 4;
    if (frag_len) std::memcpy(dst + off, frag, frag_len);
    off += frag_len;
    std::memcpy(dst + off, data + row_offsets[i] * row_nvals, payload);
    cell->len = static_cast<uint32_t>(off + payload);
    cell->seq.store(pos + 1, std::memory_order_release);
  }
  return static_cast<int>(n);
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Test hooks for the seeded-router RNG replays (native/np_rng.h): pytest
// compares these draw-for-draw against numpy / CPython so the native edge's
// seeded routing is PROVEN bit-exact, not assumed.
// ---------------------------------------------------------------------------
#include "np_rng.h"

extern "C" {

void* np_rng_new(uint64_t seed) { return new nprng::NpRng(seed); }
void np_rng_free(void* h) { delete static_cast<nprng::NpRng*>(h); }
double np_rng_random(void* h) { return static_cast<nprng::NpRng*>(h)->random(); }
uint64_t np_rng_next64(void* h) { return static_cast<nprng::NpRng*>(h)->next64(); }
uint64_t np_rng_integers(void* h, uint64_t n) {
  return static_cast<nprng::NpRng*>(h)->integers(n);
}
double np_rng_standard_normal(void* h) {
  return static_cast<nprng::NpRng*>(h)->standard_normal();
}
double np_rng_standard_exponential(void* h) {
  return static_cast<nprng::NpRng*>(h)->standard_exponential();
}
double np_rng_standard_gamma(void* h, double shape) {
  return static_cast<nprng::NpRng*>(h)->standard_gamma(shape);
}
double np_rng_beta(void* h, double a, double b) {
  return static_cast<nprng::NpRng*>(h)->beta(a, b);
}

void* py_rng_new(uint64_t seed) { return new nprng::PyRng(seed); }
void py_rng_free(void* h) { delete static_cast<nprng::PyRng*>(h); }
double py_rng_random(void* h) { return static_cast<nprng::PyRng*>(h)->random(); }
uint64_t py_rng_randrange(void* h, uint64_t n) {
  return static_cast<nprng::PyRng*>(h)->randrange(n);
}

}  // extern "C"

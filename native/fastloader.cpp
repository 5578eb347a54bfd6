// Native host-side data runtime (the TPU-native counterpart of the
// reference's C++ DataLoader worker pool / pinned-memory pipeline:
// paddle/fluid/operators/reader/ + paddle/phi/core/memory host allocator).
//
// Responsibilities:
//   * mmap a token-bin file (uint16/uint32 tokens) with zero copies
//   * a background thread pool cuts shuffled (input, label) windows into a
//     lock-free-ish ring of pre-touched buffers so Python never blocks on
//     page faults or memcpy — the feed thread only hands out pointers
//   * deterministic sampling: batch k draws its windows from an xorshift
//     stream keyed by (seed, k) and batches are handed out in order of k,
//     so the stream never depends on which worker cut a batch, or when
//
// Exposed as a C ABI for ctypes (no pybind11 in this image).
//
// Build: make -C native  (produces libfastloader.so)

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <fcntl.h>
#include <map>
#include <mutex>
#include <queue>
#include <sys/mman.h>
#include <sys/stat.h>
#include <thread>
#include <unistd.h>
#include <vector>

namespace {

struct Batch {
  std::vector<int32_t> tokens;  // [batch, seq+1] window; caller splits x/y
  uint64_t index = 0;           // position in the stream
};

struct Loader {
  // mmap state
  int fd = -1;
  const uint8_t* data = nullptr;
  size_t file_bytes = 0;
  int token_width = 2;  // bytes per token: 2 (uint16) or 4 (uint32)
  size_t n_tokens = 0;

  // batch geometry
  int batch = 0;
  int seq = 0;
  uint64_t seed = 0;

  // prefetch ring
  size_t capacity = 8;
  std::map<uint64_t, Batch*> ready;  // by stream position
  uint64_t next_fill = 0, next_out = 0;
  std::queue<Batch*> free_bufs;
  std::vector<Batch*> all_bufs;
  std::mutex mu;
  std::condition_variable cv_ready, cv_free;
  std::vector<std::thread> workers;
  std::atomic<bool> stop{false};

  static uint64_t next_rand(uint64_t& state) {
    // xorshift64* — deterministic, fast, good enough for window sampling
    uint64_t x = state;
    x ^= x >> 12;
    x ^= x << 25;
    x ^= x >> 27;
    state = x;
    return x * 0x2545F4914F6CDD1DULL;
  }

  static uint64_t mix(uint64_t z) {  // splitmix64 finalizer
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }

  int32_t token_at(size_t i) const {
    if (token_width == 2) {
      uint16_t v;
      std::memcpy(&v, data + i * 2, 2);
      return (int32_t)v;
    }
    uint32_t v;
    std::memcpy(&v, data + i * 4, 4);
    return (int32_t)v;
  }

  void fill(Batch* b) {
    const size_t window = (size_t)seq + 1;
    const size_t max_start = n_tokens - window;
    b->tokens.resize((size_t)batch * window);
    uint64_t rng = mix(seed + (b->index + 1) * 0x9E3779B97F4A7C15ULL);
    if (!rng) rng = 0x9E3779B97F4A7C15ULL;  // xorshift must not start at 0
    for (int r = 0; r < batch; ++r) {
      size_t start = (size_t)(next_rand(rng) % (max_start + 1));
      for (size_t t = 0; t < window; ++t)
        b->tokens[(size_t)r * window + t] = token_at(start + t);
    }
  }

  void worker_loop() {
    while (!stop.load()) {
      Batch* buf = nullptr;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_free.wait(lk, [&] { return stop.load() || !free_bufs.empty(); });
        if (stop.load()) return;
        buf = free_bufs.front();
        free_bufs.pop();
        buf->index = next_fill++;
      }
      fill(buf);
      {
        std::lock_guard<std::mutex> lk(mu);
        ready.emplace(buf->index, buf);
      }
      cv_ready.notify_one();
    }
  }
};

}  // namespace

extern "C" {

void* fl_open(const char* path, int token_width, int batch, int seq,
              uint64_t seed, int n_workers, int prefetch) {
  auto* L = new Loader();
  L->fd = ::open(path, O_RDONLY);
  if (L->fd < 0) { delete L; return nullptr; }
  struct stat st;
  if (fstat(L->fd, &st) != 0) { ::close(L->fd); delete L; return nullptr; }
  L->file_bytes = (size_t)st.st_size;
  L->token_width = token_width;
  L->n_tokens = L->file_bytes / (size_t)token_width;
  void* m = mmap(nullptr, L->file_bytes, PROT_READ, MAP_PRIVATE, L->fd, 0);
  if (m == MAP_FAILED) { ::close(L->fd); delete L; return nullptr; }
  madvise(m, L->file_bytes, MADV_RANDOM);
  L->data = (const uint8_t*)m;
  L->batch = batch;
  L->seq = seq;
  L->seed = seed;
  L->capacity = (size_t)(prefetch > 0 ? prefetch : 8);
  if ((size_t)seq + 1 > L->n_tokens) { munmap(m, L->file_bytes); ::close(L->fd); delete L; return nullptr; }
  for (size_t i = 0; i < L->capacity; ++i) {
    auto* b = new Batch();
    L->all_bufs.push_back(b);
    L->free_bufs.push(b);
  }
  int nw = n_workers > 0 ? n_workers : 2;
  for (int i = 0; i < nw; ++i)
    L->workers.emplace_back([L] { L->worker_loop(); });
  return L;
}

uint64_t fl_num_tokens(void* h) { return ((Loader*)h)->n_tokens; }

// Blocks until a batch is ready; copies into out [batch*(seq+1)] int32.
int fl_next(void* h, int32_t* out) {
  auto* L = (Loader*)h;
  Batch* b = nullptr;
  {
    std::unique_lock<std::mutex> lk(L->mu);
    // the lowest outstanding position is always with a worker or ready
    L->cv_ready.wait(lk, [&] {
      return L->stop.load() || L->ready.count(L->next_out); });
    if (L->stop.load()) return -1;
    b = L->ready[L->next_out];
    L->ready.erase(L->next_out++);
  }
  std::memcpy(out, b->tokens.data(), b->tokens.size() * sizeof(int32_t));
  {
    std::lock_guard<std::mutex> lk(L->mu);
    L->free_bufs.push(b);
  }
  L->cv_free.notify_one();
  return 0;
}

void fl_close(void* h) {
  auto* L = (Loader*)h;
  L->stop.store(true);
  L->cv_free.notify_all();
  L->cv_ready.notify_all();
  for (auto& t : L->workers) t.join();
  for (auto* b : L->all_bufs) delete b;
  if (L->data) munmap((void*)L->data, L->file_bytes);
  if (L->fd >= 0) ::close(L->fd);
  delete L;
}

}  // extern "C"

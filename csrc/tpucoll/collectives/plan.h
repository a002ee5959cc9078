// Persistent collective plans: per-Context LRU cache of the per-call
// setup a repeated collective otherwise rebuilds every step.
//
// Training traffic is the degenerate-best case for caching — the same
// (op, algorithm, ptr, nbytes, dtype, root/tag) tuple every step from a
// gradient bucketer — yet each call used to re-create UnboundBuffers
// (two transport-mutex passes apiece: registration bookkeeping at birth,
// cancel+drain scans at death), re-acquire scratch, and recompute the
// block/segment schedule. A Plan owns all of that across calls:
//
//   - registered UnboundBuffers over the caller's pointers (userBuf);
//   - grow-only scratch arenas with their registrations (stage);
//   - the memoized block layout and segment lists (blocks / segments).
//
// The steady-state Nth call of a repeated collective therefore performs
// zero allocations and zero buffer registrations — only posts and waits.
// `ubuf_creates` in the metrics registry is the enforced evidence;
// `plan_hits`/`plan_misses`/`plan_evictions` expose the cache itself.
//
// Pointer-lifetime contract (docs/design.md, docs/errors.md): a cached
// plan retains a registration over the caller's buffer BETWEEN calls.
// The memory is only dereferenced while a collective is running on the
// same (ptr, nbytes); freeing the buffer afterwards is safe — the stale
// registration is dropped on eviction, invalidation, or context close,
// and a recycled address is re-keyed by (ptr, nbytes) so a different
// size misses. What is NOT safe is re-issuing the collective after the
// buffer was freed — exactly as unsafe as it always was.
//
// Invalidation:
//   - Context::close() / destruction drop every plan (before the
//     transport dies — the registrations point into it);
//   - Context::setTuningTable() drops every plan: a kAuto key embeds the
//     RESOLVED algorithm, and a new table may elect a different one;
//   - an exception unwinding through a planned collective drops that
//     plan (its buffers may still carry in-flight ops; the destructor
//     drains them exactly like a transient buffer's would);
//   - a changed ptr/size/tag simply misses and ages the old entry out
//     of the LRU (capacity: TPUCOLL_PLAN_LRU, default 64).
//
// Concurrency: plans are per-(Context, key). Concurrent collectives on
// one context must use distinct tags (the library-wide contract), and
// tag is part of the key, so two legal concurrent calls never share a
// plan; a same-key race (illegal anyway) falls back to a transient plan
// via the per-plan in-use flag rather than corrupting state.
//
// TPUCOLL_PLAN_CACHE=0 disables caching entirely: every call gets a
// transient Plan whose stages ride the Context scratch pool — byte-for-
// byte the pre-plan behavior.
#pragma once

#include <atomic>
#include <cstdint>
#include <deque>
#include <list>
#include <memory>
#include <mutex>
#include <optional>
#include <unordered_map>
#include <utility>
#include <vector>

#include "tpucoll/collectives/detail.h"
#include "tpucoll/common/arena.h"
#include "tpucoll/context.h"
#include "tpucoll/transport/unbound_buffer.h"

namespace tpucoll {
namespace plan {

// Opcode namespace for plan keys (decoupled from MetricOp: plans key
// the SCHEDULE actually run, e.g. allgather and allgatherv share one).
enum class PlanOp : uint8_t {
  kAllreduce = 0,
  kReduce,
  kReduceScatter,
  kAllgatherv,
  kBroadcast,
  kBarrier,
  kGatherv,
  kScatter,
  kAlltoallv,
  kAlltoallBruck,
};

struct PlanKey {
  uint8_t opcode{0};
  uint8_t algorithm{0};  // RESOLVED algorithm (post-kAuto), 0 when n/a
  uint8_t dtype{0};
  uint8_t op{0};         // ReduceOp, 0 when n/a
  int32_t root{-1};
  uint32_t tag{0};
  uintptr_t ptrA{0};     // primary caller buffer (work / input)
  uintptr_t ptrB{0};     // secondary caller buffer (output), 0 when n/a
  uint64_t nbytes{0};    // total payload bytes
  uint64_t aux{0};       // counts-vector hash for the v-variants

  bool operator==(const PlanKey& o) const {
    return opcode == o.opcode && algorithm == o.algorithm &&
           dtype == o.dtype && op == o.op && root == o.root &&
           tag == o.tag && ptrA == o.ptrA && ptrB == o.ptrB &&
           nbytes == o.nbytes && aux == o.aux;
  }
};

// FNV-1a over a size_t vector: the aux hash for per-rank count vectors
// (allgatherv/gatherv/reduce_scatter/alltoallv schedules depend on every
// entry, not just the total).
inline uint64_t hashCounts(const std::vector<size_t>& counts) {
  uint64_t h = 1469598103934665603ull;
  for (size_t c : counts) {
    h = (h ^ static_cast<uint64_t>(c)) * 1099511628211ull;
  }
  return h;
}

struct PlanKeyHash {
  size_t operator()(const PlanKey& k) const {
    uint64_t h = 1469598103934665603ull;
    auto mix = [&h](uint64_t v) { h = (h ^ v) * 1099511628211ull; };
    mix(k.opcode | (uint64_t(k.algorithm) << 8) |
        (uint64_t(k.dtype) << 16) | (uint64_t(k.op) << 24) |
        (uint64_t(uint32_t(k.root)) << 32));
    mix(k.tag);
    mix(k.ptrA);
    mix(k.ptrB);
    mix(k.nbytes);
    mix(k.aux);
    return static_cast<size_t>(h);
  }
};

// One collective's reusable resources. Cached instances live in the
// PlanCache and survive across calls; transient instances (cache
// disabled / non-cacheable call / same-key race) live for one call and
// stage through the Context scratch pool, reproducing the pre-plan
// behavior exactly.
class Plan {
 public:
  Plan(Context* ctx, bool cached) : ctx_(ctx), cached_(cached) {}

  Context* context() const { return ctx_; }
  bool isCached() const { return cached_; }

  // Registered buffer over caller memory, slot `idx` (schedules number
  // their buffers 0..: work first). A cached plan returns the previous
  // call's registration when (ptr, nbytes) match — the zero-
  // registration steady state; a mismatch (impossible through the
  // cache, whose key pins the pointers) rebuilds.
  transport::UnboundBuffer* userBuf(size_t idx, void* ptr, size_t nbytes);

  struct Stage {
    char* data{nullptr};
    transport::UnboundBuffer* buf{nullptr};
  };
  // Arena-backed staging memory with its registration, slot `idx`.
  // Cached plans grow their arena to the high watermark once and then
  // return the same block + registration every call; transient plans
  // ride the Context scratch pool.
  Stage stage(size_t idx, size_t minBytes);

  // Staging memory only, no registration (local shuffle buffers, e.g.
  // Bruck's rotation scratch). Shares the stage slot namespace: a given
  // idx is either scratch or stage for a plan's whole life.
  char* scratch(size_t idx, size_t minBytes);

  // scratch() that also reports whether the memory is FRESH — newly
  // allocated or moved, i.e. its prior contents are gone. State that
  // must persist across calls on a cached plan (the wire rings'
  // error-feedback residuals) zero-fills exactly when *fresh is set;
  // transient plans report fresh on every call (pool pages rotate).
  char* scratch(size_t idx, size_t minBytes, bool* fresh);

  // Memoized block layout, slot `idx`: computed by `make()` on the
  // first call, returned by reference afterwards. The returned
  // reference stays valid across later blocks()/segments() calls
  // (deque storage — end-insertion never moves existing slots), so a
  // schedule may hold several layouts at once.
  template <typename Fn>
  const collectives_detail::Blocks& blocks(size_t idx, Fn&& make) {
    while (blocks_.size() <= idx) {
      blocks_.emplace_back();
    }
    auto& slot = blocks_[idx];
    if (!slot.have) {
      slot.value = make();
      slot.have = true;
    }
    return slot.value;
  }

  // Memoized segment list for one block size (collectives_detail::
  // segmentize). A ring schedule asks for at most two distinct block
  // sizes (evenBlocks remainders differ by one element), so a linear
  // scan over a tiny vector beats any map.
  const std::vector<collectives_detail::SegSpan>& segments(size_t blockBytes,
                                                           size_t elsize);

 private:
  friend class PlanCache;
  friend class PlanHandle;

  struct UserSlot {
    uintptr_t ptr{0};
    size_t nbytes{0};
    std::unique_ptr<transport::UnboundBuffer> buf;
  };
  struct StageSlot {
    Arena arena;  // cached plans
    std::optional<Context::Scratch> pooled;  // transient plans
    std::unique_ptr<transport::UnboundBuffer> buf;
  };
  struct BlocksSlot {
    bool have{false};
    collectives_detail::Blocks value;
  };

  Context* const ctx_;
  const bool cached_;
  PlanKey key_{};  // set by the cache; identifies the entry for release
  // One plan serves one collective call at a time; a same-key concurrent
  // acquire (an API-contract violation) degrades to a transient plan
  // instead of sharing live buffers. CAS acquire/release in PlanCache.
  std::atomic<bool> inUse_{false};
  // users_/stages_ hand out raw pointers to heap objects (UnboundBuffer,
  // arena block) that survive container growth; blocks_/segs_ hand out
  // REFERENCES to the elements themselves, so they live in deques,
  // whose end-insertion never relocates existing elements.
  std::vector<UserSlot> users_;
  std::vector<StageSlot> stages_;
  std::deque<BlocksSlot> blocks_;
  std::deque<std::pair<uint64_t, std::vector<collectives_detail::SegSpan>>>
      segs_;
};

// LRU cache of Plans, one per Context (and so one per async-engine lane:
// lanes fork private sub-Contexts). All methods are thread-safe.
class PlanCache {
 public:
  explicit PlanCache(Context* ctx);

  // Lookup-or-create the entry for `key`, marking it in use. Returns
  // nullptr when caching is disabled or the entry is busy (caller runs
  // a transient plan). Counts plan_hits / plan_misses / plan_evictions
  // in the context's metrics registry.
  std::shared_ptr<Plan> acquire(const PlanKey& key);

  // Return a plan acquired above. poisoned=true (an exception unwound
  // through the collective) drops the entry: its buffers may carry
  // in-flight ops that only the destructor's cancel+drain can account
  // for, so it must never serve another call.
  void release(const std::shared_ptr<Plan>& plan, bool poisoned);

  // Drop every entry (close / rebuild / tuning-table install). In-use
  // plans survive via their callers' shared_ptr and die on release.
  void clear();

  size_t size() const;
  bool enabled() const { return enabled_; }

 private:
  struct Entry {
    PlanKey key;
    std::shared_ptr<Plan> plan;
  };
  using Lru = std::list<Entry>;

  Context* const ctx_;
  const bool enabled_;
  const size_t capacity_;
  mutable std::mutex mu_;
  Lru lru_;  // front = most recently used
  std::unordered_map<PlanKey, Lru::iterator, PlanKeyHash> map_;
};

// RAII scope for one collective call: acquires the cached plan (or a
// transient one), releases it at scope exit, and poisons the cache
// entry when unwinding through an exception.
class PlanHandle {
 public:
  // Transient-only handle (non-cacheable call: custom reduction fn).
  explicit PlanHandle(Context* ctx)
      : plan_(std::make_shared<Plan>(ctx, /*cached=*/false)) {}

  PlanHandle(Context* ctx, const PlanKey& key);
  ~PlanHandle();

  PlanHandle(const PlanHandle&) = delete;
  PlanHandle& operator=(const PlanHandle&) = delete;

  Plan& operator*() const { return *plan_; }
  Plan* operator->() const { return plan_.get(); }
  Plan* get() const { return plan_.get(); }

 private:
  std::shared_ptr<Plan> plan_;
  PlanCache* cache_{nullptr};  // non-null when plan_ came from the cache
  int exceptionsAtEntry_{0};
};

// Lazy staging view (the LazyScratch successor): materializes the
// plan's stage slot on first touch, so fully fused schedules never
// allocate (transient) or warm (cached) staging they won't use.
class LazyStage {
 public:
  LazyStage(Plan& plan, size_t idx, size_t minBytes)
      : plan_(plan), idx_(idx), minBytes_(minBytes) {}
  char* data() {
    ensure();
    return stage_.data;
  }
  transport::UnboundBuffer* buf() {
    ensure();
    return stage_.buf;
  }

 private:
  void ensure() {
    if (stage_.buf == nullptr) {
      stage_ = plan_.stage(idx_, minBytes_);
    }
  }
  Plan& plan_;
  const size_t idx_;
  const size_t minBytes_;
  Plan::Stage stage_{};
};

}  // namespace plan
}  // namespace tpucoll

#include "tpucoll/collectives/plan.h"

#include <exception>

#include "tpucoll/common/env.h"
#include "tpucoll/common/metrics.h"

namespace tpucoll {
namespace plan {

transport::UnboundBuffer* Plan::userBuf(size_t idx, void* ptr,
                                        size_t nbytes) {
  if (users_.size() <= idx) {
    users_.resize(idx + 1);
  }
  UserSlot& slot = users_[idx];
  const uintptr_t p = reinterpret_cast<uintptr_t>(ptr);
  if (slot.buf != nullptr && slot.ptr == p && slot.nbytes == nbytes) {
    return slot.buf.get();
  }
  // Drop the stale registration BEFORE creating the replacement so its
  // cancel+drain pass can never see the new buffer's pending ops.
  slot.buf.reset();
  slot.buf = ctx_->createUnboundBuffer(ptr, nbytes);
  slot.ptr = p;
  slot.nbytes = nbytes;
  return slot.buf.get();
}

char* Plan::scratch(size_t idx, size_t minBytes) {
  return scratch(idx, minBytes, nullptr);
}

char* Plan::scratch(size_t idx, size_t minBytes, bool* fresh) {
  if (stages_.size() <= idx) {
    stages_.resize(idx + 1);
  }
  StageSlot& slot = stages_[idx];
  if (cached_) {
    char* data = slot.arena.require(minBytes);
    if (slot.arena.grewOnLastRequire()) {
      slot.buf.reset();  // any registration points at the old block
    }
    if (fresh != nullptr) {
      *fresh = slot.arena.grewOnLastRequire();
    }
    return data;
  }
  // Transient: the Context scratch pool (warm pages across calls, the
  // pre-plan behavior), one acquisition per call per slot.
  if (!slot.pooled.has_value() || slot.pooled->size() < minBytes) {
    slot.buf.reset();
    slot.pooled.emplace(ctx_->acquireScratch(minBytes));
  }
  if (fresh != nullptr) {
    *fresh = true;  // pool pages rotate between calls: never trust them
  }
  return slot.pooled->data();
}

Plan::Stage Plan::stage(size_t idx, size_t minBytes) {
  char* data = scratch(idx, minBytes);
  StageSlot& slot = stages_[idx];
  if (slot.buf == nullptr) {
    slot.buf = ctx_->createUnboundBuffer(
        data, cached_ ? slot.arena.capacity() : slot.pooled->size());
  }
  return Stage{data, slot.buf.get()};
}

const std::vector<collectives_detail::SegSpan>& Plan::segments(
    size_t blockBytes, size_t elsize) {
  // elsize is constant for a plan (it is derived from the key's dtype),
  // so blockBytes alone keys the memo.
  for (const auto& entry : segs_) {
    if (entry.first == blockBytes) {
      return entry.second;
    }
  }
  segs_.emplace_back(blockBytes,
                     collectives_detail::segmentize(blockBytes, elsize));
  return segs_.back().second;
}

PlanCache::PlanCache(Context* ctx)
    : ctx_(ctx),
      // Read per-cache (not function-static): the tests toggle the
      // knobs between Context constructions.
      enabled_(envFlag("TPUCOLL_PLAN_CACHE", true)),
      capacity_(static_cast<size_t>(
          envCount("TPUCOLL_PLAN_LRU", 64, 1, 1 << 20))) {}

std::shared_ptr<Plan> PlanCache::acquire(const PlanKey& key) {
  if (!enabled_) {
    return nullptr;
  }
  Metrics& metrics = ctx_->metrics();
  // Evicted entries destroy OUTSIDE mu_ (after this scope): ~Plan runs
  // ~UnboundBuffer, which takes transport mutexes and can block on a
  // drain — a concurrent acquire on another thread must not wait on
  // that. Same discipline as clear().
  Lru dropped;
  std::shared_ptr<Plan> plan;
  {
    std::lock_guard<std::mutex> guard(mu_);
    auto it = map_.find(key);
    if (it != map_.end()) {
      plan = it->second->plan;
      bool expected = false;
      if (!plan->inUse_.compare_exchange_strong(
              // Acquire on success: the previous release's writes to
              // the plan's slots must be visible to this call.
              expected, true, std::memory_order_acquire,
              std::memory_order_relaxed)) {
        // Same-key concurrency (an API-contract violation upstream):
        // degrade to a transient plan rather than sharing live buffers.
        return nullptr;
      }
      lru_.splice(lru_.begin(), lru_, it->second);
      metrics.recordPlanHit();
      return plan;
    }
    plan = std::make_shared<Plan>(ctx_, /*cached=*/true);
    plan->key_ = key;
    // Relaxed: the plan is not yet visible to any other thread.
    plan->inUse_.store(true, std::memory_order_relaxed);
    lru_.push_front(Entry{key, plan});
    map_[key] = lru_.begin();
    metrics.recordPlanMiss();
    // Evict past capacity, oldest first, skipping in-use entries
    // (their callers hold live buffers; they die on release instead).
    uint64_t evicted = 0;
    auto tail = lru_.end();
    while (map_.size() > capacity_ && tail != lru_.begin()) {
      --tail;
      // Relaxed: a stale "in use" read just defers this eviction.
      if (tail->plan->inUse_.load(std::memory_order_relaxed)) {
        continue;
      }
      map_.erase(tail->key);
      dropped.splice(dropped.begin(), lru_, tail);
      tail = lru_.end();
      // Restart the walk: splice invalidated the erased position's
      // neighborhood bookkeeping; the list is tiny (capacity_+1).
      evicted++;
    }
    if (evicted > 0) {
      metrics.recordPlanEvictions(evicted);
    }
  }
  return plan;
}

void PlanCache::release(const std::shared_ptr<Plan>& plan, bool poisoned) {
  if (plan == nullptr) {
    return;
  }
  if (poisoned) {
    std::lock_guard<std::mutex> guard(mu_);
    auto it = map_.find(plan->key_);
    // Guard against the entry having been cleared/evicted and the key
    // reused by a FRESH plan while this call was in flight.
    if (it != map_.end() && it->second->plan == plan) {
      lru_.erase(it->second);
      map_.erase(it);
    }
  }
  // Release: publish this call's slot writes to the next acquirer.
  plan->inUse_.store(false, std::memory_order_release);
  // If the entry was dropped (poison, clear, eviction) the caller's
  // shared_ptr is the last ref; the Plan's buffers drain in ~Plan.
}

void PlanCache::clear() {
  Lru dropped;
  {
    std::lock_guard<std::mutex> guard(mu_);
    map_.clear();
    dropped.swap(lru_);
  }
  // Entries destroy OUTSIDE the lock: ~UnboundBuffer takes transport
  // mutexes and can block draining in-flight ops.
}

size_t PlanCache::size() const {
  std::lock_guard<std::mutex> guard(mu_);
  return map_.size();
}

PlanHandle::PlanHandle(Context* ctx, const PlanKey& key) {
  PlanCache& cache = ctx->planCache();
  plan_ = cache.acquire(key);
  if (plan_ != nullptr) {
    cache_ = &cache;
    exceptionsAtEntry_ = std::uncaught_exceptions();
  } else {
    plan_ = std::make_shared<Plan>(ctx, /*cached=*/false);
  }
}

PlanHandle::~PlanHandle() {
  if (cache_ != nullptr) {
    // Baseline comparison (not a plain >0 check): a collective issued
    // from a destructor during unwinding must not poison its plan.
    const bool poisoned =
        std::uncaught_exceptions() > exceptionsAtEntry_;
    cache_->release(plan_, poisoned);
  }
}

}  // namespace plan
}  // namespace tpucoll

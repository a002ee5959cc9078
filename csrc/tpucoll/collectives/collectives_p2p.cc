// Schedules built from direct point-to-point exchanges: barrier, broadcast,
// gather(v), scatter, alltoall(v).
#include <cstdlib>
#include <cstring>

#include "tpucoll/collectives/collectives.h"
#include "tpucoll/collectives/detail.h"
#include "tpucoll/collectives/plan.h"
#include "tpucoll/common/profile.h"
#include "tpucoll/group/hier.h"

namespace tpucoll {

using profile::Phase;
using profile::PhaseScope;
using profile::ProfileOpScope;

namespace {

using plan::PlanHandle;
using plan::PlanKey;
using plan::PlanOp;
using transport::UnboundBuffer;

char* bytePtr(void* p) { return static_cast<char*>(p); }
const char* bytePtr(const void* p) { return static_cast<const char*>(p); }

}  // namespace

// Dissemination barrier (Hensgen–Finkel–Manber style, as in reference
// gloo/barrier.cc:23-35): ceil(log2 P) rounds; in round i, signal rank+2^i
// and await rank-2^i. Zero-byte messages carry the signal.
void barrier(BarrierOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "barrier: null context");
  auto traceSpan = ctx->tracer().span("barrier");
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kBarrier, 0);
  FlightRecOp frOp(&ctx->flightrec(), "barrier", nullptr,
                   Slot::build(SlotPrefix::kBarrier, opts.tag).value(), -1,
                   0, FlightRecorder::kNoDtype);
  ProfileOpScope profOp(&ctx->profiler(), "barrier", frOp.cseq(), 0);
  span::OpScope spanOp(&ctx->spans(), "barrier", frOp.cseq());
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  if (size == 1) {
    return;
  }
  if (opts.algorithm == HierDispatch::kHier && group::hierEligible(ctx)) {
    frOp.setAlgorithm("hier");
    profOp.setAlgorithm("hier");
    group::hierBarrier(ctx, opts.tag, timeout);
    return;
  }
  Slot slot = Slot::build(SlotPrefix::kBarrier, opts.tag);
  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kBarrier);
  key.tag = opts.tag;
  PlanHandle planh(ctx, key);
  auto* buf = planh->userBuf(0, nullptr, 0);
  const uint64_t rounds = log2ceil(static_cast<uint64_t>(size));
  for (uint64_t i = 0; i < rounds; i++) {
    const int dist = 1 << i;
    const int to = (rank + dist) % size;
    const int from = (rank - dist + size) % size;
    {
      PhaseScope ps(Phase::kPost);
      buf->send(to, slot.offset(i).value(), 0, 0);
      buf->recv(from, slot.offset(i).value(), 0, 0);
    }
    PhaseScope ps(Phase::kWireWait);
    buf->waitSend(timeout);
    buf->waitRecv(nullptr, timeout);
  }
}

// Binomial tree broadcast over virtual ranks (vrank 0 = root), matching the
// reference's mask-walk participation scheme (gloo/broadcast.cc:44-84) —
// with segment pipelining: large payloads are split into 1 MiB segments
// that relay toward the leaves as they arrive, so the tree's depth costs
// one segment of latency instead of one full payload per level.
void broadcast(BroadcastOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "broadcast: null context");
  auto traceSpan = ctx->tracer().span("broadcast", opts.count * elementSize(opts.dtype), opts.root);
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kBroadcast,
                      opts.count * elementSize(opts.dtype));
  FlightRecOp frOp(&ctx->flightrec(), "broadcast", nullptr,
                   Slot::build(SlotPrefix::kBroadcast, opts.tag).value(),
                   opts.root, opts.count * elementSize(opts.dtype),
                   static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "broadcast", frOp.cseq(),
                        opts.count * elementSize(opts.dtype));
  span::OpScope spanOp(&ctx->spans(), "broadcast", frOp.cseq());
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE(opts.root >= 0 && opts.root < size, "broadcast: bad root");
  const size_t elsize = elementSize(opts.dtype);
  const size_t nbytes = opts.count * elsize;
  if (size == 1) {
    return;
  }
  if (opts.algorithm == HierDispatch::kHier && group::hierEligible(ctx)) {
    frOp.setAlgorithm("hier");
    profOp.setAlgorithm("hier");
    group::hierBroadcast(ctx, opts.buffer, opts.count, opts.dtype,
                         opts.root, opts.tag, timeout);
    return;
  }
  Slot slot = Slot::build(SlotPrefix::kBroadcast, opts.tag);
  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kBroadcast);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.root = opts.root;
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.buffer);
  key.nbytes = nbytes;
  PlanHandle planh(ctx, key);
  auto* buf = planh->userBuf(0, opts.buffer, nbytes);
  const int vrank = (rank - opts.root + size) % size;
  auto physical = [&](int v) { return (v + opts.root) % size; };

  // 4 MiB default: measured knee on loopback (finer segments pay more in
  // per-message overhead than the relay pipelining saves; deep trees on
  // real networks may prefer smaller via TPUCOLL_BCAST_SEG — strict
  // digits-only parse, floored at 4 KiB).
  static const size_t kBroadcastSegment = std::max<size_t>(
      collectives_detail::envBytes("TPUCOLL_BCAST_SEG", 4 << 20), 4096);
  const size_t segBytes =
      std::max(kBroadcastSegment / elsize * elsize, elsize);
  const size_t numSegs = nbytes == 0 ? 1 : (nbytes + segBytes - 1) / segBytes;
  auto segSpan = [&](size_t k) {
    const size_t off = k * segBytes;
    return std::make_pair(off, std::min(segBytes, nbytes - off));
  };

  // Parent (if any) and children at this node.
  int parent = -1;
  int mask = 1;
  while (mask < size) {
    if (vrank & mask) {
      parent = physical(vrank - mask);
      break;
    }
    mask <<= 1;
  }
  std::vector<int> children;
  for (int m = mask >> 1; m > 0; m >>= 1) {
    if (vrank + m < size) {
      children.push_back(physical(vrank + m));
    }
  }

  int pendingSends = 0;
  if (parent >= 0) {
    {
      PhaseScope ps(Phase::kPost);
      for (size_t k = 0; k < numSegs; k++) {
        auto [off, len] = segSpan(k);
        buf->recv(parent, slot.offset(k).value(), off, len);
      }
    }
    for (size_t k = 0; k < numSegs; k++) {
      auto [off, len] = segSpan(k);
      {
        PhaseScope ps(Phase::kWireWait);
        buf->waitRecv(nullptr, timeout);
      }
      // Relay this segment onward the moment it lands (wire order makes
      // completion k the k-th segment).
      PhaseScope ps(Phase::kPost);
      for (int child : children) {
        buf->send(child, slot.offset(k).value(), off, len);
        pendingSends++;
      }
    }
  } else {
    PhaseScope ps(Phase::kPost);
    for (size_t k = 0; k < numSegs; k++) {
      auto [off, len] = segSpan(k);
      for (int child : children) {
        buf->send(child, slot.offset(k).value(), off, len);
        pendingSends++;
      }
    }
  }
  PhaseScope ps(Phase::kWireWait);
  while (pendingSends-- > 0) {
    buf->waitSend(timeout);
  }
}

// Shared schedule behind gather/gatherv; the public entries carry the
// instrumentation, so each op is attributed under ITS OWN name (a
// dashboard watching op="gather" must not read zero forever).
static void gathervRun(GathervOptions& opts);

void gather(GatherOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "gather: null context");
  auto traceSpan = ctx->tracer().span(
      "gather", opts.count * elementSize(opts.dtype), opts.root);
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kGather,
                      opts.count * elementSize(opts.dtype));
  FlightRecOp frOp(&ctx->flightrec(), "gather", nullptr,
                   Slot::build(SlotPrefix::kGather, opts.tag).value(),
                   opts.root, opts.count * elementSize(opts.dtype),
                   static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "gather", frOp.cseq(),
                        opts.count * elementSize(opts.dtype));
  span::OpScope spanOp(&ctx->spans(), "gather", frOp.cseq());
  GathervOptions v;
  static_cast<CollectiveOptions&>(v) = opts;
  v.input = opts.input;
  v.output = opts.output;
  v.counts.assign(opts.context->size(), opts.count);
  v.dtype = opts.dtype;
  v.root = opts.root;
  gathervRun(v);
}

void gatherv(GathervOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "gatherv: null context");
  auto traceSpan = ctx->tracer().span("gatherv", 0, opts.root);
  // Guarded: the counts-size enforce runs inside gathervRun.
  const uint64_t myBytes =
      static_cast<size_t>(ctx->rank()) < opts.counts.size()
          ? opts.counts[ctx->rank()] * elementSize(opts.dtype)
          : 0;
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kGatherv, myBytes);
  // Fingerprint over the GROUP total: per-rank counts legitimately
  // differ on a matching gatherv schedule, their sum must not.
  uint64_t totalCount = 0;
  for (size_t c : opts.counts) {
    totalCount += c;
  }
  FlightRecOp frOp(&ctx->flightrec(), "gatherv", nullptr,
                   Slot::build(SlotPrefix::kGather, opts.tag).value(),
                   opts.root, myBytes, static_cast<uint8_t>(opts.dtype),
                   totalCount * elementSize(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "gatherv", frOp.cseq(),
                        myBytes);
  span::OpScope spanOp(&ctx->spans(), "gatherv", frOp.cseq());
  gathervRun(opts);
}

// Root posts P-1 receives at per-rank offsets; leaves send once (reference:
// gloo/gather.cc:28-59, gatherv.cc:58-109).
static void gathervRun(GathervOptions& opts) {
  Context* ctx = opts.context;
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE_EQ(opts.counts.size(), static_cast<size_t>(size),
                "gatherv: counts must have one entry per rank");
  const size_t elsize = elementSize(opts.dtype);
  Slot slot = Slot::build(SlotPrefix::kGather, opts.tag);
  const size_t myBytes = opts.counts[rank] * elsize;
  size_t total = 0;
  for (size_t c : opts.counts) {
    total += c;
  }

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kGatherv);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.root = opts.root;
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = total * elsize;
  key.aux = plan::hashCounts(opts.counts);
  PlanHandle planh(ctx, key);

  if (rank == opts.root) {
    auto* out = planh->userBuf(0, opts.output, total * elsize);
    size_t offset = 0;
    int pending = 0;
    for (int j = 0; j < size; j++) {
      const size_t jBytes = opts.counts[j] * elsize;
      if (j == rank) {
        PhaseScope ps(Phase::kPack);
        std::memcpy(bytePtr(opts.output) + offset, opts.input, jBytes);
      } else {
        PhaseScope ps(Phase::kPost);
        out->recv(j, slot.value(), offset, jBytes);
        pending++;
      }
      offset += jBytes;
    }
    PhaseScope ps(Phase::kWireWait);
    while (pending-- > 0) {
      out->waitRecv(nullptr, timeout);
    }
  } else {
    auto* in =
        planh->userBuf(0, const_cast<void*>(opts.input), myBytes);
    {
      PhaseScope ps(Phase::kPost);
      in->send(opts.root, slot.value(), 0, myBytes);
    }
    PhaseScope ps(Phase::kWireWait);
    in->waitSend(timeout);
  }
}

// Root sends slice j to rank j; leaves post one receive (reference:
// gloo/scatter.cc:38-60).
void scatter(ScatterOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "scatter: null context");
  auto traceSpan = ctx->tracer().span("scatter", opts.count * elementSize(opts.dtype), opts.root);
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kScatter,
                      opts.count * elementSize(opts.dtype));
  FlightRecOp frOp(&ctx->flightrec(), "scatter", nullptr,
                   Slot::build(SlotPrefix::kScatter, opts.tag).value(),
                   opts.root, opts.count * elementSize(opts.dtype),
                   static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "scatter", frOp.cseq(),
                        opts.count * elementSize(opts.dtype));
  span::OpScope spanOp(&ctx->spans(), "scatter", frOp.cseq());
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  const size_t nbytes = opts.count * elementSize(opts.dtype);
  Slot slot = Slot::build(SlotPrefix::kScatter, opts.tag);

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kScatter);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.root = opts.root;
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = nbytes;
  PlanHandle planh(ctx, key);

  if (rank == opts.root) {
    auto* in = planh->userBuf(0, const_cast<void*>(opts.input),
                              nbytes * size);
    int pending = 0;
    for (int j = 0; j < size; j++) {
      if (j == rank) {
        PhaseScope ps(Phase::kUnpack);
        std::memcpy(opts.output, bytePtr(opts.input) + j * nbytes, nbytes);
      } else {
        PhaseScope ps(Phase::kPost);
        in->send(j, slot.value(), j * nbytes, nbytes);
        pending++;
      }
    }
    PhaseScope ps(Phase::kWireWait);
    while (pending-- > 0) {
      in->waitSend(timeout);
    }
  } else {
    auto* out = planh->userBuf(0, opts.output, nbytes);
    {
      PhaseScope ps(Phase::kPost);
      out->recv(opts.root, slot.value(), 0, nbytes);
    }
    PhaseScope ps(Phase::kWireWait);
    out->waitRecv(nullptr, timeout);
  }
}

namespace {

// Bruck's log-round alltoall (Bruck et al., "Efficient Algorithms for
// All-to-All Communications in Multiport Message-Passing Systems",
// IEEE TPDS 1997): ceil(log2 P) rounds instead of the pairwise
// exchange's P-1, at the price of each block traveling up to log2 P
// hops (total traffic ~(P/2)log2(P) blocks vs P-1). The win is the
// latency-dominated regime — small blocks, where round count is the
// whole cost — which is exactly the EP/MoE dispatch control case. The
// reference ships only the single-round pattern (gloo/alltoall.cc);
// this tier is beyond it.
//
// Phases: (1) local rotation tmp[j] = in[(rank+j) mod P] so slot j
// holds the block destined to rank+j; (2) for k = 1,2,4,...: gather
// every slot with bit k set into a contiguous staging buffer, send to
// rank+k, receive the same slots from rank-k (already-received blocks
// keep traveling — that is the algorithm); (3) inverse rotation
// out[(rank - j) mod P] = tmp[j].
void bruckAlltoall(Context* ctx, const AlltoallOptions& opts,
                   size_t blockBytes, std::chrono::milliseconds timeout) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const uint8_t* in = static_cast<const uint8_t*>(opts.input);
  uint8_t* out = static_cast<uint8_t*>(opts.output);

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kAlltoallBruck);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = blockBytes * size;
  PlanHandle planh(ctx, key);

  // Rotation scratch (slot 0: memory only, never registered) and the
  // per-round wire stages (slots 1/2), all plan-backed.
  uint8_t* tmp = reinterpret_cast<uint8_t*>(
      planh->scratch(0, static_cast<size_t>(size) * blockBytes));
  {
    PhaseScope ps(Phase::kPack);
    for (int j = 0; j < size; j++) {
      std::memcpy(tmp + static_cast<size_t>(j) * blockBytes,
                  in + static_cast<size_t>((rank + j) % size) * blockBytes,
                  blockBytes);
    }
  }

  const size_t maxBlocks = static_cast<size_t>((size + 1) / 2);
  auto sendSt = planh->stage(1, maxBlocks * blockBytes);
  auto recvSt = planh->stage(2, maxBlocks * blockBytes);
  uint8_t* sendStage = reinterpret_cast<uint8_t*>(sendSt.data);
  uint8_t* recvStage = reinterpret_cast<uint8_t*>(recvSt.data);
  auto* sendBuf = sendSt.buf;
  auto* recvBuf = recvSt.buf;
  Slot slot = Slot::build(SlotPrefix::kAlltoall, opts.tag);

  for (int k = 1; k < size; k <<= 1) {
    size_t nblocks = 0;
    {
      PhaseScope ps(Phase::kPack);
      for (int j = k; j < size; j++) {
        if ((j & k) != 0) {
          std::memcpy(sendStage + nblocks * blockBytes,
                      tmp + static_cast<size_t>(j) * blockBytes,
                      blockBytes);
          nblocks++;
        }
      }
    }
    const int sendTo = (rank + k) % size;
    const int recvFrom = (rank - k + size) % size;
    {
      PhaseScope ps(Phase::kPost);
      sendBuf->send(sendTo, slot.value(), 0, nblocks * blockBytes);
      recvBuf->recv(recvFrom, slot.value(), 0, nblocks * blockBytes);
    }
    {
      PhaseScope ps(Phase::kWireWait);
      sendBuf->waitSend(timeout);
      recvBuf->waitRecv(nullptr, timeout);
    }
    PhaseScope ps(Phase::kUnpack);
    size_t b = 0;
    for (int j = k; j < size; j++) {
      if ((j & k) != 0) {
        std::memcpy(tmp + static_cast<size_t>(j) * blockBytes,
                    recvStage + b * blockBytes, blockBytes);
        b++;
      }
    }
  }

  PhaseScope ps(Phase::kUnpack);
  for (int j = 0; j < size; j++) {
    std::memcpy(out + static_cast<size_t>((rank - j + size) % size) *
                          blockBytes,
                tmp + static_cast<size_t>(j) * blockBytes,
                blockBytes);
  }
}

}  // namespace

// Shared schedule behind alltoall/alltoallv (instrumentation lives in
// the public entries, same rationale as gathervRun).
static void alltoallvRun(AlltoallvOptions& opts);

void alltoall(AlltoallOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "alltoall: null context");
  const size_t blockBytes = opts.count * elementSize(opts.dtype);
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kAlltoall,
                      blockBytes * ctx->size());
  FlightRecOp frOp(&ctx->flightrec(), "alltoall", nullptr,
                   Slot::build(SlotPrefix::kAlltoall, opts.tag).value(),
                   -1, blockBytes * ctx->size(),
                   static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "alltoall", frOp.cseq(),
                        blockBytes * ctx->size());
  span::OpScope spanOp(&ctx->spans(), "alltoall", frOp.cseq());
  // Crossover: Bruck's ceil(log2 P) rounds win while per-block payload
  // is latency-dominated; the pairwise exchange's P-1 single-hop
  // rounds win once bandwidth dominates (each Bruck block travels up
  // to log2 P hops). Loopback P=8 measurement (round 4): p50
  // crosses below 2 KiB blocks on the shared-core host (Bruck 2.3x
  // better at 512 B), while min latency favors Bruck through ~4 KiB
  // (8.6 vs 246 us at 512 B — 28x). Default follows the p50 crossover;
  // on real DCN, where a round costs an RTT instead of a scheduler
  // quantum, the knob should move UP.
  static const size_t bruckMax = collectives_detail::envBytes(
      "TPUCOLL_ALLTOALL_BRUCK_MAX", 1 << 10);
  if (ctx->size() > 2 && blockBytes > 0 && blockBytes <= bruckMax) {
    auto traceSpan = ctx->tracer().span("alltoall", blockBytes, -1,
                                        "bruck");
    frOp.setAlgorithm("bruck");
    profOp.setAlgorithm("bruck");
    bruckAlltoall(ctx, opts, blockBytes,
                  detail::effectiveTimeout(opts));
    return;
  }
  auto traceSpan = ctx->tracer().span("alltoall", blockBytes, -1,
                                      "pairwise");
  frOp.setAlgorithm("pairwise");
  profOp.setAlgorithm("pairwise");
  AlltoallvOptions v;
  static_cast<CollectiveOptions&>(v) = opts;
  v.input = opts.input;
  v.output = opts.output;
  v.inCounts.assign(opts.context->size(), opts.count);
  v.outCounts.assign(opts.context->size(), opts.count);
  v.dtype = opts.dtype;
  alltoallvRun(v);
}

void alltoallv(AlltoallvOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "alltoallv: null context");
  auto traceSpan = ctx->tracer().span("alltoallv");
  size_t inCountTotal = 0;
  for (size_t c : opts.inCounts) {
    inCountTotal += c;
  }
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kAlltoallv,
                      inCountTotal * elementSize(opts.dtype));
  // fpBytes = 0: alltoallv's in/out counts are legitimately different on
  // every rank, so only (op, dtype) participate in the fingerprint.
  FlightRecOp frOp(&ctx->flightrec(), "alltoallv", nullptr,
                   Slot::build(SlotPrefix::kAlltoall, opts.tag).value(),
                   -1, inCountTotal * elementSize(opts.dtype),
                   static_cast<uint8_t>(opts.dtype), /*fpBytes=*/0);
  ProfileOpScope profOp(&ctx->profiler(), "alltoallv", frOp.cseq(),
                        inCountTotal * elementSize(opts.dtype));
  span::OpScope spanOp(&ctx->spans(), "alltoallv", frOp.cseq());
  alltoallvRun(opts);
}

// Rotated pairwise exchange: at step i, send to rank+i and receive from
// rank-i, so every step moves disjoint pairs and link load stays balanced
// (reference: gloo/alltoall.cc:39-50, alltoallv.cc:19-30).
static void alltoallvRun(AlltoallvOptions& opts) {
  Context* ctx = opts.context;
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE_EQ(opts.inCounts.size(), static_cast<size_t>(size));
  TC_ENFORCE_EQ(opts.outCounts.size(), static_cast<size_t>(size));
  const size_t elsize = elementSize(opts.dtype);

  size_t inTotal = 0, outTotal = 0;
  for (int j = 0; j < size; j++) {
    inTotal += opts.inCounts[j] * elsize;
    outTotal += opts.outCounts[j] * elsize;
  }

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kAlltoallv);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = inTotal;
  // Both count vectors shape the schedule; mix both into aux.
  key.aux = plan::hashCounts(opts.inCounts) * 1099511628211ull ^
            plan::hashCounts(opts.outCounts);
  PlanHandle planh(ctx, key);
  // countBlocks doubles as the per-peer offset table (memoized).
  const auto& inBlocks = planh->blocks(
      0, [&] { return collectives_detail::countBlocks(opts.inCounts,
                                                      elsize); });
  const auto& outBlocks = planh->blocks(
      1, [&] { return collectives_detail::countBlocks(opts.outCounts,
                                                      elsize); });

  {
    PhaseScope ps(Phase::kPack);
    std::memcpy(bytePtr(opts.output) + outBlocks.offset[rank],
                bytePtr(opts.input) + inBlocks.offset[rank],
                opts.inCounts[rank] * elsize);
  }
  if (size == 1) {
    return;
  }

  Slot slot = Slot::build(SlotPrefix::kAlltoall, opts.tag);
  auto* in =
      planh->userBuf(0, const_cast<void*>(opts.input), inTotal);
  auto* out = planh->userBuf(1, opts.output, outTotal);
  for (int i = 1; i < size; i++) {
    const int sendTo = (rank + i) % size;
    const int recvFrom = (rank - i + size) % size;
    {
      PhaseScope ps(Phase::kPost);
      in->send(sendTo, slot.value(), inBlocks.offset[sendTo],
               opts.inCounts[sendTo] * elsize);
      out->recv(recvFrom, slot.value(), outBlocks.offset[recvFrom],
                opts.outCounts[recvFrom] * elsize);
    }
    PhaseScope ps(Phase::kWireWait);
    in->waitSend(timeout);
    out->waitRecv(nullptr, timeout);
  }
}

}  // namespace tpucoll

// Ring schedules: allgather(v), allreduce (reduce-scatter + allgather),
// reduce_scatter, and the binomial-tree reduce.
//
// Ring block bookkeeping: `count` elements are split into `size` blocks
// (allreduce) or taken from per-rank counts (v-variants / reduce_scatter).
// All rings send to rank+1 and receive from rank-1; per-step sub-slots keep
// pipelined messages on one pair from cross-matching.
//
// Every public entry resolves its algorithm, then acquires a persistent
// plan (plan.h) keyed by the call's full identity: the plan owns the
// registered work/stage buffers and the memoized block/segment layout,
// so a repeated call replays with zero allocations and registrations.
#include <algorithm>
#include <cstring>
#include <optional>
#include <vector>

#include "tpucoll/collectives/algorithms.h"
#include "tpucoll/collectives/collectives.h"
#include "tpucoll/collectives/detail.h"
#include "tpucoll/collectives/plan.h"
#include "tpucoll/common/profile.h"
#include "tpucoll/group/hier.h"
#include "tpucoll/schedule/interpreter.h"
#include "tpucoll/tuning/dispatch.h"

namespace tpucoll {

using collectives_detail::Blocks;
using collectives_detail::countBlocks;
using collectives_detail::evenBlocks;
using collectives_detail::fuseRecvReduce;
using plan::LazyStage;
using plan::PlanHandle;
using plan::PlanKey;
using plan::PlanOp;
using profile::Phase;
using profile::PhaseScope;
using profile::ProfileOpScope;

namespace {

char* bytePtr(void* p) { return static_cast<char*>(p); }

// Plan stage-slot map for this file's schedules (indices are per-plan,
// and a plan is keyed by its resolved algorithm, so only slots used by
// ONE schedule may collide):
//   0  algorithm-internal staging (binomial reduce)
//   1  ring reduce-scatter double-buffered staging
//   2  reduce_scatter work copy (the caller's input stays intact)
//   3  reduce non-root result
constexpr size_t kStageBinomial = 0;
constexpr size_t kStageRingRs = 1;
constexpr size_t kStageRsWork = 2;
constexpr size_t kStageReduceResult = 3;

// PlanKey.algorithm sentinel for scheduled (IR-interpreted) dispatch;
// the schedule's identity rides in PlanKey.aux as an FNV-1a name hash.
// Native algorithm enums are tiny, so 0xFF can never collide.
constexpr uint8_t kScheduledAlgorithm = 0xFF;

uint64_t fnvName(const std::string& name) {
  uint64_t h = 1469598103934665603ull;
  for (char c : name) {
    h ^= static_cast<uint8_t>(c);
    h *= 1099511628211ull;
  }
  return h;
}

// Elected-schedule lookup for a kAuto dispatch. A schedule election
// names one exact (collective, world, dtype, size-bucket) cell — the
// most specific evidence the tuner can record — so it outranks both the
// tuning table and the compile-time fallback thresholds. Null when no
// plane is installed, no cell matches this call, the elected schedule
// was not resolvable for this world, or the program carries bf16-coded
// wire steps without the caller's lossy-wire opt-in (codedOk).
std::shared_ptr<const schedule::ResolvedProgram> electedSchedule(
    Context* ctx, const char* collective, DataType dtype, size_t nbytes,
    bool codedOk) {
  auto inst = ctx->schedules();
  if (inst == nullptr) {
    return nullptr;
  }
  const schedule::Schedule* sel = inst->table->elected(
      collective, ctx->size(), tuning::dataTypeName(dtype), nbytes);
  if (sel == nullptr) {
    return nullptr;
  }
  auto it = inst->programs.find(sel->name);
  if (it == inst->programs.end() || (it->second->hasCoded && !codedOk)) {
    return nullptr;
  }
  return it->second;
}

// Ring reduce-scatter over `work` (in place). After P-1 steps, rank r owns
// block (r + 1 + startShift) mod P fully reduced. startShift=0 feeds the
// allreduce allgather phase; startShift=-1 makes rank r own block r for the
// standalone reduce_scatter.
//
// Pipelining (the reference's key allreduce optimization, maxSegmentSize +
// two-in-flight at gloo/allreduce.cc:196-218, re-derived for the eager
// transport): block transfers are split into segments of at most
// kMaxSegmentBytes; receives are pre-posted TWO steps ahead into
// double-buffered staging so arriving payloads always land directly in
// their destination (never the stash), and each segment is reduced the
// moment it arrives, overlapping the VPU/AVX reduction with socket I/O of
// later segments.
// Slot span ringReduceScatter consumes starting at its slotBase: P-1
// steps of maxSegs segment slots each, rounded up to P*maxSegs. Any
// phase layered behind it on the same tag (allgather, gather-to-root)
// MUST derive its slot base from this helper, so a change to the RS
// slot schedule cannot silently collide with a follow-on phase.
uint64_t ringReduceScatterSlotSpan(plan::Plan& plan, const Blocks& blocks,
                                   size_t elsize) {
  size_t maxBlock = 0;
  for (size_t b : blocks.bytes) {
    maxBlock = std::max(maxBlock, b);
  }
  return uint64_t(blocks.bytes.size()) *
         plan.segments(maxBlock, elsize).size();
}

void ringReduceScatter(Context* ctx, plan::Plan& plan, char* work,
                       const Blocks& blocks, ReduceFn fn, size_t elsize,
                       Slot slot, uint64_t slotBase, int startShift,
                       std::chrono::milliseconds timeout,
                       transport::UnboundBuffer* workBuf, bool fuseOk) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  size_t maxBlock = 0;
  for (size_t b : blocks.bytes) {
    maxBlock = std::max(maxBlock, b);
  }
  const size_t maxSegs = plan.segments(maxBlock, elsize).size();
  const int right = (rank + 1) % size;
  const int left = (rank - 1 + size) % size;
  // Fused receive-reduce: arrivals are combined into `work` by the
  // transport itself (straight out of the shm ring), so the schedule
  // needs no staging at all and each payload byte is touched once instead
  // of copy+reduce. Receives still pre-post two steps ahead; an in-flight
  // combined segment is always disjoint from the blocks being sent (recv
  // of step s writes block r-s-1 while sends read block r-s). Custom
  // reduce fns stay on the scratch path: they may not be safe on the
  // transport's loop thread (Python callbacks need the GIL). Fusing is
  // per-source: the ring only ever receives from `left`, so one check
  // picks the schedule (collectives_detail::fuseRecvReduce).
  const bool fuse = fuseRecvReduce(ctx, fuseOk, elsize, left);
  // Plan-backed staging, scratch path only (lazy: the fused path receives
  // straight into `work`): cached plans keep the pages AND the
  // registration warm across calls.
  LazyStage stage(plan, kStageRingRs, 2 * std::max(maxBlock, size_t(1)));
  const int steps = size - 1;

  auto sendBlockAt = [&](int step) {
    return (rank + startShift - step + 2 * size) % size;
  };
  auto recvBlockAt = [&](int step) {
    return (rank + startShift - step - 1 + 2 * size) % size;
  };
  auto segSlot = [&](int step, size_t seg) {
    return slot.offset(slotBase + uint64_t(step) * maxSegs + seg).value();
  };

  // Post all segment receives of `step`: fused, straight into the work
  // block (combined on arrival); scratch path, into staging half (step%2).
  auto postRecvsFor = [&](int step) {
    PhaseScope ps(Phase::kPost);
    const int rb = recvBlockAt(step);
    const auto& segs = plan.segments(blocks.bytes[rb], elsize);
    if (fuse) {
      for (size_t k = 0; k < segs.size(); k++) {
        workBuf->recvReduce(left, segSlot(step, k), fn, elsize,
                            blocks.offset[rb] + segs[k].offset,
                            segs[k].nbytes);
      }
      return;
    }
    const size_t base = (step % 2) * maxBlock;
    for (size_t k = 0; k < segs.size(); k++) {
      stage.buf()->recv(left, segSlot(step, k), base + segs[k].offset,
                        segs[k].nbytes);
    }
  };
  auto postSendsFor = [&](int step) {
    const size_t blockOff = blocks.offset[sendBlockAt(step)];
    const auto& segs =
        plan.segments(blocks.bytes[sendBlockAt(step)], elsize);
    for (size_t k = 0; k < segs.size(); k++) {
      // Annotated per segment: each send post is one causal span.
      PhaseScope ps(Phase::kPost, right, segSlot(step, k),
                    segs[k].nbytes);
      workBuf->send(right, segSlot(step, k), blockOff + segs[k].offset,
                    segs[k].nbytes);
    }
  };

  postRecvsFor(0);
  if (steps > 1) {
    postRecvsFor(1);
  }
  postSendsFor(0);

  for (int step = 0; step < steps; step++) {
    const int recvBlock = recvBlockAt(step);
    const size_t base = (step % 2) * maxBlock;
    const auto& segs = plan.segments(blocks.bytes[recvBlock], elsize);
    for (size_t k = 0; k < segs.size(); k++) {
      if (fuse) {
        // The combine already ran (loop thread / stash hit); the wait is
        // purely the completion count.
        PhaseScope ps(Phase::kWireWait, left, segSlot(step, k),
                      segs[k].nbytes);
        workBuf->waitRecv(nullptr, timeout);
        continue;
      }
      {
        PhaseScope ps(Phase::kWireWait, left, segSlot(step, k),
                      segs[k].nbytes);
        stage.buf()->waitRecv(nullptr, timeout);
      }
      // Segments on one pair complete in wire order, so segment k of this
      // step is the k-th completion.
      if (segs[k].nbytes > 0) {
        PhaseScope ps(Phase::kReduce);
        fn(work + blocks.offset[recvBlock] + segs[k].offset,
           stage.data() + base + segs[k].offset, segs[k].nbytes / elsize);
      }
    }
    // Drain this step's sends — counted from the SEND block's segment list,
    // which can differ from the recv block's when block sizes straddle a
    // segment boundary (e.g. evenBlocks remainders).
    const size_t sendSegCount =
        plan.segments(blocks.bytes[sendBlockAt(step)], elsize).size();
    {
      PhaseScope ps(Phase::kWireWait);
      for (size_t k = 0; k < sendSegCount; k++) {
        workBuf->waitSend(timeout);
      }
    }
    if (step + 2 < steps) {
      postRecvsFor(step + 2);  // staging half (step % 2) is free again
    }
    if (step + 1 < steps) {
      postSendsFor(step + 1);  // its block finished reducing just now
    }
  }
}

// Ring allgather phase over an in-place buffer: at step s, send block
// (rank + shift - s), receive block (rank + shift - s - 1) directly into
// place. All receives are pre-posted (each step writes a distinct block),
// the own/seed block is sent first, and every received segment is forwarded
// to the right neighbor the moment it arrives. shift=0 gathers each rank's
// own block (plain allgather); shift=+1 rides behind a reduce-scatter that
// left rank r owning reduced block r+1 (the allreduce second phase).
void ringAllgatherPhase(Context* ctx, plan::Plan& plan,
                        transport::UnboundBuffer* buf, const Blocks& blocks,
                        size_t elsize, Slot slot, uint64_t slotBase,
                        size_t maxSegs, int shift,
                        std::chrono::milliseconds timeout) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const int right = (rank + 1) % size;
  const int left = (rank - 1 + size) % size;
  const int steps = size - 1;
  auto blockAt = [&](int step) {
    return (rank + shift - step + 2 * size) % size;
  };
  auto segSlot = [&](int step, size_t seg) {
    return slot.offset(slotBase + uint64_t(step) * maxSegs + seg).value();
  };
  {
    PhaseScope ps(Phase::kPost);
    for (int step = 0; step < steps; step++) {
      const int recvBlock = blockAt(step + 1);  // == sendBlock(step) - 1
      const auto& segs = plan.segments(blocks.bytes[recvBlock], elsize);
      for (size_t k = 0; k < segs.size(); k++) {
        buf->recv(left, segSlot(step, k),
                  blocks.offset[recvBlock] + segs[k].offset,
                  segs[k].nbytes);
      }
    }
  }
  int pendingSends = 0;
  {
    const int sb = blockAt(0);
    const auto& segs = plan.segments(blocks.bytes[sb], elsize);
    for (size_t k = 0; k < segs.size(); k++) {
      PhaseScope ps(Phase::kPost, right, segSlot(0, k), segs[k].nbytes);
      buf->send(right, segSlot(0, k), blocks.offset[sb] + segs[k].offset,
                segs[k].nbytes);
      pendingSends++;
    }
  }
  for (int step = 0; step < steps; step++) {
    const int recvBlock = blockAt(step + 1);
    const auto& segs = plan.segments(blocks.bytes[recvBlock], elsize);
    for (size_t k = 0; k < segs.size(); k++) {
      {
        PhaseScope ps(Phase::kWireWait, left, segSlot(step, k),
                      segs[k].nbytes);
        buf->waitRecv(nullptr, timeout);
      }
      if (step + 1 < steps) {
        // This segment is exactly segment k of the next step's send block.
        PhaseScope ps(Phase::kPost, right, segSlot(step + 1, k),
                      segs[k].nbytes);
        buf->send(right, segSlot(step + 1, k),
                  blocks.offset[recvBlock] + segs[k].offset,
                  segs[k].nbytes);
        pendingSends++;
      }
    }
  }
  {
    PhaseScope ps(Phase::kWireWait);
    while (pendingSends-- > 0) {
      buf->waitSend(timeout);
    }
  }
}

}  // namespace

// Shared schedule behind allgather/allgatherv; instrumentation lives in
// the public entries so each op is attributed under its own name.
static void allgathervRun(AllgathervOptions& opts);

void allgatherv(AllgathervOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "allgatherv: null context");
  auto traceSpan = ctx->tracer().span("allgatherv");
  // Guarded: the counts-size enforce runs inside allgathervRun.
  const uint64_t myBytes =
      static_cast<size_t>(ctx->rank()) < opts.counts.size()
          ? opts.counts[ctx->rank()] * elementSize(opts.dtype)
          : 0;
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kAllgatherv, myBytes);
  // Fingerprint over the GROUP total: per-rank counts legitimately
  // differ on a matching allgatherv schedule, the counts vector (and so
  // its sum) must not.
  uint64_t totalCount = 0;
  for (size_t c : opts.counts) {
    totalCount += c;
  }
  FlightRecOp frOp(&ctx->flightrec(), "allgatherv", nullptr,
                   Slot::build(SlotPrefix::kAllgather, opts.tag).value(),
                   -1, myBytes, static_cast<uint8_t>(opts.dtype),
                   totalCount * elementSize(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "allgatherv", frOp.cseq(),
                        myBytes);
  span::OpScope spanOp(&ctx->spans(), "allgatherv", frOp.cseq());
  allgathervRun(opts);
}

void allgather(AllgatherOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "allgather: null context");
  auto traceSpan = ctx->tracer().span(
      "allgather", opts.count * elementSize(opts.dtype));
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kAllgather,
                      opts.count * elementSize(opts.dtype));
  FlightRecOp frOp(&ctx->flightrec(), "allgather", nullptr,
                   Slot::build(SlotPrefix::kAllgather, opts.tag).value(),
                   -1, opts.count * elementSize(opts.dtype),
                   static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "allgather", frOp.cseq(),
                        opts.count * elementSize(opts.dtype));
  span::OpScope spanOp(&ctx->spans(), "allgather", frOp.cseq());
  if (opts.algorithm == HierDispatch::kHier && group::hierEligible(ctx) &&
      ctx->size() > 1 && opts.count > 0) {
    frOp.setAlgorithm("hier");
    profOp.setAlgorithm("hier");
    group::hierAllgather(ctx, opts.input, opts.output, opts.count,
                         opts.dtype, opts.tag,
                         detail::effectiveTimeout(opts));
    return;
  }
  if (ctx->size() > 1 && opts.count > 0 &&
      opts.algorithm != HierDispatch::kHier) {
    // Installed schedule plane first (see allreduce). Allgather
    // elections are bucketed by TOTAL output bytes — the quantity the
    // wire actually moves. Coded schedules never match: allgather has
    // no reduction to absorb bf16 rounding, so generators don't emit
    // them and electedSchedule's codedOk=false keeps it that way.
    const int size = ctx->size();
    const size_t elsize = elementSize(opts.dtype);
    const size_t total = opts.count * size_t(size) * elsize;
    if (auto prog = electedSchedule(ctx, "allgather", opts.dtype, total,
                                    /*codedOk=*/false)) {
      const char* lbl = schedule::internedLabel(prog->label);
      auto schedSpan = ctx->tracer().span("allgather", total, -1, lbl);
      frOp.setAlgorithm(lbl);
      profOp.setAlgorithm(lbl);
      const auto timeout = detail::effectiveTimeout(opts);
      Slot slot = Slot::build(SlotPrefix::kAllgather, opts.tag);
      char* out = bytePtr(opts.output);
      PlanKey key;
      key.opcode = static_cast<uint8_t>(PlanOp::kAllgatherv);
      key.algorithm = kScheduledAlgorithm;
      key.dtype = static_cast<uint8_t>(opts.dtype);
      key.tag = opts.tag;
      key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
      key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
      key.nbytes = total;
      key.aux = fnvName(prog->name);
      PlanHandle planh(ctx, key);
      if (opts.input != nullptr) {
        PhaseScope ps(Phase::kPack);
        std::memcpy(out + size_t(ctx->rank()) * opts.count * elsize,
                    opts.input, opts.count * elsize);
      }
      schedule::run(ctx, *planh, *prog, out, opts.count * size_t(size),
                    elsize, /*fn=*/nullptr, opts.dtype, slot, timeout);
      return;
    }
  }
  AllgathervOptions v;
  static_cast<CollectiveOptions&>(v) = opts;
  v.input = opts.input;
  v.output = opts.output;
  v.counts.assign(opts.context->size(), opts.count);
  v.dtype = opts.dtype;
  allgathervRun(v);
}

// Ring allgather: block b travels P-1 hops; receives land in place in the
// output (reference schedule shape: gloo/allgather.cc:55-98, with the
// pre-post + segment-forward pipeline of ringAllgatherPhase).
static void allgathervRun(AllgathervOptions& opts) {
  Context* ctx = opts.context;
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE_EQ(opts.counts.size(), static_cast<size_t>(size));
  const size_t elsize = elementSize(opts.dtype);
  size_t total = 0;
  for (size_t c : opts.counts) {
    total += c * elsize;
  }

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kAllgatherv);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = total;
  key.aux = plan::hashCounts(opts.counts);
  PlanHandle planh(ctx, key);
  const Blocks& blocks = planh->blocks(
      0, [&] { return countBlocks(opts.counts, elsize); });

  if (opts.input != nullptr) {
    PhaseScope ps(Phase::kPack);
    std::memcpy(bytePtr(opts.output) + blocks.offset[rank], opts.input,
                blocks.bytes[rank]);
  }
  if (size == 1) {
    return;
  }

  size_t maxBlock = 0;
  for (size_t b : blocks.bytes) {
    maxBlock = std::max(maxBlock, b);
  }
  Slot slot = Slot::build(SlotPrefix::kAllgather, opts.tag);
  auto* out = planh->userBuf(0, opts.output, total);

  // Small/medium payloads: direct exchange — every pair transfers
  // concurrently with no store-and-forward chain (measured ~2x faster
  // than the ring below the threshold; the ring wins for bulk payloads
  // where per-link balance matters). Loopback-tuned default; re-sweep on
  // real DCN via TPUCOLL_ALLGATHER_DIRECT_MAX (bytes of total non-local
  // traffic per rank).
  static const size_t directMax =
      collectives_detail::envBytes("TPUCOLL_ALLGATHER_DIRECT_MAX", 8u << 20);
  if (maxBlock * size_t(size - 1) <= directMax) {
    {
      PhaseScope ps(Phase::kPost);
      for (int i = 1; i < size; i++) {
        const int to = (rank + i) % size;
        const int from = (rank - i + size) % size;
        out->recv(from, slot.offset(0).value(), blocks.offset[from],
                  blocks.bytes[from]);
        out->send(to, slot.offset(0).value(), blocks.offset[rank],
                  blocks.bytes[rank]);
      }
    }
    PhaseScope ps(Phase::kWireWait);
    for (int i = 1; i < size; i++) {
      out->waitRecv(nullptr, timeout);
      out->waitSend(timeout);
    }
    return;
  }

  ringAllgatherPhase(ctx, *planh, out, blocks, elsize, slot, 0,
                     planh->segments(maxBlock, elsize).size(), /*shift=*/0,
                     timeout);
}

// Bandwidth-optimal ring allreduce (reference hot path: gloo/allreduce.cc:
// 147-392): local multi-input reduce, algorithm-specific exchange, then fan
// the result to every output buffer.
void allreduce(AllreduceOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "allreduce: null context");
  TC_ENFORCE(!opts.inputs.empty() && !opts.outputs.empty(),
             "allreduce: need at least one input and output");
  const auto timeout = detail::effectiveTimeout(opts);
  const int size = ctx->size();
  const size_t elsize = elementSize(opts.dtype);
  const size_t nbytes = opts.count * elsize;
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kAllreduce, nbytes);
  FlightRecOp frOp(&ctx->flightrec(), "allreduce", nullptr,
                   Slot::build(SlotPrefix::kAllreduce, opts.tag).value(),
                   -1, nbytes, static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "allreduce", frOp.cseq(),
                        nbytes);
  span::OpScope spanOp(&ctx->spans(), "allreduce", frOp.cseq());
  ReduceFn fn = opts.customFn != nullptr
                  ? opts.customFn
                  : getReduceFn(opts.dtype, opts.op);

  // Local reduction of all inputs into outputs[0].
  char* work = bytePtr(opts.outputs[0]);
  {
    PhaseScope ps(Phase::kPack);
    if (work != opts.inputs[0]) {
      std::memcpy(work, opts.inputs[0], nbytes);
    }
    for (size_t i = 1; i < opts.inputs.size(); i++) {
      fn(work, opts.inputs[i], opts.count);
    }
  }

  TC_ENFORCE(opts.customFn == nullptr ||
                 (opts.algorithm != AllreduceAlgorithm::kRingBf16Wire &&
                  opts.algorithm != AllreduceAlgorithm::kRingQ8Wire &&
                  opts.algorithm != AllreduceAlgorithm::kRingQ4Wire),
             "allreduce: custom reduction functions are incompatible "
             "with the wire-compressed algorithms (they reduce through "
             "the wire codec)");

  if (size > 1 && opts.count > 0) {
    Slot slot = Slot::build(SlotPrefix::kAllreduce, opts.tag);
    AllreduceAlgorithm algo = opts.algorithm;
    // An explicit hierarchical request on a flat topology (single host,
    // or one rank per host) has no second plane to exploit; dispatch it
    // like kAuto so kHier is always safe to hardcode.
    if (algo == AllreduceAlgorithm::kHier && !group::hierEligible(ctx)) {
      algo = AllreduceAlgorithm::kAuto;
    }
    if (algo == AllreduceAlgorithm::kAutoLossyWire) {
      // The caller's explicit opt-in to lossy wire precision. Only the
      // float32 sum shape has wire codecs; anything else dispatches as
      // plain kAuto. Tuned contexts elect from measurement (wire arms
      // included); the untuned fallback routes the bandwidth tier to
      // the q8 ring — the caller asked for wire compression exactly
      // because the payload is bandwidth-bound.
      if (opts.dtype == DataType::kFloat32 && opts.op == ReduceOp::kSum &&
          opts.customFn == nullptr) {
        if (auto tuned =
                tuning::tableAllreduce(ctx, opts.dtype, nbytes,
                                       /*lossyWireOk=*/true)) {
          algo = *tuned;
        } else {
          static const size_t hdMaxLossy = collectives_detail::envBytes(
              "TPUCOLL_ALLREDUCE_HD_MAX", 1u << 20);
          algo = nbytes > hdMaxLossy ? AllreduceAlgorithm::kRingQ8Wire
                                     : AllreduceAlgorithm::kAuto;
        }
      } else {
        algo = AllreduceAlgorithm::kAuto;
      }
    }
    if (algo == AllreduceAlgorithm::kAuto && opts.customFn == nullptr) {
      // Installed schedule plane first: an election names one exact
      // (collective, world, dtype, bucket) cell, which is stronger
      // evidence than the tuning table's whole-curve crossovers.
      // Schedules carrying bf16-coded wire steps require the same
      // float32 + sum + kAutoLossyWire opt-in as the native wire arms.
      const bool codedOk =
          opts.algorithm == AllreduceAlgorithm::kAutoLossyWire &&
          opts.dtype == DataType::kFloat32 && opts.op == ReduceOp::kSum;
      if (auto prog = electedSchedule(ctx, "allreduce", opts.dtype, nbytes,
                                      codedOk)) {
        const char* lbl = schedule::internedLabel(prog->label);
        auto traceSpan = ctx->tracer().span("allreduce", nbytes, -1, lbl);
        frOp.setAlgorithm(lbl);
        profOp.setAlgorithm(lbl);
        PlanKey key;
        key.opcode = static_cast<uint8_t>(PlanOp::kAllreduce);
        key.algorithm = kScheduledAlgorithm;
        key.dtype = static_cast<uint8_t>(opts.dtype);
        key.op = static_cast<uint8_t>(opts.op);
        key.tag = opts.tag;
        key.ptrA = reinterpret_cast<uintptr_t>(work);
        key.nbytes = nbytes;
        key.aux = fnvName(prog->name);
        PlanHandle planh(ctx, key);
        schedule::run(ctx, *planh, *prog, work, opts.count, elsize, fn,
                      opts.dtype, slot, timeout);
        if (opts.outputs.size() > 1) {
          PhaseScope ps(Phase::kUnpack);
          for (size_t i = 1; i < opts.outputs.size(); i++) {
            std::memcpy(opts.outputs[i], work, nbytes);
          }
        }
        return;
      }
    }
    if (algo == AllreduceAlgorithm::kAuto) {
      // Measured tuning table first (tuning/dispatch.h: per-deployment
      // crossovers elected by tuning::tune and installed identically on
      // every rank), then the loopback-measured compile-time fallback:
      // recursive doubling (log2 P full-vector rounds;
      // non-power-of-2 groups take a pre/post fold) for the
      // alpha-dominated tiny tier, halving-doubling up to ~1 MiB, the
      // pipelined ring beyond. Re-sweep via bench.py --autotune, or move
      // the fallback thresholds with TPUCOLL_ALLREDUCE_RD_MAX /
      // TPUCOLL_ALLREDUCE_HD_MAX (bytes).
      if (auto tuned = tuning::tableAllreduce(ctx, opts.dtype, nbytes)) {
        algo = *tuned;
      } else {
        static const size_t rdMax = collectives_detail::envBytes(
            "TPUCOLL_ALLREDUCE_RD_MAX", 16u << 10);
        static const size_t hdMax = collectives_detail::envBytes(
            "TPUCOLL_ALLREDUCE_HD_MAX", 1u << 20);
        algo = nbytes <= rdMax ? AllreduceAlgorithm::kRecursiveDoubling
               : nbytes <= hdMax ? AllreduceAlgorithm::kHalvingDoubling
                                 : AllreduceAlgorithm::kRing;
      }
    }
    auto traceSpan = ctx->tracer().span(
        "allreduce", nbytes, -1, tuning::allreduceAlgorithmName(algo));
    frOp.setAlgorithm(tuning::allreduceAlgorithmName(algo));
    profOp.setAlgorithm(tuning::allreduceAlgorithmName(algo));
    if (algo == AllreduceAlgorithm::kHier) {
      // Hierarchical composition: every phase is an ordinary collective
      // on a split sub-context, each with its own plan cache — the
      // parent-level plan machinery below is deliberately skipped.
      group::hierAllreduce(ctx, work, opts.count, opts.dtype, opts.op,
                           opts.customFn, opts.tag, timeout);
      if (opts.outputs.size() > 1) {
        PhaseScope ps(Phase::kUnpack);
        for (size_t i = 1; i < opts.outputs.size(); i++) {
          std::memcpy(opts.outputs[i], work, nbytes);
        }
      }
      return;
    }
    // Persistent plan, keyed by the RESOLVED algorithm (a tuning-table
    // install clears the cache, so a stale kAuto choice cannot replay).
    // Custom reductions stay transient: the fn pointer's identity is
    // not stable across calls (Python rebuilds its trampoline).
    PlanKey key;
    key.opcode = static_cast<uint8_t>(PlanOp::kAllreduce);
    key.algorithm = static_cast<uint8_t>(algo);
    key.dtype = static_cast<uint8_t>(opts.dtype);
    key.op = static_cast<uint8_t>(opts.op);
    key.tag = opts.tag;
    key.ptrA = reinterpret_cast<uintptr_t>(work);
    key.nbytes = nbytes;
    PlanHandle planh = opts.customFn == nullptr ? PlanHandle(ctx, key)
                                                : PlanHandle(ctx);
    switch (algo) {
      case AllreduceAlgorithm::kRing:
        algorithms::ringAllreduce(ctx, *planh, work, opts.count, elsize,
                                  fn, slot, timeout,
                                  opts.customFn == nullptr);
        break;
      case AllreduceAlgorithm::kHalvingDoubling:
        algorithms::halvingDoublingAllreduce(ctx, *planh, work, opts.count,
                                             elsize, fn, slot, timeout,
                                             opts.customFn == nullptr);
        break;
      case AllreduceAlgorithm::kHdFold:
        algorithms::hdFoldAllreduce(ctx, *planh, work, opts.count, elsize,
                                    fn, slot, timeout,
                                    opts.customFn == nullptr);
        break;
      case AllreduceAlgorithm::kHdBlocks:
        algorithms::hdBinaryBlocksAllreduce(ctx, *planh, work, opts.count,
                                            elsize, fn, slot, timeout,
                                            opts.customFn == nullptr);
        break;
      case AllreduceAlgorithm::kRecursiveDoubling:
        algorithms::recursiveDoublingAllreduce(ctx, *planh, work,
                                               opts.count, elsize, fn,
                                               slot, timeout);
        break;
      case AllreduceAlgorithm::kBcube:
        algorithms::bcubeAllreduce(ctx, *planh, work, opts.count, elsize,
                                   fn, slot, timeout,
                                   opts.customFn == nullptr);
        break;
      case AllreduceAlgorithm::kRingBf16Wire:
        TC_ENFORCE(opts.dtype == DataType::kFloat32,
                   "bf16-wire allreduce requires float32 payloads");
        TC_ENFORCE(opts.op == ReduceOp::kSum,
                   "bf16-wire allreduce supports sum only");
        algorithms::bf16WireRingAllreduce(ctx, *planh, work, opts.count,
                                          slot, timeout);
        break;
      case AllreduceAlgorithm::kRingQ8Wire:
        TC_ENFORCE(opts.dtype == DataType::kFloat32,
                   "q8-wire allreduce requires float32 payloads");
        TC_ENFORCE(opts.op == ReduceOp::kSum,
                   "q8-wire allreduce supports sum only");
        algorithms::q8WireRingAllreduce(ctx, *planh, work, opts.count,
                                        slot, timeout);
        break;
      case AllreduceAlgorithm::kRingQ4Wire:
        TC_ENFORCE(opts.dtype == DataType::kFloat32,
                   "q4-wire allreduce requires float32 payloads");
        TC_ENFORCE(opts.op == ReduceOp::kSum,
                   "q4-wire allreduce supports sum only");
        algorithms::q4WireRingAllreduce(ctx, *planh, work, opts.count,
                                        slot, timeout);
        break;
      default:
        TC_THROW(EnforceError, "unknown allreduce algorithm");
    }
  }

  if (opts.outputs.size() > 1) {
    PhaseScope ps(Phase::kUnpack);
    for (size_t i = 1; i < opts.outputs.size(); i++) {
      std::memcpy(opts.outputs[i], work, nbytes);
    }
  }
}

namespace algorithms {

void ringAllreduce(Context* ctx, plan::Plan& plan, char* work,
                   size_t count, size_t elsize, ReduceFn fn, Slot slot,
                   std::chrono::milliseconds timeout, bool fuseOk) {
  const int size = ctx->size();
  const size_t nbytes = count * elsize;
  const Blocks& blocks =
      plan.blocks(0, [&] { return evenBlocks(count, size, elsize); });
  size_t maxBlock = 0;
  for (size_t b : blocks.bytes) {
    maxBlock = std::max(maxBlock, b);
  }
  const size_t maxSegs = plan.segments(maxBlock, elsize).size();
  auto* workBuf = plan.userBuf(0, work, nbytes);
  ringReduceScatter(ctx, plan, work, blocks, fn, elsize, slot, 0, 0,
                    timeout, workBuf, fuseOk);
  // Allgather phase: rank r starts owning reduced block (r+1); the block
  // then rides the ring into place on every rank.
  ringAllgatherPhase(ctx, plan, workBuf, blocks, elsize, slot,
                     /*slotBase=*/
                     ringReduceScatterSlotSpan(plan, blocks, elsize),
                     maxSegs, /*shift=*/1, timeout);
}

}  // namespace algorithms

namespace {

// Binomial reduction tree: leaves push partials toward the root, halving
// the number of active ranks per round. log2(P) latency steps, but every
// round moves a FULL payload and the root's in-link carries log2(P) * N
// bytes — latency-optimal, bandwidth-hostile.
void binomialReduce(Context* ctx, plan::Plan& plan, char* result,
                    transport::UnboundBuffer* resultBuf, size_t count,
                    size_t elsize, ReduceFn fn, int root, bool fuseOk,
                    Slot slot, std::chrono::milliseconds timeout) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const size_t nbytes = count * elsize;
  const int vrank = (rank - root + size) % size;
  auto physical = [&](int v) { return (v + root) % size; };
  // Fused receive-reduce: partner partials are combined into `result` by
  // the transport (from the shm ring / stash, no scratch vector at all).
  // Rounds are serialized by waitRecv, so result is never concurrently a
  // send source and a combine target. Custom fns stay on the scratch path
  // (not loop-thread-safe); fuseRecvReduce picks per partner, per round.
  LazyStage stage(plan, kStageBinomial, nbytes);

  int mask = 1;
  uint64_t round = 0;
  while (mask < size) {
    if (vrank & mask) {
      {
        PhaseScope ps(Phase::kPost);
        resultBuf->send(physical(vrank - mask),
                        slot.offset(round).value(), 0, nbytes);
      }
      PhaseScope ps(Phase::kWireWait);
      resultBuf->waitSend(timeout);
      break;
    }
    const int partner = vrank + mask;
    if (partner < size) {
      const int src = physical(partner);
      if (fuseRecvReduce(ctx, fuseOk, elsize, src)) {
        {
          PhaseScope ps(Phase::kPost);
          resultBuf->recvReduce(src, slot.offset(round).value(), fn,
                                elsize, 0, nbytes);
        }
        PhaseScope ps(Phase::kWireWait);
        resultBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kPost);
          stage.buf()->recv(src, slot.offset(round).value(), 0, nbytes);
        }
        {
          PhaseScope ps(Phase::kWireWait);
          stage.buf()->waitRecv(nullptr, timeout);
        }
        PhaseScope ps(Phase::kReduce);
        fn(result, stage.data(), count);
      }
    }
    mask <<= 1;
    round++;
  }
}

// Bandwidth-optimal reduce-to-root (contract of gloo/reduce.cc:61-246):
// the pipelined ring reduce-scatter leaves rank r owning reduced block r
// in-place, then every rank ships its one block straight to the root —
// ~2N bytes per link total and ~N bytes through the root's in-link,
// vs the binomial's log2(P) * N. Reuses ringReduceScatter wholesale
// (segment pipelining, two-ahead pre-posts, fused receive-reduce).
void ringReduce(Context* ctx, plan::Plan& plan, char* work,
                transport::UnboundBuffer* workBuf, size_t count,
                size_t elsize, ReduceFn fn, int root, bool fuseOk,
                Slot slot, std::chrono::milliseconds timeout) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const Blocks& blocks =
      plan.blocks(0, [&] { return evenBlocks(count, size, elsize); });
  ringReduceScatter(ctx, plan, work, blocks, fn, elsize, slot, 0,
                    /*startShift=*/-1, timeout, workBuf, fuseOk);
  // Gather phase: block b travels root's in-link exactly once. Slots
  // continue past the reduce-scatter's reserved range.
  const uint64_t gatherBase =
      ringReduceScatterSlotSpan(plan, blocks, elsize);
  if (rank == root) {
    int pending = 0;
    {
      PhaseScope ps(Phase::kPost);
      for (int b = 0; b < size; b++) {
        if (b == rank || blocks.bytes[b] == 0) {
          continue;
        }
        workBuf->recv(b, slot.offset(gatherBase + uint64_t(b)).value(),
                      blocks.offset[b], blocks.bytes[b]);
        pending++;
      }
    }
    PhaseScope ps(Phase::kWireWait);
    for (int i = 0; i < pending; i++) {
      workBuf->waitRecv(nullptr, timeout);
    }
  } else if (blocks.bytes[rank] > 0) {
    {
      PhaseScope ps(Phase::kPost);
      workBuf->send(root,
                    slot.offset(gatherBase + uint64_t(rank)).value(),
                    blocks.offset[rank], blocks.bytes[rank]);
    }
    PhaseScope ps(Phase::kWireWait);
    workBuf->waitSend(timeout);
  }
}

}  // namespace

void reduce(ReduceOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "reduce: null context");
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE(opts.root >= 0 && opts.root < size, "reduce: bad root");
  const size_t elsize = elementSize(opts.dtype);
  const size_t nbytes = opts.count * elsize;
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kReduce, nbytes);
  FlightRecOp frOp(&ctx->flightrec(), "reduce", nullptr,
                   Slot::build(SlotPrefix::kReduce, opts.tag).value(),
                   opts.root, nbytes, static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "reduce", frOp.cseq(), nbytes);
  span::OpScope spanOp(&ctx->spans(), "reduce", frOp.cseq());
  ReduceFn fn = opts.customFn != nullptr
                  ? opts.customFn
                  : getReduceFn(opts.dtype, opts.op);

  const bool isRoot = rank == opts.root;
  TC_ENFORCE(!isRoot || opts.output != nullptr, "reduce: root needs output");
  if (size == 1 || opts.count == 0) {
    if (isRoot && opts.output != opts.input && nbytes > 0) {
      std::memcpy(opts.output, opts.input, nbytes);
    }
    return;
  }

  Slot slot = Slot::build(SlotPrefix::kReduce, opts.tag);
  const bool fuseOk = opts.customFn == nullptr;
  ReduceAlgorithm algo = opts.algorithm;
  if (algo == ReduceAlgorithm::kAuto) {
    // Measured tuning table first, then the loopback-measured fallback
    // (reduce-to-root sweep, round 4): the binomial wins
    // p50 through ~4 MiB (its log2(P) full-payload rounds ride the eager
    // pipeline well on one host) but its p99 tail is 3-4x WORSE than the
    // ring's from ~1 MiB up (full-payload rounds spike when the
    // shared-core scheduler misaligns). The fallback follows the p99
    // crossover — tail latency is what a collective's callers stall on —
    // and real multi-host DCN crosses earlier still (the root's in-link
    // serializes): tune there, or drop TPUCOLL_REDUCE_BINOMIAL_MAX to
    // ~256K-1M.
    if (auto tuned = tuning::tableReduce(ctx, opts.dtype, nbytes)) {
      algo = *tuned;
    } else {
      static const size_t binMax = collectives_detail::envBytes(
          "TPUCOLL_REDUCE_BINOMIAL_MAX", 2u << 20);
      algo = nbytes <= binMax ? ReduceAlgorithm::kBinomial
                              : ReduceAlgorithm::kRing;
    }
  }
  auto traceSpan = ctx->tracer().span(
      "reduce", nbytes, -1, tuning::reduceAlgorithmName(algo));
  frOp.setAlgorithm(tuning::reduceAlgorithmName(algo));
  profOp.setAlgorithm(tuning::reduceAlgorithmName(algo));

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kReduce);
  key.algorithm = static_cast<uint8_t>(algo);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.op = static_cast<uint8_t>(opts.op);
  key.root = opts.root;
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = nbytes;
  PlanHandle planh =
      fuseOk ? PlanHandle(ctx, key) : PlanHandle(ctx);

  // Non-root ranks work in plan scratch (the ring writes the whole
  // buffer during the reduce-scatter phase, so it must be full-size
  // even though only one block of it is ever sent on). The stage's
  // registration doubles as the schedule's work buffer.
  char* result;
  transport::UnboundBuffer* resultBuf;
  if (isRoot) {
    result = bytePtr(opts.output);
    resultBuf = planh->userBuf(0, result, nbytes);
  } else {
    auto st = planh->stage(kStageReduceResult, nbytes);
    result = st.data;
    resultBuf = st.buf;
  }
  if (result != opts.input) {
    PhaseScope ps(Phase::kPack);
    std::memcpy(result, opts.input, nbytes);
  }

  switch (algo) {
    case ReduceAlgorithm::kBinomial:
      binomialReduce(ctx, *planh, result, resultBuf, opts.count, elsize,
                     fn, opts.root, fuseOk, slot, timeout);
      break;
    case ReduceAlgorithm::kRing:
      ringReduce(ctx, *planh, result, resultBuf, opts.count, elsize, fn,
                 opts.root, fuseOk, slot, timeout);
      break;
    default:
      TC_THROW(EnforceError, "unknown reduce algorithm");
  }
}

// Ring reduce-scatter with per-rank result blocks (reference analog:
// gloo/reduce_scatter.h halving-doubling; the ring keeps per-step traffic
// uniform and handles arbitrary recvCounts without bit-reversal reordering).
void reduceScatter(ReduceScatterOptions& opts) {
  Context* ctx = opts.context;
  TC_ENFORCE(ctx != nullptr, "reduceScatter: null context");
  auto traceSpan = ctx->tracer().span("reduce_scatter");
  const auto timeout = detail::effectiveTimeout(opts);
  const int rank = ctx->rank();
  const int size = ctx->size();
  TC_ENFORCE_EQ(opts.recvCounts.size(), static_cast<size_t>(size));
  const size_t elsize = elementSize(opts.dtype);
  ReduceFn fn = opts.customFn != nullptr
                  ? opts.customFn
                  : getReduceFn(opts.dtype, opts.op);
  size_t total = 0;
  for (size_t c : opts.recvCounts) {
    total += c * elsize;
  }
  MetricsOp metricsOp(&ctx->metrics(), MetricOp::kReduceScatter, total);
  FlightRecOp frOp(
      &ctx->flightrec(), "reduce_scatter", nullptr,
      Slot::build(SlotPrefix::kReduceScatter, opts.tag).value(), -1, total,
      static_cast<uint8_t>(opts.dtype));
  ProfileOpScope profOp(&ctx->profiler(), "reduce_scatter", frOp.cseq(),
                        total);
  span::OpScope spanOp(&ctx->spans(), "reduce_scatter", frOp.cseq());

  if (size == 1) {
    std::memcpy(opts.output, opts.input, total);
    return;
  }

  Slot slot = Slot::build(SlotPrefix::kReduceScatter, opts.tag);
  const bool fuseOk = opts.customFn == nullptr;
  ReduceScatterAlgorithm algo = opts.algorithm;
  // Flat topology: a hierarchical request has no second plane; run it
  // through the normal auto dispatch instead.
  if (algo == ReduceScatterAlgorithm::kHier && !group::hierEligible(ctx)) {
    algo = ReduceScatterAlgorithm::kAuto;
  }
  if (algo == ReduceScatterAlgorithm::kAuto && fuseOk) {
    // Installed schedule plane first (see allreduce). Generated
    // reduce-scatter schedules assume even chunk geometry (chunk r is
    // rank r's result block); uneven recvCounts fall through to native.
    bool even = true;
    for (size_t c : opts.recvCounts) {
      even = even && c == opts.recvCounts[0];
    }
    if (even) {
      if (auto prog = electedSchedule(ctx, "reduce_scatter", opts.dtype,
                                      total, /*codedOk=*/false)) {
        const char* lbl = schedule::internedLabel(prog->label);
        auto schedSpan =
            ctx->tracer().span("reduce_scatter", total, -1, lbl);
        frOp.setAlgorithm(lbl);
        profOp.setAlgorithm(lbl);
        PlanKey key;
        key.opcode = static_cast<uint8_t>(PlanOp::kReduceScatter);
        key.algorithm = kScheduledAlgorithm;
        key.dtype = static_cast<uint8_t>(opts.dtype);
        key.op = static_cast<uint8_t>(opts.op);
        key.tag = opts.tag;
        key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
        key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
        key.nbytes = total;
        key.aux = plan::hashCounts(opts.recvCounts) ^ fnvName(prog->name);
        PlanHandle planh(ctx, key);
        // Work in a plan-staged copy so the caller's input stays
        // intact; the stage's registration doubles as the schedule's
        // work buffer (the interpreter owns slots 0/1).
        auto st = planh->stage(kStageRsWork, total);
        {
          PhaseScope ps(Phase::kPack);
          std::memcpy(st.data, opts.input, total);
        }
        schedule::run(ctx, *planh, *prog, st.data, total / elsize, elsize,
                      fn, opts.dtype, slot, timeout, st.buf);
        {
          PhaseScope ps(Phase::kUnpack);
          const size_t blockBytes = opts.recvCounts[rank] * elsize;
          std::memcpy(opts.output, st.data + size_t(rank) * blockBytes,
                      blockBytes);
        }
        return;
      }
    }
  }
  if (algo == ReduceScatterAlgorithm::kAuto) {
    // Measured tuning table first (keyed by total payload bytes), then
    // the crossovers measured on loopback P=4/8 (round 3):
    // recursive halving wins through ~256K, the ring beyond. The
    // single-round direct exchange loses on a shared-core loopback
    // (its P*(P-1) total messages cost more than its one-round latency
    // saves there), so the fallback defaults it OFF; a tuned table on
    // real DCN, where propagation delay dominates per-message CPU, can
    // elect it from measurement. TPUCOLL_RS_DIRECT_MAX /
    // TPUCOLL_RS_HD_MAX move the fallback crossovers (total payload
    // bytes).
    if (auto tuned = tuning::tableReduceScatter(ctx, opts.dtype, total)) {
      algo = *tuned;
    } else {
      static const size_t directMax = collectives_detail::envBytes(
          "TPUCOLL_RS_DIRECT_MAX", 0);
      static const size_t hdMax = collectives_detail::envBytes(
          "TPUCOLL_RS_HD_MAX", 256u << 10);
      algo = total <= directMax ? ReduceScatterAlgorithm::kDirect
             : total <= hdMax   ? ReduceScatterAlgorithm::kHalvingDoubling
                                : ReduceScatterAlgorithm::kRing;
    }
  }
  frOp.setAlgorithm(tuning::reduceScatterAlgorithmName(algo));
  profOp.setAlgorithm(tuning::reduceScatterAlgorithmName(algo));
  if (algo == ReduceScatterAlgorithm::kHier) {
    // Phases are collectives on split sub-contexts with their own plan
    // caches; the parent plan machinery below is skipped.
    group::hierReduceScatter(ctx, opts.input, opts.output,
                             opts.recvCounts, opts.dtype, opts.op,
                             opts.customFn, opts.tag, timeout);
    return;
  }

  PlanKey key;
  key.opcode = static_cast<uint8_t>(PlanOp::kReduceScatter);
  key.algorithm = static_cast<uint8_t>(algo);
  key.dtype = static_cast<uint8_t>(opts.dtype);
  key.op = static_cast<uint8_t>(opts.op);
  key.tag = opts.tag;
  key.ptrA = reinterpret_cast<uintptr_t>(opts.input);
  key.ptrB = reinterpret_cast<uintptr_t>(opts.output);
  key.nbytes = total;
  key.aux = plan::hashCounts(opts.recvCounts);
  PlanHandle planh =
      fuseOk ? PlanHandle(ctx, key) : PlanHandle(ctx);
  const Blocks& blocks = planh->blocks(
      0, [&] { return countBlocks(opts.recvCounts, elsize); });

  // Work in a plan-staged copy so the caller's input stays intact; the
  // stage's registration is the schedule's work buffer.
  auto st = planh->stage(kStageRsWork, total);
  char* work = st.data;
  {
    PhaseScope ps(Phase::kPack);
    std::memcpy(work, opts.input, total);
  }
  switch (algo) {
    case ReduceScatterAlgorithm::kDirect:
      algorithms::directReduceScatter(ctx, *planh, work, st.buf, blocks,
                                      fn, elsize, slot, timeout, fuseOk);
      break;
    case ReduceScatterAlgorithm::kHalvingDoubling:
      algorithms::hdReduceScatter(ctx, *planh, work, st.buf, blocks, fn,
                                  elsize, slot, timeout, fuseOk);
      break;
    case ReduceScatterAlgorithm::kRing:
      ringReduceScatter(ctx, *planh, work, blocks, fn, elsize, slot, 0,
                        /*startShift=*/-1, timeout, st.buf, fuseOk);
      break;
    case ReduceScatterAlgorithm::kRingQ8Wire:
      TC_ENFORCE(opts.dtype == DataType::kFloat32,
                 "q8-wire reduce_scatter requires float32 payloads");
      TC_ENFORCE(opts.op == ReduceOp::kSum && opts.customFn == nullptr,
                 "q8-wire reduce_scatter supports builtin sum only");
      algorithms::q8WireRingReduceScatter(ctx, *planh, work, st.buf,
                                          blocks, slot, timeout);
      break;
    case ReduceScatterAlgorithm::kRingQ4Wire:
      TC_ENFORCE(opts.dtype == DataType::kFloat32,
                 "q4-wire reduce_scatter requires float32 payloads");
      TC_ENFORCE(opts.op == ReduceOp::kSum && opts.customFn == nullptr,
                 "q4-wire reduce_scatter supports builtin sum only");
      algorithms::q4WireRingReduceScatter(ctx, *planh, work, st.buf,
                                          blocks, slot, timeout);
      break;
    default:
      TC_THROW(EnforceError, "unknown reduce_scatter algorithm");
  }
  {
    PhaseScope ps(Phase::kUnpack);
    std::memcpy(opts.output, work + blocks.offset[rank],
                blocks.bytes[rank]);
  }
}

}  // namespace tpucoll

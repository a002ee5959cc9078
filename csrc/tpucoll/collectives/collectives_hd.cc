// Recursive halving-doubling allreduce (Rabenseifner's algorithm over
// block windows).
//
// Reduce-scatter by recursive vector halving: at each round a rank and its
// partner (rank XOR mask, mask from P/2 down to 1) exchange complementary
// halves of the current block window and reduce the half they keep. After
// log2(P) rounds each rank's window is exactly its own block, fully
// reduced — the window bookkeeping lands block r on rank r directly, with
// no bit-reversal pass (contrast reference reduce_scatter.h:21-329).
// Allgather by recursive doubling reverses the walk, windows merging with
// their siblings until every rank holds the full vector.
//
// Non-power-of-2 group sizes use a binary-blocks decomposition (behavior
// parity with gloo/allreduce_halving_doubling.h:39-64 initBinaryBlocks,
// re-derived for this build's in-order window walk): P is split into
// power-of-2 blocks by its binary representation, larger blocks at lower
// ranks. Each block reduce-scatters internally over the full vector, then
// partial windows flow up the block chain smallest -> largest (each rank's
// inter-block traffic is proportional to its window, unlike the fold,
// where 2*rem ranks exchange the whole vector twice). The fully reduced
// windows flow back down the chain, and each block allgathers internally.
// The fold path is kept as TPUCOLL_HD_NP2=fold for small payloads where
// its fewer messages can win.
#include <cstdlib>
#include <cstring>

#include "tpucoll/collectives/algorithms.h"
#include "tpucoll/collectives/detail.h"
#include "tpucoll/collectives/plan.h"
#include "tpucoll/common/profile.h"
#include "tpucoll/tuning/dispatch.h"

namespace tpucoll {
namespace algorithms {

using collectives_detail::Blocks;
using collectives_detail::evenBlocks;
using collectives_detail::largestPow2AtMost;
using collectives_detail::fuseRecvReduce;
using plan::LazyStage;
using profile::Phase;
using profile::PhaseScope;

namespace {

// Slot-delta bases keep every phase's tags disjoint (Slot::offset is
// bounds-checked against the 24-bit delta field, types.h).
constexpr uint64_t kRsBase = 0x1000;
constexpr uint64_t kFwdBase = 0x2000;
constexpr uint64_t kBwdBase = 0x3000;
constexpr uint64_t kAgBase = 0x4000;
constexpr uint64_t kRedistBase = 0x5000;
constexpr uint64_t kFoldBase = 0;
constexpr uint64_t kUnfoldSlot = 1 << 20;

}  // namespace

void hdFoldAllreduce(Context* ctx, plan::Plan& plan, char* work,
                     size_t count, size_t elsize, ReduceFn fn, Slot slot,
                     std::chrono::milliseconds timeout, bool fuseOk) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const size_t nbytes = count * elsize;
  const int pow2 = static_cast<int>(largestPow2AtMost(size));
  const int rem = size - pow2;

  auto* workBuf = plan.userBuf(0, work, nbytes);
  // Fused receive-reduce (single policy: collectives_detail::
  // fuseRecvReduce): every receive-with-reduce in this walk targets a
  // range disjoint from any concurrently sent range, so partner partials
  // may be combined into `work` by the transport. The decision is per
  // partner (they change each round); scratch materializes lazily, only
  // if some round falls back.
  auto canFuse = [&](int src) {
    return fuseRecvReduce(ctx, fuseOk, elsize, src);
  };
  LazyStage stage(plan, 1, nbytes);

  // Fold: the first 2*rem ranks pair (even, odd); odds contribute their
  // vector to their even partner and sit out the exchange.
  uint64_t round = kFoldBase;
  int vrank;
  if (rank < 2 * rem) {
    if (rank % 2 == 1) {
      {
        PhaseScope ps(Phase::kPost, rank - 1, slot.offset(round).value(),
                      nbytes);
        workBuf->send(rank - 1, slot.offset(round).value(), 0, nbytes);
      }
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
      vrank = -1;
    } else {
      if (canFuse(rank + 1)) {
        {
          PhaseScope ps(Phase::kPost);
          workBuf->recvReduce(rank + 1, slot.offset(round).value(), fn,
                              elsize, 0, nbytes);
        }
        PhaseScope ps(Phase::kWireWait, rank + 1,
                      slot.offset(round).value(), nbytes);
        workBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kPost);
          stage.buf()->recv(rank + 1, slot.offset(round).value(), 0,
                            nbytes);
        }
        {
          PhaseScope ps(Phase::kWireWait, rank + 1,
                        slot.offset(round).value(), nbytes);
          stage.buf()->waitRecv(nullptr, timeout);
        }
        PhaseScope ps(Phase::kReduce);
        fn(work, stage.data(), count);
      }
      vrank = rank / 2;
    }
  } else {
    vrank = rank - rem;
  }
  round++;
  auto physical = [&](int v) { return v < rem ? 2 * v : v + rem; };

  if (vrank >= 0 && pow2 > 1) {
    const Blocks& blocks =
        plan.blocks(0, [&] { return evenBlocks(count, pow2, elsize); });
    auto rangeOff = [&](int first) { return blocks.offset[first]; };
    auto rangeBytes = [&](int first, int n) {
      return blocks.rangeBytes(first, n);
    };

    // --- reduce-scatter: recursive vector halving ---
    int winStart = 0;
    int winCount = pow2;
    for (int mask = pow2 / 2; mask >= 1; mask >>= 1, round++) {
      const int partner = physical(vrank ^ mask);
      const int half = winCount / 2;
      const bool keepLower = (vrank & mask) == 0;
      const int keepStart = keepLower ? winStart : winStart + half;
      const int sendStart = keepLower ? winStart + half : winStart;
      const uint64_t s = slot.offset(round).value();
      const bool fused = canFuse(partner);
      {
        PhaseScope ps(Phase::kPost);
        if (fused) {
          // Combined into the kept range on arrival; the sent half is
          // disjoint, so the in-flight send never reads combined bytes.
          workBuf->recvReduce(partner, s, fn, elsize, rangeOff(keepStart),
                              rangeBytes(keepStart, half));
        } else {
          // Receive into the scratch mirror at the kept range's own
          // offsets.
          stage.buf()->recv(partner, s, rangeOff(keepStart),
                            rangeBytes(keepStart, half));
        }
      }
      {
        PhaseScope ps(Phase::kPost, partner, s,
                      rangeBytes(sendStart, half));
        workBuf->send(partner, s, rangeOff(sendStart),
                      rangeBytes(sendStart, half));
      }
      if (fused) {
        PhaseScope ps(Phase::kWireWait, partner, s,
                      rangeBytes(keepStart, half));
        workBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kWireWait, partner, s,
                        rangeBytes(keepStart, half));
          stage.buf()->waitRecv(nullptr, timeout);
        }
        if (rangeBytes(keepStart, half) > 0) {
          PhaseScope ps(Phase::kReduce);
          fn(work + rangeOff(keepStart), stage.data() + rangeOff(keepStart),
             rangeBytes(keepStart, half) / elsize);
        }
      }
      {
        PhaseScope ps(Phase::kWireWait);
        workBuf->waitSend(timeout);
      }
      winStart = keepStart;
      winCount = half;
    }

    // --- allgather: recursive doubling (receives land in place) ---
    for (int mask = 1; mask < pow2; mask <<= 1, round++) {
      const int partner = physical(vrank ^ mask);
      const int partnerStart = winStart ^ winCount;  // sibling window
      const uint64_t s = slot.offset(round).value();
      {
        PhaseScope ps(Phase::kPost);
        workBuf->recv(partner, s, rangeOff(partnerStart),
                      rangeBytes(partnerStart, winCount));
      }
      {
        PhaseScope ps(Phase::kPost, partner, s,
                      rangeBytes(winStart, winCount));
        workBuf->send(partner, s, rangeOff(winStart),
                      rangeBytes(winStart, winCount));
      }
      {
        PhaseScope ps(Phase::kWireWait, partner, s,
                      rangeBytes(partnerStart, winCount));
        workBuf->waitRecv(nullptr, timeout);
      }
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
      winStart = std::min(winStart, partnerStart);
      winCount *= 2;
    }
  }

  // Unfold: even partners push the final vector back to the odd ranks.
  // A distinct sub-slot avoids any overlap with exchange rounds.
  const uint64_t finalSlot = slot.offset(kUnfoldSlot).value();
  if (rank < 2 * rem) {
    if (rank % 2 == 1) {
      {
        PhaseScope ps(Phase::kPost);
        workBuf->recv(rank - 1, finalSlot, 0, nbytes);
      }
      PhaseScope ps(Phase::kWireWait, rank - 1, finalSlot, nbytes);
      workBuf->waitRecv(nullptr, timeout);
    } else {
      {
        PhaseScope ps(Phase::kPost, rank + 1, finalSlot, nbytes);
        workBuf->send(rank + 1, finalSlot, 0, nbytes);
      }
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
    }
  }
}

void hdBinaryBlocksAllreduce(Context* ctx, plan::Plan& plan, char* work,
                             size_t count, size_t elsize, ReduceFn fn,
                             Slot slot, std::chrono::milliseconds timeout,
                             bool fuseOk) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const size_t nbytes = count * elsize;

  // Binary-blocks layout: one block per set bit of P, larger blocks at
  // lower ranks (so blocks[0] is the largest, at rank offset 0).
  std::vector<int> bsize, boff;
  for (int bit = 30, off = 0; bit >= 0; bit--) {
    if (size & (1 << bit)) {
      bsize.push_back(1 << bit);
      boff.push_back(off);
      off += 1 << bit;
    }
  }
  const int k = static_cast<int>(bsize.size());
  int b = k - 1;
  while (boff[b] > rank) {
    b--;
  }
  const int r = rank - boff[b];   // rank within my block
  const int B = bsize[b];         // my block's size
  const int Bmax = bsize[0];

  // All windows are unions of "atoms": the vector split Bmax ways. Every
  // block size divides Bmax, so window boundaries align across blocks.
  const Blocks& atoms =
      plan.blocks(0, [&] { return evenBlocks(count, Bmax, elsize); });
  auto atomOff = [&](int first) { return atoms.offset[first]; };
  auto atomBytes = [&](int first, int n) { return atoms.rangeBytes(first, n); };

  auto* workBuf = plan.userBuf(0, work, nbytes);
  // Fused receive-reduce (single policy: collectives_detail::
  // fuseRecvReduce; disjoint kept/sent ranges make direct combining
  // safe). Scratch only materializes if a partner falls back.
  auto canFuse = [&](int src) {
    return fuseRecvReduce(ctx, fuseOk, elsize, src);
  };
  LazyStage stage(plan, 1, nbytes);

  // --- intra-block reduce-scatter: recursive vector halving ---
  // The window walk lands atoms [r*Bmax/B, (r+1)*Bmax/B) on block rank r.
  int winStart = 0;
  int winCount = Bmax;
  int step = 0;
  for (int mask = B / 2; mask >= 1; mask >>= 1, step++) {
    const int partner = boff[b] + (r ^ mask);
    const int half = winCount / 2;
    const bool keepLower = (r & mask) == 0;
    const int keepStart = keepLower ? winStart : winStart + half;
    const int sendStart = keepLower ? winStart + half : winStart;
    const uint64_t s = slot.offset(kRsBase + step).value();
    const bool fused = canFuse(partner);
    {
      PhaseScope ps(Phase::kPost);
      if (fused) {
        workBuf->recvReduce(partner, s, fn, elsize, atomOff(keepStart),
                            atomBytes(keepStart, half));
      } else {
        stage.buf()->recv(partner, s, atomOff(keepStart),
                          atomBytes(keepStart, half));
      }
      workBuf->send(partner, s, atomOff(sendStart),
                    atomBytes(sendStart, half));
    }
    if (fused) {
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitRecv(nullptr, timeout);
    } else {
      {
        PhaseScope ps(Phase::kWireWait);
        stage.buf()->waitRecv(nullptr, timeout);
      }
      if (atomBytes(keepStart, half) > 0) {
        PhaseScope ps(Phase::kReduce);
        fn(work + atomOff(keepStart), stage.data() + atomOff(keepStart),
           atomBytes(keepStart, half) / elsize);
      }
    }
    {
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
    }
    winStart = keepStart;
    winCount = half;
  }

  // --- inter-block chain, forward leg (smallest -> largest) ---
  // Exchange e joins blocks e (larger side) and e+1 (smaller side); the
  // smaller side's windows are unions of the larger side's, so each
  // smaller rank scatters pieces while each larger rank receives exactly
  // its own window. The chain serializes naturally: a block cannot send
  // partials up before it has absorbed the block below it.
  if (b + 1 < k) {  // I am the larger side of exchange b.
    const int ratio = B / bsize[b + 1];
    const int peer = boff[b + 1] + r / ratio;
    const uint64_t s = slot.offset(kFwdBase + b).value();
    if (canFuse(peer)) {
      // No send is in flight on this side of the exchange; the partial
      // combines into the window in place.
      {
        PhaseScope ps(Phase::kPost);
        workBuf->recvReduce(peer, s, fn, elsize, atomOff(winStart),
                            atomBytes(winStart, winCount));
      }
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitRecv(nullptr, timeout);
    } else {
      {
        PhaseScope ps(Phase::kPost);
        stage.buf()->recv(peer, s, atomOff(winStart),
                          atomBytes(winStart, winCount));
      }
      {
        PhaseScope ps(Phase::kWireWait);
        stage.buf()->waitRecv(nullptr, timeout);
      }
      if (atomBytes(winStart, winCount) > 0) {
        PhaseScope ps(Phase::kReduce);
        fn(work + atomOff(winStart), stage.data() + atomOff(winStart),
           atomBytes(winStart, winCount) / elsize);
      }
    }
  }
  if (b > 0) {  // I am the smaller side of exchange b-1.
    const int ratioUp = bsize[b - 1] / B;
    const int Aup = Bmax / bsize[b - 1];  // atoms per larger-side window
    const uint64_t fwd = slot.offset(kFwdBase + b - 1).value();
    const uint64_t bwd = slot.offset(kBwdBase + b - 1).value();
    {
      PhaseScope ps(Phase::kPost);
      for (int j = 0; j < ratioUp; j++) {
        const int rUp = r * ratioUp + j;
        workBuf->send(boff[b - 1] + rUp, fwd, atomOff(rUp * Aup),
                      atomBytes(rUp * Aup, Aup));
      }
    }
    {
      PhaseScope ps(Phase::kWireWait);
      for (int j = 0; j < ratioUp; j++) {
        workBuf->waitSend(timeout);
      }
    }
    // --- backward leg: fully reduced pieces come back in place ---
    {
      PhaseScope ps(Phase::kPost);
      for (int j = 0; j < ratioUp; j++) {
        const int rUp = r * ratioUp + j;
        workBuf->recv(boff[b - 1] + rUp, bwd, atomOff(rUp * Aup),
                      atomBytes(rUp * Aup, Aup));
      }
    }
    PhaseScope ps(Phase::kWireWait);
    for (int j = 0; j < ratioUp; j++) {
      workBuf->waitRecv(nullptr, timeout);
    }
  }
  if (b + 1 < k) {  // Backward leg toward the block below me.
    const int ratio = B / bsize[b + 1];
    const int peer = boff[b + 1] + r / ratio;
    const uint64_t s = slot.offset(kBwdBase + b).value();
    {
      PhaseScope ps(Phase::kPost);
      workBuf->send(peer, s, atomOff(winStart),
                    atomBytes(winStart, winCount));
    }
    PhaseScope ps(Phase::kWireWait);
    workBuf->waitSend(timeout);
  }

  // --- intra-block allgather: recursive doubling ---
  step = 0;
  for (int mask = 1; mask < B; mask <<= 1, step++) {
    const int partner = boff[b] + (r ^ mask);
    const int partnerStart = winStart ^ winCount;  // sibling window
    const uint64_t s = slot.offset(kAgBase + step).value();
    {
      PhaseScope ps(Phase::kPost);
      workBuf->recv(partner, s, atomOff(partnerStart),
                    atomBytes(partnerStart, winCount));
      workBuf->send(partner, s, atomOff(winStart),
                    atomBytes(winStart, winCount));
    }
    PhaseScope ps(Phase::kWireWait);
    workBuf->waitRecv(nullptr, timeout);
    workBuf->waitSend(timeout);
    winStart = std::min(winStart, partnerStart);
    winCount *= 2;
  }
}

void hdReduceScatter(Context* ctx, plan::Plan& plan, char* work,
                     transport::UnboundBuffer* workBuf,
                     const Blocks& blocks, ReduceFn fn, size_t elsize,
                     Slot slot, std::chrono::milliseconds timeout,
                     bool fuseOk) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  const size_t nbytes =
      blocks.offset[size - 1] + blocks.bytes[size - 1];
  const int pow2 = static_cast<int>(largestPow2AtMost(size));
  const int rem = size - pow2;

  auto canFuse = [&](int src) {
    return fuseRecvReduce(ctx, fuseOk, elsize, src);
  };
  LazyStage stage(plan, 1, nbytes);

  // Fold (non-power-of-2 only): odd ranks of the first 2*rem contribute
  // their whole vector to their even partner and rejoin for the
  // redistribution at the end.
  int vrank;
  if (rank < 2 * rem) {
    if (rank % 2 == 1) {
      {
        PhaseScope ps(Phase::kPost);
        workBuf->send(rank - 1, slot.offset(kFoldBase).value(), 0, nbytes);
      }
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
      vrank = -1;
    } else {
      if (canFuse(rank + 1)) {
        {
          PhaseScope ps(Phase::kPost);
          workBuf->recvReduce(rank + 1, slot.offset(kFoldBase).value(),
                              fn, elsize, 0, nbytes);
        }
        PhaseScope ps(Phase::kWireWait);
        workBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kPost);
          stage.buf()->recv(rank + 1, slot.offset(kFoldBase).value(), 0,
                            nbytes);
        }
        {
          PhaseScope ps(Phase::kWireWait);
          stage.buf()->waitRecv(nullptr, timeout);
        }
        if (nbytes > 0) {
          PhaseScope ps(Phase::kReduce);
          fn(work, stage.data(), nbytes / elsize);
        }
      }
      vrank = rank / 2;
    }
  } else {
    vrank = rank - rem;
  }
  auto physical = [&](int v) { return v < rem ? 2 * v : v + rem; };

  // Recursive vector halving over windows of RESULT blocks (size of
  // them, arbitrary byte counts). Floor splits: both partners compute
  // half = c/2 from the shared window, so uneven windows stay in
  // lockstep; the upper window takes the extra block. Window byte
  // ranges are contiguous, so each round is one transfer.
  int pendingSends = 0;
  int winStart = 0;
  int winCount = size;
  if (vrank >= 0) {
    int step = 0;
    for (int mask = pow2 / 2; mask >= 1; mask >>= 1, step++) {
      const int half = winCount / 2;
      const int partner = physical(vrank ^ mask);
      const bool keepLower = (vrank & mask) == 0;
      const int keepStart = keepLower ? winStart : winStart + half;
      const int keepCount = keepLower ? half : winCount - half;
      const int sendStart = keepLower ? winStart + half : winStart;
      const int sendCount = winCount - keepCount;
      const uint64_t s = slot.offset(kRsBase + step).value();
      const size_t keepBytes = blocks.rangeBytes(keepStart, keepCount);
      const bool fused = canFuse(partner);
      {
        PhaseScope ps(Phase::kPost);
        if (fused) {
          workBuf->recvReduce(partner, s, fn, elsize,
                              blocks.offset[keepStart], keepBytes);
        } else {
          stage.buf()->recv(partner, s, blocks.offset[keepStart],
                            keepBytes);
        }
        workBuf->send(partner, s, blocks.offset[sendStart],
                      blocks.rangeBytes(sendStart, sendCount));
      }
      if (fused) {
        PhaseScope ps(Phase::kWireWait);
        workBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kWireWait);
          stage.buf()->waitRecv(nullptr, timeout);
        }
        if (keepBytes > 0) {
          PhaseScope ps(Phase::kReduce);
          fn(work + blocks.offset[keepStart],
             stage.data() + blocks.offset[keepStart], keepBytes / elsize);
        }
      }
      // Send completions are deferred to the end of the call: every
      // round's sent range is disjoint from all later combine targets
      // (each round's keep window excludes what was sent), so in-flight
      // data is never rewritten and the blocking wait would only add
      // log2(P) stalls to a latency-bound path.
      pendingSends++;
      winStart = keepStart;
      winCount = keepCount;
    }
  }

  // Redistribution: power-of-2 groups land window == {block vrank ==
  // block rank} and this phase is empty. Otherwise each participant
  // ships the foreign blocks in its window to their real ranks, and
  // every rank whose block ended elsewhere (including folded-out odd
  // ranks) receives it. ownerOf replays the deterministic window walk.
  auto ownerOf = [&](int j) {
    int v = 0, s = 0, c = size;
    for (int mask = pow2 / 2; mask >= 1; mask >>= 1) {
      const int half = c / 2;
      if (j < s + half) {
        c = half;
      } else {
        v |= mask;
        s += half;
        c -= half;
      }
    }
    return v;
  };
  if (vrank >= 0) {
    PhaseScope ps(Phase::kPost);
    for (int j = winStart; j < winStart + winCount; j++) {
      if (j == rank || blocks.bytes[j] == 0) {
        continue;
      }
      workBuf->send(j, slot.offset(kRedistBase + uint64_t(j)).value(),
                    blocks.offset[j], blocks.bytes[j]);
      pendingSends++;
    }
  }
  const int owner = physical(ownerOf(rank));
  if (owner != rank && blocks.bytes[rank] > 0) {
    {
      PhaseScope ps(Phase::kPost);
      workBuf->recv(owner,
                    slot.offset(kRedistBase + uint64_t(rank)).value(),
                    blocks.offset[rank], blocks.bytes[rank]);
    }
    PhaseScope ps(Phase::kWireWait);
    workBuf->waitRecv(nullptr, timeout);
  }
  PhaseScope ps(Phase::kWireWait);
  for (int i = 0; i < pendingSends; i++) {
    workBuf->waitSend(timeout);
  }
}

void directReduceScatter(Context* ctx, plan::Plan& plan, char* work,
                         transport::UnboundBuffer* workBuf,
                         const Blocks& blocks, ReduceFn fn, size_t elsize,
                         Slot slot, std::chrono::milliseconds timeout,
                         bool fuseOk) {
  const int rank = ctx->rank();
  const int size = ctx->size();

  // One latency round: ship this rank's copy of block j straight to
  // rank j, all P-1 transfers concurrently in flight.
  int sends = 0;
  {
    PhaseScope ps(Phase::kPost);
    for (int j = 0; j < size; j++) {
      if (j == rank || blocks.bytes[j] == 0) {
        continue;
      }
      workBuf->send(j, slot.offset(uint64_t(j)).value(), blocks.offset[j],
                    blocks.bytes[j]);
      sends++;
    }
  }
  // P-1 partials land in this rank's block. The combines are serialized
  // (one outstanding recvReduce at a time): combine-on-arrival may run
  // on the loop thread or, for stash hits, on this thread — two
  // outstanding posts into the SAME range could race their accumulates.
  // Serial posting keeps the zero-copy combine and still overlaps the
  // wire time: senders fired already, later arrivals wait in the stash.
  if (blocks.bytes[rank] > 0) {
    LazyStage stage(plan, 1, blocks.bytes[rank]);
    for (int s = 0; s < size; s++) {
      if (s == rank) {
        continue;
      }
      if (fuseRecvReduce(ctx, fuseOk, elsize, s)) {
        {
          PhaseScope ps(Phase::kPost);
          workBuf->recvReduce(s, slot.offset(uint64_t(rank)).value(), fn,
                              elsize, blocks.offset[rank],
                              blocks.bytes[rank]);
        }
        PhaseScope ps(Phase::kWireWait);
        workBuf->waitRecv(nullptr, timeout);
      } else {
        {
          PhaseScope ps(Phase::kPost);
          stage.buf()->recv(s, slot.offset(uint64_t(rank)).value(), 0,
                            blocks.bytes[rank]);
        }
        {
          PhaseScope ps(Phase::kWireWait);
          stage.buf()->waitRecv(nullptr, timeout);
        }
        PhaseScope ps(Phase::kReduce);
        fn(work + blocks.offset[rank], stage.data(),
           blocks.bytes[rank] / elsize);
      }
    }
  }
  PhaseScope ps(Phase::kWireWait);
  for (int i = 0; i < sends; i++) {
    workBuf->waitSend(timeout);
  }
}

// Recursive doubling: log2(P) rounds; round k exchanges the FULL
// running vector with partner = rank ^ (1 << k) and folds it in. Half
// the rounds of the halving-doubling pair (no allgather phase), at
// full-vector bytes per round — the alpha-dominated tiny-payload tier.
// Send and receive ranges overlap (both are the whole vector), so the
// receive always stages: folding into `work` while the concurrent send
// still reads it would race.
//
// Non-power-of-2 groups use the standard pre/post fold (Rabenseifner's
// small-message variant): with p2 the largest power of 2 <= P and
// rem = P - p2, the first 2*rem ranks pair up — each odd "extra" ships
// its whole vector to the even survivor below it, sits out the log
// rounds, and receives the finished result. At the tiny payloads this
// tier serves the two fold messages cost ~1 alpha each, keeping total
// latency at log2(p2)+2 rounds vs fold-HD's 2*log2(p2)+2 — the same
// 2x round advantage the pow-2 path has.
//
// Bitwise identity across ranks: survivors enter the log rounds with
// subgroup-identical values; at each round both partners compute
// fn(X, Y) / fn(Y, X) over identical operand bits, and IEEE addition
// (and min/max) is commutative, so every merged group stays bitwise
// identical by induction. Extras receive those exact bits.
void recursiveDoublingAllreduce(Context* ctx, plan::Plan& plan,
                                char* work, size_t count, size_t elsize,
                                ReduceFn fn, Slot slot,
                                std::chrono::milliseconds timeout) {
  const int rank = ctx->rank();
  const int size = ctx->size();
  int p2 = 1;
  while (p2 * 2 <= size) {
    p2 *= 2;
  }
  const int rem = size - p2;
  const size_t nbytes = count * elsize;
  auto* workBuf = plan.userBuf(0, work, nbytes);
  // Slot layout: offset 0 = pre-fold, 1 = result return, 2+k = round k.
  const bool extra = rank < 2 * rem && (rank & 1) != 0;
  const bool paired = rank < 2 * rem && (rank & 1) == 0;
  if (extra) {
    // Extras never touch scratch — keep their path allocation-free.
    {
      PhaseScope ps(Phase::kPost, rank - 1, slot.offset(0).value(), nbytes);
      workBuf->send(rank - 1, slot.offset(0).value(), 0, nbytes);
    }
    {
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
    }
    {
      PhaseScope ps(Phase::kPost);
      workBuf->recv(rank - 1, slot.offset(1).value(), 0, nbytes);
    }
    PhaseScope ps(Phase::kWireWait, rank - 1, slot.offset(1).value(), nbytes);
    workBuf->waitRecv(nullptr, timeout);
    return;
  }
  // Receive staging (send/recv ranges overlap — both are the whole
  // vector — so the receive can never fold in place): plan-staged, so
  // the repeated tiny-payload call this tier serves replays with no
  // allocation and no registration. This was the last per-op
  // std::vector<char> scratch in the allreduce family.
  auto st = plan.stage(1, nbytes);
  char* scratch = st.data;
  transport::UnboundBuffer* scratchBuf = st.buf;
  if (paired) {
    {
      PhaseScope ps(Phase::kPost);
      scratchBuf->recv(rank + 1, slot.offset(0).value(), 0, nbytes);
    }
    {
      PhaseScope ps(Phase::kWireWait, rank + 1, slot.offset(0).value(),
                    nbytes);
      scratchBuf->waitRecv(nullptr, timeout);
    }
    PhaseScope ps(Phase::kReduce);
    fn(work, scratch, count);
  }
  // Survivors renumber into a dense [0, p2) space for the XOR walk.
  const int rdRank = paired ? rank / 2 : rank - rem;
  uint64_t round = 0;
  for (int k = 1; k < p2; k <<= 1, round++) {
    const int rdPartner = rdRank ^ k;
    const int partner = rdPartner < rem ? 2 * rdPartner : rdPartner + rem;
    {
      PhaseScope ps(Phase::kPost);
      scratchBuf->recv(partner, slot.offset(2 + round).value(), 0, nbytes);
    }
    {
      PhaseScope ps(Phase::kPost, partner, slot.offset(2 + round).value(),
                    nbytes);
      workBuf->send(partner, slot.offset(2 + round).value(), 0, nbytes);
    }
    {
      PhaseScope ps(Phase::kWireWait);
      workBuf->waitSend(timeout);
    }
    {
      PhaseScope ps(Phase::kWireWait, partner,
                    slot.offset(2 + round).value(), nbytes);
      scratchBuf->waitRecv(nullptr, timeout);
    }
    PhaseScope ps(Phase::kReduce);
    fn(work, scratch, count);
  }
  if (paired) {
    {
      PhaseScope ps(Phase::kPost, rank + 1, slot.offset(1).value(), nbytes);
      workBuf->send(rank + 1, slot.offset(1).value(), 0, nbytes);
    }
    PhaseScope ps(Phase::kWireWait);
    workBuf->waitSend(timeout);
  }
}

void halvingDoublingAllreduce(Context* ctx, plan::Plan& plan, char* work,
                              size_t count, size_t elsize, ReduceFn fn,
                              Slot slot, std::chrono::milliseconds timeout,
                              bool fuseOk) {
  const int size = ctx->size();
  const bool pow2 = (size & (size - 1)) == 0;
  if (pow2) {
    // Power-of-2 groups: binary-blocks degenerates to the same single-
    // block walk; route through the fold path (rem == 0, no fold step).
    hdFoldAllreduce(ctx, plan, work, count, elsize, fn, slot, timeout,
                    fuseOk);
    return;
  }
  // Non-power-of-2 strategy. Loopback-measured crossover (P=6): fold's fewer messages win while per-message overhead dominates;
  // binary-blocks' proportional byte work wins once payloads are large.
  // TPUCOLL_HD_NP2=blocks|fold forces either; otherwise the installed
  // tuning table's measured hd_fold/hd_blocks curves decide when both
  // arms were swept on this deployment, and the TPUCOLL_HD_NP2_CROSSOVER
  // byte threshold is the untuned fallback.
  bool useBlocks;
  const char* env =
      envChoice("TPUCOLL_HD_NP2", "auto", {"blocks", "fold", "auto"});
  if (std::strcmp(env, "blocks") == 0) {
    useBlocks = true;
  } else if (std::strcmp(env, "fold") == 0) {
    useBlocks = false;
  } else if (auto tuned = tuning::tableHdUseBlocks(ctx, count * elsize)) {
    useBlocks = *tuned;
  } else {
    static const size_t crossover = collectives_detail::envBytes(
        "TPUCOLL_HD_NP2_CROSSOVER", 1 << 20);
    useBlocks = count * elsize >= crossover;
  }
  if (useBlocks) {
    hdBinaryBlocksAllreduce(ctx, plan, work, count, elsize, fn, slot,
                            timeout, fuseOk);
  } else {
    hdFoldAllreduce(ctx, plan, work, count, elsize, fn, slot, timeout,
                    fuseOk);
  }
}

}  // namespace algorithms
}  // namespace tpucoll

// Codec worker pool: the shared thread lanes the wire codecs (math.h
// q8/q4/bf16 streams) run on when a hop is large enough to shard.
//
// A phase profile of the q8 ring on a CPU host put its bottleneck in the
// encoder, not the wire: at 64 MiB pack+unpack was ~62 ms of a ~100 ms
// op while wire_wait sat at 16 ms. The pool takes the serial codec off
// the caller's critical path two ways:
//
//   - parallelFor(): shard a stream across the caller + workers at
//     deterministic whole-unit boundaries (collectives/wire_codec.h
//     computes them), so the concatenated output is byte-identical to
//     the serial walk for ANY pool width — wire consensus never depends
//     on TPUCOLL_CODEC_THREADS.
//   - submit()/wait(): run one sub-block's encode+send (or decode)
//     asynchronously while the caller blocks in waitRecv, which is what
//     lets the pipelined ring (TPUCOLL_CODEC_PIPELINE) overlap codec
//     time with wire time and keep the op thread's pack bucket down to
//     the residual join.
//
// Sizing: TPUCOLL_CODEC_THREADS (strict, [1, 64]); unset defaults to
// the transport loop width (TPUCOLL_LOOP_THREADS, itself default 1), so
// a host provisioned with N loop threads gets N codec lanes without a
// second knob. Width 1 means no worker threads at all: submit() runs
// inline and parallelFor() degrades to the serial loop — byte-identical
// by construction, zero new threads (the default).
//
// Fork-safety: workers are spawned lazily on first use and pinned to
// the spawning pid; a forked child sees a foreign pid and runs inline
// instead of touching inherited (dead) threads.
#pragma once

#include <condition_variable>
#include <cstddef>
#include <cstdint>
#include <deque>
#include <functional>
#include <memory>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include <sys/types.h>

namespace tpucoll {
namespace codec {

// Resolved pool width (TPUCOLL_CODEC_THREADS, default = loop threads);
// >= 1, read once per process.
int codecThreads();

// Resolved pipeline depth for the wire rings (TPUCOLL_CODEC_PIPELINE,
// strict [1, 32], default 4): sub-blocks per ring hop. 1 restores the
// serial hop (one message per hop, the pre-pipeline wire protocol).
// Like TPUCOLL_Q8_BLOCK, the depth must match on every rank: it changes
// the per-hop message count and slot layout.
int codecPipelineDepth();

class CodecPool {
 public:
  static CodecPool& instance();

  int width() const { return width_; }
  int workers() const { return width_ - 1; }

  // Async job handle; 0 means "ran inline, nothing to wait for".
  using Ticket = uint64_t;

  // Enqueue fn on a worker; runs inline (and returns 0) when the pool
  // has no workers or the caller is a forked child. Jobs must not
  // throw — codec kernels are pure math over caller-owned memory.
  Ticket submit(std::function<void()> fn);

  // Block until the job behind `t` finished (no-op for t == 0).
  void wait(Ticket t);

  // fn(shard) for shard in [0, nShards), caller lane included; returns
  // when all shards finished. Shard->lane assignment is dynamic, so fn
  // must write only shard-owned ranges (the codec shards do).
  void parallelFor(size_t nShards, const std::function<void(size_t)>& fn);

  ~CodecPool();

 private:
  CodecPool();

  struct Job {
    std::function<void()> fn;
    Ticket id{0};
    bool done{false};
  };

  void ensureWorkers();
  void workerMain();

  const int width_;
  std::mutex mu_;
  std::condition_variable cv_;       // workers: queue not empty / stop
  std::condition_variable doneCv_;   // waiters: a job finished
  std::deque<std::shared_ptr<Job>> queue_;
  std::unordered_map<Ticket, std::shared_ptr<Job>> live_;
  Ticket nextId_{1};
  bool stop_{false};
  bool spawned_{false};
  pid_t ownerPid_{0};
  std::vector<std::thread> threads_;
};

}  // namespace codec
}  // namespace tpucoll

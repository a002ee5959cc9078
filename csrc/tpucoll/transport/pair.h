// Pair: one bidirectional point-to-point channel between this process and a
// peer rank, multiplexing all slot-tagged messages over a single TCP stream.
//
// Contract parity with the reference pair state machine (gloo/transport/tcp/
// pair.h:87-92, pair.cc) — connect/close lifecycle, async sends with inline
// fast path, error fan-out to pending operations — but with the eager wire
// protocol of wire.h instead of the notify/ready handshake, and with receive
// matching delegated to transport::Context.
#pragma once

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "tpucoll/fault/fault.h"
#include "tpucoll/transport/address.h"
#include "tpucoll/transport/loop.h"
#include "tpucoll/transport/shm.h"
#include "tpucoll/transport/unbound_buffer.h"
#include "tpucoll/transport/wire.h"

namespace tpucoll {
namespace transport {

class Context;
class Listener;

// Shared completion state for one striped logical send (TPUCOLL_CHANNELS
// > 1). The logical operation resolves EXACTLY ONCE, when the LAST
// stripe resolves (wire-completed or errored) — never earlier: an early
// onSendError would zero the buffer's pending-send count while sibling
// stripes on other channel pairs are still transmitting from its
// memory, letting ~UnboundBuffer free bytes a loop thread is reading
// (use-after-free). The last resolver delivers onSendError when ANY
// stripe failed (first recorded message wins) and onSendComplete
// otherwise; striped sends are never cancelled (cancelQueuedSends skips
// them — a sibling may already be on the wire, and shipping a partial
// message would hang the receiver's reassembly). Stripes live on
// different Pair objects, so the state is atomics + one cold-path mutex.
struct StripeTx {
  explicit StripeTx(int n) : remaining(n) {}
  std::atomic<int> remaining;  // unresolved stripes
  std::atomic<bool> failed{false};
  std::mutex errMu;
  std::string error;  // first failure message (errMu)

  void recordError(const std::string& msg) {
    std::lock_guard<std::mutex> guard(errMu);
    if (!failed.load(std::memory_order_relaxed)) {
      error = msg;
      failed.store(true, std::memory_order_relaxed);
    }
  }
};

class Pair : public Handler {
 public:
  enum class State : int {
    kInitializing = 0,
    kConnected = 2,
    kFailed = 3,
    kClosed = 4,
  };

  // `channel` is this connection's data-channel index within the logical
  // pair (0 = the primary connection, which alone carries control
  // traffic, sub-threshold messages, and the shm plane; >= 1 = an extra
  // stripe lane with its own handshake/encryption state, ideally on its
  // own loop). `loopIndex` names `loop` within the device pool for the
  // per-loop progress metrics.
  Pair(Context* context, Loop* loop, int selfRank, int peerRank,
       uint64_t localPairId, int channel = 0, int loopIndex = 0);
  ~Pair() override;

  uint64_t localPairId() const { return localPairId_; }
  int peerRank() const { return peerRank_; }
  int channel() const { return channel_; }

  // Initiator path (blocking, user thread): TCP connect to the peer's
  // listener and write the hello routing this connection to `remotePairId`.
  // Retries retryable failures (peer not accepting yet, reset mid-
  // handshake) with backoff until the deadline, emitting a structured
  // ConnectDebugData record per attempt (common/debug.h); set
  // TPUCOLL_DISABLE_CONNECTION_RETRIES to fail on the first error
  // (reference: GLOO_DISABLE_CONNECTION_RETRIES).
  void connect(const SockAddr& remote, uint64_t remotePairId,
               std::chrono::milliseconds timeout);

  // Listener path: register interest in an inbound connection carrying our
  // localPairId; the listener hands us the fd once the hello arrives.
  void expectViaListener(Listener* listener);

  void waitConnected(std::chrono::milliseconds timeout);

  // Async send; data must stay valid until the matching waitSend completes.
  void send(UnboundBuffer* ubuf, uint64_t slot, const char* data,
            size_t nbytes);

  // One stripe of a striped logical message (wire.h kStripe): this
  // channel's contiguous [data, data+nbytes) share of a `total`-byte
  // message split over `count` channels. `st` is the shared completion
  // state; `seqLow` tags all stripes of one message (reassembly
  // disambiguation). Only transport::Context calls this, once per
  // channel, in channel order.
  void sendStripe(UnboundBuffer* ubuf, uint64_t slot, const char* data,
                  size_t nbytes, uint64_t total, uint8_t count,
                  uint8_t seqLow, std::shared_ptr<StripeTx> st);

  // One-sided write into the peer's registered region (kPut framing).
  // notify: the target's exporting buffer gets a waitRecv completion on
  // arrival (bound-buffer semantics). `st` carries the shared completion
  // state when the put is one stripe of a striped logical put.
  void sendPut(UnboundBuffer* ubuf, uint64_t token, uint64_t roffset,
               const char* data, size_t nbytes, bool notify = false,
               std::shared_ptr<StripeTx> st = nullptr);

  // Enqueue a message whose payload the op itself owns (get requests and
  // get responses): no completion callback, safe from any thread.
  void sendOwned(WireHeader header, std::vector<char> payload);

  // Remove queued sends for `ubuf` that have not started hitting the wire;
  // returns how many were dropped. A partially-written front op cannot be
  // cancelled (removing it would corrupt the stream framing).
  int cancelQueuedSends(UnboundBuffer* ubuf);
  // True if any tx op (including a partially-written one) references ubuf.
  bool hasInflightSend(UnboundBuffer* ubuf);
  // Watchdog introspection: slot of the first queued/in-flight tx op that
  // references ubuf. Returns false when none does.
  bool sendSlotFor(UnboundBuffer* ubuf, uint64_t* slot);

  // Graceful close; pending operations fail. Idempotent, thread-safe.
  // `grace` bounds the goodbye/EOF drain (the default matches the
  // historical close behavior; the lazy broker evicts with a shorter
  // grace so a slow peer cannot stall the dial that triggered eviction).
  void close(std::chrono::milliseconds grace = std::chrono::milliseconds(2000));

  // ---- lazy broker hooks (transport::Context, boot plane) ----
  // Marks a peer-initiated connection accepted on demand via the lazy
  // pair-id namespace. Such a pair is rx-only (dual simplex: each side
  // sends only on connections it dialed), and on receiving the peer's
  // goodbye it answers with its own immediately — the evicting side's
  // close() then completes without waiting out its grace, and this
  // side's EOF tears down orderly. Set before connect/expect.
  void setLazyInbound() { lazyInbound_ = true; }
  // True once the pair tore down (failed or closed) — the broker drops
  // such pairs from its tables on the next scan.
  bool defunct() const {
    State s = state_.load(std::memory_order_acquire);
    return s == State::kFailed || s == State::kClosed;
  }
  // Eviction gate: connected with nothing queued or on the wire.
  bool idleForEvict();

  // Hard-fail the pair from a user thread (see Context::
  // failPairsWithInflightSend).
  void failFromUser(const std::string& message) { fail(message); }

  void handleEvents(uint32_t events) override;
  // Submission data path (uring engine): completion of an asyncRecv/
  // asyncSend posted by this pair. Loop thread.
  void handleIoComplete(bool isRecv, int32_t res) override;

  // Called by the listener (loop thread) when our inbound connection is up.
  // `keys` carries the connection's AEAD keys on encrypted devices; `shm`
  // the negotiated same-host payload segment (nullptr: TCP payloads), with
  // `shmInitiator` selecting this side's ring directions.
  void assumeConnected(int fd, const ConnKeys& keys = ConnKeys{},
                       std::unique_ptr<ShmSegment> shm = nullptr,
                       bool shmInitiator = false);

  // One-line tx/flow-control state for Context::debugDump (any thread).
  std::string debugState();

  // Shared-memory payload plane introspection (any thread).
  bool shmActive() const { return shmActive_.load(std::memory_order_relaxed); }
  uint64_t shmTxBytes() const {
    return shmTxBytes_.load(std::memory_order_relaxed);
  }
  uint64_t shmRxBytes() const {
    return shmRxBytes_.load(std::memory_order_relaxed);
  }

  // Receiver-side flow control (called by Context under its own lock):
  // pause stops reading this pair's socket so TCP backpressure throttles a
  // runaway sender; resume re-arms EPOLLIN. Safe from any thread.
  void pauseReading();
  void resumeReading();

 private:
  struct TxOp {
    WireHeader header;
    size_t headerSent{0};
    UnboundBuffer* ubuf;
    const char* data;
    size_t nbytes;
    size_t dataSent{0};
    // Striped logical send: completion routes through the shared state
    // (last stripe in wins) instead of completing ubuf directly.
    std::shared_ptr<StripeTx> stripe;
    // Encrypted framing: one sealed frame at a time (header frame, then
    // payload frames of kEncFrameBytes), built lazily when the op FIRST
    // starts transmitting so cancelled queued sends never consume a tx
    // sequence number (a consumed-but-unsent seq would desynchronize the
    // receiver's nonce counter). Framing bounds the staging buffer and
    // overlaps sealing with socket writes.
    std::vector<char> cipher;   // current frame (ciphertext + tag)
    size_t cipherSent{0};
    bool headerSealed{false};
    size_t sealOffset{0};       // payload bytes sealed so far
    // Self-owned payload (get requests/responses): `data` points into it.
    std::vector<char> ownedData;
    // Shared-memory payload plane (wire.h kShm*): the payload moves
    // through the pair's shm ring; the socket carries only the announce
    // header and per-chunk headers.
    bool viaShm{false};
    bool announceDone{false};       // announce header fully on the wire
    uint64_t shmWritten{0};         // payload bytes copied into the ring
    uint64_t shmAnnounced{0};       // payload bytes covered by chunk headers
    bool creditReqSent{false};      // a kShmCreditReq is out for this stall
    int64_t creditReqUs{0};         // when it went out (link RTT probe)
    WireHeader chunkHeader{};       // current chunk header (plain path)
    size_t chunkHeaderSent{0};
    bool chunkInFlight{false};
  };

  // Outcome of trying to advance the front shm op (mu_ held).
  enum class ShmTxStatus { kDone, kSocketFull, kRingBlocked, kError };

  // A finished tx op's completion routing: direct (ubuf) or through the
  // striped-send shared state. Built under mu_, delivered without it.
  struct TxDone {
    UnboundBuffer* ubuf;
    std::shared_ptr<StripeTx> stripe;
  };
  static void deliverSendComplete(const TxDone& d);
  static void deliverSendError(const TxDone& d, const std::string& msg);
  // Last-resolution outcome delivery for a striped send (see StripeTx).
  static void finalizeStripe(const TxDone& d);

  // Which tx cursor an in-flight data-path send advances on completion.
  // Each socket-write site in the flush functions is one site; the
  // completion replays exactly the cursor arithmetic the synchronous
  // path would have applied after its send() returned.
  enum class TxSite : uint8_t {
    kCtrl,             // ctrlSent_
    kFrontHeader,      // tx_.front().headerSent (plain shm announce)
    kFrontChunkHeader, // tx_.front().chunkHeaderSent (plain shm chunk)
    kFrontCipher,      // tx_.front().cipherSent (any sealed frame)
    kFrontPlain,       // tx_.front() header+data sendmsg split
  };

  // The socket-write primitive behind every flush site. Readiness mode:
  // sendmsg/send directly (EINTR retried; EAGAIN reported). Data-path
  // mode: submit ONE sendmsg SQE for the iovec (at most one in flight),
  // record `site`, and report EAGAIN — the flush stops exactly as if
  // the socket were full, and the completion advances the cursors and
  // re-runs it. mu_ held.
  ssize_t txWrite(TxSite site, const iovec* iov, int iovcnt);
  // Apply `n` sent bytes to the cursors of the in-flight site (mu_ held).
  void txAdvanceInFlight(size_t n);

  // Data-path rx driver (loop thread unless noted).
  struct RxWant {
    char* ptr;
    size_t len;
  };
  RxWant rxWant();  // next bytes the rx state machine needs
  enum class RxStep { kMore, kStop };
  // Post-read processing shared by readLoop (readiness) and
  // handleIoComplete (data path): advance the state machine by n
  // received bytes.
  RxStep processRxBytes(size_t n, size_t* consumed);
  RxStep processHeader(size_t* consumed);  // header complete: dispatch
  void onRxEof();                          // peer closed (read returned 0)
  // Post the next recv if connected, unposted, and not paused at a
  // message boundary. Requires mu_ held; rxPosted_ is the latch that
  // keeps any other thread from posting while the loop thread still
  // owns the rx cursors (cleared only at its repost decision points).
  void maybePostRecvLocked();

  // Write queued ops until EAGAIN or empty; requires mu_ held. Completed
  // ops' buffers are appended to `completed` (callbacks run without mu_).
  void flushTx(std::vector<TxDone>* completed);
  // Advance the front (shm) op: announce header, ring writes, chunk
  // headers, credit requests. mu_ held.
  ShmTxStatus flushShmFront(TxOp* op, std::vector<TxDone>* completed);
  // Drain the control channel (credits/credit requests), which preempts
  // the data stream only at wire-message boundaries. Returns false when
  // the socket is full or an error was recorded. mu_ held.
  bool flushCtrl();
  bool streamAtBoundary() const;  // mu_ held
  void queueCtrl(Opcode opcode);  // mu_ held; caller flushes + updates mask
  // Shared enqueue path behind send/sendPut/sendOwned (acquires mu_).
  void enqueue(TxOp op);
  // Fault-injection cold paths (fault/fault.h): send/sendPut delegate
  // here when a schedule is armed, keeping the disarmed hot path at
  // exactly one predictable check.
  void sendFaulted(UnboundBuffer* ubuf, uint64_t slot, const char* data,
                   size_t nbytes);
  void sendPutFaulted(UnboundBuffer* ubuf, uint64_t token,
                      uint64_t roffset, const char* data, size_t nbytes,
                      bool notify, std::shared_ptr<StripeTx> st);
  // Mutate the op per the fired decision (corrupt/truncate), or veto
  // the enqueue entirely (kill — the pair is already failed when this
  // returns false).
  bool applyTxFault(const fault::TxDecision& fd, TxOp* op);
  // Post-enqueue fault tail: duplicate copy / sever after truncation.
  void finishTxFault(const fault::TxDecision& fd,
                     const WireHeader& cleanHeader, const char* data,
                     size_t nbytes);
  // One connection attempt: TCP connect + hello + (optional) PSK
  // handshake; throws on failure. Fills *localAddr once bound.
  void connectAttempt(const SockAddr& remote, uint64_t remotePairId,
                      std::chrono::steady_clock::time_point deadline,
                      std::string* localAddr);
  // Seal the next frame (header, then payload chunks) into op->cipher,
  // consuming one tx seq each (mu_ held).
  void sealHeaderFrame(TxOp* op);
  void sealPayloadFrame(TxOp* op);
  void updateEpollMask();  // mu_ held
  void readLoop();         // loop thread only
  // Consume a fully received message (loop thread).
  void finishMessage();
  // Transition to kFailed, release resources, fan error out. Safe from any
  // thread; idempotent.
  void fail(const std::string& message);
  void teardown(State target, const std::string& message, bool notifyContext);

  Context* const context_;
  Loop* const loop_;
  const int selfRank_;
  const int peerRank_;
  const uint64_t localPairId_;
  const int channel_;    // data-channel index within the logical pair
  const int loopIndex_;  // loop_'s index in the device pool (metrics)
  // Engine-selected I/O mode: submission data path (uring) vs readiness
  // + direct syscalls (epoll). Fixed at construction.
  const bool dataPath_;

  // Ordering protocol (tools/check explicit-atomics): connect publishes
  // keys_/shm rings/fd_ with release stores of state_/everConnected_;
  // lock-free fast paths pair them with acquire loads. fd_ reads off
  // the hot path are relaxed — the fd number itself is the data.
  std::atomic<State> state_{State::kInitializing};
  std::atomic<bool> everConnected_{false};
  Listener* expectedAt_{nullptr};
  bool closing_{false};      // goodbye enqueued (mu_)
  bool peerGoodbye_{false};  // peer announced orderly departure (mu_)
  bool rxPaused_{false};     // stash backpressure engaged (mu_)
  bool lazyInbound_{false};  // broker-accepted rx-only pair (pre-connect)

  std::mutex mu_;
  std::condition_variable cv_;
  // Atomic: written during teardown (under mu_) while the loop thread's
  // read path inspects it without the pair lock. The close() sequencing
  // (state flip + loop tick barrier before ::close) provides the actual
  // lifetime guarantee; atomicity just keeps the access well-defined.
  std::atomic<int> fd_{-1};
  uint32_t epollMask_{0};
  std::deque<TxOp> tx_;
  std::string error_;
  std::string pendingTxError_;  // set by flushTx (mu_ held), drained by caller
  // Data-path state (mu_): one in-flight sendmsg SQE + its cursor site;
  // one in-flight recv SQE flag (flipped under mu_, cursors loop-thread).
  bool txInFlight_{false};
  TxSite txSite_{TxSite::kCtrl};
  bool rxPosted_{false};
  UnboundBuffer* rxUbuf_{nullptr};  // guarded by mu_ (cross-thread on fail)

  // Connection cipher state. keys_ is written once before the pair is
  // CONNECTED (handshake thread) and only read afterwards; the seq
  // counters live on their owning threads (tx under mu_, rx on the loop
  // thread).
  ConnKeys keys_;
  uint64_t txSeq_{0};
  uint64_t rxSeq_{0};

  // ---- shared-memory payload plane ----
  std::unique_ptr<ShmSegment> shm_;  // set before CONNECTED, freed in dtor
  ShmRing shmTx_;
  ShmRing shmRx_;
  std::atomic<bool> shmActive_{false};
  std::atomic<uint64_t> shmTxBytes_{0};
  std::atomic<uint64_t> shmRxBytes_{0};
  // tx-side flow control (mu_): front op stalled on ring space, waiting
  // for a kShmCredit wakeup.
  bool txRingBlocked_{false};
  // Control channel (mu_): queued credit/credit-request opcodes plus the
  // one currently hitting the wire (raw header, or sealed frame).
  std::deque<Opcode> ctrlQ_;
  char ctrlBuf_[sizeof(WireHeader) + kAeadTagBytes];
  size_t ctrlLen_{0};
  size_t ctrlSent_{0};


  // rx state, loop thread only
  enum class RxMode { kDirect, kStash, kPut, kGetReq, kStripe };
  WireHeader rxHeader_{};
  size_t rxHeaderRead_{0};
  bool rxInPayload_{false};
  char* rxDest_{nullptr};
  std::vector<char> rxStashData_;
  RxMode rxMode_{RxMode::kDirect};
  // Fused receive-reduce over the byte-stream path: payload (incl.
  // ciphertext) stages in rxCombineStage_ so partial reads never clobber
  // the accumulator; at message completion rxCombine_ folds the staging
  // into rxFinalDest_ (the posted recvReduce destination). The stage is
  // grow-only (kept across messages): fused TCP traffic must not pay a
  // malloc + zero-fill per message.
  //
  // Encrypted connections instead fold FRAME-BY-FRAME (rxFoldInline_):
  // each kEncFrameBytes frame's plaintext is combined into the
  // accumulator right after its AEAD tag verifies, while it is still
  // cache-hot — the whole-message fold at completion would re-read the
  // stage cold, one full memory traversal per byte (measured on the
  // 16 MiB encrypted-allreduce A/B, round 5). Only verified
  // plaintext is ever folded; a tampered later frame poisons the pair
  // and the pending op errors out with the accumulator partially
  // updated — same contents-undefined-on-error contract as every other
  // failed in-place collective.
  RecvReduceFn rxCombine_{nullptr};
  size_t rxCombineElsize_{0};     // wire bytes per element
  size_t rxCombineAccElsize_{0};  // accumulator bytes per element
  char* rxFinalDest_{nullptr};
  bool rxFoldInline_{false};
  std::vector<char> rxCombineStage_;
  size_t rxPayloadRead_{0};  // progress within the current frame
  size_t rxPlainDone_{0};    // completed (verified) payload bytes
  // Encrypted rx staging: ciphertext header+tag, and the payload tag that
  // trails the in-place payload ciphertext.
  uint8_t rxHeaderCipher_[sizeof(WireHeader) + kAeadTagBytes];
  uint8_t rxPayloadTag_[kAeadTagBytes];

  // rx-side shm message state (loop thread only): set by a kShmData/kShmPut
  // announce, advanced by kShmChunk, cleared at message completion.
  bool shmRxActive_{false};
  RxMode shmRxMode_{RxMode::kDirect};
  WireHeader shmRxHeader_{};   // the announce header (slot/aux/flags)
  char* shmRxDest_{nullptr};   // direct: user memory; stash: shmRxStash_
  std::vector<char> shmRxStash_;
  uint64_t shmRxTotal_{0};
  uint64_t shmRxDone_{0};
  // Fused receive-reduce from the shm ring: spans are combined into the
  // destination straight out of shared memory (no staging copy at all —
  // the whole point of recvReduce). Ring wrap and chunk caps can split an
  // element across spans; the carry buffer bridges those bytes.
  RecvReduceFn shmRxCombine_{nullptr};
  size_t shmRxCombineElsize_{0};     // wire bytes per element
  size_t shmRxCombineAccElsize_{0};  // accumulator bytes per element
  // Over-aligned: the carry is fed to typed reduce kernels as a 1-element
  // span, so it must satisfy the strictest alignment any kernel wants
  // (kMaxCombineElsize itself is no longer a power of two — it is sized
  // for q8 wire units — so the alignment is pinned at a cache line).
  alignas(64) uint8_t shmRxCarry_[kMaxCombineElsize];
  size_t shmRxCarryLen_{0};

  // Combine one in-order span of the active shm message (handles
  // element-straddling span boundaries via shmRxCarry_). `msgOff` is the
  // span's byte offset within the WIRE message; the accumulator address
  // for wire element i is shmRxDest_ + i * shmRxCombineAccElsize_.
  void combineShmSpan(uint64_t msgOff, const char* src, size_t len);

  // Reassembly handle of the stripe currently landing (RxMode::kStripe;
  // loop thread only) and its channel index echo.
  uint64_t rxStripeEntry_{0};

  // Stamp this pair's last-progress timestamp (the watchdog's liveness
  // signal), the per-channel byte counters, and the per-loop progress
  // stamp in the metrics registry. Called wherever payload or wire bytes
  // actually move; `tx` picks the byte-counter direction.
  void touchProgress(bool tx, size_t bytes);
};

}  // namespace transport
}  // namespace tpucoll

// C API for the tpucoll core, consumed by the gloo_tpu Python package over
// ctypes (the repo's equivalent of a pybind layer, using only the stable C
// ABI). Conventions:
//  - handles are opaque pointers; *_free releases them;
//  - functions return 0 on success or a TC_ERR_* code, with the message
//    available from tc_last_error() (thread-local);
//  - blocking calls release the GIL implicitly because ctypes drops it for
//    foreign calls.
#include <algorithm>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <sstream>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "tpucoll/async/engine.h"
#include "tpucoll/boot/boot.h"
#include "tpucoll/collectives/collectives.h"
#include "tpucoll/collectives/plan.h"
#include "tpucoll/collectives/wire_codec.h"
#include "tpucoll/common/codec_pool.h"
#include "tpucoll/common/debug.h"
#include "tpucoll/context.h"
#include "tpucoll/fault/fault.h"
#include "tpucoll/transport/loop_uring.h"
#include "tpucoll/transport/wire.h"
#include "tpucoll/common/crypto.h"
#include "tpucoll/common/json.h"
#include "tpucoll/common/keyring.h"
#include "tpucoll/elastic/elastic.h"
#include "tpucoll/rendezvous/file_store.h"
#include "tpucoll/rendezvous/hash_store.h"
#include "tpucoll/rendezvous/store.h"
#include "tpucoll/rendezvous/tcp_store.h"
#include "tpucoll/transport/device.h"
#include "tpucoll/schedule/generators.h"
#include "tpucoll/schedule/interpreter.h"
#include "tpucoll/schedule/ir.h"
#include "tpucoll/schedule/verifier.h"
#include "tpucoll/tuning/tuner.h"
#include "tpucoll/tuning/tuning_table.h"

namespace {

using tpucoll::Context;
using tpucoll::DataType;
using tpucoll::ReduceOp;
using tpucoll::Store;
using tpucoll::transport::Device;
using tpucoll::transport::UnboundBuffer;

thread_local std::string g_lastError;

constexpr int TC_OK = 0;
constexpr int TC_ERR = 1;
constexpr int TC_ERR_TIMEOUT = 2;
constexpr int TC_ERR_IO = 3;
constexpr int TC_ERR_ABORTED = 4;

template <typename Fn>
int wrap(Fn&& fn) {
  try {
    fn();
    return TC_OK;
  } catch (const tpucoll::TimeoutException& e) {
    g_lastError = e.what();
    return TC_ERR_TIMEOUT;
  } catch (const tpucoll::IoException& e) {
    g_lastError = e.what();
    return TC_ERR_IO;
  } catch (const tpucoll::AbortedException& e) {
    g_lastError = e.what();
    return TC_ERR_ABORTED;
  } catch (const std::exception& e) {
    g_lastError = e.what();
    return TC_ERR;
  } catch (...) {
    g_lastError = "unknown error";
    return TC_ERR;
  }
}


// Handle-factory boundary: nullptr + tc_last_error on failure — the
// handle-returning mirror of wrap().
template <typename Fn>
void* wrapPtr(Fn&& fn) {
  try {
    return fn();
  } catch (const std::exception& e) {
    g_lastError = e.what();
    return nullptr;
  } catch (...) {
    g_lastError = "unknown error";
    return nullptr;
  }
}

// Value-returning boundary: `fallback` + tc_last_error on failure, for
// introspection entries whose return channel has no error code.
template <typename T, typename Fn>
T wrapVal(T fallback, Fn&& fn) {
  try {
    return fn();
  } catch (const std::exception& e) {
    g_lastError = e.what();
    return fallback;
  } catch (...) {
    g_lastError = "unknown error";
    return fallback;
  }
}

// Void boundary (teardown/config entries): failures land in
// tc_last_error and are swallowed — a free/abort path has no error
// channel, and an exception crossing the C ABI aborts the process.
template <typename Fn>
void wrapVoid(Fn&& fn) {
  try {
    fn();
  } catch (const std::exception& e) {
    g_lastError = e.what();
  } catch (...) {
    g_lastError = "unknown error";
  }
}

std::chrono::milliseconds ms(int64_t v) {
  return std::chrono::milliseconds(v);
}

using StoreHandle = std::shared_ptr<Store>;
using DeviceHandle = std::shared_ptr<Device>;

StoreHandle* asStore(void* h) { return static_cast<StoreHandle*>(h); }
DeviceHandle* asDevice(void* h) { return static_cast<DeviceHandle*>(h); }
Context* asContext(void* h) { return static_cast<Context*>(h); }
UnboundBuffer* asBuffer(void* h) { return static_cast<UnboundBuffer*>(h); }
tpucoll::elastic::ElasticAgent* asElastic(void* h) {
  return static_cast<tpucoll::elastic::ElasticAgent*>(h);
}

template <typename Opts>
void fillCommon(Opts& opts, Context* ctx, uint32_t tag, int64_t timeoutMs) {
  opts.context = ctx;
  opts.tag = tag;
  opts.timeout = ms(timeoutMs);
}

std::vector<size_t> countsVec(const size_t* counts, int size) {
  return std::vector<size_t>(counts, counts + size);
}

int copyOut(const std::string& s, uint8_t** out, size_t* outLen) {
  *outLen = s.size();
  *out = static_cast<uint8_t*>(malloc(s.size()));
  if (s.empty()) {
    return TC_OK;  // malloc(0) may be NULL; memcpy(NULL, ..., 0) is UB
  }
  if (*out == nullptr) {
    throw std::bad_alloc();
  }
  std::memcpy(*out, s.data(), s.size());
  return TC_OK;
}

// p2p wait instrumentation: span against the buffer's tracer when the
// owning context set one (standalone transport contexts have none).
tpucoll::Tracer::Span maybeSpan(UnboundBuffer* buf, const char* name) {
  tpucoll::Tracer* tracer = buf->transportContext()->tracer();
  if (tracer == nullptr) {
    return tpucoll::Tracer::Span();
  }
  return tracer->span(name, buf->size());
}

tpucoll::Metrics* bufMetrics(UnboundBuffer* buf) {
  return buf->transportContext()->metrics();
}

tpucoll::FlightRecorder* bufFlightrec(UnboundBuffer* buf) {
  return buf->transportContext()->flightrec();
}

// Flight-recorder opcodes for user-facing p2p ops.
const char kFrSend[] = "send";
const char kFrRecv[] = "recv";
const char kFrPut[] = "put";
const char kFrGet[] = "get";

// Flight-recorder p2p completion bookkeeping: each buffer's posted ops'
// ring seqs, per direction, so a wait completes exactly an op posted on
// THE BUFFER IT WAITED ON — never an older op pending on a different
// (possibly hung) buffer. Waits on one buffer count completions rather
// than naming ops, so within a buffer the oldest post of the direction
// is the honest match. A mutex here is fine: this is the Python-facing
// p2p path (a ctypes round-trip per call), not the collective hot path —
// the recorder itself stays lock-free.
struct FrPending {
  std::deque<uint64_t> send;  // send + put posts
  std::deque<uint64_t> recv;  // recv + get posts
};
std::mutex g_frPendingMu;
std::unordered_map<void*, FrPending> g_frPending;

void frPush(void* buf, bool isSend, uint64_t seq) {
  std::lock_guard<std::mutex> guard(g_frPendingMu);
  FrPending& p = g_frPending[buf];
  (isSend ? p.send : p.recv).push_back(seq);
}

uint64_t frPop(void* buf, bool isSend) {
  std::lock_guard<std::mutex> guard(g_frPendingMu);
  auto it = g_frPending.find(buf);
  if (it == g_frPending.end()) {
    return tpucoll::FlightRecorder::kNoSeq;
  }
  std::deque<uint64_t>& q = isSend ? it->second.send : it->second.recv;
  if (q.empty()) {
    return tpucoll::FlightRecorder::kNoSeq;
  }
  const uint64_t seq = q.front();
  q.pop_front();
  return seq;
}

void frErase(void* buf) {
  std::lock_guard<std::mutex> guard(g_frPendingMu);
  g_frPending.erase(buf);
}

// ---- async engine plumbing (async/engine.h) ----

tpucoll::async::Engine* asEngine(void* h) {
  return static_cast<tpucoll::async::Engine*>(h);
}

using WorkHandle = std::shared_ptr<tpucoll::async::Work>;

WorkHandle* asWork(void* h) { return static_cast<WorkHandle*>(h); }

// Heap-wrap a submitted Work as an opaque handle (NULL + tc_last_error
// when submission itself failed, e.g. after shutdown).
template <typename Fn>
void* submitWork(Fn&& fn) {
  try {
    return new WorkHandle(fn());
  } catch (const std::exception& e) {
    g_lastError = e.what();
    return nullptr;
  }
}

// tc_work_wait timeout resolution: <= 0 means "no deadline"; clamp
// everything to ~24 days so wait_for's nanosecond conversion can never
// overflow (an overflowed deadline lands in the past and reads as an
// instant spurious timeout).
std::chrono::milliseconds workTimeout(int64_t timeoutMs) {
  constexpr int64_t kMaxMs = int64_t(1) << 31;
  return ms(timeoutMs > 0 && timeoutMs < kMaxMs ? timeoutMs : kMaxMs);
}

}  // namespace

extern "C" {

const char* tc_last_error() { return g_lastError.c_str(); }

// ---- stores ----

void* tc_hash_store_new() {
  return wrapPtr([&]() -> void* {
    return new StoreHandle(std::make_shared<tpucoll::HashStore>());
  });
}

void* tc_file_store_new(const char* path) {
  return wrapPtr([&]() -> void* {
    return new StoreHandle(std::make_shared<tpucoll::FileStore>(path));
  });
}

void* tc_prefix_store_new(void* base, const char* prefix) {
  return wrapPtr([&]() -> void* {
    return new StoreHandle(
        std::make_shared<tpucoll::PrefixStore>(*asStore(base), prefix));
  });
}

void tc_store_free(void* store) {
  wrapVoid([&] { delete asStore(store); });
}

void* tc_tcp_store_server_new(const char* host, uint16_t port) {
  return wrapPtr([&]() -> void* {
    return new tpucoll::TcpStoreServer(host, port);
  });
}

uint16_t tc_tcp_store_server_port(void* server) {
  return wrapVal<uint16_t>(0, [&] {
    return static_cast<tpucoll::TcpStoreServer*>(server)->port();
  });
}

void tc_tcp_store_server_free(void* server) {
  wrapVoid([&] {
    delete static_cast<tpucoll::TcpStoreServer*>(server);
  });
}

void* tc_tcp_store_new(const char* host, uint16_t port) {
  return wrapPtr([&]() -> void* {
    return new StoreHandle(
        std::make_shared<tpucoll::TcpStore>(host, port));
  });
}

int tc_store_set(void* store, const char* key, const uint8_t* data,
                 size_t len) {
  return wrap([&] {
    (*asStore(store))->set(key, Store::Buf(data, data + len));
  });
}

int tc_store_get(void* store, const char* key, int64_t timeoutMs,
                 uint8_t** out, size_t* outLen) {
  return wrap([&] {
    auto buf = (*asStore(store))->get(key, ms(timeoutMs));
    *outLen = buf.size();
    *out = static_cast<uint8_t*>(malloc(buf.size()));
    if (*out == nullptr && !buf.empty()) {
      throw std::bad_alloc();
    }
    std::memcpy(*out, buf.data(), buf.size());
  });
}

void tc_buf_free(uint8_t* buf) { wrapVoid([&] { free(buf); }); }

int tc_store_add(void* store, const char* key, int64_t delta,
                 int64_t* result) {
  return wrap([&] { *result = (*asStore(store))->add(key, delta); });
}

// Remove a key; *deleted = 1 when it existed. Namespace hygiene (lease
// reaping, retired rebuild/epoch namespaces — docs/rendezvous.md).
int tc_store_delete(void* store, const char* key, int* deleted) {
  return wrap([&] {
    *deleted = (*asStore(store))->deleteKey(key) ? 1 : 0;
  });
}

// Keys currently present under `prefix`, as a JSON array of strings
// (malloc'd; free with tc_buf_free). Snapshot semantics only.
int tc_store_list(void* store, const char* prefix, uint8_t** out,
                  size_t* outLen) {
  return wrap([&] {
    std::ostringstream json;
    json << "[";
    bool first = true;
    for (const auto& key : (*asStore(store))->listKeys(prefix)) {
      json << (first ? "" : ",");
      tpucoll::appendJsonString(json, key);
      first = false;
    }
    json << "]";
    copyOut(json.str(), out, outLen);
  });
}

// ---- device / context ----

void* tc_device_new(const char* hostname, uint16_t port,
                    const char* authKey, int encrypt, const char* iface,
                    int busyPoll, const char* engine, const char* keyring) {
  return wrapPtr([&]() -> void* {
    tpucoll::transport::DeviceAttr attr;
    if (hostname != nullptr && hostname[0] != '\0') {
      attr.hostname = hostname;
    }
    if (iface != nullptr) {
      attr.iface = iface;
    }
    attr.port = port;
    if (authKey != nullptr) {
      attr.authKey = authKey;
    }
    if (keyring != nullptr) {
      attr.keyring = keyring;
    }
    attr.encrypt = encrypt != 0;
    attr.busyPoll = busyPoll != 0;
    if (engine != nullptr) {
      attr.engine = engine;
    }
    return new DeviceHandle(std::make_shared<Device>(attr));
  });
}

// Launcher-side helper: derive rank `rank`'s serialized keyring from the
// root secret (common/keyring.h threat model). The returned buffer is a
// NUL-terminated string; free with tc_buf_free.
int tc_derive_keyring(const char* rootKey, int rank, int size,
                      uint8_t** out) {
  return wrap([&] {
    const std::string s =
        tpucoll::Keyring::derive(rootKey != nullptr ? rootKey : "", rank,
                                 size)
            .serialize();
    *out = static_cast<uint8_t*>(malloc(s.size() + 1));
    if (*out == nullptr) {
      throw std::bad_alloc();
    }
    std::memcpy(*out, s.data(), s.size() + 1);
  });
}

void tc_device_free(void* dev) {
  wrapVoid([&] { delete asDevice(dev); });
}

// Event-engine submission counters (loop.h Loop::EngineStats): uring
// reports io_uring_enter syscalls / SQEs submitted / CQEs drained since
// device creation; epoll reports zeros. sqes > enters is the batched-
// submission evidence (readiness engines pay >=1 syscall per I/O op).
void tc_device_engine_stats(void* dev, uint64_t* enters, uint64_t* sqes,
                            uint64_t* cqes) {
  wrapVoid([&] {
    const auto s = (*asDevice(dev))->loop()->engineStats();
    *enters = s.enters;
    *sqes = s.sqes;
    *cqes = s.cqes;
  });
}

// Engine introspection: lets callers pick engine="uring" only where the
// kernel/sandbox supports it (an explicit uring request throws otherwise).
// AEAD bulk tier this process dispatches to (crypto.h aeadIsaTier):
// 2 = fused AVX-512, 1 = AVX2, 0 = scalar.
int tc_crypto_isa_tier() {
  return wrapVal(0, [&] { return tpucoll::aeadIsaTier(); });
}

int tc_uring_available() {
  return wrapVal(0, [&] {
    return tpucoll::transport::uringAvailable() ? 1 : 0;
  });
}

// Structured connect diagnostics hook (reference: tcp/debug_data.h +
// DebugLogger). The callback runs on connecting threads; pass nullptr to
// clear.
typedef void (*tc_connect_logger_fn)(int selfRank, int peerRank,
                                     const char* remote, const char* local,
                                     int attempt, int ok, int willRetry,
                                     const char* error);

void tc_set_connect_debug_logger(tc_connect_logger_fn cb) {
  wrapVoid([&] {
    if (cb == nullptr) {
      tpucoll::setConnectDebugLogger(nullptr);
      return;
    }
    tpucoll::setConnectDebugLogger(
        [cb](const tpucoll::ConnectDebugData& d) {
          cb(d.selfRank, d.peerRank, d.remote.c_str(), d.local.c_str(),
             d.attempt, d.ok ? 1 : 0, d.willRetry ? 1 : 0,
             d.error.c_str());
        });
  });
}

void* tc_context_new(int rank, int size) {
  return wrapPtr([&]() -> void* { return new Context(rank, size); });
}

void tc_context_set_timeout(void* ctx, int64_t timeoutMs) {
  wrapVoid([&] { asContext(ctx)->setTimeout(ms(timeoutMs)); });
}

int tc_context_connect(void* ctx, void* store, void* device) {
  return wrap([&] {
    asContext(ctx)->connectFullMesh(*asStore(store), *asDevice(device));
  });
}

int tc_context_fork(void* ctx, void* parent, uint32_t tag) {
  return wrap([&] { asContext(ctx)->forkFrom(*asContext(parent), tag); });
}

// ---- process-group subsystem (group/): topology + communicator split --

int tc_context_rank(void* ctx) {
  return wrapVal(-1, [&] { return asContext(ctx)->rank(); });
}

int tc_context_size(void* ctx) {
  return wrapVal(-1, [&] { return asContext(ctx)->size(); });
}

// Host-fingerprint override for topology discovery; must run before
// tc_context_connect (throws afterwards). Empty/NULL restores the
// TPUCOLL_HOST_ID / hostname+boot-id default.
int tc_context_set_host_id(void* ctx, const char* hostId) {
  return wrap([&] {
    asContext(ctx)->setHostId(hostId != nullptr ? hostId : "");
  });
}

// Discovered topology as JSON ({"rank","host_index","local_rank",
// "local_size","leader","is_leader","n_hosts","non_flat","hosts":[...]});
// malloc'd, free with tc_buf_free. Errors when the context never
// discovered one (not connected).
int tc_topology_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    auto topo = asContext(ctx)->topology();
    TC_ENFORCE(topo != nullptr, "tc_topology_json: no topology "
               "(context not connected)");
    copyOut(topo->toJson(), out, outLen);
  });
}

// Group tag namespace of this communicator ("" for a root context);
// malloc'd, free with tc_buf_free.
int tc_context_group_tag(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] { copyOut(asContext(ctx)->groupTag(), out, outLen); });
}

// Communicator split (MPI_Comm_split semantics): a COLLECTIVE over the
// parent — every rank calls concurrently with the same `tag`
// (concurrent splits need distinct tags). On success *out is the new
// context handle (owned by the caller; tc_context_free it), or NULL
// when color < 0 (this rank opted out). See Context::split.
int tc_split(void* ctx, int color, int key, uint32_t tag, void** out) {
  return wrap([&] {
    *out = asContext(ctx)->split(color, key, tag).release();
  });
}

// split(color = host index, key = rank): the intra-host communicator.
int tc_split_by_host(void* ctx, uint32_t tag, void** out) {
  return wrap([&] {
    *out = asContext(ctx)->splitByHost(tag).release();
  });
}

int tc_context_close(void* ctx) {
  return wrap([&] { asContext(ctx)->close(); });
}

void tc_context_free(void* ctx) {
  wrapVoid([&] { delete asContext(ctx); });
}

uint64_t tc_next_slot(void* ctx, uint32_t num) {
  return wrapVal<uint64_t>(0, [&] {
    return asContext(ctx)->nextSlot(num);
  });
}

void tc_debug_dump(void* ctx) {
  wrapVoid([&] { asContext(ctx)->transport()->debugDump(); });
}

void tc_context_shm_stats(void* ctx, uint64_t* txBytes, uint64_t* rxBytes,
                          int* activePairs) {
  wrapVoid([&] {
    asContext(ctx)->transport()->shmStats(txBytes, rxBytes, activePairs);
  });
}

void tc_trace_start(void* ctx) {
  wrapVoid([&] { asContext(ctx)->tracer().start(); });
}

void tc_trace_stop(void* ctx) {
  wrapVoid([&] { asContext(ctx)->tracer().stop(); });
}

// Returns a malloc'd JSON string (Chrome trace-event format); caller frees
// with tc_buf_free.
int tc_trace_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    Context* c = asContext(ctx);
    std::string json = c->tracer().toJson(c->rank());
    *outLen = json.size();
    *out = static_cast<uint8_t*>(malloc(json.size()));
    if (*out == nullptr && !json.empty()) {
      throw std::bad_alloc();
    }
    std::memcpy(*out, json.data(), json.size());
  });
}

// ---- metrics ----

void tc_metrics_enable(void* ctx, int on) {
  wrapVoid([&] { asContext(ctx)->metrics().setEnabled(on != 0); });
}

int tc_metrics_enabled(void* ctx) {
  return wrapVal(0, [&] {
    return asContext(ctx)->metrics().enabled() ? 1 : 0;
  });
}

// Straggler watchdog threshold; <= 0 disables. Overrides the
// TPUCOLL_WATCHDOG_MS environment default for this context.
void tc_metrics_set_watchdog(void* ctx, int64_t thresholdMs) {
  wrapVoid([&] {
    asContext(ctx)->metrics().setWatchdogUs(thresholdMs * 1000);
  });
}

// Returns a malloc'd JSON object (see Metrics::toJson); caller frees with
// tc_buf_free. drain != 0 resets counters/histograms after the snapshot.
int tc_metrics_json(void* ctx, int drain, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    std::string json = asContext(ctx)->metricsJson(drain != 0);
    *outLen = json.size();
    *out = static_cast<uint8_t*>(malloc(json.size()));
    if (*out == nullptr && !json.empty()) {
      throw std::bad_alloc();
    }
    std::memcpy(*out, json.data(), json.size());
  });
}

// ---- flight recorder (common/flightrec.h) ----

// Always-on flight-recorder ring as a JSON document (docs/flightrec.md);
// malloc'd, free with tc_buf_free. Never drains: the ring keeps rolling.
int tc_flightrec_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    std::string json = asContext(ctx)->flightrec().toJson();
    *outLen = json.size();
    *out = static_cast<uint8_t*>(malloc(json.size()));
    if (*out == nullptr && !json.empty()) {
      throw std::bad_alloc();
    }
    std::memcpy(*out, json.data(), json.size());
  });
}

// Explicit dump to `path` (the Python-side trigger; automatic triggers
// write to TPUCOLL_FLIGHTREC_DIR on their own).
int tc_flightrec_dump(void* ctx, const char* path) {
  return wrap([&] {
    TC_ENFORCE(path != nullptr && path[0] != '\0',
               "tc_flightrec_dump: empty path");
    TC_ENFORCE(asContext(ctx)->flightrec().dumpToFile(path, "explicit", -1),
               "tc_flightrec_dump: cannot write ", path);
  });
}

// Next per-context collective sequence number (== ops recorded so far).
uint64_t tc_flightrec_seq(void* ctx) {
  return wrapVal<uint64_t>(0, [&] {
    return asContext(ctx)->flightrec().nextSeq();
  });
}

// Opt-in fatal-signal dumping (SIGSEGV/SIGABRT/SIGBUS/SIGFPE/SIGILL/
// SIGTERM -> dump every live recorder to TPUCOLL_FLIGHTREC_DIR, then
// re-raise). Also installable via TPUCOLL_FLIGHTREC_SIGNALS=1.
void tc_flightrec_install_signal_handler() {
  wrapVoid([&] { tpucoll::FlightRecorder::installSignalHandler(); });
}

// ---- phase-level collective profiler (common/profile.h) ----

// Per-op phase-breakdown ring as JSON (docs/profiling.md); non-draining
// like the flight recorder. Malloc'd, free with tc_buf_free.
int tc_profile_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    copyOut(asContext(ctx)->profileJson(), out, outLen);
  });
}

// Runtime override of the TPUCOLL_PROFILE gate for this context.
void tc_profile_enable(void* ctx, int on) {
  wrapVoid([&] { asContext(ctx)->profiler().setEnabled(on != 0); });
}

int tc_profile_enabled(void* ctx) {
  return wrapVal(0, [&] {
    return asContext(ctx)->profiler().enabled() ? 1 : 0;
  });
}

// ---- causal span recorder (common/span.h) ----

// Per-op step/phase-instance span ring as JSON (docs/critpath.md);
// non-draining like the profiler ring. Malloc'd, free with tc_buf_free.
int tc_spans_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    copyOut(asContext(ctx)->spansJson(), out, outLen);
  });
}

// Runtime override of the TPUCOLL_SPANS gate for this context.
void tc_spans_enable(void* ctx, int on) {
  wrapVoid([&] { asContext(ctx)->spans().setEnabled(on != 0); });
}

int tc_spans_enabled(void* ctx) {
  return wrapVal(0, [&] {
    return asContext(ctx)->spans().enabled() ? 1 : 0;
  });
}

// ---- in-band fleet observability plane (common/fleetobs.h) ----

// Start the hierarchical telemetry fold for this rank's topology role
// (docs/fleet.md): members push fixed-size reports to their host leader,
// leaders pre-aggregate and relay to rank 0, which runs the anomaly
// detectors. Requires a connected context; under TPUCOLL_FLEETOBS=0 the
// start is a no-op and tc_fleetobs_running stays 0.
int tc_fleetobs_start(void* ctx) {
  return wrap([&] { asContext(ctx)->fleetObsStart(); });
}

// Stop and join the aggregation thread. Safe when never started; also
// runs automatically at context close/destruction.
int tc_fleetobs_stop(void* ctx) {
  return wrap([&] { asContext(ctx)->fleetObsStop(); });
}

int tc_fleetobs_running(void* ctx) {
  return wrapVal(0, [&] {
    return asContext(ctx)->fleetObsRunning() ? 1 : 0;
  });
}

// Merge `auxJson` (a JSON object — e.g. the Python elastic agent's
// status) into this rank's next report as its "aux" field. Validated
// here so malformed JSON fails this call, never the aggregation thread.
int tc_fleetobs_set_aux(void* ctx, const char* auxJson) {
  return wrap([&] {
    asContext(ctx)->fleetObsSetAux(auxJson != nullptr ? auxJson : "");
  });
}

// Latest merged fleet document (rank 0; a role stub elsewhere) — the
// telemetry endpoint's /fleet payload. Malloc'd, free with tc_buf_free.
int tc_fleet_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] { copyOut(asContext(ctx)->fleetJson(), out, outLen); });
}

// ---- bootstrap plane (boot/, docs/bootstrap.md) ----

// Store-choreography cost model (tests/test_bootstrap_scale.py): run
// `nranks` in-process rank threads through ONE bootstrap rendezvous
// over a shared FileStore rooted at `storePath` (which must be fresh
// per call — the key schema is fixed). lazy != 0 runs the leader-
// relayed choreography (boot::relayedRendezvous) with `ranksPerHost`
// ranks per simulated host and `shards` key shards; lazy == 0 runs the
// full-mesh publish/multiGet-all choreography with an O(N)-sized
// synthetic pair-id table per rank. `payloadBytes` sizes the lazy arm's
// per-rank address payload. Writes a JSON summary — wall_ms plus
// aggregate/max per-phase stats — to *out (malloc'd, free with
// tc_buf_free). This measures the STORE protocol, not sockets: the
// point of the sweep is the O(N^2) -> O(hosts^2 + N) curve.
int tc_boot_rendezvous_bench(const char* storePath, int nranks,
                             int ranksPerHost, int shards, int lazy,
                             int payloadBytes, int64_t timeoutMs,
                             uint8_t** out, size_t* outLen) {
  return wrap([&] {
    TC_ENFORCE(storePath != nullptr && storePath[0] != '\0',
               "tc_boot_rendezvous_bench: empty store path");
    TC_ENFORCE(nranks > 0 && nranks <= 4096,
               "tc_boot_rendezvous_bench: nranks out of range");
    TC_ENFORCE(ranksPerHost > 0, "tc_boot_rendezvous_bench: ranksPerHost "
               "must be positive");
    TC_ENFORCE(payloadBytes >= 0 && payloadBytes <= (1 << 20),
               "tc_boot_rendezvous_bench: payloadBytes out of range");
    const auto timeout = ms(timeoutMs > 0 ? timeoutMs : 120000);
    std::vector<tpucoll::boot::RendezvousStats> stats(nranks);
    std::vector<std::string> errors(nranks);
    std::vector<int64_t> wallUs(nranks, 0);
    std::vector<std::thread> threads;
    threads.reserve(nranks);
    for (int r = 0; r < nranks; r++) {
      threads.emplace_back([&, r] {
        try {
          // Every "rank" opens its own FileStore client over the shared
          // directory, exactly like separate processes would.
          tpucoll::FileStore store(storePath);
          const std::string fp =
              "simhost-" + std::to_string(r / ranksPerHost);
          Store::Buf payload(static_cast<size_t>(payloadBytes));
          for (size_t i = 0; i < payload.size(); i++) {
            payload[i] = static_cast<uint8_t>((r + static_cast<int>(i)) & 0xff);
          }
          const auto t0 = std::chrono::steady_clock::now();
          if (lazy != 0) {
            tpucoll::boot::relayedRendezvous(store, r, nranks, fp, payload,
                                             shards, timeout, &stats[r]);
          } else {
            tpucoll::boot::fullMeshRendezvousSim(store, r, nranks, fp,
                                                 payload, timeout, &stats[r]);
          }
          wallUs[r] = std::chrono::duration_cast<std::chrono::microseconds>(
                          std::chrono::steady_clock::now() - t0)
                          .count();
        } catch (const std::exception& e) {
          errors[r] = e.what();
        }
      });
    }
    for (auto& t : threads) {
      t.join();
    }
    for (int r = 0; r < nranks; r++) {
      TC_ENFORCE(errors[r].empty(), "bootstrap bench rank ", r, ": ",
                 errors[r]);
    }
    int64_t maxWallUs = 0;
    int64_t maxPublishUs = 0;
    int64_t maxTopoUs = 0;
    int64_t maxExchangeUs = 0;
    int64_t totalOps = 0;
    int64_t totalBytes = 0;
    for (int r = 0; r < nranks; r++) {
      maxWallUs = std::max(maxWallUs, wallUs[r]);
      maxPublishUs = std::max(maxPublishUs, stats[r].publishUs);
      maxTopoUs = std::max(maxTopoUs, stats[r].topoUs);
      maxExchangeUs = std::max(maxExchangeUs, stats[r].exchangeUs);
      totalOps += stats[r].storeOps;
      totalBytes += stats[r].storeBytes;
    }
    std::ostringstream json;
    json << "{\"nranks\":" << nranks << ",\"ranks_per_host\":" << ranksPerHost
         << ",\"lazy\":" << (lazy != 0 ? "true" : "false")
         << ",\"shards\":" << shards << ",\"wall_ms\":"
         << static_cast<double>(maxWallUs) / 1000.0
         << ",\"publish_ms\":" << static_cast<double>(maxPublishUs) / 1000.0
         << ",\"topo_ms\":" << static_cast<double>(maxTopoUs) / 1000.0
         << ",\"exchange_ms\":"
         << static_cast<double>(maxExchangeUs) / 1000.0
         << ",\"store_ops\":" << totalOps
         << ",\"store_bytes\":" << totalBytes << "}";
    copyOut(json.str(), out, outLen);
  });
}

// ---- collective autotuning plane (tuning/) ----

// Run the tuner sweep (a COLLECTIVE — every rank must call concurrently
// with identical arguments), elect + publish + install rank 0's table,
// and return the installed table's JSON (malloc'd; free with
// tc_buf_free). See tuning/tuner.h.
int tc_tune(void* ctx, size_t minBytes, size_t maxBytes, int iters,
            int warmup, uint32_t tag, int64_t timeoutMs, uint8_t** out,
            size_t* outLen) {
  return wrap([&] {
    tpucoll::tuning::TunerOptions opts;
    opts.minBytes = minBytes;
    opts.maxBytes = maxBytes;
    opts.iters = iters;
    opts.warmup = warmup;
    opts.tag = tag;
    opts.timeout = ms(timeoutMs);
    auto table = tpucoll::tuning::tune(asContext(ctx), opts);
    copyOut(table->toJson(), out, outLen);
  });
}

// Install a serialized table on THIS rank only (callers own the
// all-ranks-identical contract; tc_tune handles it automatically). NULL
// or empty JSON clears the installed table, restoring fallback dispatch.
int tc_tuning_install(void* ctx, const char* json) {
  return wrap([&] {
    if (json == nullptr || json[0] == '\0') {
      asContext(ctx)->setTuningTable(nullptr);
      return;
    }
    asContext(ctx)->setTuningTable(
        std::make_shared<const tpucoll::tuning::TuningTable>(
            tpucoll::tuning::TuningTable::fromJson(json)));
  });
}

// Serialized installed table (empty string when none is installed);
// malloc'd, free with tc_buf_free.
int tc_tuning_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    auto table = asContext(ctx)->tuningTable();
    copyOut(table != nullptr ? table->toJson() : std::string(), out,
            outLen);
  });
}

// ---- collective schedule plane (schedule/) ----

// Install a serialized schedule table on THIS rank only (the
// all-ranks-identical contract is the caller's, exactly like
// tc_tuning_install). Every schedule matching the context's world size
// is verified AND resolved before the swap — malformed JSON or a
// semantically invalid schedule fails the call and leaves the previous
// plane (and the plan cache) untouched. NULL or empty JSON clears the
// plane, restoring native dispatch.
int tc_schedule_install(void* ctx, const char* json) {
  return wrap([&] {
    if (json == nullptr || json[0] == '\0') {
      asContext(ctx)->setScheduleTable(nullptr);
      return;
    }
    asContext(ctx)->setScheduleTable(
        std::make_shared<const tpucoll::schedule::ScheduleTable>(
            tpucoll::schedule::ScheduleTable::fromJson(json)));
  });
}

// Serialized installed schedule table (empty string when none);
// malloc'd, free with tc_buf_free.
int tc_schedule_json(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    auto inst = asContext(ctx)->schedules();
    copyOut(inst != nullptr ? inst->table->toJson() : std::string(), out,
            outLen);
  });
}

// Installed schedule summaries as a JSON array:
//   [{"name","collective","world_size","steps","resolved"}]
// "resolved" is 1 when the schedule matches this context's world (its
// elections can fire), 0 when it is carried for round-trip only.
int tc_schedule_list(void* ctx, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    auto inst = asContext(ctx)->schedules();
    std::ostringstream os;
    os << "[";
    if (inst != nullptr) {
      bool first = true;
      for (const auto& s : inst->table->schedules()) {
        if (!first) {
          os << ",";
        }
        first = false;
        os << "{\"name\":";
        tpucoll::appendJsonString(os, s.name);
        os << ",\"collective\":\""
           << tpucoll::schedule::collectiveName(s.collective)
           << "\",\"world_size\":" << s.worldSize
           << ",\"steps\":" << s.steps.size() << ",\"resolved\":"
           << (inst->programs.count(s.name) != 0 ? 1 : 0) << "}";
      }
    }
    os << "]";
    copyOut(os.str(), out, outLen);
  });
}

// One installed schedule in full, serialized as a single-schedule table
// (same interchange JSON as tc_schedule_json). TC_ERR for unknown names.
int tc_schedule_describe(void* ctx, const char* name, uint8_t** out,
                         size_t* outLen) {
  return wrap([&] {
    TC_ENFORCE(name != nullptr && name[0] != '\0',
               "tc_schedule_describe: empty name");
    auto inst = asContext(ctx)->schedules();
    const tpucoll::schedule::Schedule* s =
        inst != nullptr ? inst->table->find(name) : nullptr;
    TC_ENFORCE(s != nullptr, "tc_schedule_describe: no installed ",
               "schedule named \"", name, "\"");
    tpucoll::schedule::ScheduleTable one;
    one.add(*s);
    copyOut(one.toJson(), out, outLen);
  });
}

// Context-free: run `family` through the generator (paramsJson is a
// JSON object of integer parameters, e.g. {"depth":2}; NULL/empty =
// defaults), verify the result, and return it serialized as a
// single-schedule table ready to merge or install. See
// schedule/generators.h for the family list.
int tc_schedule_generate(const char* family, int worldSize,
                         const char* paramsJson, uint8_t** out,
                         size_t* outLen) {
  return wrap([&] {
    TC_ENFORCE(family != nullptr && family[0] != '\0',
               "tc_schedule_generate: empty family");
    std::map<std::string, int> params;
    if (paramsJson != nullptr && paramsJson[0] != '\0') {
      // JsonReader keeps a reference to the text; give it a named string
      // (a temporary from the char* would dangle past the constructor).
      const std::string ptext(paramsJson);
      tpucoll::JsonReader r(ptext, "schedule params",
                            /*rejectDuplicateKeys=*/true);
      using JValue = tpucoll::JsonReader::Value;
      JValue v = r.parse();
      TC_ENFORCE(v.kind == JValue::Kind::kObject,
                 "schedule params: expected a JSON object");
      for (const auto& kv : v.fields) {
        TC_ENFORCE(kv.second.kind == JValue::Kind::kNumber,
                   "schedule params: \"", kv.first,
                   "\" must be an integer");
        params[kv.first] = static_cast<int>(kv.second.number);
      }
    }
    tpucoll::schedule::Schedule s =
        tpucoll::schedule::generate(family, worldSize, params);
    tpucoll::schedule::verifyOrThrow(s);
    tpucoll::schedule::ScheduleTable one;
    one.add(std::move(s));
    copyOut(one.toJson(), out, outLen);
  });
}

// Context-free: JSON array of generator family names.
int tc_schedule_families(uint8_t** out, size_t* outLen) {
  return wrap([&] {
    std::ostringstream os;
    os << "[";
    bool first = true;
    for (const auto& f : tpucoll::schedule::generatorFamilies()) {
      if (!first) {
        os << ",";
      }
      first = false;
      tpucoll::appendJsonString(os, f);
    }
    os << "]";
    copyOut(os.str(), out, outLen);
  });
}

// Context-free: parse a schedule table and statically verify EVERY
// schedule in it (all ranks of each schedule's declared world). 0 when
// all pass; TC_ERR with the verifier's typed, step-naming message
// (tc_last_error) on the first failure.
int tc_schedule_verify(const char* json) {
  return wrap([&] {
    TC_ENFORCE(json != nullptr && json[0] != '\0',
               "tc_schedule_verify: empty JSON");
    auto table = tpucoll::schedule::ScheduleTable::fromJson(json);
    for (const auto& s : table.schedules()) {
      tpucoll::schedule::verifyOrThrow(s);
    }
  });
}

// ---- elastic membership plane (elastic/elastic.h) ----

// Create AND start an elastic agent: publishes this worker's lease
// (renewed by a background heartbeat thread every TPUCOLL_LEASE_MS),
// founds epoch 1 (rank 0, join == 0) or enqueues on the join queue
// (join != 0; `rank` is then ignored and a fresh worker id is drawn),
// and starts the membership monitor. `hostId` (nullable) overrides
// topology discovery for rebuilt meshes; `timeoutMs` bounds document
// waits and the default rebuild/collective timeout. NULL +
// tc_last_error on failure.
void* tc_elastic_new(void* store, void* device, int rank, int worldSize,
                     int minSize, int join, const char* hostId,
                     int64_t timeoutMs) {
  return wrapPtr([&]() -> void* {
    tpucoll::elastic::AgentOptions opts;
    opts.rank = rank;
    opts.worldSize = worldSize;
    opts.minSize = minSize;
    opts.join = join != 0;
    if (hostId != nullptr) {
      opts.hostId = hostId;
    }
    if (timeoutMs > 0) {
      opts.timeout = ms(timeoutMs);
    }
    return new tpucoll::elastic::ElasticAgent(*asStore(store),
                                              *asDevice(device), opts);
  });
}

// Build the communicator for the CURRENT head epoch and bind it as the
// agent's monitored context. *out is a full Context handle owned by the
// caller (tc_context_free it — but only AFTER a later tc_elastic_rebuild
// or tc_elastic_stop has unbound it). Typed failures: TC_ERR_TIMEOUT
// past `timeoutMs` (<= 0 uses the agent default), TC_ERR_IO "evicted" /
// "below min_size".
int tc_elastic_rebuild(void* agent, int64_t timeoutMs, void** out) {
  return wrap([&] {
    *out = asElastic(agent)->rebuild(ms(timeoutMs)).release();
  });
}

// Publish hard failure evidence ({"suspect_wid": w|-1, ...}) for the
// bound epoch; the coordinator folds it into the next membership bump.
int tc_elastic_note_failure(void* agent, const char* evidenceJson) {
  return wrap([&] {
    TC_ENFORCE(evidenceJson != nullptr && evidenceJson[0] != '\0',
               "tc_elastic_note_failure: empty evidence");
    asElastic(agent)->noteFailure(evidenceJson);
  });
}

// Graceful leave: stop the heartbeat + monitor threads and delete this
// worker's lease (peers observe an immediate departure). Idempotent.
int tc_elastic_stop(void* agent) {
  return wrap([&] { asElastic(agent)->stop(); });
}

void tc_elastic_free(void* agent) {
  wrapVoid([&] { delete asElastic(agent); });
}

// Epoch of the bound context (0 before the first rebuild).
uint64_t tc_elastic_epoch(void* agent) {
  return wrapVal<uint64_t>(0, [&] { return asElastic(agent)->boundEpoch(); });
}

// Latest published epoch this agent has observed.
uint64_t tc_elastic_head_epoch(void* agent) {
  return wrapVal<uint64_t>(0, [&] { return asElastic(agent)->headEpoch(); });
}

// 1 when the membership moved past the bound context's epoch (the bound
// collective surface is — or is about to be — poisoned); 0 otherwise.
int tc_elastic_poll(void* agent) {
  return wrapVal(0, [&] {
    return asElastic(agent)->epochChanged() ? 1 : 0;
  });
}

// Agent status document (the metrics()["elastic"] payload —
// docs/observability.md); malloc'd, free with tc_buf_free.
int tc_elastic_status_json(void* agent, uint8_t** out, size_t* outLen) {
  return wrap([&] { copyOut(asElastic(agent)->statusJson(), out, outLen); });
}

// ---- deterministic fault-injection plane (fault/) ----

// Install a fault schedule (JSON, docs/faults.md) for THIS process,
// replacing any previous one and resetting the firing report. The table
// is process-global: rules pin the injecting `rank` so several
// in-process ranks can share it. Returns TC_ERR on malformed input.
int tc_fault_install(const char* json) {
  return wrap([&] {
    TC_ENFORCE(json != nullptr && json[0] != '\0',
               "tc_fault_install: empty schedule (use tc_fault_clear)");
    tpucoll::fault::install(json);
  });
}

// Remove the installed schedule; the transport hot path returns to its
// single armed() pointer check costing nothing.
void tc_fault_clear() {
  wrapVoid([&] { tpucoll::fault::clear(); });
}

// Deterministic firing log as a JSON array (malloc'd; free with
// tc_buf_free). Same seed + schedule + per-rank workload => the
// per-rank subsequences are byte-identical across runs.
int tc_fault_report(uint8_t** out, size_t* outLen) {
  return wrap([&] { copyOut(tpucoll::fault::report(), out, outLen); });
}

// ---- collectives ----

// `algorithm` on barrier/broadcast/allgather: 0 = the flat schedule,
// 1 = hierarchical (HierDispatch::kHier; degrades to flat on a flat
// topology — see group/hier.h).
int tc_barrier(void* ctx, int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::BarrierOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.algorithm = static_cast<tpucoll::HierDispatch>(algorithm);
    tpucoll::barrier(opts);
  });
}

int tc_broadcast(void* ctx, void* buffer, size_t count, int dtype, int root,
                 int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::BroadcastOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.buffer = buffer;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.root = root;
    opts.algorithm = static_cast<tpucoll::HierDispatch>(algorithm);
    tpucoll::broadcast(opts);
  });
}

int tc_allreduce(void* ctx, const void* input, void* output, size_t count,
                 int dtype, int op, int algorithm, uint32_t tag,
                 int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllreduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.inputs = {input};
    opts.outputs = {output};
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.algorithm = static_cast<tpucoll::AllreduceAlgorithm>(algorithm);
    tpucoll::allreduce(opts);
  });
}

// ---- zero-copy in-place entries (persistent-plan hot path) ----
// One stable buffer pointer in, result written straight into it — no
// copy-out pair, no per-call output allocation on the Python side, and
// a (ptr, nbytes)-stable key for the plan cache (collectives/plan.h):
// the steady-state Nth call performs zero allocations and zero buffer
// registrations.

// In-place allreduce of `buffer` (count elements of dtype).
int tc_allreduce_inplace(void* ctx, void* buffer, size_t count, int dtype,
                         int op, int algorithm, uint32_t tag,
                         int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllreduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.inputs = {buffer};
    opts.outputs = {buffer};
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.algorithm = static_cast<tpucoll::AllreduceAlgorithm>(algorithm);
    tpucoll::allreduce(opts);
  });
}

// In-place reduce_scatter: this rank's reduced block (recvCounts[rank]
// elements) lands at the FRONT of `buffer`; the rest of the buffer's
// contents are unspecified afterwards (the schedule works in plan
// scratch, so they are in practice left as the caller's input — but
// only the front block is contract).
int tc_reduce_scatter_inplace(void* ctx, void* buffer,
                              const size_t* recvCounts, int dtype, int op,
                              int algorithm, uint32_t tag,
                              int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ReduceScatterOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = buffer;
    opts.output = buffer;
    opts.recvCounts = countsVec(recvCounts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.algorithm = static_cast<tpucoll::ReduceScatterAlgorithm>(algorithm);
    tpucoll::reduceScatter(opts);
  });
}

// ---- plan-cache introspection (collectives/plan.h) ----

// Entries currently cached on this context (hits/misses/evictions and
// the ubuf_creates registration counter live in tc_metrics_json).
size_t tc_plan_cache_size(void* ctx) {
  return wrapVal<size_t>(0, [&] {
    return asContext(ctx)->planCache().size();
  });
}

// Drop every cached plan (A/B measurement, tests). Safe at any point a
// collective is not concurrently running on the context.
void tc_plan_cache_clear(void* ctx) {
  wrapVoid([&] { asContext(ctx)->planCache().clear(); });
}

int tc_reduce(void* ctx, const void* input, void* output, size_t count,
              int dtype, int op, int root, int algorithm, uint32_t tag,
              int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ReduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.root = root;
    opts.algorithm = static_cast<tpucoll::ReduceAlgorithm>(algorithm);
    tpucoll::reduce(opts);
  });
}


// Custom-reduction variants: `fn` is an arbitrary commutative-associative
// accumulate callback fn(acc, in, n_elems) invoked on the calling thread
// (reference: gloo/allreduce.h:36 arbitrary Func; gloo/algorithm.h:59-95
// ReductionFunction CUSTOM). Python passes a ctypes CFUNCTYPE here.
int tc_allreduce_fn(void* ctx, const void* input, void* output, size_t count,
                    int dtype, void (*fn)(void*, const void*, size_t),
                    int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllreduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.inputs = {input};
    opts.outputs = {output};
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.customFn = fn;
    opts.algorithm = static_cast<tpucoll::AllreduceAlgorithm>(algorithm);
    tpucoll::allreduce(opts);
  });
}

int tc_reduce_fn(void* ctx, const void* input, void* output, size_t count,
                 int dtype, void (*fn)(void*, const void*, size_t), int root,
                 int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ReduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.customFn = fn;
    opts.root = root;
    opts.algorithm = static_cast<tpucoll::ReduceAlgorithm>(algorithm);
    tpucoll::reduce(opts);
  });
}

int tc_reduce_scatter_fn(void* ctx, const void* input, void* output,
                         const size_t* recvCounts, int dtype,
                         void (*fn)(void*, const void*, size_t),
                         int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ReduceScatterOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.recvCounts = countsVec(recvCounts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    opts.customFn = fn;
    opts.algorithm = static_cast<tpucoll::ReduceScatterAlgorithm>(algorithm);
    tpucoll::reduceScatter(opts);
  });
}

int tc_allreduce_multi_fn(void* ctx, const void** inputs, void** outputs,
                          size_t nbufs, size_t count, int dtype,
                          void (*fn)(void*, const void*, size_t),
                          int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllreduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.inputs.assign(inputs, inputs + nbufs);
    opts.outputs.assign(outputs, outputs + nbufs);
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.customFn = fn;
    opts.algorithm = static_cast<tpucoll::AllreduceAlgorithm>(algorithm);
    tpucoll::allreduce(opts);
  });
}

int tc_gather(void* ctx, const void* input, void* output, size_t count,
              int dtype, int root, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::GatherOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.root = root;
    tpucoll::gather(opts);
  });
}

int tc_gatherv(void* ctx, const void* input, void* output,
               const size_t* counts, int dtype, int root, uint32_t tag,
               int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::GathervOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.counts = countsVec(counts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    opts.root = root;
    tpucoll::gatherv(opts);
  });
}

int tc_scatter(void* ctx, const void* input, void* output, size_t count,
               int dtype, int root, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ScatterOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.root = root;
    tpucoll::scatter(opts);
  });
}

int tc_allgather(void* ctx, const void* input, void* output, size_t count,
                 int dtype, int algorithm, uint32_t tag,
                 int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllgatherOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.algorithm = static_cast<tpucoll::HierDispatch>(algorithm);
    tpucoll::allgather(opts);
  });
}

int tc_allgatherv(void* ctx, const void* input, void* output,
                  const size_t* counts, int dtype, uint32_t tag,
                  int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllgathervOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.counts = countsVec(counts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    tpucoll::allgatherv(opts);
  });
}

int tc_alltoall(void* ctx, const void* input, void* output, size_t count,
                int dtype, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AlltoallOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    tpucoll::alltoall(opts);
  });
}

int tc_alltoallv(void* ctx, const void* input, const size_t* inCounts,
                 void* output, const size_t* outCounts, int dtype,
                 uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AlltoallvOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.inCounts = countsVec(inCounts, asContext(ctx)->size());
    opts.outCounts = countsVec(outCounts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    tpucoll::alltoallv(opts);
  });
}

int tc_reduce_scatter(void* ctx, const void* input, void* output,
                      const size_t* recvCounts, int dtype, int op,
                      int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::ReduceScatterOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.input = input;
    opts.output = output;
    opts.recvCounts = countsVec(recvCounts, asContext(ctx)->size());
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.algorithm = static_cast<tpucoll::ReduceScatterAlgorithm>(algorithm);
    tpucoll::reduceScatter(opts);
  });
}

int tc_allreduce_multi(void* ctx, const void** inputs, void** outputs,
                       size_t nbuffers, size_t count, int dtype, int op,
                       int algorithm, uint32_t tag, int64_t timeoutMs) {
  return wrap([&] {
    tpucoll::AllreduceOptions opts;
    fillCommon(opts, asContext(ctx), tag, timeoutMs);
    opts.inputs.assign(inputs, inputs + nbuffers);
    opts.outputs.assign(outputs, outputs + nbuffers);
    opts.count = count;
    opts.dtype = static_cast<DataType>(dtype);
    opts.op = static_cast<ReduceOp>(op);
    opts.algorithm = static_cast<tpucoll::AllreduceAlgorithm>(algorithm);
    tpucoll::allreduce(opts);
  });
}

// ---- int8 block-quantized wire codec (math.h q8 stream layout) ----
// Exposed for the Python surface and the q8 property tests: the same
// kernels AllreduceAlgorithm::kRingQ8Wire runs per hop.

// Resolved TPUCOLL_Q8_BLOCK (elements per block); 0 + tc_last_error on a
// malformed knob.
size_t tc_q8_block() {
  return wrapVal<size_t>(0, [&] { return tpucoll::q8BlockElems(); });
}

// Wire bytes a `count`-element float32 stream occupies after encoding.
size_t tc_q8_wire_bytes(size_t count) {
  return wrapVal<size_t>(0, [&] {
    return tpucoll::q8WireBytes(count, tpucoll::q8BlockElems());
  });
}

// Encode `count` float32 elements into the q8 wire stream. dstBytes must
// equal tc_q8_wire_bytes(count) — a size echo so a stale caller fails
// loudly instead of overrunning.
int tc_q8_encode(const void* src, size_t count, void* dst,
                 size_t dstBytes) {
  return wrap([&] {
    const size_t block = tpucoll::q8BlockElems();
    TC_ENFORCE_EQ(dstBytes, tpucoll::q8WireBytes(count, block));
    tpucoll::f32StreamToQ8(static_cast<const float*>(src),
                           static_cast<uint8_t*>(dst), count, block);
  });
}

// Decode a q8 wire stream back to `count` float32 elements (srcBytes
// echoes tc_q8_wire_bytes(count)).
int tc_q8_decode(const void* src, size_t srcBytes, void* dst,
                 size_t count) {
  return wrap([&] {
    const size_t block = tpucoll::q8BlockElems();
    TC_ENFORCE_EQ(srcBytes, tpucoll::q8WireBytes(count, block));
    tpucoll::q8StreamToF32(static_cast<const uint8_t*>(src),
                           static_cast<float*>(dst), count, block);
  });
}

// ---- int4 packed-nibble wire codec (math.h q4 stream layout) ----
// Same surface as q8: the kernels AllreduceAlgorithm::kRingQ4Wire runs.

// Resolved TPUCOLL_Q4_BLOCK (elements per block).
size_t tc_q4_block() {
  return wrapVal<size_t>(0, [&] { return tpucoll::q4BlockElems(); });
}

size_t tc_q4_wire_bytes(size_t count) {
  return wrapVal<size_t>(0, [&] {
    return tpucoll::q4WireBytes(count, tpucoll::q4BlockElems());
  });
}

int tc_q4_encode(const void* src, size_t count, void* dst,
                 size_t dstBytes) {
  return wrap([&] {
    const size_t block = tpucoll::q4BlockElems();
    TC_ENFORCE_EQ(dstBytes, tpucoll::q4WireBytes(count, block));
    tpucoll::f32StreamToQ4(static_cast<const float*>(src),
                           static_cast<uint8_t*>(dst), count, block);
  });
}

int tc_q4_decode(const void* src, size_t srcBytes, void* dst,
                 size_t count) {
  return wrap([&] {
    const size_t block = tpucoll::q4BlockElems();
    TC_ENFORCE_EQ(srcBytes, tpucoll::q4WireBytes(count, block));
    tpucoll::q4StreamToF32(static_cast<const uint8_t*>(src),
                           static_cast<float*>(dst), count, block);
  });
}

// ---- sharded codec surface (common/codec_pool.h + wire_codec.h) ----
// The exact kernels the pipelined wire rings shard across the codec
// pool, exposed so tests can prove byte-identity against the serial
// walk for any shard count. `kind`: 0 = bf16, 1 = q8, 2 = q4.

namespace {
const tpucoll::algorithms::WireCodec& codecFor(int kind) {
  switch (kind) {
    case tpucoll::algorithms::kWireCodecBf16:
      return tpucoll::algorithms::bf16WireCodec();
    case tpucoll::algorithms::kWireCodecQ8:
      return tpucoll::algorithms::q8WireCodec();
    case tpucoll::algorithms::kWireCodecQ4:
      return tpucoll::algorithms::q4WireCodec();
    default:
      TC_THROW(tpucoll::EnforceError, "unknown wire codec kind ", kind);
  }
}
}  // namespace

// Resolved TPUCOLL_CODEC_THREADS (pool width, >= 1).
int tc_codec_threads() {
  return wrapVal(0, [&] { return tpucoll::codec::codecThreads(); });
}

// Resolved TPUCOLL_CODEC_PIPELINE (sub-blocks per ring hop, >= 1).
int tc_codec_pipeline() {
  return wrapVal(0, [&] { return tpucoll::codec::codecPipelineDepth(); });
}

// Encode `count` float32 elements into `kind`'s wire stream across
// `shards` pool shards (dstBytes echoes the codec's wire size). Output
// is byte-identical to shards == 1 for every shard count.
int tc_codec_encode_sharded(int kind, const void* src, size_t count,
                            void* dst, size_t dstBytes, size_t shards) {
  return wrap([&] {
    const auto& codec = codecFor(kind);
    TC_ENFORCE_EQ(dstBytes, codec.wire(count));
    tpucoll::algorithms::wireEncode(codec, static_cast<const float*>(src),
                                    static_cast<uint8_t*>(dst), count,
                                    shards);
  });
}

// acc[i] += decode(wire)[i] across `shards` pool shards (the fused
// dequant-accumulate the reduce-scatter hops run).
int tc_codec_accumulate_sharded(int kind, void* acc, const void* wire,
                                size_t count, size_t wireBytes,
                                size_t shards) {
  return wrap([&] {
    const auto& codec = codecFor(kind);
    TC_ENFORCE_EQ(wireBytes, codec.wire(count));
    tpucoll::algorithms::wireAccumulate(codec, static_cast<float*>(acc),
                                        static_cast<const uint8_t*>(wire),
                                        count, shards);
  });
}

// ---- async collective engine (async/engine.h) ----

// COLLECTIVE constructor: forks `lanes` privately-tagged sub-contexts
// over `ctx`, so every rank must call concurrently with the same lane
// count and tag base (0 = the default base). Returns NULL + tc_last_error
// on failure.
void* tc_async_new(void* ctx, int lanes, uint32_t tagBase) {
  try {
    tpucoll::async::EngineOptions opts;
    opts.lanes = lanes;
    if (tagBase != 0) {
      opts.tagBase = tagBase;
    }
    return new tpucoll::async::Engine(asContext(ctx), opts);
  } catch (const std::exception& e) {
    g_lastError = e.what();
    return nullptr;
  }
}

// Fail queued work (typed, at wait), abort the in-flight op on every
// lane, join the lane threads. Idempotent; also run by tc_async_free.
int tc_async_shutdown(void* eng) {
  return wrap([&] { asEngine(eng)->shutdown(); });
}

void tc_async_free(void* eng) {
  wrapVoid([&] { delete asEngine(eng); });
}

int tc_async_lanes(void* eng) {
  return wrapVal(0, [&] { return asEngine(eng)->lanes(); });
}

// Borrowed handle to lane `lane`'s forked sub-context, usable with the
// introspection entry points (tc_metrics_json / tc_flightrec_json /
// tc_flightrec_dump). Owned by the engine — never tc_context_free it.
void* tc_async_lane_context(void* eng, int lane) {
  return wrapPtr([&]() -> void* {
    return asEngine(eng)->laneContext(lane);
  });
}

// Engine counters: {"lanes","in_flight","submitted","completed",
// "errors","per_lane":[{"submitted","completed","errors","queue_depth",
// "poisoned"}]}. malloc'd; free with tc_buf_free.
int tc_async_stats_json(void* eng, uint8_t** out, size_t* outLen) {
  return wrap([&] { copyOut(asEngine(eng)->statsJson(), out, outLen); });
}

// Async collectives: same semantics as the blocking forms, except the
// call returns a work handle immediately and the collective runs on the
// engine's deterministically-assigned lane. Buffers must stay valid
// until the work completes; on error the buffer contents are UNDEFINED
// (docs/errors.md "In-place collectives" — the undefined window opens at
// ISSUE time, not at wait). timeoutMs 0 uses the parent context default.
void* tc_async_allreduce(void* eng, const void* input, void* output,
                         size_t count, int dtype, int op, int algorithm,
                         int64_t timeoutMs) {
  return submitWork([&] {
    return asEngine(eng)->allreduce(
        input, output, count, static_cast<DataType>(dtype),
        static_cast<ReduceOp>(op), algorithm, ms(timeoutMs));
  });
}

// In-place async allreduce — the tc_allreduce_inplace analog on the
// engine's lane (stable buffer pointer -> per-lane plan-cache hits).
void* tc_async_allreduce_inplace(void* eng, void* buffer, size_t count,
                                 int dtype, int op, int algorithm,
                                 int64_t timeoutMs) {
  return submitWork([&] {
    return asEngine(eng)->allreduce(
        buffer, buffer, count, static_cast<DataType>(dtype),
        static_cast<ReduceOp>(op), algorithm, ms(timeoutMs));
  });
}

void* tc_async_reduce_scatter(void* eng, const void* input, void* output,
                              const size_t* recvCounts, int size,
                              int dtype, int op, int algorithm,
                              int64_t timeoutMs) {
  return submitWork([&] {
    return asEngine(eng)->reduceScatter(
        input, output, countsVec(recvCounts, size),
        static_cast<DataType>(dtype), static_cast<ReduceOp>(op), algorithm,
        ms(timeoutMs));
  });
}

void* tc_async_allgather(void* eng, const void* input, void* output,
                         size_t count, int dtype, int algorithm,
                         int64_t timeoutMs) {
  return submitWork([&] {
    return asEngine(eng)->allgather(input, output, count,
                                    static_cast<DataType>(dtype),
                                    algorithm, ms(timeoutMs));
  });
}

// Block until the work completes. Returns TC_OK on success; the op's own
// (lane/op-augmented) typed failure otherwise — TC_ERR_TIMEOUT both for
// an op that timed out and for a wait that gave up first (the message
// distinguishes them; the op is NOT cancelled by a wait timeout).
// timeoutMs <= 0 waits with no deadline.
int tc_work_wait(void* work, int64_t timeoutMs) {
  return wrap([&] { (*asWork(work))->wait(workTimeout(timeoutMs)); });
}

// Non-blocking status probe: 0 queued, 1 running, 2 completed ok,
// 3 completed with error (the error itself surfaces at tc_work_wait).
int tc_work_status(void* work) {
  // -1 (with tc_last_error set) when the probe itself fails.
  return wrapVal(-1, [&] {
    return static_cast<int>((*asWork(work))->status());
  });
}

// Error message of a failed work ("" when none / not finished); malloc'd,
// free with tc_buf_free.
int tc_work_error_message(void* work, uint8_t** out, size_t* outLen) {
  return wrap([&] {
    copyOut((*asWork(work))->errorMessage(), out, outLen);
  });
}

void tc_work_free(void* work) {
  wrapVoid([&] { delete asWork(work); });
}

// ---- point-to-point ----

void* tc_buffer_new(void* ctx, void* ptr, size_t size) {
  return wrapPtr([&]() -> void* {
    return asContext(ctx)->createUnboundBuffer(ptr, size).release();
  });
}

void tc_buffer_free(void* buf) {
  wrapVoid([&] {
    frErase(buf);
    delete asBuffer(buf);
  });
}

int tc_buffer_send(void* buf, int dst, uint64_t slot, size_t offset,
                   size_t nbytes) {
  return wrap([&] {
    asBuffer(buf)->send(dst, slot, offset, nbytes);
    if (auto* m = bufMetrics(asBuffer(buf))) {
      m->recordCall(tpucoll::MetricOp::kSend, nbytes);
    }
    if (auto* fr = bufFlightrec(asBuffer(buf))) {
      frPush(buf, /*isSend=*/true, fr->beginP2p(kFrSend, slot, dst, nbytes));
    }
  });
}

int tc_buffer_recv(void* buf, int src, uint64_t slot, size_t offset,
                   size_t nbytes) {
  return wrap([&] {
    asBuffer(buf)->recv(src, slot, offset, nbytes);
    if (auto* m = bufMetrics(asBuffer(buf))) {
      m->recordCall(tpucoll::MetricOp::kRecv, nbytes);
    }
    if (auto* fr = bufFlightrec(asBuffer(buf))) {
      frPush(buf, /*isSend=*/false,
             fr->beginP2p(kFrRecv, slot, src, nbytes));
    }
  });
}

int tc_buffer_recv_any(void* buf, const int* srcs, size_t nsrcs,
                       uint64_t slot, size_t offset, size_t nbytes) {
  return wrap([&] {
    asBuffer(buf)->recv(std::vector<int>(srcs, srcs + nsrcs), slot, offset,
                        nbytes);
    if (auto* m = bufMetrics(asBuffer(buf))) {
      m->recordCall(tpucoll::MetricOp::kRecv, nbytes);
    }
    if (auto* fr = bufFlightrec(asBuffer(buf))) {
      // peer resolves when the wait completes (setPeer).
      frPush(buf, /*isSend=*/false,
             fr->beginP2p(kFrRecv, slot, nsrcs == 1 ? srcs[0] : -1, nbytes));
    }
  });
}

// The p2p waits carry the user-facing instrumentation (tracer span +
// latency histogram + error counter). Collective-internal waits are NOT
// routed through here, so p2p spans never flood a collective trace.
int tc_buffer_wait_send(void* buf, int64_t timeoutMs) {
  UnboundBuffer* b = asBuffer(buf);
  tpucoll::Metrics* m = bufMetrics(b);
  const bool measured = m != nullptr && m->enabled();
  const int64_t startUs = measured ? tpucoll::Tracer::nowUs() : 0;
  int rv = TC_OK;
  int code;
  {
    auto span = maybeSpan(b, "wait_send");
    code = wrap([&] {
      if (!b->waitSend(ms(timeoutMs))) {
        rv = TC_ERR_ABORTED;
      }
    });
  }
  if (measured) {
    m->recordLatency(tpucoll::MetricOp::kSend,
                     tpucoll::Tracer::nowUs() - startUs);
    if (code != TC_OK) {
      m->recordError(tpucoll::MetricOp::kSend);
    }
  }
  if (code == TC_OK && rv == TC_OK) {
    if (auto* fr = bufFlightrec(b)) {
      fr->transition(frPop(buf, /*isSend=*/true),
                     tpucoll::FlightRecorder::kCompleted);
    }
  }
  return code != TC_OK ? code : rv;
}

int tc_buffer_wait_put(void* buf, int64_t timeoutMs, int* srcOut) {
  UnboundBuffer* b = asBuffer(buf);
  int rv = TC_OK;
  int code;
  {
    auto span = maybeSpan(b, "wait_put");
    code = wrap([&] {
      if (!b->waitPutArrival(srcOut, ms(timeoutMs))) {
        rv = TC_ERR_ABORTED;
      }
    });
    if (code == TC_OK && rv == TC_OK && srcOut != nullptr) {
      span.setPeer(*srcOut);
    }
  }
  return code != TC_OK ? code : rv;
}

int tc_buffer_wait_recv(void* buf, int64_t timeoutMs, int* srcOut) {
  UnboundBuffer* b = asBuffer(buf);
  tpucoll::Metrics* m = bufMetrics(b);
  const bool measured = m != nullptr && m->enabled();
  const int64_t startUs = measured ? tpucoll::Tracer::nowUs() : 0;
  int rv = TC_OK;
  int code;
  {
    auto span = maybeSpan(b, "wait_recv");
    code = wrap([&] {
      if (!b->waitRecv(srcOut, ms(timeoutMs))) {
        rv = TC_ERR_ABORTED;
      }
    });
    if (code == TC_OK && rv == TC_OK && srcOut != nullptr) {
      span.setPeer(*srcOut);
    }
  }
  if (measured) {
    m->recordLatency(tpucoll::MetricOp::kRecv,
                     tpucoll::Tracer::nowUs() - startUs);
    if (code != TC_OK) {
      m->recordError(tpucoll::MetricOp::kRecv);
    }
  }
  if (code == TC_OK && rv == TC_OK) {
    if (auto* fr = bufFlightrec(b)) {
      const uint64_t seq = frPop(buf, /*isSend=*/false);
      fr->transition(seq, tpucoll::FlightRecorder::kCompleted);
      if (srcOut != nullptr) {
        fr->setPeer(seq, *srcOut);
      }
    }
  }
  return code != TC_OK ? code : rv;
}

size_t tc_remote_key_size() {
  return wrapVal<size_t>(0, [&] {
    return sizeof(tpucoll::transport::WireRemoteKey);
  });
}

int tc_buffer_remote_key(void* buf, char* out, size_t outLen) {
  return wrap([&] {
    auto key = asBuffer(buf)->getRemoteKey();
    TC_ENFORCE_EQ(key.size(), outLen, "remote key buffer size mismatch");
    std::memcpy(out, key.data(), key.size());
  });
}

int tc_buffer_put(void* buf, const char* key, size_t keyLen, size_t offset,
                  size_t roffset, size_t nbytes, int notify) {
  return wrap([&] {
    asBuffer(buf)->put(std::string(key, keyLen), offset, roffset, nbytes,
                       notify != 0);
    if (auto* fr = bufFlightrec(asBuffer(buf))) {
      frPush(buf, /*isSend=*/true, fr->beginP2p(kFrPut, 0, -1, nbytes));
    }
  });
}

int tc_buffer_get(void* buf, const char* key, size_t keyLen, uint64_t slot,
                  size_t offset, size_t roffset, size_t nbytes) {
  return wrap([&] {
    asBuffer(buf)->get(std::string(key, keyLen), slot, offset, roffset,
                       nbytes);
    if (auto* fr = bufFlightrec(asBuffer(buf))) {
      frPush(buf, /*isSend=*/false, fr->beginP2p(kFrGet, slot, -1, nbytes));
    }
  });
}

void tc_buffer_abort_wait_send(void* buf) {
  wrapVoid([&] { asBuffer(buf)->abortWaitSend(); });
}

void tc_buffer_abort_wait_recv(void* buf) {
  wrapVoid([&] { asBuffer(buf)->abortWaitRecv(); });
}

}  // extern "C"

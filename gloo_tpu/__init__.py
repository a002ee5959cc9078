"""gloo_tpu: a TPU-native collective communications framework.

Two data planes, mirroring the reference's tcp-vs-ibverbs/CUDA split
(/root/reference/gloo, see SURVEY.md):

- **Host plane** (`gloo_tpu.core`, C++ core in `csrc/`): store-based
  rendezvous into a full-mesh process group, slot-tagged async send/recv over
  an epoll TCP transport, and the full collective suite (barrier, broadcast,
  allreduce, reduce, gather(v), scatter, allgather(v), alltoall(v),
  reduce_scatter) with timeouts and abortable waits.
- **Device plane** (`gloo_tpu.tpu`): the same collective surface over jax
  arrays sharded across a `jax.sharding.Mesh` — XLA collectives compiled over
  ICI, plus Pallas ring kernels for custom schedules.
"""

from gloo_tpu import elastic, fault, schedule, tuning
from gloo_tpu.bootstrap import detect_launch_env, init_from_env
from gloo_tpu.bucketer import GradientBucketer
from gloo_tpu.core import (
    Aborted,
    AsyncEngine,
    CollectivePlan,
    Context,
    Device,
    Error,
    FileStore,
    HashStore,
    IoError,
    PrefixStore,
    ReduceOp,
    Store,
    TcpStore,
    TcpStoreServer,
    set_connect_debug_logger,
    TimeoutError,
    UnboundBuffer,
    Work,
    codec_pipeline,
    codec_threads,
    crypto_isa_tier,
    derive_keyring,
    q4_block,
    q4_decode,
    q4_encode,
    q4_wire_bytes,
    q8_block,
    q8_decode,
    q8_encode,
    q8_wire_bytes,
    uring_available,
)

__version__ = "0.1.0"

__all__ = [
    "Aborted",
    "AsyncEngine",
    "Context",
    "GradientBucketer",
    "Work",
    "Device",
    "Error",
    "FileStore",
    "HashStore",
    "IoError",
    "PrefixStore",
    "ReduceOp",
    "Store",
    "TcpStore",
    "TcpStoreServer",
    "TimeoutError",
    "UnboundBuffer",
    "__version__",
    "crypto_isa_tier",
    "detect_launch_env",
    "init_from_env",
    "derive_keyring",
    "elastic",
    "fault",
    "codec_pipeline",
    "codec_threads",
    "q4_block",
    "q4_decode",
    "q4_encode",
    "q4_wire_bytes",
    "q8_block",
    "q8_decode",
    "q8_encode",
    "q8_wire_bytes",
    "schedule",
    "tuning",
    "uring_available",
]

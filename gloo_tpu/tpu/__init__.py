"""Device data plane: collectives over jax arrays sharded across a TPU mesh.

This package is the TPU-native analog of the reference's accelerator layer
(/root/reference/gloo/cuda*.{h,cu}, gloo/nccl/): where gloo moves GPU buffers
with NCCL ops and CUDA-aware ring schedules, gloo_tpu moves sharded jax
arrays with XLA collectives compiled over the ICI mesh (`spmd` module —
psum/all_gather/ppermute lowered by XLA) and with hand-written Pallas ring
kernels (`gloo_tpu.ops.pallas_ring`) for custom schedules.

Two usage levels:
- `gloo_tpu.tpu.spmd`: collective primitives used *inside* your own
  shard_map/pjit code (the moral equivalent of calling nccl ops on a
  stream);
- `TpuProcessGroup`: an array-level process-group API mirroring the host
  `gloo_tpu.Context` surface, where "rank" = mesh position along one axis
  and every call is a compiled XLA program.
"""

from gloo_tpu.tpu import spmd
from gloo_tpu.tpu.compile_cache import enable_compile_cache
from gloo_tpu.tpu.group import TpuProcessGroup
from gloo_tpu.tpu.hierarchical import (HierarchicalGroup,
                                       make_hierarchical_ddp)
from gloo_tpu.tpu.hlo_stats import CollectiveStats, collective_stats
from gloo_tpu.tpu.mesh import make_mesh
from gloo_tpu.tpu.multihost import init_multihost

__all__ = ["CollectiveStats", "HierarchicalGroup", "TpuProcessGroup",
           "collective_stats", "enable_compile_cache", "init_multihost",
           "make_hierarchical_ddp", "make_mesh", "spmd"]

"""Parallelism strategies built on the gloo_tpu collective layers.

The reference sits one layer below these (SURVEY.md §2.10): it supplies the
collectives that DP/TP/PP/SP are built from. This package closes the loop
by shipping the strategies themselves, each built on a gloo_tpu plane:

- `ddp`: data parallelism — device-plane gradient psum over the mesh, and
  host-plane gradient allreduce over the C++ TCP transport (the exact role
  the reference plays under PyTorch DDP);
- `tp`: Megatron-style tensor parallelism (column/row-parallel dense);
- `sp`: sequence/context parallelism — ring attention over ppermute,
  plus Ulysses-style all-to-all head/sequence exchange;
- `pp`: pipeline parallelism — the GPipe forward schedule plus the
  1F1B training schedule (activation stash bounded by stages, not
  microbatches), both static timetables under one lax.scan;
- `ep`: expert parallelism — a dropless MoE layer whose tokens reach the
  chips holding their experts over a ragged all-to-all;
- `fsdp`: ZeRO-3-style fully-sharded data parallelism — just-in-time
  parameter allgather whose autodiff transpose is the gradient
  reduce-scatter.
"""

from gloo_tpu.parallel.ddp import HostGradSync, make_ddp_train_step
from gloo_tpu.parallel.ep import moe
from gloo_tpu.parallel.fsdp import (make_fsdp_train_step, shard_params,
                                    unshard_params)
from gloo_tpu.parallel.pp import pipeline_apply, pipeline_train_1f1b
from gloo_tpu.parallel.sp import (ring_attention, ring_flash_attention,
                                  ulysses_attention)
from gloo_tpu.parallel.tp import (allgather_matmul_dense_auto,
                                  column_parallel_dense,
                                  estimate_comm_share, fused_compute_ratio,
                                  measure_fused_ratio, row_parallel_dense,
                                  row_parallel_dense_scattered_auto,
                                  tp_mlp_block, use_fused_overlap)

__all__ = [
    "HostGradSync",
    "allgather_matmul_dense_auto",
    "column_parallel_dense",
    "estimate_comm_share",
    "fused_compute_ratio",
    "measure_fused_ratio",
    "row_parallel_dense_scattered_auto",
    "use_fused_overlap",
    "make_ddp_train_step",
    "make_fsdp_train_step",
    "moe",
    "pipeline_apply",
    "pipeline_train_1f1b",
    "ring_attention",
    "ring_flash_attention",
    "row_parallel_dense",
    "shard_params",
    "ulysses_attention",
    "unshard_params",
    "tp_mlp_block",
]

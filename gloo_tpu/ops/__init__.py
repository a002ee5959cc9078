"""Pallas TPU kernels: custom collective schedules over ICI.

Device-plane analog of the reference's hand-written CUDA ring algorithms
(gloo/cuda_allreduce_ring*.cc): where XLA's built-in collectives (see
gloo_tpu.tpu.spmd) are the "NCCL path", these kernels drive the inter-chip
DMA engines directly for schedules XLA does not emit.
"""

from gloo_tpu.ops.attention import (flash_attention, flash_attention_step,
                                    flash_attention_bwd_step,
                                    largest_block, save_flash_residuals)
from gloo_tpu.ops.overlap import allgather_matmul, matmul_reduce_scatter
from gloo_tpu.ops.rope import apply_rope, rope_positions
from gloo_tpu.ops.pallas_ring import (pallas_alltoall, ring_allgather,
                                       ring_allreduce,
                                       ring_allreduce_bidir,
                                       ring_allreduce_hbm,
                                       ring_allreduce_q8,
                                       ring_allreduce_torus,
                                       ring_reduce_scatter)

__all__ = ["allgather_matmul", "apply_rope", "matmul_reduce_scatter",
           "rope_positions",
           "flash_attention", "flash_attention_step",
           "flash_attention_bwd_step", "pallas_alltoall", "ring_allgather",
           "ring_allreduce",
           "ring_allreduce_bidir",
           "ring_allreduce_hbm", "ring_allreduce_q8",
           "ring_allreduce_torus", "ring_reduce_scatter",
           "save_flash_residuals"]

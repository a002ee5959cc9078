"""Data-parallel training on both gloo_tpu planes.

Device plane: `make_ddp_train_step` compiles one XLA program where the
batch is sharded over the mesh's data axis, gradients are psum-averaged
over ICI inside shard_map, and the optimizer runs replicated — the
standard TPU DDP recipe. Leaves named in `param_specs` may instead be
split over the axis (expert parallelism's experts, one block a chip).

Host plane: `HostGradSync` averages numpy gradient pytrees across OS
processes with the C++ allreduce — exactly the role the reference plays as
PyTorch's ProcessGroup backend for DDP (SURVEY.md §2.10: "allreduce → DP
gradient sync").
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gloo_tpu.tpu import spmd


def make_ddp_train_step(loss_fn: Callable, optimizer, mesh,
                        axis: str = "data", param_specs=None):
    """Build a jitted (params, opt_state, batch) -> (params, opt_state,
    loss) step with gradient averaging over `axis`.

    `loss_fn(params, batch)` consumes the per-device micro-batch; `batch`
    leaves must have a leading axis divisible by the axis size.

    `param_specs`: a pytree of PartitionSpec like params, `P()` (every
    leaf replicated) by default. A leaf on `P(axis)` enters and leaves the
    step split over `axis`, each chip with its own slice (expert weights
    under expert parallelism); its gradient reaches it through the
    transpose of the exchange that fed it, not through an all-reduce, and
    its optimizer state stays split the same way.
    """
    specs = P() if param_specs is None else param_specs

    def local_grads(params, batch):
        # Forward ops carry `gloo_tpu.ddp.loss/jvp()` in their HLO op_name,
        # backward ops `gloo_tpu.ddp.loss/transpose(jvp())`.
        with jax.named_scope("gloo_tpu.ddp.loss"):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # Replicated params enter the manual region invariant, so AD's
        # transpose has already psum'd the per-device gradients across
        # `axis` (the all-reduce is `.../transpose(jvp())/psum_invariant`);
        # a split leaf's gradient is likewise every chip's contribution,
        # summed by the exchange's transpose. Dividing by the axis size
        # yields the mean for both (adding a pmean here would be a no-op
        # on the already-replicated value, not a division).
        n = spmd.size(axis)
        grads = jax.tree.map(lambda g: g / n, grads)
        return spmd.mean(loss, axis), grads

    import optax

    sharded_grads = jax.shard_map(
        local_grads, mesh=mesh,
        in_specs=(specs, P(axis)),
        out_specs=(P(), specs))

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = sharded_grads(params, batch)
        with jax.named_scope("gloo_tpu.ddp.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


class HostGradSync:
    """Average gradient pytrees across processes via the host data plane.

    Usage: each training process builds a connected `gloo_tpu.Context`,
    computes local gradients (any jax backend), then calls
    `average_(grads)` before the optimizer step. Matches the reference's
    DDP contract: allreduce(SUM) then divide by world size.

    bucketed=True switches to the async engine + gradient bucketer
    (docs/async.md): leaves are flattened into ~25 MiB per-dtype buckets
    issued asynchronously, so bucket k+1's pack overlaps bucket k's wire
    time — the fast path for the many-small-tensors shape of real
    models. Construction is then a COLLECTIVE (it forks lane
    sub-contexts), as is every average() call — same contract as the
    sequential path.
    """

    def __init__(self, context, bucketed: bool = False,
                 bucket_bytes=None, lanes=None, wire=None):
        """wire: opt-in wire compression for float32 gradients — "q8" /
        "bf16" / "lossy" (the Context.allreduce shorthand; precision
        contract in docs/algorithms.md). Gradient averaging is the
        canonical tolerant workload for lossy wire (EQuARX line of
        work); non-float32 leaves always ride the lossless path."""
        self.context = context
        self._tag = 1 << 20  # leave low tags to the application
        self._bucketer = None
        self._wire = wire
        if bucketed:
            from gloo_tpu.bucketer import GradientBucketer

            engine = context.async_engine(lanes=lanes)
            self._bucketer = GradientBucketer(
                engine, bucket_bytes=bucket_bytes, average=True,
                wire=wire)

    def average(self, grads):
        from gloo_tpu.utils.tracing import annotate

        size = self.context.size
        leaves, treedef = jax.tree.flatten(grads)
        out = []
        # The annotation puts the host-plane allreduce on the jax
        # profiler timeline next to device activity (the C++ tracer's
        # own span covers the native side; see docs/observability.md).
        with annotate("gloo_tpu.ddp.host_grad_sync"):
            if self._bucketer is not None:
                arrs = [np.ascontiguousarray(np.asarray(leaf))
                        for leaf in leaves]
                for arr in arrs:
                    self._bucketer.add(arr)
                self._bucketer.finish()  # arrs now hold the means
                out = [jnp.asarray(arr, dtype=leaf.dtype)
                       if hasattr(leaf, "dtype") else arr
                       for leaf, arr in zip(leaves, arrs)]
                return jax.tree.unflatten(treedef, out)
            for i, leaf in enumerate(leaves):
                arr = np.ascontiguousarray(np.asarray(leaf))
                wire = self._wire if arr.dtype == np.float32 else None
                self.context.allreduce(arr, op="sum", tag=self._tag + i,
                                       wire=wire)
                out.append(jnp.asarray(arr / size, dtype=leaf.dtype)
                           if hasattr(leaf, "dtype") else arr / size)
        return jax.tree.unflatten(treedef, out)

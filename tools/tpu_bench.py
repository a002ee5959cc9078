"""Device-plane benchmark: the north star's `--device tpu` sweep.

Measures compiled mesh collectives (the XLA/ICI path) and the Pallas ring
kernels over whatever devices are visible — a real TPU slice in
production, or a forced CPU mesh for functional runs:

    python tools/tpu_bench.py --op allreduce --elements 1024,1048576
    JAX_PLATFORMS_FORCE_CPU=8 python tools/tpu_bench.py --op all

Reports the same min/p50/p99/algbw table as tpucoll_bench. On a single
device, collectives compile and execute but involve no inter-chip
traffic; numbers then measure dispatch + on-chip bandwidth only (noted
in the header).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--op", default="allreduce",
                        choices=["allreduce", "allgather", "reduce_scatter",
                                 "alltoall", "ppermute", "pallas_ring",
                                 "pallas_ring_hbm", "flash_attention",
                                 "flash_attention_bwd", "overlap",
                                 "tp_step", "all"])
    parser.add_argument("--tp-shape", default="2048x4096x4096",
                        help="MxDxF for --op tp_step (seq x model x ffn)")
    parser.add_argument("--elements", default="1024,65536,1048576,16777216")
    parser.add_argument("--min-time", type=float, default=1.0)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--flash-blocks", default=None,
                        help="comma list of BQxBK pairs to sweep, e.g. "
                             "128x128,512x1024 (default: kernel defaults)")
    parser.add_argument("--overlap-shapes", default="4096x2048,2048x4096,"
                        "4096x4096",
                        help="MxK list for --op overlap (cols==K)")
    parser.add_argument("--overlap-ranks", type=int, default=8,
                        help="virtual ring size for --op overlap")
    args = parser.parse_args()

    if args.op in ("overlap", "tp_step"):
        # The overlap kernels keep x, w and 4 staging buffers resident in
        # VMEM; the default 16 MiB scoped-vmem budget rejects realistic TP
        # shard shapes. Must be set before libtpu loads — and ONLY for
        # this op, so the other rows stay comparable with prior runs
        # (the flag can shift XLA's fusion/tiling choices). `--op all`
        # re-execs overlap as a subprocess for the same reason.
        cur = os.environ.get("LIBTPU_INIT_ARGS", "")
        if "scoped_vmem_limit" not in cur:
            os.environ["LIBTPU_INIT_ARGS"] = (
                cur + " --xla_tpu_scoped_vmem_limit_kib=114688").strip()
    elif args.op == "all":
        # BEFORE this process touches JAX: the chip belongs to one process
        # at a time, so each child runs to its end first, and a child that
        # fails fails the run.
        import subprocess
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--op", "overlap",
             "--overlap-shapes", args.overlap_shapes,
             "--overlap-ranks", str(args.overlap_ranks),
             "--warmup", str(args.warmup)], check=True)
        subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--op", "tp_step",
             "--tp-shape", args.tp_shape,
             "--overlap-ranks", str(args.overlap_ranks),
             "--warmup", str(args.warmup)], check=True)

    force_cpu = os.environ.get("JAX_PLATFORMS_FORCE_CPU")
    if force_cpu:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{force_cpu}").strip()
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from gloo_tpu.tpu import enable_compile_cache, make_mesh, spmd

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu" and not force_cpu:
        raise SystemExit(
            f"tpu_bench needs a TPU; JAX found {device.platform}. A CPU "
            "mesh is only taken when JAX_PLATFORMS_FORCE_CPU=N asks for it.")
    mesh = make_mesh()
    n = int(np.prod(list(mesh.shape.values())))
    axis = mesh.axis_names[0]
    print(f"# tpu_bench devices={n}x{device.platform} "
          f"({device.device_kind}) mesh={dict(mesh.shape)}"
          + (" (single device: dispatch/on-chip only)" if n == 1 else ""))
    print(f"{'op':>16} {'bytes':>12} {'elements':>12} {'min(us)':>9} "
          f"{'p50(us)':>9} {'p99(us)':>9} {'algbw(GB/s)':>12} {'iters':>7}")

    def build(op, elements):
        per = max(elements // n, 1)
        if op in ("pallas_ring", "pallas_ring_hbm"):
            from gloo_tpu.ops import ring_allreduce, ring_allreduce_hbm
            base = (ring_allreduce if op == "pallas_ring"
                    else ring_allreduce_hbm)
            # CPU backends only run pallas through the interpreter.
            interp = jax.devices()[0].platform == "cpu"
            kern = lambda s, a: base(s, a, interpret=interp)  # noqa: E731
            rows = max(per // 128, n)
            rows -= rows % n or 0
            rows = max(rows, n)
            if op == "pallas_ring_hbm" and (rows // n) > 256:
                rows -= rows % (256 * n)
            x = jnp.ones((n * rows, 128), jnp.float32)
            fn = jax.jit(jax.shard_map(lambda s: kern(s, axis), mesh=mesh,
                                       in_specs=P(axis), out_specs=P(axis),
                                       check_vma=False))
            nbytes = rows * 128 * 4  # per-shard payload
            return fn, (x,), nbytes
        x = jnp.ones((n, per), jnp.float32)
        shard_ops = {
            "allreduce": lambda s: spmd.allreduce(s, axis),
            "allgather": lambda s: spmd.allgather(s[0], axis)[None],
            "reduce_scatter": lambda s: spmd.reduce_scatter(
                s[0].reshape(n, -1) if per >= n else s, axis)[None],
            "alltoall": lambda s: spmd.alltoall(
                s[0].reshape(n, -1), axis)[None] if per >= n else s,
            "ppermute": lambda s: spmd.shift(s, axis, 1),
        }
        fn = jax.jit(jax.shard_map(shard_ops[op], mesh=mesh,
                                   in_specs=P(axis), out_specs=P(axis)))
        return fn, (x,), per * 4

    ops = (["allreduce", "allgather", "reduce_scatter", "alltoall",
            "ppermute", "pallas_ring", "pallas_ring_hbm",
            "flash_attention", "flash_attention_bwd", "overlap"]
           if args.op == "all" else [args.op])
    elements_list = [int(e) for e in args.elements.split(",")]

    for mode in ("flash_attention", "flash_attention_bwd"):
        if mode in ops:
            bench_flash_attention(args, jax, jnp, elements_list,
                                  backward=mode.endswith("bwd"))
            ops = [o for o in ops if o != mode]
    if "overlap" in ops:
        if args.op == "overlap":
            bench_overlap(args, jax, jnp, mesh, axis)
        # else: already ran as a pre-JAX-init subprocess above
        ops = [o for o in ops if o != "overlap"]
    if "tp_step" in ops:
        bench_tp_step(args, jax, jnp, axis)
        ops = [o for o in ops if o != "tp_step"]
    for op in ops:
        for elements in elements_list:
            fn, fargs, nbytes = build(op, elements)
            jax.block_until_ready(fn(*fargs))
            for _ in range(args.warmup):
                jax.block_until_ready(fn(*fargs))
            samples = []
            t_start = time.perf_counter()
            while time.perf_counter() - t_start < args.min_time:
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*fargs))
                samples.append(time.perf_counter() - t0)
            samples.sort()
            p = lambda q: samples[min(len(samples) - 1,
                                      int(q * len(samples)))] * 1e6
            algbw = nbytes / (p(0.5) / 1e6) / 1e9
            print(f"{op:>16} {nbytes:>12} {elements:>12} {p(0):>9.1f} "
                  f"{p(0.5):>9.1f} {p(0.99):>9.1f} {algbw:>12.3f} "
                  f"{len(samples):>7}")


def bench_flash_attention(args, jax, jnp, elements_list, backward=False):
    """MXU kernel timing by differencing: chain K kernel applications
    inside ONE jitted fori_loop (output feeds the next query, defeating
    DCE), force completion with a scalar fetch, and difference a K=1 run
    to cancel dispatch and the fetch. algbw column = achieved GFLOP/s.

    backward=True times fwd+bwd via jax.grad (flops counted 3.5x fwd:
    one forward pass plus the fused one-pass backward kernel, whose
    ideal matmul work is ~2.5x forward). --flash-blocks sweeps tile
    sizes."""
    import time as _time

    from jax import lax

    from gloo_tpu.ops import flash_attention

    interp = jax.devices()[0].platform == "cpu"
    h, d = 8, 128
    label = "flash_bwd" if backward else "flash_attention"
    print(f"# {label} rows: the last column is GFLOP/s, not GB/s")
    if args.flash_blocks:
        block_list = [tuple(int(x) for x in pair.split("x"))
                      for pair in args.flash_blocks.split(",")]
    else:
        block_list = [(None, None)]

    seen = set()
    for elements in elements_list:
        t = max(elements // (h * d) // 128 * 128, 128)
        if interp:
            # The interpreter executes each grid step in Python; large t
            # means (t/128)^2 * h invocations per call — cap it.
            t = min(t, 256)
        if t in seen:  # small elements values clamp to the same config
            continue
        seen.add(t)
        for bq, bk in block_list:
            tag = label if bq is None else f"{label}:{bq}x{bk}"
            q = jnp.ones((1, h, t, d), jnp.bfloat16)

            def apply(c):
                return flash_attention(c, c, c, causal=True,
                                       block_q=bq, block_k=bk,
                                       interpret=interp)

            if backward:
                step = jax.grad(
                    lambda c: jnp.sum(apply(c).astype(jnp.float32) ** 2))
            else:
                step = apply

            def chain(k):
                def body(i, c):
                    return step(c).astype(c.dtype)
                return jax.jit(lambda q: lax.fori_loop(0, k, body, q))

            per_iter, k_iters = _chain_rate(args, jax, chain, q,
                                            interp, _time, k0=64)
            if per_iter is None:
                print(f"{tag:>16} {'-':>12} {h * t * d:>12}   "
                      "skipped: timing noise exceeded kernel time "
                      "(t too small to difference)")
                continue
            fwd_flops = 2 * h * (t * t // 2) * d * 2
            flops = int(fwd_flops * 3.5) if backward else fwd_flops
            nbytes = 3 * h * t * d * 2
            if backward:
                # + dO/O/lse/delta reads and three f32 gradient writes.
                nbytes = nbytes + 2 * h * t * d * 2 + 3 * h * t * d * 4
            # Chained differenced timing: one per-iteration figure
            # (best-of-reps min), not a percentile.
            print(f"{tag:>16} {nbytes:>12} {h * t * d:>12} "
                  f"{per_iter * 1e6:>9.1f} {'-':>9} "
                  f"{'-':>9} {flops / per_iter / 1e9:>12.3f} {k_iters:>7}")


def bench_overlap(args, jax, jnp, mesh, axis):
    """Real-chip proof of the collective-matmul kernels' compute pipeline.

    On one chip the ring runs with self-loop neighbors (virtual_ranks):
    every hop's async copy lands in the local comm slot, so the kernel
    executes its full P-step schedule — per-chunk MXU matmuls, staged
    copies, semaphore waits — with the ICI leg replaced by on-chip DMA.
    Comparing against a plain jnp.dot of the same [M,K]@[K,K] answers the
    question that matters before any multi-chip run: how much MXU
    throughput does the fused schedule's chunking give up? (The ICI leg
    itself needs a multi-chip slice; tests/test_overlap.py covers ring
    correctness on the interpret mesh.)

    Timing is the differenced chained fori_loop (see
    bench_flash_attention): the output feeds the next input, and the
    chain grows until the differenced time exceeds 250 ms. The GFLOP/s
    column counts 2*M*K*K per iteration for all three variants.
    """
    import time as _time

    from jax import lax
    from jax.sharding import PartitionSpec as P

    from gloo_tpu.ops.overlap import _ag_matmul_shard, _matmul_rs_shard

    import numpy as np
    from jax.sharding import Mesh

    interp = jax.devices()[0].platform == "cpu"
    V = args.overlap_ranks
    # Self-loop mode needs a 1-device axis regardless of the full mesh.
    mesh = Mesh(np.asarray(jax.devices()[:1], dtype=object), (axis,))
    shapes = [tuple(int(v) for v in s.split("x"))
              for s in args.overlap_shapes.split(",")]
    print(f"# overlap: virtual ring V={V} (self-loop RDMA), cols=K; "
          f"last columns are GFLOP/s and fused/plain ratio")
    seen = set()
    for m, k in shapes:
        if interp:
            m, k = min(m, 256), min(k, 256)  # functional smoke only
        if (m, k) in seen:  # interp clamp collapses shapes
            continue
        seen.add((m, k))
        chunk = m // V
        if chunk == 0 or chunk % 8:
            print(f"{'overlap':>16} {'-':>12} {m}x{k}   skipped: "
                  f"M/V={m}/{V} not a usable chunk")
            continue
        w = jnp.full((k, k), 1.0 / k, jnp.bfloat16)
        flops = 2 * m * k * k

        def plain_body(c):
            return jnp.dot(c, w, preferred_element_type=jnp.float32
                           ).astype(c.dtype)

        def mmrs_body(c):
            y = _matmul_rs_shard(c, w, axis_name=axis, mesh_axes=None,
                                 collective_id=21, interpret=interp,
                                 virtual_ranks=V)
            return c.at[:chunk, :].set(y)

        def agmm_body(c):
            y, _ = _ag_matmul_shard(c, w, axis_name=axis, mesh_axes=None,
                                    collective_id=23, interpret=interp,
                                    virtual_ranks=V)
            return y[:chunk, :]

        variants = [("plain_dot", plain_body, (m, k)),
                    ("matmul_rs", mmrs_body, (m, k)),
                    ("ag_matmul", agmm_body, (chunk, k))]
        rates = {}
        for name, body, xshape in variants:
            x = jnp.ones(xshape, jnp.bfloat16)

            def make_chain(n_iter, body=body):
                def outer(xv):
                    return lax.fori_loop(0, n_iter,
                                         lambda i, c: body(c), xv)
                return jax.jit(jax.shard_map(outer, mesh=mesh,
                                             in_specs=P(), out_specs=P(),
                                             check_vma=False))

            per, _k = _chain_rate(args, jax, make_chain, x, interp,
                                  _time)
            if per is None:
                print(f"{name:>16} {'-':>12} {m}x{k}   skipped: timing "
                      "noise exceeded kernel time")
                continue
            rates[name] = flops / per / 1e9
            ratio = (f"{rates[name] / rates['plain_dot']:>8.2f}"
                     if name != "plain_dot" and "plain_dot" in rates
                     else f"{'-':>8}")
            # Chained differenced timing yields one per-iteration figure
            # (best-of-reps); it is a min, not a percentile.
            print(f"{name:>16} {m * k * 2:>12} {f'{m}x{k}':>12} "
                  f"{per * 1e6:>9.1f} {'-':>9} {'-':>9} "
                  f"{rates[name]:>12.3f} {ratio}")


def bench_tp_step(args, jax, jnp, axis):
    """End-to-end fused-TP training-step A/B on one chip.

    The integration proof the kernel microbenches don't give: a full
    forward + backward + SGD update through the Megatron-SP MLP pair,
    with BOTH collectives fused into their matmuls (allgather_matmul up,
    matmul_reduce_scatter down; each kernel is the other's VJP seed), vs
    the identical-FLOP unfused step (plain dots — on ONE chip the
    collectives are free, so plain dots are exactly the unfused math).
    Virtual-ring mode: the fused path executes its full V-step schedule
    with self-loop RDMA, so parity here means the pod-scale win (hidden
    comm) costs nothing when there is nothing to hide.
    """
    import time as _time

    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from gloo_tpu.ops.overlap import _ag_matmul_shard, _matmul_rs_shard

    interp = jax.devices()[0].platform == "cpu"
    V = args.overlap_ranks
    mesh = Mesh(np.asarray(jax.devices()[:1], dtype=object), (axis,))
    m, d, f = (int(v) for v in args.tp_shape.split("x"))
    if interp:
        m, d, f, = 256, 256, 256
    chunk = m // V
    assert chunk and chunk % 8 == 0, f"M/V={m}/{V} not a usable chunk"

    # Bench-local custom-vjp wrappers threading virtual_ranks through the
    # same fused-dual structure as the public ops (overlap.py).
    def make_fused_pair():
        kw = dict(axis_name=axis, mesh_axes=None, interpret=interp,
                  virtual_ranks=V)

        @jax.custom_vjp
        def ag_mm(xv, wv):
            y, _ = _ag_matmul_shard(xv, wv, collective_id=23, **kw)
            return y

        def ag_fwd(xv, wv):
            y, gx = _ag_matmul_shard(xv, wv, collective_id=23, **kw)
            return y, (gx, wv)

        def ag_bwd(res, g):
            gx, wv = res
            dx = _matmul_rs_shard(g, wv.T, collective_id=21, **kw)
            dw = jnp.dot(gx.T, g, preferred_element_type=jnp.float32
                         ).astype(wv.dtype)
            return dx, dw

        ag_mm.defvjp(ag_fwd, ag_bwd)

        @jax.custom_vjp
        def rs_mm(av, wv):
            return _matmul_rs_shard(av, wv, collective_id=25, **kw)

        def rs_fwd(av, wv):
            return rs_mm(av, wv), (av, wv)

        def rs_bwd(res, g):
            av, wv = res
            # dual: da = gather(g) @ w^T via the fused allgather kernel
            da, gfull = _ag_matmul_shard(g, wv.T, collective_id=27, **kw)
            dw = jnp.dot(av.T, gfull, preferred_element_type=jnp.float32
                         ).astype(wv.dtype)
            return da, dw

        rs_mm.defvjp(rs_fwd, rs_bwd)
        return ag_mm, rs_mm

    ag_mm, rs_mm = make_fused_pair()
    lr = 1e-3

    def fused_loss(params, x_loc):
        h = ag_mm(x_loc, params["up"])          # [m, f]
        a = jax.nn.gelu(h)
        y = rs_mm(a, params["down"])            # [chunk, d]
        return jnp.mean(jnp.square(y.astype(jnp.float32)))

    def plain_loss(params, x_full):
        h = jnp.dot(x_full, params["up"], preferred_element_type=jnp.float32
                    ).astype(x_full.dtype)
        a = jax.nn.gelu(h)
        y = jnp.dot(a, params["down"], preferred_element_type=jnp.float32)
        # Loss over ALL rows: slicing to [chunk] here would let XLA
        # dead-code-eliminate most of the down-projection and its
        # backward (measured >peak "FLOP/s"), biasing the baseline. A
        # real unfused TP rank computes the full [m,f]@[f,d] partial and
        # reduce-scatters it; the fused path does the same work inside
        # the kernel, so full-row loss is the equal-FLOPs comparison.
        return jnp.mean(jnp.square(y))

    def make_step(loss_fn):
        def step(params, x):
            # Grad w.r.t. x too: a real TP block sits in a stack and
            # always produces dx for the layer below. Without this the
            # plain path DCEs its dx matmul while the fused path's
            # side-effecting kernels cannot — a structural 6-vs-5-matmul
            # bias. The tiny x update keeps dx live in the chain.
            g, gx = jax.grad(loss_fn, argnums=(0, 1))(params, x)
            new_params = jax.tree.map(lambda p, gg: (p - lr * gg.astype(
                jnp.float32)).astype(p.dtype), params, g)
            return new_params, (x - 1e-6 * gx.astype(jnp.float32)).astype(
                x.dtype)
        return step

    params = {"up": jnp.full((d, f), 1.0 / d, jnp.bfloat16),
              "down": jnp.full((f, d), 1.0 / f, jnp.bfloat16)}
    # fwd 2 matmuls + bwd 4 (dx, dw each layer) of m*d*f MACs.
    flops = 2 * m * d * f * 6
    print(f"# tp_step: Megatron-SP MLP pair, M={m} D={d} F={f}, virtual "
          f"ring V={V}; full train step (fwd+bwd+sgd), GFLOP/s and ratio")
    rates = {}
    for name, loss_fn, xshape in (
            ("unfused_step", plain_loss, (m, d)),
            ("fused_step", fused_loss, (chunk, d))):
        step = make_step(loss_fn)
        x = jnp.ones(xshape, jnp.bfloat16)

        def make_chain(n_iter, step=step):
            def outer(pv):
                fin = lax.fori_loop(0, n_iter,
                                    lambda i, c: step(c[0], c[1]),
                                    (pv, x))
                return fin[0]["up"]  # array probe for _chain_rate's fetch
            return jax.jit(jax.shard_map(outer, mesh=mesh, in_specs=P(),
                                         out_specs=P(), check_vma=False))

        per, _k = _chain_rate(args, jax,
                              lambda n, mk=make_chain: mk(n), params,
                              interp, _time)
        if per is None:
            print(f"{name:>16}   skipped: timing noise exceeded step time")
            continue
        rates[name] = flops / per / 1e9
        ratio = ("" if "unfused_step" not in rates or name == "unfused_step"
                 else f" {rates[name] / rates['unfused_step']:>8.2f}")
        print(f"{name:>16} {per * 1e6:>12.1f} us/step "
              f"{rates[name]:>12.1f} GFLOP/s{ratio}")

    # Dispatcher check (r5): on one chip comm is free (share=0), so
    # use_fused_overlap must pick unfused for this shape — and the
    # measured ratio tells whether the model's flip threshold (1-ratio)
    # brackets reality. Printed so sweep logs double as calibration
    # evidence for gloo_tpu.parallel.use_fused_overlap.
    if "unfused_step" in rates and "fused_step" in rates:
        from gloo_tpu.parallel import fused_compute_ratio
        measured = rates["fused_step"] / rates["unfused_step"]
        model = fused_compute_ratio(m, f, V)
        # The model decision directly (share=0 > 1-ratio), NOT
        # use_fused_overlap: that honors TPUCOLL_TP_OVERLAP, and a
        # forced env would mislabel these calibration logs.
        picks_fused = 0.0 > 1.0 - model
        winner_ok = picks_fused == (measured > 1.0)
        print(f"# dispatch: model ratio {model:.2f} (measured {measured:.2f},"
              f" flip at comm>{1 - model:.0%}); share=0 picks "
              f"{'fused' if picks_fused else 'unfused'} -> "
              f"{'MATCHES' if winner_ok else 'CONTRADICTS'} measured winner")


def _chain_rate(args, jax, make_chain, x, interp, _time, k0=32):
    """(seconds-per-chained-iteration, chain length) — differenced
    against a 1-iteration run to cancel dispatch and the fetch. Small
    kernels: k0 chained iterations are dwarfed by that overhead's
    variance, so the chain keeps growing until the measured difference
    exceeds 250 ms of work (a single re-estimate can itself be
    noise-inflated), with an iteration cap as the stop. Returns
    (None, k) when even the longest chain is inside the noise."""
    k_iters = 2 if interp else k0
    f1, fk = make_chain(1), make_chain(k_iters)

    def run(f):
        out = f(x)
        _ = float(out.ravel()[0])  # forces completion + fetch

    for _ in range(max(1, args.warmup)):
        run(f1), run(fk)
    reps = 1 if interp else 5
    t1 = min(_timeit(run, f1, _time) for _ in range(reps))
    tk = min(_timeit(run, fk, _time) for _ in range(reps))
    while not interp and tk - t1 < 0.25 and k_iters < 16384:
        per_est = max((tk - t1) / (k_iters - 1), 5e-7)
        k_iters = min(max(int(0.25 / per_est) + k0, k_iters * 4), 16384)
        fk = make_chain(k_iters)
        run(fk)  # compile
        tk = min(_timeit(run, fk, _time) for _ in range(reps))
    if tk <= t1:
        return None, k_iters
    return (tk - t1) / (k_iters - 1), k_iters


def _timeit(run, f, _time):
    t0 = _time.perf_counter()
    run(f)
    return _time.perf_counter() - t0


if __name__ == "__main__":
    main()

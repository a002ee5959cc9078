"""Discriminate a bimodality of the overlap kernels' throughput.

The 2048x4096 collective-matmul cells were once seen bimodal across
process restarts, while plain dot varied little; that reading predates
the current code and is not a measurement of it. Three candidate causes,
separated by this harness:

  run noise        — same compiled executable re-timed twice differs
  compile draw     — two fresh compiles of identical HLO in ONE process
                     differ (Mosaic scheduling nondeterminism)
  process state    — in-process compiles agree, only restarts differ
                     (per-process seed / allocator layout)

Method per trial: clear the jit cache; time plain dot; time fused
compile A; re-time compile A's SAME objects (run-noise bound); time a
second fresh compile B (in-process compile-draw bound). Chains are
sized to >0.25 s of differenced work so dispatch and fetch noise
cancels. The chain length is FIXED, deliberately, not adapted to the
rate: compiles A and B must be timed over
identical chain lengths or the comparison confounds chain growth with
the compile draw it exists to isolate.

Run several times from fresh processes to capture the cross-restart
axis:  for i in 1 2 3; do python tools/overlap_probe.py; done
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

cur = os.environ.get("LIBTPU_INIT_ARGS", "")
if "scoped_vmem_limit" not in cur:
    os.environ["LIBTPU_INIT_ARGS"] = (
        cur + " --xla_tpu_scoped_vmem_limit_kib=114688").strip()

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="2048x4096", help="MxK (cols=K)")
    ap.add_argument("--ranks", type=int, default=8)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--chain", type=int, default=700)
    ap.add_argument("--smoke", action="store_true",
                    help="CPU + Pallas interpreter + tiny shape: proves "
                         "the harness executes end-to-end where no TPU "
                         "is reachable (timing columns meaningless)")
    args = ap.parse_args()
    if args.chain < 2:
        ap.error("--chain must be >= 2")

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
        args.shape, args.ranks, args.chain = "32x64", 4, 3
        args.trials = min(args.trials, 1)

    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from gloo_tpu.ops.overlap import _matmul_rs_shard

    m, k = (int(v) for v in args.shape.split("x"))
    V, N = args.ranks, args.chain
    chunk = m // V
    mesh = Mesh(np.asarray(jax.devices()[:1], dtype=object), ("x",))
    w = jnp.full((k, k), 1.0 / k, jnp.bfloat16)
    x = jnp.ones((m, k), jnp.bfloat16)

    def mmrs_body(c):
        y = _matmul_rs_shard(c, w, axis_name="x", mesh_axes=None,
                             collective_id=21, interpret=args.smoke,
                             virtual_ranks=V)
        return c.at[:chunk, :].set(y)

    def plain_body(c):
        return jnp.dot(c, w, preferred_element_type=jnp.float32
                       ).astype(c.dtype)

    def chain(body):
        # Traced trip count: one executable serves both chain lengths,
        # so t1/tk difference the SAME schedule draw.
        def outer(xv, n):
            return lax.fori_loop(0, n, lambda i, c: body(c), xv)
        return jax.jit(jax.shard_map(outer, mesh=mesh,
                                     in_specs=(P(), P()), out_specs=P(),
                                     check_vma=False))

    def run(f, n):
        _ = float(np.asarray(f(x, jnp.int32(n))).ravel()[0])

    def timeit(f, n):
        t0 = time.perf_counter()
        run(f, n)
        return time.perf_counter() - t0

    def measure(f, reps=5):
        run(f, 1), run(f, N)
        t1 = min(timeit(f, 1) for _ in range(reps))
        tk = min(timeit(f, N) for _ in range(reps))
        return (tk - t1) / (N - 1)

    # Caveat: a compilation cache that dedupes by HLO fingerprint (e.g.
    # JAX_COMPILATION_CACHE_DIR, or a remote-compile service that
    # caches) makes compile B an alias of compile A and the A-vs-B
    # column vacuously equal — clear_caches() below handles the
    # in-process caches, but an external cache must be disabled for the
    # discrimination to mean anything.
    print(f"# overlap_probe {m}x{k} V={V} chain={N} pid={os.getpid()}")
    print("trial  plain_us  cmpA_us  cmpA2_us  cmpB_us  ratioA  ratioB")
    for trial in range(args.trials):
        jax.clear_caches()
        p = measure(chain(plain_body))
        fA = chain(mmrs_body)
        fa = measure(fA)
        fa2 = measure(fA)       # same executable: run-noise bound
        # Fresh compile of identical HLO. clear_caches drops the
        # in-process jit/executable caches so B really recompiles;
        # fA's live executable keeps working for reference.
        jax.clear_caches()
        fb = measure(chain(mmrs_body))
        print(f"{trial:>5}  {p*1e6:8.1f} {fa*1e6:8.1f}  {fa2*1e6:8.1f} "
              f"{fb*1e6:8.1f}   {p/fa:5.2f}   {p/fb:5.2f}", flush=True)


if __name__ == "__main__":
    main()

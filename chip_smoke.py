"""Run gloo_tpu's device path on TPU chips and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the paths that exist only across chips

One chip: the device check, a fresh build of the native library, the
device-plane collectives (`TpuProcessGroup`) on 64 MiB, the device<->host
hop users take between processes (`HierarchicalGroup` over a one-rank host
`Context`) on a 25 MiB DDP bucket, and five `make_ddp_train_step` steps of
a GPT-2-small-width `Transformer` with the flash kernel.

Four chips: each Pallas ring against the XLA collective on the same input,
and the DDP step on the 4-chip mesh against one chip that accumulates the
four micro-batches' gradients.

Each phase prints one JSON line. Any failure raises, so the run exits
non-zero and prints no `ok` line. The last line of a passing run is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
There is no CPU fallback: with no TPU the first phase fails. Everything
runs in this one process, which holds the chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0

# GPT-2 small (Radford et al. 2019; HF `gpt2` config): 12 layers, d_model
# 768, 12 heads, d_ff 3072, context 1024. The vocabulary 50257 is padded to
# a multiple of 128 as nanoGPT does.
GPT2_SMALL = dict(vocab_size=50304, d_model=768, n_heads=12, n_layers=12,
                  d_ff=3072, max_seq_len=1024)
LR = 6e-4  # GPT-2 small's peak learning rate (nanoGPT)
# A phase that outlives its deadline dumps every thread's stack and exits
# non-zero, so a kernel that never completes releases the chip instead of
# holding it. A ring call's deadline is short: each one compiles in seconds.
PHASE_SECONDS = 600
RING_SECONDS = 90


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def deadline(seconds: float) -> None:
    """(Re)arm the watchdog: exit non-zero unless re-armed in time."""
    faulthandler.dump_traceback_later(seconds, exit=True)


# ---- phase 1: the device ------------------------------------------------

def check_device(chips: int):
    """The first `chips` TPU devices; fails on anything but a TPU."""
    import jax

    devices = jax.devices()
    require(devices[0].platform == "tpu",
            f"chip_smoke needs a TPU; JAX found {devices[0].platform} "
            f"({devices[0].device_kind})")
    require(len(devices) >= chips,
            f"--chips {chips} but JAX found {len(devices)} devices")
    report("device", platform=devices[0].platform,
           kind=devices[0].device_kind, count=len(devices))
    return devices[:chips]


# ---- phase 2: the native library ----------------------------------------

def build_native(build_dir: str) -> str:
    """Build libtpucoll.so from csrc/ into a directory this run creates,
    and point gloo_tpu at it. Call before anything imports gloo_tpu: the
    package loads the library as it is imported."""
    shutil.rmtree(build_dir, ignore_errors=True)
    out_dir = os.path.join(build_dir, "lib")
    t0 = time.perf_counter()
    # Build chatter goes to stderr so stdout keeps one JSON line a phase.
    subprocess.run(
        ["cmake", "-S", os.path.join(REPO, "csrc"), "-B", build_dir,
         "-G", "Ninja", "-DCMAKE_BUILD_TYPE=RelWithDebInfo",
         f"-DTPUCOLL_OUTPUT_DIR={out_dir}"],
        check=True, stdout=sys.stderr)
    subprocess.run(["cmake", "--build", build_dir, "--target", "tpucoll"],
                   check=True, stdout=sys.stderr)
    lib = os.path.join(out_dir, "libtpucoll.so")
    require(os.path.exists(lib), f"build produced no {lib}")
    os.environ["TPUCOLL_LIB"] = lib
    report("build_native", lib=os.path.relpath(lib, REPO),
           seconds=time.perf_counter() - t0)
    return lib


# ---- phase 3: device-plane collectives ----------------------------------

def phase_collectives(devices, rows: int = 4096, cols: int = 4096):
    """TpuProcessGroup collectives on a (rows, cols) float32 row per rank,
    each against numpy."""
    import jax

    from gloo_tpu.tpu import TpuProcessGroup, make_mesh

    pg = TpuProcessGroup(make_mesh({"data": -1}, devices=devices))
    p = pg.size
    x = np.random.default_rng(SEED).standard_normal(
        (p, rows, cols), dtype=np.float32)
    xd = pg.shard(x)
    total = x.sum(axis=0)
    k = rows // p
    expected = {
        "allreduce": np.broadcast_to(total, x.shape),
        "broadcast": np.broadcast_to(x[0], x.shape),
        "allgather": np.broadcast_to(x, (p,) + x.shape),
        "reduce_scatter": total.reshape(p, k, cols),
        "alltoall": x.reshape(p, p, k, cols).transpose(1, 0, 2, 3).reshape(
            p, rows, cols),
    }
    seconds = {}
    for op, want in expected.items():
        t0 = time.perf_counter()
        out = jax.block_until_ready(getattr(pg, op)(xd))
        seconds[op] = time.perf_counter() - t0
        require(out.sharding.device_set == set(devices),
                f"{op}: result on {out.sharding.device_set}")
        np.testing.assert_allclose(pg.unshard(out), want, rtol=1e-5,
                                   atol=1e-5, err_msg=op)
    report("collectives", ranks=p, bytes_per_rank=rows * cols * 4,
           ops=list(expected), first_call_seconds=seconds)


# ---- phase 4: the device<->host hop -------------------------------------

def phase_host_hop(device, nbytes: int = 25 * 2**20):
    """HierarchicalGroup allreduce of one device array through a one-rank
    host Context: device->host, the host plane, host->device."""
    import jax

    import gloo_tpu
    from gloo_tpu.tpu import HierarchicalGroup

    x = np.random.default_rng(SEED + 1).standard_normal(
        nbytes // 4, dtype=np.float32)
    xd = jax.device_put(x, device)
    with tempfile.TemporaryDirectory() as store_dir:
        ctx = gloo_tpu.Context(0, 1, timeout=60)
        try:
            ctx.connect_full_mesh(gloo_tpu.FileStore(store_dir),
                                  gloo_tpu.Device())
            group = HierarchicalGroup(ctx, devices=[device])
            times = []
            for _ in range(3):
                t0 = time.perf_counter()
                out = jax.block_until_ready(group.allreduce(xd))
                times.append(time.perf_counter() - t0)
        finally:
            ctx.close()
    require(isinstance(out, jax.Array) and out.devices() == {device},
            f"hop result is not on {device}: {type(out)}")
    np.testing.assert_array_equal(np.asarray(out), x)
    report("host_hop", bytes=nbytes, seconds=times)


# ---- phase 5: GPT-2-small DDP steps -------------------------------------

def _gpt2_batch(cfg, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len),
                          dtype=np.int32)
    return tokens, np.roll(tokens, -1, axis=1)


def _placed(mesh, params, opt, batch):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = jax.device_put(params, NamedSharding(mesh, P()))
    return (params, opt.init(params),
            jax.device_put(batch, NamedSharding(mesh, P("data"))))


def phase_train(devices, model_kw=GPT2_SMALL, batch: int = 8,
                steps: int = 5):
    """Five DDP steps with the flash kernel on a fixed batch: finite,
    falling loss; the first loss matches the materialized-attention model
    on the same params and batch. Returns what the caller checks and
    prints."""
    import jax
    import optax

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step
    from gloo_tpu.tpu import make_mesh

    cfg = TransformerConfig(**model_kw, use_flash_attention=True)
    model = Transformer(cfg)
    mesh = make_mesh({"data": -1}, devices=devices)
    opt = optax.adamw(LR)
    params0 = model.init(jax.random.PRNGKey(SEED))
    tokens = _gpt2_batch(cfg, batch, SEED + 2)
    params, opt_state, data = _placed(mesh, params0, opt, tokens)

    step = make_ddp_train_step(model.loss, opt, mesh)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, data).compile()
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = jax.block_until_ready(
            compiled(params, opt_state, data))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))

    ref_model = Transformer(dataclasses.replace(cfg,
                                                use_flash_attention=False))
    ref_loss = float(jax.jit(ref_model.loss)(params0, tokens))
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    require(rel < 1e-2, f"flash loss {losses[0]} vs materialized "
            f"{ref_loss}: rel {rel}")
    stats = devices[0].memory_stats() or {}
    return {"losses": losses, "materialized_loss": ref_loss,
            "first_loss_rel_diff": rel, "tpu_custom_call": kernel,
            "compile_seconds": compile_s,
            "steady_step_seconds": float(np.median(step_s[1:])),
            "step_seconds": step_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---- four chips: Pallas rings against XLA collectives -------------------

# Per-shard ring payloads: 4 MiB for the VMEM-resident kernels (1 MiB in
# for allgather, whose output is n times that), 64 MiB for the HBM ring.
RING_VMEM_BYTES = 4 * 2**20
RING_HBM_BYTES = 64 * 2**20
RING_COLS = 1024


def _ring_cases(n: int, interpret: bool, vmem_bytes: int = RING_VMEM_BYTES,
                hbm_bytes: int = RING_HBM_BYTES, cols: int = RING_COLS):
    """(name, pallas(shard), xla(shard), per-shard rows, lossy) for every
    ring. The kernels compile on a TPU and run through the Pallas
    interpreter only on the CPU backend (tests)."""
    from jax import lax

    from gloo_tpu.ops import (pallas_alltoall, ring_allgather,
                              ring_allreduce, ring_allreduce_hbm,
                              ring_allreduce_q8, ring_reduce_scatter)

    rows = vmem_bytes // (4 * cols)
    hbm_rows = hbm_bytes // (4 * cols)
    ax, kw = "data", {"interpret": interpret}
    return [
        ("ring_allreduce", lambda s: ring_allreduce(s, ax, **kw),
         lambda s: lax.psum(s, ax), rows, False),
        ("ring_allreduce_hbm", lambda s: ring_allreduce_hbm(s, ax, **kw),
         lambda s: lax.psum(s, ax), hbm_rows, False),
        ("ring_reduce_scatter", lambda s: ring_reduce_scatter(s, ax, **kw),
         lambda s: lax.psum_scatter(s, ax, scatter_dimension=0,
                                    tiled=True), rows, False),
        ("ring_allgather", lambda s: ring_allgather(s, ax, **kw),
         lambda s: lax.all_gather(s, ax, axis=0, tiled=True),
         rows // n, False),
        ("ring_allreduce_q8", lambda s: ring_allreduce_q8(s, ax, **kw),
         lambda s: lax.psum(s, ax), rows, True),
        ("pallas_alltoall", lambda s: pallas_alltoall(s, ax, **kw),
         lambda s: lax.all_to_all(s, ax, 0, 0, tiled=True), rows, False),
    ]


def _spread(name: str, arr, devices) -> None:
    """Fail unless `arr` lives on every device of the mesh, one shard or
    replica each: code that never ran on several chips may put it all on
    device 0."""
    require(arr.sharding.device_set == set(devices),
            f"{name}: on {arr.sharding.device_set}, not all of {devices}")
    require(len({s.device for s in arr.addressable_shards}) == len(devices),
            f"{name}: shards share a device")


def phase_rings(devices, vmem_bytes: int = RING_VMEM_BYTES,
                hbm_bytes: int = RING_HBM_BYTES, cols: int = RING_COLS
                ) -> list:
    """Each ring against its XLA collective. A ring that fails is reported
    with its traceback and the next one still runs, so one run on the
    chips shows every ring; returns the names of those that failed."""
    import traceback

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gloo_tpu.tpu import make_mesh

    mesh = make_mesh({"data": -1}, devices=devices)
    n = len(devices)
    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(SEED + 3)

    def smap(fn, check_vma):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=check_vma))

    failed = []
    for name, pallas, xla, rows, lossy in _ring_cases(
            n, interpret, vmem_bytes, hbm_bytes, cols):
        deadline(RING_SECONDS)
        try:
            _check_ring(name, pallas, xla, rows, lossy, smap, devices,
                        mesh, cols, rng)
        except Exception as e:  # noqa: BLE001 - reported; the run fails
            traceback.print_exc()
            report("ring", op=name, ok=False, error=f"{type(e).__name__}: "
                   f"{str(e)[:2000]}")
            failed.append(name)
    return failed


def _check_ring(name, pallas, xla, rows, lossy, smap, devices, mesh, cols,
                rng) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = len(devices)
    x = jax.device_put(
        rng.standard_normal((n * rows, cols), dtype=np.float32),
        NamedSharding(mesh, P("data")))
    ring_fn = smap(pallas, False)
    got = jax.block_until_ready(ring_fn(x))
    want = jax.block_until_ready(smap(xla, True)(x))
    _spread(name, got, devices)
    _spread(name + " (xla)", want, devices)
    g, w = np.asarray(got), np.asarray(want)
    require(g.shape == w.shape, f"{name}: {g.shape} vs {w.shape}")
    err = float(np.abs(g - w).max())
    if lossy:
        # The repo's own q8 bound (tests/test_pallas_ring.py), and
        # every rank decodes the same values.
        bound = 0.05 * float(np.abs(w).max())
        shards = g.reshape(n, -1)
        require(all(np.array_equal(shards[0], s) for s in shards[1:]),
                f"{name}: ranks disagree")
    else:
        bound = 1e-4
    require(err <= bound, f"{name}: max |pallas - xla| {err} > {bound}")
    t0 = time.perf_counter()
    jax.block_until_ready(ring_fn(x))
    report("ring", op=name, ok=True, bytes_per_shard=rows * cols * 4,
           max_abs_diff_vs_xla=err, bound=bound,
           warm_call_seconds=time.perf_counter() - t0)


# ---- four chips: DDP against one chip accumulating micro-batches --------

def phase_ddp_vs_accum(devices, model_kw=GPT2_SMALL, per_chip: int = 8):
    """One DDP step over all `devices` at global batch per_chip * n,
    against one chip that accumulates the n micro-batches' gradients and
    applies the same AdamW update. Compares the loss, the first moment
    (0.1 x the averaged gradient, so the gradient's scale is checked too)
    and the updated params."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gloo_tpu.models import Transformer, TransformerConfig
    from gloo_tpu.parallel import make_ddp_train_step
    from gloo_tpu.tpu import make_mesh

    n = len(devices)
    cfg = TransformerConfig(**model_kw, use_flash_attention=True)
    model = Transformer(cfg)
    opt = optax.adamw(LR)
    mesh = make_mesh({"data": -1}, devices=devices)
    params0 = jax.device_put(model.init(jax.random.PRNGKey(SEED)),
                             devices[0])
    tokens, targets = _gpt2_batch(cfg, per_chip * n, SEED + 4)

    params, opt_state, data = _placed(mesh, params0, opt, (tokens, targets))
    step = make_ddp_train_step(model.loss, opt, mesh)
    t0 = time.perf_counter()
    params, opt_state, loss = jax.block_until_ready(
        step(params, opt_state, data))
    ddp_s = time.perf_counter() - t0
    for leaf in jax.tree.leaves(params):
        _spread("ddp params", leaf, devices)
    _spread("ddp batch", data[0], devices)
    require(data[0].sharding == NamedSharding(mesh, P("data")),
            "batch is not sharded over data")

    grad_fn = jax.jit(jax.value_and_grad(model.loss))
    acc, micro_losses = None, []
    for i in range(n):
        mb = jax.device_put((tokens[i * per_chip:(i + 1) * per_chip],
                             targets[i * per_chip:(i + 1) * per_chip]),
                            devices[0])
        micro_loss, g = grad_fn(params0, mb)
        micro_losses.append(float(micro_loss))
        acc = g if acc is None else jax.tree.map(jax.numpy.add, acc, g)

    @jax.jit
    def apply(p, g):
        g = jax.tree.map(lambda a: a / n, g)
        updates, state = opt.update(g, opt.init(p), p)
        return optax.apply_updates(p, updates), state

    ref_params, ref_state = apply(params0, acc)
    ref_loss = float(np.mean(micro_losses))

    loss_rel = abs(float(loss) - ref_loss) / abs(ref_loss)
    mu, ref_mu = (np.concatenate([np.asarray(a, np.float32).ravel()
                                  for a in jax.tree.leaves(
                                      optax.tree_utils.tree_get(s, "mu"))])
                  for s in (opt_state, ref_state))
    mu_rel = float(np.linalg.norm(mu - ref_mu) / np.linalg.norm(ref_mu))
    p4, p1 = (np.concatenate([np.asarray(a).ravel()
                              for a in jax.tree.leaves(t)])
              for t in (params, ref_params))
    diff = np.abs(p4 - p1)
    # At AdamW's first step each update is about lr * sign(grad), so a
    # param differs by ~2 lr exactly where the two gradients' signs differ
    # (gradients at rounding level): few may, none by more.
    flipped = float(np.mean(diff > LR / 2))
    report("ddp_vs_accum", chips=n, global_batch=per_chip * n,
           loss=float(loss), accumulated_loss=ref_loss, loss_rel_diff=loss_rel,
           grad_moment_rel_l2=mu_rel, params_max_abs_diff=float(diff.max()),
           params_flipped_fraction=flipped, ddp_first_call_seconds=ddp_s)
    require(loss_rel < 1e-3, f"loss {float(loss)} vs {ref_loss}")
    require(mu_rel < 2e-2, f"gradient moments differ: rel {mu_rel}")
    require(float(diff.max()) <= 2.05 * LR, f"params differ by {diff.max()}")
    require(flipped < 1e-2, f"{flipped:.2%} of params moved differently")


# ---- main -----------------------------------------------------------------

def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the paths that exist across chips")
    args = parser.parse_args(argv)

    deadline(PHASE_SECONDS)
    devices = check_device(args.chips)
    build_native(os.path.join(REPO, ".smoke_build"))
    from gloo_tpu.tpu import enable_compile_cache

    report("compile_cache", dir=enable_compile_cache())

    if args.chips == 1:
        deadline(PHASE_SECONDS)
        phase_collectives(devices)
        deadline(PHASE_SECONDS)
        phase_host_hop(devices[0])
        deadline(PHASE_SECONDS)
        train = phase_train(devices)
        report("train", **train)
        require(train["tpu_custom_call"],
                "the compiled step holds no tpu_custom_call: the flash "
                "kernel did not run")
    else:
        report("ring_order", devices=[
            {"id": d.id, "coords": list(d.coords)} for d in devices])
        failed = phase_rings(devices)
        deadline(PHASE_SECONDS)
        phase_ddp_vs_accum(devices)
        require(not failed, f"rings failed: {failed}")
    faulthandler.cancel_dump_traceback_later()

    import jax

    dev = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)


if __name__ == "__main__":
    main()

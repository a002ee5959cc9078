"""gloo_tpu's benchmark: one cell of BENCHMARK.json on the chips this
machine holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of stdout, one JSON object with `correct`,
`attempted`, `failed`, `metrics`, `device` (and with --trace 1
`breakdown`), then `checks`: each number compared beside its limit, which
also close stderr. With --trace 0 the metrics are the cell's end-to-end
ones, with --trace 1 its per-layer ones. Exits non-zero with no result
where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """JAX_COMPILATION_CACHE_DIR where set, else a fixed path in the
    checkout: the path is part of the cache's key."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def require_chips(chips: int):
    """The first `chips` TPU devices, or exit non-zero."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU; JAX found {devices[0].platform} "
                 f"({devices[0].device_kind})")
    if len(devices) < chips:
        sys.exit(f"benchmark: the cell needs {chips} chips; JAX found "
                 f"{len(devices)}")
    return devices[:chips]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # The checkout's own gloo_tpu and benchmark, whatever else is on the
    # path; a directory without gloo_tpu fails at the import.
    sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    devices = require_chips(cell.chips)
    import gloo_tpu

    if not os.path.abspath(gloo_tpu.__file__).startswith(ROOT + os.sep):
        sys.exit(f"benchmark: gloo_tpu comes from {gloo_tpu.__file__}, "
                 f"not from this checkout")
    result, extra = harness.run_cell(cell, devices, args.seed, args.seconds,
                                     bool(args.trace), T_PROCESS)
    harness.print_result(result, extra)


if __name__ == "__main__":
    main()

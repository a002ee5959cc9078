"""A cell of the benchmark at a tiny width, for the CPU tests: the real
harness, recipe, reference and limits, with the sizes cut so that the
Pallas interpreter runs a step in a fraction of a second."""

from __future__ import annotations

import copy
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

TINY = dict(n_layer=2, n_embd=64, n_head=4, n_inner=256, n_positions=64,
            vocab_size=256, published={"vocab_size": 250})


def cells() -> list:
    """The names of BENCHMARK.json's cells."""
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def cell(name: str = "gpt2s-dp4-b2", chips: int = None, rows: int = 2,
         seq: int = 64) -> SimpleNamespace:
    """The BENCHMARK.json cell `name` at tiny width: its recipe, limits and
    traffic kind, `rows` rows a chip of `seq` tokens."""
    c = harness.load_cell(name)
    c.config = copy.deepcopy(c.config)
    c.config.update(TINY)
    chips = chips or c.chips
    c.chips = chips
    c.traffic = dict(c.traffic, batch_per_chip=rows, data_parallel=chips,
                     seq_len=seq)
    return c


def run(c, devices, seed: int = 2**31 + 7, seconds: float = 0.5):
    return harness.run_cell(c, devices, seed, seconds, False,
                            time.perf_counter())

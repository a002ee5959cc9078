"""The one traffic generator: reads a mix's parameters from
`benchmark/traffic/<name>.json` and makes its batches on the device.

`uniform_tokens`: `distinct_batches` global batches of
`batch_per_chip * data_parallel` rows, each of `seq_len + 1` token ids
drawn uniformly below the published vocabulary from the seed. A step's
inputs are a row's first `seq_len` ids and its targets the last
`seq_len`. A dense model's timing does not depend on which ids come, so
every seed gives the same work.
"""

from __future__ import annotations

import jax

from benchmark.references.gpt2 import key_from_seed


def global_rows(traffic: dict) -> int:
    return traffic["batch_per_chip"] * traffic["data_parallel"]


def make_tokens(traffic: dict, vocab: int, words):
    """(distinct_batches, rows, seq_len + 1) int32 on the default device,
    from one jitted call."""
    if traffic["kind"] != "uniform_tokens":
        raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
    shape = (traffic["distinct_batches"], global_rows(traffic),
             traffic["seq_len"] + 1)

    @jax.jit
    def draw(words):
        key = jax.random.fold_in(key_from_seed(words), 0x7A11C)
        return jax.random.randint(key, shape, 0, vocab, dtype="int32")

    return draw(words)

"""The FLOP and byte functions against counts made by hand."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.flops import gpt2  # noqa: E402


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# GPT-2 medium's widths: its cell waits under PERF.md's Open questions,
# and the family's FLOP function serves it unchanged.
MEDIUM = dict(n_layer=24, n_embd=1024, n_inner=4096, vocab_size=50304)


@pytest.mark.parametrize("cfg,per_token", [(_cfg("gpt2-small"), 8.546e8),
                                           (MEDIUM, 2.423e9)],
                         ids=["gpt2-small", "gpt2-medium"])
def test_model_flops_per_token(cfg, per_token):
    assert gpt2.flops_per_token(cfg, 1024) == pytest.approx(per_token,
                                                            rel=1e-4)


def test_matmul_params_small_by_hand():
    # 12 x (768 x 2304 + 768^2 + 2 x 768 x 3072) + 50304 x 768
    assert gpt2.matmul_params(_cfg("gpt2-small")) == 123_568_128


def test_flash_counts_by_hand():
    cfg = dict(n_head=2, n_embd=16, n_layer=1)
    got = gpt2.flash_attention(cfg, rows=1, seq_len=4)
    # 2 heads x 10 causal pairs x head dim 8 x 2 FLOPs x 7 matmuls
    assert got["flops"] == 2 * 10 * 8 * 2 * 7
    elems = 2 * 4 * 8
    stats = 2 * 4 * 4
    assert got["bytes"] == (4 * elems * 2 + stats) + (
        4 * elems * 2 + 2 * stats + 3 * elems * 4)

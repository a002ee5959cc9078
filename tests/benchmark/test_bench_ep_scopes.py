"""The expert layer's readers (`benchmark/ep_scopes.py` and the three EP
metrics) on a drawn trace, and DeepSeek-V2's FLOP counts from shapes."""

from types import SimpleNamespace

import pytest

from benchmark import ep_scopes, harness
from benchmark.trace import Trace

OP_NAMES = {
    "fusion.1": "jit(step)/shard_map/gloo_tpu.ddp.loss/jvp(gloo_tpu.ep.route)"
                "/sort",
    "fusion.2": "jit(step)/shard_map/gloo_tpu.ddp.loss/transpose(jvp("
                "gloo_tpu.ep.combine))/mul",
    "fusion.3": "jit(step)/shard_map/gloo_tpu.ddp.loss/jvp(gloo_tpu.ep."
                "experts)/silu",
    "all-gather.4": "jit(step)/shard_map/gloo_tpu.ddp.loss/jvp(gloo_tpu.ep."
                    "dispatch)/gloo_tpu.ragged_alltoall/all_gather",
    "fusion.5": "jit(step)/shard_map/gloo_tpu.ddp.loss/jvp(gloo_tpu.mla)/dot",
}


def _drawn():
    """Chip 0, three steps of 100 ns; the first is left out. In each
    counted step: route 10, combine 5, experts 20 (8 in a scoped fusion,
    12 in XLA's grouped matmul), the exchange 7 (async, 3 of it under
    other ops) and the counts' all-gather 1 (in none of the parts),
    attention 30."""
    ops, async_ops = [], []
    for base in (0, 100, 200):
        ops += [("fusion.1 fusion f32[8]", base, base + 10),
                ("fusion.2 fusion f32[8]", base + 10, base + 15),
                ("fusion.3 fusion bf16[8]", base + 15, base + 23),
                ("ragged-dot-none.1 custom-call bf16[8]", base + 23,
                 base + 35),
                ("all-gather.4 all-gather s32[4]", base + 35, base + 36),
                ("fusion.5 fusion bf16[8]", base + 40, base + 70)]
        async_ops += [("ragged_all_to_all.6 ragged-all-to-all bf16[8]",
                       base + 67, base + 74)]
    steps = [("jit_step(1)", b, b + 100) for b in (0, 100, 200)]
    return Trace(ops={0: ops}, async_ops={0: async_ops}, steps={0: steps},
                 chips=[0])


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(ep_scopes, "step_stats",
                        lambda run: SimpleNamespace(op_names=OP_NAMES))
    c = harness.load_cell("dsv2l-ep1-s8k")
    return SimpleNamespace(
        trace=_drawn(), config=c.config, traffic=c.traffic, chips=c.chips,
        flops=harness.module("flops", "deepseek_v2"),
        peaks=harness.peaks("TPU v5 lite"))


@pytest.mark.parametrize("name,op_name,want", [
    ("fusion.1 fusion f32[8]", OP_NAMES["fusion.1"], "route"),
    ("fusion.2 fusion f32[8]", OP_NAMES["fusion.2"], "combine"),
    ("ragged-dot-none.1 custom-call bf16[8]", "ragged-dot-none", "experts"),
    ("ragged_all_to_all.6 ragged-all-to-all bf16[8]",
     "gloo_tpu.ep.dispatch/gloo_tpu.ragged_alltoall", None),
    ("all-to-all.7 all-to-all s32[4]", "gloo_tpu.ep.combine", None),
    ("all-gather.4 all-gather s32[4]", OP_NAMES["all-gather.4"], None),
    ("all-reduce.8 all-reduce f32[8]", "gloo_tpu.ep.experts/psum", None),
    ("fusion.5 fusion bf16[8]", OP_NAMES["fusion.5"], None),
])
def test_part_by_opcode_name_and_scope(name, op_name, want):
    assert ep_scopes.part(name, op_name) == want


def test_ep_readers_on_the_drawn_trace(run):
    read = {m: harness.module("metrics", m).read(run) for m in (
        "ep_route_ms", "expert_ffn_ms", "expert_ffn_roofline")}
    assert read["ep_route_ms"] == pytest.approx(15 / 1e6)
    assert read["expert_ffn_ms"] == pytest.approx(20 / 1e6)
    need = run.flops.expert_ffn(run.config, 2 * 8192, 1)
    least = max(need["flops"] / 1.97e14, need["bytes"] / 8.19e11)
    assert read["expert_ffn_roofline"] == pytest.approx(
        100 * least / 20e-9)


def test_ep_readers_return_nothing_without_their_ops(run):
    run.trace.ops[0] = [op for op in run.trace.ops[0]
                        if op[0].startswith("fusion.5")]
    run.trace.async_ops[0] = []
    for m in ("ep_route_ms", "expert_ffn_ms", "expert_ffn_roofline"):
        assert harness.module("metrics", m).read(run) is None


def test_deepseek_v2_flops_from_shapes():
    """The cut's model FLOPs a token at 8192 positions: 6 N for N =
    257.9M matmul parameters (routed experts at 6 x 8 / 64 of one), plus
    MLA's scores and values, 3 x 2 s (16 x 192 + 16 x 128) a layer; the
    grouped SwiGLU's rows on a chip are T x 6 x 8 / 64 whatever the chips,
    its weights the chip's share."""
    c = harness.load_cell("dsv2l-ep1-s8k").config
    flops = harness.module("flops", "deepseek_v2")
    assert flops.matmul_params(c) == 257_949_696
    attention = 5 * 3 * 2 * 8192 * (16 * 192 + 16 * 128)
    assert flops.flops_per_token(c, 8192) == 6 * 257_949_696 + attention
    one, four = flops.expert_ffn(c, 16384), flops.expert_ffn(c, 16384, 4)
    assert one["flops"] == four["flops"] == 4 * 18 * 12288 * 2048 * 1408
    weights = 3 * 2048 * 1408 * 2
    assert one["bytes"] - four["bytes"] == 4 * 2 * (8 - 2) * weights

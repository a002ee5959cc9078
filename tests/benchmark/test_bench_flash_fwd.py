"""flash_fwd_per_layer (`benchmark/metrics/flash_fwd_per_layer.py`) on
the counts a compiled step gives, for each cell's recipe, and on a
checkout whose counter has no Pallas tally."""

from types import SimpleNamespace

import pytest

from benchmark import ep_scopes, harness, phases
from gloo_tpu.tpu import CollectiveStats


def _read(monkeypatch, cell, stats):
    """The reader on `cell` with both recipes' compiles giving `stats`
    (the cell's own) or a marker that fails the reading (the other's)."""
    c = harness.load_cell(cell)
    own = ep_scopes if c.config["family"] == "deepseek_v2" else phases
    for mod in (phases, ep_scopes):
        monkeypatch.setattr(mod, "step_stats",
                            (lambda run: stats) if mod is own
                            else (lambda run: pytest.fail("wrong recipe")))
    run = SimpleNamespace(config=c.config, traffic=c.traffic,
                          chips=c.chips)
    return harness.module("metrics", "flash_fwd_per_layer").read(run)


@pytest.mark.parametrize("cell,forwards,expected", [
    ("gpt2s-dp1-b8", 12, 1.0), ("gpt2s-dp4-b2", 12, 1.0),
    ("dsv2l-ep1-s8k", 5, 1.0), ("dsv2l-ep1-s8k", 10, 2.0)])
def test_forwards_per_layer(monkeypatch, cell, forwards, expected):
    stats = CollectiveStats(pallas_calls={
        "flash_attention": forwards, "flash_attention_bwd_fused": 5})
    assert _read(monkeypatch, cell, stats) == expected


@pytest.mark.parametrize("stats", [
    None, SimpleNamespace(op_names={"flash_attention.1": "jit(step)"}),
    CollectiveStats(pallas_calls={"flash_attention_bwd_fused": 5})])
def test_nothing_without_a_tally_or_a_forward(monkeypatch, stats):
    """No counter, a counter from before the Pallas tally, or no flash
    forward in the step: no reading, and no error."""
    for cell in ("gpt2s-dp1-b8", "dsv2l-ep1-s8k"):
        assert _read(monkeypatch, cell, stats) is None

"""flash_fwd_per_layer: the flash forward kernels one run of the compiled
step launches, per layer of the configuration: the `flash_attention`
entry of `gloo_tpu.tpu.collective_stats(...).pallas_calls` over
`n_layer`. The step is the one `phases.py` (the `ddp` recipe's GPT-2) or
`ep_scopes.py` (the `ep` recipe's DeepSeek-V2) compiles for its other
readers. 1.0 where the backward reuses the forward's out and logsumexp,
2.0 where each layer's remat runs the forward again. Moves tokens_per_s.
Nothing when the checkout's counter has no Pallas tally or the step no
flash forward. It reports; the guard is the described-v5e compile tests,
which pin the same counts."""

from benchmark import ep_scopes, phases

# The module that compiles each configuration family's step.
STEP = {"gpt2": phases, "deepseek_v2": ep_scopes}


def read(run):
    stats = STEP[run.config["family"]].step_stats(run)
    forwards = getattr(stats, "pallas_calls", {}).get("flash_attention", 0)
    return forwards / run.config["n_layer"] if forwards else None

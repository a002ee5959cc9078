"""Demo model family used to validate the framework end-to-end.

The reference is a communications library, not a model zoo — these models
exist for the same reason gloo's examples and benchmark workloads do: to
prove the collective layer under a real training loop (DDP gradient sync,
tensor-parallel matmuls, pipeline-ish shifts)."""

from gloo_tpu.models.deepseek_v2 import DeepSeekV2, DeepSeekV2Config
from gloo_tpu.models.mlp import MLP
from gloo_tpu.models.transformer import Transformer, TransformerConfig

__all__ = ["DeepSeekV2", "DeepSeekV2Config", "MLP", "Transformer", "TransformerConfig"]

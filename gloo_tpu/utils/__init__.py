from gloo_tpu.utils import fleet
from gloo_tpu.utils import flightrec
from gloo_tpu.utils import profile
from gloo_tpu.utils.flightrec import DesyncError
from gloo_tpu.utils.metrics import (histogram_quantile, merge_snapshots,
                                    summarize_ops, to_prometheus)
from gloo_tpu.utils.telemetry import TelemetryServer, serve_telemetry
from gloo_tpu.utils.tracing import annotate, merge_traces

__all__ = [
    "DesyncError",
    "TelemetryServer",
    "annotate",
    "fleet",
    "flightrec",
    "histogram_quantile",
    "merge_snapshots",
    "merge_traces",
    "profile",
    "serve_telemetry",
    "summarize_ops",
    "to_prometheus",
]

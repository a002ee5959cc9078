"""Tracing utilities spanning both data planes.

Host plane: `Context.trace_start()/trace_json()` records collective spans
in the C++ core (Chrome trace-event format). Device plane:
`jax.profiler.trace(logdir)` captures compiled collectives over the mesh
in the same investigation (view in TensorBoard / Perfetto); `annotate`
puts host-plane work on that timeline. `merge_traces` combines per-rank
host traces into one timeline.
"""

from __future__ import annotations

import contextlib
import json
from typing import Iterable


@contextlib.contextmanager
def annotate(name: str):
    """Label a host-side region in the jax profiler timeline (and as a
    named scope during tracing), so gloo_tpu host collectives line up
    with XLA device activity in one Perfetto view. No-ops when jax is
    unavailable — safe to leave in production code paths."""
    try:
        import jax

        with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
            yield
    except ImportError:
        yield


def merge_traces(jsons: Iterable[str]) -> str:
    """Merge per-rank Chrome trace JSON arrays into one document.

    Emits `process_name`/`process_sort_index` metadata ("M") events per
    rank pid so Perfetto shows labeled per-rank rows, and sorts data
    events by timestamp so the merged document reads as one timeline
    (inputs with unsorted timestamps are fine). Pre-existing metadata
    events in the inputs are preserved (except process_name/
    process_sort_index, which are regenerated). Degrades gracefully over
    a crashed rank's leavings: empty or unparseable documents are
    skipped — the merge of the survivors must not throw.
    """
    events = []
    for doc in jsons:
        if not doc:
            continue
        try:
            parsed = json.loads(doc)
        except ValueError:
            continue
        if isinstance(parsed, list):
            events.extend(e for e in parsed if isinstance(e, dict))
    data = [e for e in events
            if e.get("ph") != "M"
            or e.get("name") not in ("process_name",
                                     "process_sort_index")]
    data.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    pids = sorted({e.get("pid", 0) for e in data})
    meta = []
    for pid in pids:
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": f"rank {pid}"}})
        meta.append({"name": "process_sort_index", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"sort_index": pid}})
    return json.dumps(meta + data)

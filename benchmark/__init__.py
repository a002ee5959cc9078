"""The on-chip benchmark of gloo_tpu: `python3 benchmark/run.py`."""

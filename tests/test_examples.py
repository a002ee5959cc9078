"""Smoke the runnable examples and the graft entry: they are the first
code a new user executes, and nothing else in CI runs them. Each runs
as the README documents it, on the virtual CPU mesh, asserting the
script's own success line.

Speed is measured by `benchmark/run.py` on the chip, not here. The
native `tpucoll_bench` CLI has its own smoke,
`tests/test_native_unit.py::test_bench_cli_smoke`."""

import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EX = os.path.join(_REPO, "examples")


def _run(name, timeout=420, env_extra=None):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               **(env_extra or {}))
    proc = subprocess.run([sys.executable, os.path.join(_EX, name)],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    assert proc.returncode == 0, (name, proc.stdout[-1500:],
                                  proc.stderr[-1500:])
    return proc.stdout


def test_example_fused_tp():
    out = _run("example_fused_tp.py")
    assert "fused tensor-parallel example OK" in out
    assert "auto dispatcher" in out


def test_graft_entry_one_process():
    """`python __graft_entry__.py 4`: the forward, then the dry run over 4
    of the 8 virtual devices, in one process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "__graft_entry__.py"), "4"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "dryrun_multichip(4): OK on cpu" in proc.stdout


def test_example_device_plane():
    out = _run("example_device_plane.py")
    assert "done" in out


def test_example_fsdp_long_context():
    out = _run("example_fsdp_long_context.py")
    assert "fsdp + long-context example OK" in out


def test_example_observability():
    out = _run("example_observability.py", timeout=180)
    assert "observability example OK" in out
    assert "[watchdog] rank0 was blocked" in out
    assert "labeled rank rows" in out


def test_example_chaos():
    out = _run("example_chaos.py", timeout=180)
    assert "chaos example: OK" in out
    assert "fault firing sequence:" in out
    assert '"action": "stall"' in out and '"action": "kill"' in out
    assert "rebuilt OK" in out
    assert "[watchdog] rank0 was blocked" in out
    assert "merged chaos trace" in out


def test_example_flightrec():
    out = _run("example_flightrec.py", timeout=180)
    assert "flightrec example: OK" in out
    assert "reason=stall blamed_peer=1" in out
    assert "desync verdict: collective desync" in out
    assert "merged Perfetto timeline" in out

import os
import subprocess
import sys

# Device-plane tests run on a virtual 8-device CPU mesh, whatever hardware
# the machine has: a test process must never take a chip (a chip belongs to
# one process at a time). chip_smoke.py is what runs on the chip.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def pytest_configure(config):
    # Build (or rebuild) the native core once per session. Sanitizer runs
    # set TPUCOLL_SKIP_BUILD=1 (the toolchain cannot run under LD_PRELOADed
    # sanitizer runtimes) and point TPUCOLL_LIB at a prebuilt library.
    if os.environ.get("TPUCOLL_SKIP_BUILD"):
        return
    subprocess.run(["make", "native"], cwd=_REPO_ROOT, check=True,
                   capture_output=True)

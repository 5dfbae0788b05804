import os
import subprocess
import sys

import pytest

# NOTE: no xla_force_host_platform_device_count here -- smoke tests and
# benches must see exactly 1 device (the dry-run sets its own flag).

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
TESTS = os.path.dirname(os.path.abspath(__file__))
if SRC not in sys.path:
    sys.path.insert(0, SRC)

from repro.core.compare import assert_results_equal  # noqa: E402,F401


def run_with_devices(n_devices: int, code: str, timeout: int = 420) -> str:
    """Run ``code`` in a subprocess with N host devices; returns stdout.

    The tests directory rides on PYTHONPATH so subprocess snippets can
    ``from conftest import assert_results_equal`` instead of re-rolling
    result comparison inline.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{n_devices}")
    env["PYTHONPATH"] = (SRC + os.pathsep + TESTS + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.fixture
def subproc():
    return run_with_devices

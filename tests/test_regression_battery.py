"""The native regression battery (scripts/regression.py) enumerates the
reference's full ~55-query list (/root/reference/scripts/regression.py:20-312)
case-for-case, and its normalized output is machine-diffed here against the
reference script ITSELF running through the compat shim — on every backend
(VERDICT r04 item 6).

The reference script's memory-vs-tensor identity is already proven by
test_reference_shim.py; diffing each native backend against the shimmed
reference/memory output therefore closes the chain for all three."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.full  # heavy block: excluded from `pytest -m quick`

from tests.conftest import reference_path
from tests.test_reference_shim import _shim_env, normalize_regression_output

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, env, timeout=900):
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


@pytest.fixture(scope="module")
def reference_blocks():
    out = _run(
        [sys.executable, reference_path("scripts", "regression.py")],
        _shim_env(DAS_TPU_BACKEND="memory"),
    )
    blocks = normalize_regression_output(out)
    assert len(blocks) == 56
    return blocks


@pytest.mark.parametrize("backend", ["memory", "tensor", "sharded"])
def test_native_battery_matches_reference_script(reference_blocks, backend):
    env = _shim_env()
    if backend == "sharded":
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = _run(
        [sys.executable, "scripts/regression.py", "--backend", backend],
        env,
        timeout=1800,
    )
    native = normalize_regression_output(out)
    assert len(native) == len(reference_blocks) == 56
    for i, (a, b) in enumerate(zip(native, reference_blocks)):
        assert a == b, f"block {i} ({b[0] if b[0] else 'list'}) differs on {backend}"

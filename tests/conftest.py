"""Shared fixtures.  NOTE: no XLA_FLAGS here — pytest must see ONE device;
multi-device assertions run via tests/_multidevice_checks.py in a subprocess
(see tests/test_multidevice.py) and the dry-run sets its own flag."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# `hypothesis` is optional (requirements-dev.txt): fall back to the
# deterministic stub so the property tests still run without it.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    if os.path.dirname(os.path.abspath(__file__)) not in sys.path:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _hypothesis_stub

    sys.modules["hypothesis"] = _hypothesis_stub
    sys.modules["hypothesis.strategies"] = _hypothesis_stub.strategies


@pytest.fixture(scope="session")
def multidevice_results():
    """Run the 8-device check battery once; tests assert on its JSON."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    # the child forces host devices: it must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_multidevice_checks.py")],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert proc.returncode == 0, f"multidevice subprocess failed:\n{proc.stderr[-3000:]}"
    line = proc.stdout.strip().splitlines()[-1]
    return json.loads(line)

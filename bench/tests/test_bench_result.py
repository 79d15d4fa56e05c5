"""A run's result line and last stderr lines, and the command off the chip."""
import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness.registry import ROOT, Benchmark

from .tiny import run

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture(scope="module")
def mr_result():
    err = io.StringIO()
    res = run("mr-xdt-closed4", err=err)
    return res, err.getvalue()


def test_result_has_exactly_the_contract_keys(mr_result):
    res, _ = mr_result
    assert list(res) == KEYS            # "checks", the numbers compared, last
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    for v in res["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    json.loads(json.dumps(res))


def test_metrics_are_the_cells_end_to_end_metrics(mr_result):
    res, _ = mr_result
    want = {m["name"] for m in Benchmark().metrics("mr-xdt-closed4", trace=False)}
    assert set(res["metrics"]) == want == {"completed_rps", "latency_p95_ms", "setup_s"}


def test_numbers_compared_are_the_last_stderr_lines(mr_result):
    res, err = mr_result
    lines = err.strip().splitlines()
    tail = lines[-len(res["checks"]):]
    for (name, c), line in zip(res["checks"].items(), tail):
        assert line.startswith(f"check {name} = {c['value']} (limit {c['limit']})")
        assert set(c) == {"value", "limit"}


def test_traced_run_adds_breakdown_and_busy_time():
    res = run("mr-xdt-closed4", trace=True, seconds=1.0)
    assert list(res) == KEYS[:5] + ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    want = {m["name"] for m in Benchmark().metrics("mr-xdt-closed4", trace=True)}
    # the CPU trace has no device plane: host-side metrics only
    assert {"engine_host_ms.mr", "xfer_host_ms.mr"} <= set(res["metrics"]) <= want


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mr-xdt-closed4",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_command_off_the_chip_exits_nonzero_without_a_result():
    p = _cli(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout), p.stderr[-2000:]
    assert "no TPU" in p.stderr


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and _no_result(p.stdout), p.stderr[-2000:]

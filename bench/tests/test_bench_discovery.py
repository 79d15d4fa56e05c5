"""A new traffic mix and a new metric are found by name from files of their
own plus entries in BENCHMARK.json, with no existing file edited."""
import json
import shutil

from bench.harness.registry import ROOT, Benchmark

from .tiny import MR_TINY, run


def test_extra_mix_and_metric_are_picked_up(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")     # the program under test
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "closed2.json").write_text(
        json.dumps({"loop": "closed", "clients": 2}))
    (tmp_path / "bench" / "metrics" / "requests_sent.py").write_text(
        "def read(run):\n    return float(len(run.window.records))\n")
    spec["workloads"].append({"name": "mr-xdt-closed2", "config": "vswarm-mr-xdt",
                              "traffic": "closed2", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "requests_sent", "unit": "req", "better": "higher",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["mr-xdt-closed2"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = Benchmark(tmp_path)
    assert bench.mix("closed2")["clients"] == 2
    assert "requests_sent" in {m["name"] for m in bench.metrics("mr-xdt-closed2", False)}
    assert "requests_sent" not in {m["name"] for m in bench.metrics("mr-xdt-closed4", False)}

    res = run("mr-xdt-closed2", root=tmp_path, conf_override=MR_TINY, seconds=1.0)
    assert res["correct"]
    assert res["metrics"]["requests_sent"]["value"] == res["attempted"]
    # metrics listed for other cells stay out; setup_s, listed for none, is in
    assert set(res["metrics"]) == {"requests_sent", "setup_s"}

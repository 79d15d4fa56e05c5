"""``correct`` at the cells' tiny sizes on the CPU: true for the program as
it is, false with the control in the program's place, and false with the
timed path broken underneath in each way the cell can break.

The chip's own look for a TPU is skipped (``require_chip=False``); the rest
of a run is driven as the command drives it.
"""
import pytest

from bench.harness.registry import Benchmark

from .tiny import run


def _checks_failed(res):
    return {k for k, v in res["checks"].items() if v["value"] > v["limit"]}


# ---------------------------------------------------------------- sound runs


@pytest.mark.parametrize("workload", ["mr-xdt-closed4", "disagg-chat"])
def test_sound_run_is_correct(workload):
    res = run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


# ---------------------------------------------------------------- controls


def _controls(workload):
    bench = Benchmark()
    return bench.deployment_module(bench.cell(workload)["config"]).Deployment.controls()


def test_mr_control_bf16_stage_arithmetic_is_not_correct():
    res = run("mr-xdt-closed4", patch=_controls("mr-xdt-closed4")["control_bf16_stage"])
    assert not res["correct"] and "mismatched_elements" in _checks_failed(res)


def test_mr_control_s3_medium_breaks_zero_copy():
    res = run("mr-xdt-closed4", patch=_controls("mr-xdt-closed4")["control_s3_medium"])
    assert not res["correct"] and "copied_slices" in _checks_failed(res)


def test_serving_control_fp8_reads_above_the_limit():
    res = run("disagg-chat", patch=_controls("disagg-chat")["control_fp8"])
    assert not res["correct"] and "max_logit_gap" in _checks_failed(res)


@pytest.mark.parametrize("workload", ["mr-xdt-closed4", "disagg-chat"])
def test_control_script_reads_every_control_not_correct(workload):
    """``bench/control.py`` runs the program and each control through the
    harness's check and prints what each read."""
    from bench.control import readings

    from .tiny import tiny_kw

    got = {kind: readings(workload, 2**31 + 7, 1.5, kind, patch, require_chip=False,
                          **tiny_kw(workload))
           for kind, patch in [("program", None), *_controls(workload).items()]}
    assert got["program"]["correct"] and not got["program"]["failed_checks"]
    for kind, line in got.items():
        assert set(line["checks"]) == set(got["program"]["checks"])
        if kind != "program":
            assert not line["correct"] and line["failed_checks"], kind


# ---------------------------------------------------------------- faults


def test_mr_answer_altered_in_transfer(monkeypatch):
    from repro.core.transfer import TransferEngine

    get = TransferEngine.get
    monkeypatch.setattr(TransferEngine, "get",
                        lambda self, ref, **kw: get(self, ref, **kw) + 1)
    res = run("mr-xdt-closed4")
    assert not res["correct"] and "mismatched_elements" in _checks_failed(res)


def test_mr_half_of_the_mappers_left_out(monkeypatch):
    from repro.core.workflow import Context

    def half_scatter(self, fn_name, objs):
        done = [self.invoke(fn_name, o) for o in objs[: len(objs) // 2]]
        return done + done

    monkeypatch.setattr(Context, "scatter", half_scatter)
    res = run("mr-xdt-closed4")
    assert not res["correct"]


def test_serving_token_altered_where_produced(monkeypatch):
    from repro.serving.engine import ServingEngine

    step = ServingEngine.step

    def altered(self):
        live = [r for r in self.slots if r is not None]
        step(self)
        for r in live:
            r.generated[-1] = (r.generated[-1] + 1) % self.cfg.vocab

    monkeypatch.setattr(ServingEngine, "step", altered)
    res = run("disagg-chat")
    assert not res["correct"] and "max_logit_gap" in _checks_failed(res)


def test_serving_step_returns_its_state_unchanged(monkeypatch):
    from repro.serving import engine

    fns = engine._step_fns

    def stale(cfg, mesh, max_len):
        prefill, decode = fns(cfg, mesh, max_len)
        return prefill, (lambda params, cache, tokens: (decode(params, cache, tokens)[0], cache))

    monkeypatch.setattr(engine, "_step_fns", stale)
    res = run("disagg-chat")
    assert not res["correct"] and "max_logit_gap" in _checks_failed(res)

"""chip_smoke.py rehearsed on the CPU at toy sizes: each phase's control flow
and checks, and the refusal to run without a TPU.  (Phase C needs four
devices: it is rehearsed in tests/_multidevice_checks.py.)"""
import importlib.util
import os

import jax
import numpy as np

from repro.configs import smoke_config
from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_refuses_to_run_without_a_tpu(capsys):
    """On the CPU the smoke exits non-zero and prints no result line."""
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_phase_a_mapreduce_on_every_medium(capsys):
    chip_smoke.phase_a(jax.devices()[0], input_bytes=30 * 4096, slice_bytes=4096)
    out = capsys.readouterr().out
    for medium in chip_smoke.MEDIA:
        assert f"phase A [{medium}]: ok" in out
    assert "phase A: ok  identical results" in out


def test_phase_b_disagg_serving_and_pull_kernel(capsys):
    before = dict(ops.FALLBACKS)
    chip_smoke.phase_b(jax.devices()[0], smoke_config("smollm_360m"),
                       chip_smoke.CompileStats(), max_len=64, n_requests=4,
                       prompt_len=16, new_tokens=4)
    out = capsys.readouterr().out
    assert "phase B [xdt]: ok" in out and "phase B [staged]: ok" in out
    assert "generations identical" in out
    assert "ops.xdt_pull [interpret]" in out
    assert dict(ops.FALLBACKS) == before


def test_compile_stats_buckets_by_function_name():
    stats = chip_smoke.CompileStats()
    stats._duration("/jax/core/compile/backend_compile_duration", 2.0,
                    fun_name="prefill")
    stats._duration("/jax/core/compile/backend_compile_duration", 1.5,
                    fun_name="decode")
    stats._duration("/jax/core/compile/backend_compile_duration", 0.5,
                    fun_name="prefill")
    stats._event("/jax/compilation_cache/cache_hits")
    assert stats.of("prefill") == (2, 2.5)
    assert stats.of("decode") == (1, 1.5)
    assert np.isclose(stats.seconds, 4.0) and stats.hits == 1

"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) runs the kernel bodies on the CPU but
never asks Mosaic whether it accepts their blocking and VMEM use.  These
tests hand the kernels, at the widths of the configurations they serve, to
the TPU compiler for one chip of a ``v5e:2x2`` topology; nothing runs.
Each asserts that the kernel reached the compiled program as a Mosaic
custom call.  The serving engine's decode program is compiled the same way,
to see that the TPU compiler updates its cache in place.  The topology is described inside a fixture (never at import):
only the worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.xdt_pull import xdt_pull

BF16, F32 = jnp.bfloat16, jnp.float32

#: (heads, kv heads, head dim) of the attention configurations
ATTN_WIDTHS = {"smollm_360m": (15, 5, 64), "qwen3_4b": (32, 8, 128)}
SEQ = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """The persistent compile cache off: an entry written here could not be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, no_compile_cache):
    """Compile ``fn`` for one described chip."""
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile()

    return compile_


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_flash_attention_compiles(arch, compile_for_chip):
    H, KV, hd = ATTN_WIDTHS[arch]
    compiled = compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        ((1, SEQ, H, hd), BF16), ((1, SEQ, KV, hd), BF16), ((1, SEQ, KV, hd), BF16),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", sorted(ATTN_WIDTHS))
def test_decode_attention_compiles(arch, compile_for_chip):
    H, KV, hd = ATTN_WIDTHS[arch]
    B = 4
    compiled = compile_for_chip(
        decode_attention,
        ((B, H, hd), BF16), ((B, SEQ, KV, hd), BF16), ((B, SEQ, KV, hd), BF16),
        ((B,), jnp.int32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_mamba_scan_compiles_at_falcon_mamba_7b_widths(compile_for_chip):
    d_inner, d_state = 8192, 16
    compiled = compile_for_chip(
        mamba_scan,
        ((1, SEQ, d_inner), BF16), ((1, SEQ, d_inner), BF16),
        ((1, SEQ, d_state), BF16), ((1, SEQ, d_state), BF16),
        ((d_inner, d_state), F32), ((d_inner,), F32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("D", [4096, 14336])
@pytest.mark.parametrize("variant", ["bf16", "int8+scale"])
def test_xdt_pull_compiles(variant, D, compile_for_chip):
    N = 8192
    if variant == "bf16":
        compiled = compile_for_chip(
            lambda s: xdt_pull(s, None, out_dtype=BF16), ((N, D), BF16))
    else:
        compiled = compile_for_chip(
            lambda s, sc: xdt_pull(s, sc, out_dtype=BF16),
            ((N, D), jnp.int8), ((N,), F32))
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_decode_updates_smollm_cache_in_place(one_chip, no_compile_cache):
    """The engine's decode program for SmolLM-360M at a pod of the serving
    benchmark (16 slots over 2048 positions) aliases its whole cache and
    copies no cache leaf: a one-position write into the cache's lane layout
    (a scatter, or a dynamic-update-slice of one row) makes the TPU
    compiler copy each leaf whole on the way into the step and out of it."""
    from repro.configs import get_config
    from repro.models import abstract_params, cache_shapes
    from repro.serving.engine import _donating, _step_fns

    cfg, B, T = get_config("smollm_360m"), 16, 2048
    on_chip = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on_chip, abstract_params(cfg, None))
    cache = {k: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
             for k, (shape, _axes, dt) in cache_shapes(cfg, B, T).items()}
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    compiled = _donating(_step_fns(cfg, None, T)[1]).lower(params, cache, tokens).compile()

    # the chip pads the (B,) positions to a tile, so the aliased bytes may
    # exceed the cache's own
    nbytes = sum(x.size * x.dtype.itemsize for x in cache.values())
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
    hlo_types = {jnp.dtype(BF16): "bf16", jnp.dtype(F32): "f32"}
    leaves = {f"{hlo_types[jnp.dtype(x.dtype)]}[{','.join(map(str, x.shape))}]"
              for x in cache.values() if x.ndim > 1}
    copies = set(re.findall(r"= (\w+\[[\d,]*\])\{[^}]*\} copy(?:-start)?\(",
                            compiled.as_text()))
    assert leaves and not leaves & copies

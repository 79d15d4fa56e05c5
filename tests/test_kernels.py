"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles.

All kernels run in interpret mode on CPU (the kernel BODY executes, so the
blocking/indexing/accumulator logic is what's validated; the TPU lowering
shares that body).  Whether Mosaic accepts the blocking at real widths is
checked by the ahead-of-time compiles in tests/test_tpu_compile.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as decode_kernel
from repro.kernels.flash_attention import flash_attention as flash_kernel
from repro.kernels.mamba_scan import mamba_scan as mamba_kernel
from repro.kernels.xdt_pull import xdt_pull as pull_kernel

TOL = {
    jnp.float32: dict(rtol=2e-5, atol=2e-5),
    jnp.bfloat16: dict(rtol=2e-2, atol=2e-2),
}


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------- flash


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,KV,hd,bq,bk",
    [
        (1, 128, 128, 4, 4, 64, 64, 64),     # MHA square
        (2, 128, 128, 8, 2, 32, 128, 64),    # GQA 4:1
        (1, 256, 128, 6, 1, 64, 64, 128),    # MQA, Sq != Sk
        (1, 64, 256, 4, 2, 128, 64, 64),     # cross lengths, wide head
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Sq, Sk, H, KV, hd, bq, bk, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Sk, KV, hd), dtype)
    v = _rand(ks[2], (B, Sk, KV, hd), dtype)
    out = flash_kernel(q, k, v, causal=causal, block_q=bq, block_k=bk,
                       interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_flash_attention_q_offset():
    """q_offset shifts the causal mask (the context-parallel contract)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (1, 64, 4, 32), jnp.float32)
    k = _rand(ks[1], (1, 128, 4, 32), jnp.float32)
    v = _rand(ks[2], (1, 128, 4, 32), jnp.float32)
    out = flash_kernel(q, k, v, causal=True, q_offset=64, block_q=64,
                       block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_flash_matches_chunked_attention_layer():
    """Kernel == the model library's chunked_attention (same contract)."""
    from repro.models.layers import chunked_attention

    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (2, 128, 4, 32), jnp.float32)
    k = _rand(ks[1], (2, 128, 2, 32), jnp.float32)
    v = _rand(ks[2], (2, 128, 2, 32), jnp.float32)
    out = flash_kernel(q, k, v, causal=True, block_q=64, block_k=64, interpret=True)
    want = chunked_attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------- decode


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,H,KV,hd,bt",
    [
        (2, 256, 8, 2, 64, 64),
        (4, 512, 4, 4, 32, 128),
        (1, 1024, 16, 2, 64, 256),
        (3, 128, 2, 1, 128, 128),
    ],
)
def test_decode_attention_sweep(B, T, H, KV, hd, bt, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(ks[0], (B, H, hd), dtype)
    k = _rand(ks[1], (B, T, KV, hd), dtype)
    v = _rand(ks[2], (B, T, KV, hd), dtype)
    lengths = jax.random.randint(ks[3], (B,), 0, T - 1)
    out = decode_kernel(q, k, v, lengths, block_t=bt, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **TOL[dtype]
    )


def test_decode_attention_ragged_lengths():
    """Each sequence masks independently at its own length."""
    B, T, H, KV, hd = 4, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(ks[0], (B, H, hd), jnp.float32)
    k = _rand(ks[1], (B, T, KV, hd), jnp.float32)
    v = _rand(ks[2], (B, T, KV, hd), jnp.float32)
    lengths = jnp.asarray([0, 31, 128, 255])
    out = decode_kernel(q, k, v, lengths, block_t=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_matches_model_decode_layer():
    """Kernel == decode_attention_layer's math for the same KV/positions."""
    from repro.models.config import ModelConfig
    from repro.models.layers import decode_attention_layer, write_cache_rows

    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=64, head_dim=16)
    B, T = 2, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    p = {
        "wq": _rand(ks[0], (64, 4, 16), jnp.float32) * 0.1,
        "wk": _rand(ks[1], (64, 2, 16), jnp.float32) * 0.1,
        "wv": _rand(ks[2], (64, 2, 16), jnp.float32) * 0.1,
        "wo": _rand(ks[3], (4, 16, 64), jnp.float32) * 0.1,
    }
    x = _rand(ks[4], (B, 1, 64), jnp.float32)
    cache_k = _rand(ks[5], (B, T, 2, 16), jnp.float32)
    cache_v = _rand(ks[5], (B, T, 2, 16), jnp.float32)
    pos = jnp.asarray([3, 17])
    out_layer, k_row, v_row = decode_attention_layer(x, p, cfg, cache_k, cache_v, pos)
    nk = write_cache_rows(cache_k[None], k_row[None], pos)[0]
    nv = write_cache_rows(cache_v[None], v_row[None], pos)[0]

    # reproduce with the kernel on the updated cache
    from repro.models.layers import _project_qkv, apply_rope, rope_angles

    q, k_new, v_new = _project_qkv(x, p, cfg)
    cos, sin = rope_angles(pos[:, None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    out_k = decode_kernel(q[:, 0], nk, nv, pos, block_t=64, interpret=True)
    out_k = jnp.einsum("bk,kd->bd", out_k.reshape(B, -1),
                       p["wo"].reshape(4 * 16, 64))
    np.testing.assert_allclose(
        np.asarray(out_layer[:, 0]), np.asarray(out_k), rtol=2e-4, atol=2e-4
    )


# ---------------------------------------------------------------- mamba


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,d_in,ds,chunk,bd",
    [
        (2, 64, 128, 16, 32, 64),
        (1, 128, 256, 8, 64, 128),
        (2, 32, 64, 4, 32, 64),      # single chunk
        (1, 256, 128, 16, 64, 32),   # many chunks, narrow channel block
    ],
)
def test_mamba_scan_sweep(B, S, d_in, ds, chunk, bd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 5)
    x = _rand(ks[0], (B, S, d_in), dtype) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, S, d_in), dtype))
    Bi = _rand(ks[2], (B, S, ds), dtype) * 0.3
    Ci = _rand(ks[3], (B, S, ds), dtype) * 0.3
    A = -jnp.exp(_rand(ks[4], (d_in, ds), jnp.float32) * 0.3)
    D = jnp.ones((d_in,), jnp.float32)
    y, h = mamba_kernel(x, dt, Bi, Ci, A, D, chunk=chunk, block_d=bd, interpret=True)
    yr, hr = ref.mamba_scan_ref(x, dt, Bi, Ci, A, D)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **TOL[dtype]
    )
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-4, atol=1e-4)


def test_mamba_scan_carried_state():
    """Scanning [first half] then [second half with h0] == one full scan."""
    B, S, d_in, ds = 1, 64, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = _rand(ks[0], (B, S, d_in), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, S, d_in), jnp.float32))
    Bi = _rand(ks[2], (B, S, ds), jnp.float32) * 0.3
    Ci = _rand(ks[3], (B, S, ds), jnp.float32) * 0.3
    A = -jnp.exp(_rand(ks[4], (d_in, ds), jnp.float32) * 0.3)
    D = jnp.ones((d_in,), jnp.float32)
    y_full, h_full = mamba_kernel(x, dt, Bi, Ci, A, D, chunk=32, block_d=64,
                                  interpret=True)
    h = S // 2
    y1, h1 = mamba_kernel(x[:, :h], dt[:, :h], Bi[:, :h], Ci[:, :h], A, D,
                          chunk=32, block_d=64, interpret=True)
    y2, h2 = mamba_kernel(x[:, h:], dt[:, h:], Bi[:, h:], Ci[:, h:], A, D,
                          h0=h1, chunk=32, block_d=64, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), rtol=1e-5, atol=1e-5)


def test_mamba_scan_matches_model_block():
    """Kernel == models.ssm.mamba1_mix for the same inputs."""
    from repro.models.ssm import mamba1_mix

    B, S, d_in, ds = 2, 64, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    x = _rand(ks[0], (B, S, d_in), jnp.float32) * 0.5
    dt = jax.nn.softplus(_rand(ks[1], (B, S, d_in), jnp.float32))
    Bi = _rand(ks[2], (B, S, ds), jnp.float32) * 0.3
    Ci = _rand(ks[3], (B, S, ds), jnp.float32) * 0.3
    A = -jnp.exp(_rand(ks[4], (d_in, ds), jnp.float32) * 0.3)
    D = jnp.ones((d_in,), jnp.float32)
    y_k, h_k = mamba_kernel(x, dt, Bi, Ci, A, D, chunk=32, block_d=128, interpret=True)
    y_m, h_m = mamba1_mix(x, dt, Bi, Ci, A, D, chunk=16)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_m), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- xdt_pull


@pytest.mark.parametrize("src_dtype,out_dtype", [
    (jnp.int8, jnp.bfloat16),
    (jnp.int8, jnp.float32),
    (jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.bfloat16),
])
@pytest.mark.parametrize("N,Dm,bn", [(512, 128, 128), (1024, 64, 512), (256, 256, 256)])
def test_xdt_pull_sweep(N, Dm, bn, src_dtype, out_dtype):
    key = jax.random.PRNGKey(9)
    if src_dtype == jnp.int8:
        src = jax.random.randint(key, (N, Dm), -127, 127, jnp.int32).astype(jnp.int8)
        scale = jnp.abs(jax.random.normal(key, (N,), jnp.float32)) * 0.01 + 1e-4
    else:
        src = _rand(key, (N, Dm), src_dtype)
        scale = None
    out = pull_kernel(src, scale, out_dtype=out_dtype, block_n=bn, interpret=True)
    want = ref.xdt_pull_ref(src, scale, out_dtype=out_dtype)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=1e-2, atol=1e-4,
    )


def test_xdt_pull_roundtrip_quantized_cache():
    """int8-compress a KV cache, pull+dequant, verify reconstruction error
    bounded by one quantization step per element."""
    from repro.optim.compression import int8_compress

    key = jax.random.PRNGKey(10)
    kv = jax.random.normal(key, (512, 128), jnp.float32)
    q, scale = int8_compress(kv)
    out = pull_kernel(q, jnp.full((512,), scale), out_dtype=jnp.float32,
                      block_n=128, interpret=True)
    assert float(jnp.max(jnp.abs(out - kv))) <= float(scale) + 1e-6


# ---------------------------------------------------------------- dispatch


def test_ops_fallback_on_ragged_shapes():
    """Non-divisible shapes route to the oracle, same numerics contract."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (1, 200, 3, 24), jnp.float32)     # 200 % 128 != 0
    k = _rand(ks[1], (1, 200, 3, 24), jnp.float32)
    v = _rand(ks[2], (1, 200, 3, 24), jnp.float32)
    before = ops.FALLBACKS["flash_attention"]
    out = ops.flash_attention(q, k, v, causal=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert ops.FALLBACKS["flash_attention"] == before + 1   # the fallback is visible


def test_ops_aligned_shapes_take_the_kernel():
    """Tileable shapes run the kernel (interpreted on CPU): no fallback.
    640 columns exceed one 512-wide block, so the pull tiles them by 128."""
    before = dict(ops.FALLBACKS)
    src = _rand(jax.random.PRNGKey(12), (1024, 640), jnp.float32)
    out = ops.xdt_pull(src, out_dtype=jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(ref.xdt_pull_ref(src, out_dtype=jnp.bfloat16), np.float32))
    assert dict(ops.FALLBACKS) == before
    assert ops.kernel_mode() == "interpret"


def test_ops_refuses_backends_without_a_kernel_path(monkeypatch):
    """Only TPU (Mosaic) and CPU (interpret) have a kernel path; any other
    backend raises instead of silently interpreting."""
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    src = jnp.ones((512, 128), jnp.float32)
    with pytest.raises(RuntimeError, match="no Pallas kernel path"):
        ops.xdt_pull(src)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.kernel_mode() == "mosaic"

"""§Perf hillclimb knobs: every optimization must be numerics-preserving.

These run mesh-free on CPU (the mesh-level checks for zero1 / fsdp /
seq_shard / moe-a2a live in tests/_multidevice_checks.py and the hillclimb
artifacts); here we pin the single-device contracts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.data import ShardedLoader
from repro.models import cache_shapes, init_params, make_decode_fn, make_loss_fn, make_prefill_fn
from repro.serving.engine import insert_cache


@pytest.fixture(scope="module")
def dense_setup():
    cfg = smoke_config("granite_8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = ShardedLoader(cfg, 4, 16).batch_at(0)
    return cfg, params, batch


def test_loss_chunk_matches_full(dense_setup):
    """Streamed CE == monolithic CE, in value AND gradient."""
    cfg, params, batch = dense_setup
    full = make_loss_fn(cfg, None, remat="none")
    chunked = make_loss_fn(dataclasses.replace(cfg, loss_chunk=4), None, remat="none")
    assert abs(float(full(params, batch)) - float(chunked(params, batch))) < 1e-4
    gf = jax.grad(full)(params, batch)
    gc = jax.grad(chunked)(params, batch)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gc)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=2e-3,
        )


def test_loss_chunk_ragged_falls_back(dense_setup):
    """Chunk sizes that don't divide S transparently use the full path."""
    cfg, params, batch = dense_setup
    odd = make_loss_fn(dataclasses.replace(cfg, loss_chunk=7), None, remat="none")
    full = make_loss_fn(cfg, None, remat="none")
    assert abs(float(odd(params, batch)) - float(full(params, batch))) < 1e-5


#: prompt lengths of the live slots per cache length: the rows a step
#: writes sit inside the first block, on either side of the edge between two
#: 128-position blocks, and at max_len - 1
_PROMPTS = {16: (5, 15), 256: (127, 128, 255)}


@pytest.mark.parametrize("T", sorted(_PROMPTS))
@pytest.mark.parametrize("empty_pos", ["at-0", "past-the-cache"])
@pytest.mark.parametrize("arch", ["smollm_360m", "granite-4.0-h-micro", "falcon_mamba_7b"])
def test_decode_writes_one_row_per_slot(arch, empty_pos, T):
    """One un-jitted decode step over the live slots of ``_PROMPTS`` and one
    empty slot changes no KV element but each slot's row at its position in
    each layer, writes there the K/V that prefill gives the prompt one token
    longer, and gives that prefill's logits and states.  An empty slot past
    the cache writes nothing."""
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(3))
    prefill = make_prefill_fn(cfg, None, remat="none", pad_to=T)
    rng = np.random.default_rng(0)
    prompts = {b: rng.integers(1, cfg.vocab, n) for b, n in enumerate(_PROMPTS[T])}
    nxt = {b: int(rng.integers(1, cfg.vocab)) for b in prompts}
    B = len(prompts) + 1                  # the last slot is empty
    # a pod's cache after earlier requests: what lies beyond a prompt is
    # not zero
    cache = {k: jnp.asarray(rng.standard_normal(shape), dtype)
             for k, (shape, _axes, dtype) in cache_shapes(cfg, B, T).items()}
    cache["pos"] = jnp.asarray([0] * (B - 1) + [0 if empty_pos == "at-0" else T], jnp.int32)
    for b, p in prompts.items():
        _, single = prefill(params, {"tokens": jnp.asarray(p)[None]})
        cache = insert_cache(cache, single, b)
    tokens = jnp.asarray([[nxt.get(b, 0)] for b in range(B)], jnp.int32)
    before = {k: np.asarray(v) for k, v in cache.items()}

    logits, out = make_decode_fn(cfg, None)(params, cache, tokens)
    after = {k: np.asarray(v) for k, v in out.items()}

    pos = before["pos"]
    np.testing.assert_array_equal(after["pos"], pos + 1)
    for key in ("k", "v"):
        if key not in before:
            continue
        written = np.zeros(before[key].shape[:3], bool)
        for b in range(B):
            if pos[b] < T:
                written[:, b, pos[b]] = True
        bits = lambda a: a.view(np.uint16)   # noqa: E731 — bf16 compared bit for bit
        np.testing.assert_array_equal(bits(after[key][~written]), bits(before[key][~written]))
    for b, p in prompts.items():
        want_logits, want = prefill(params, {"tokens": jnp.asarray(np.append(p, nxt[b]))[None]})
        np.testing.assert_allclose(np.asarray(logits[b], np.float32),
                                   np.asarray(want_logits[0], np.float32), rtol=0.05, atol=0.15)
        for key in ("k", "v"):
            if key in after:
                np.testing.assert_allclose(
                    np.asarray(after[key][:, b, pos[b]], np.float32),
                    np.asarray(want[key][:, 0, pos[b]], np.float32), rtol=0.05, atol=0.05)
        for key in ("conv", "ssm"):
            if key in after:
                np.testing.assert_allclose(
                    np.asarray(after[key][:, b], np.float32),
                    np.asarray(want[key][:, 0], np.float32), rtol=0.05, atol=0.05)


def test_seq_shard_and_fsdp_noop_without_mesh(dense_setup):
    """Mesh-free lowering ignores the layout knobs (identical loss)."""
    cfg, params, batch = dense_setup
    base = float(make_loss_fn(cfg, None, remat="none")(params, batch))
    for kw in ({"seq_shard_acts": True}, {"fsdp_params": True}):
        v = float(make_loss_fn(dataclasses.replace(cfg, **kw), None,
                               remat="none")(params, batch))
        assert v == pytest.approx(base, abs=1e-6), kw


def test_moe_a2a_single_shard_degenerates():
    """dispatch='a2a' without a model axis falls back to single-rank EP."""
    cfg = smoke_config("moonshot_v1_16b_a3b")
    params = init_params(cfg, jax.random.PRNGKey(2))
    batch = ShardedLoader(cfg, 2, 8).batch_at(0)
    base = float(make_loss_fn(cfg, None, remat="none")(params, batch))
    cfg2 = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="a2a"))
    v = float(make_loss_fn(cfg2, None, remat="none")(params, batch))
    assert v == pytest.approx(base, abs=1e-5)


def test_zero1_resolve_layout():
    """ZeRO-1 spec: DP axes land on the first free divisible dim only."""
    import os
    import subprocess
    import sys

    # needs >1 device: verified in tests/_multidevice_checks.py; here we
    # check the pure resolver logic on a trivial mesh via direct call
    from repro.distributed.sharding import ShardingRules
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=1, model=1)
    rules = ShardingRules(mesh)
    spec = rules.zero1_resolve(["embed", "d_ff"], [64, 128])
    # with 1-sized axes nothing shards, but resolution must not crash
    assert len(spec) == 2

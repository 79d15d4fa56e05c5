"""Model assembly: parameters, forward/loss, prefill and decode steps for
every assigned architecture family (dense / moe / ssm / hybrid / encoder /
vlm).

Design notes
------------
* **Scan over layers.**  All per-layer parameters are stacked with a leading
  ``n_layers`` dim and the forward is a single ``lax.scan`` (hybrid archs:
  one scan per run of Mamba2 layers between attention layers,
  :func:`hybrid_walk`), keeping HLO size — and hence dry-run compile time —
  O(1) in depth.
* **Remat.**  The layer body is wrapped in ``jax.checkpoint`` (policy
  selectable) so 4k-sequence training fits HBM at batch 16/device.
* **Sharding.**  Tensors are annotated through
  :class:`repro.distributed.sharding.ShardingRules`; activations are
  constrained after embedding and between blocks.  Attention picks its plan
  (head-TP vs context-parallel) from mesh divisibility — see
  :mod:`repro.models.layers`.
* **Caches.**  Decode state is a pytree: attention archs carry
  ``{"k","v"}`` of shape (L, B, T, KV, hd) with T sequence-sharded over the
  model axis (flash-decoding layout); SSM archs carry (conv, ssm) states;
  hybrids carry both: KV for each attention application, states for each
  Mamba2 layer.  The KV cache is THE ephemeral object the XDT serving
  path hands between prefill and decode pods.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..distributed.sharding import ShardingRules, rules_for
from .config import ModelConfig
from .layers import (
    AttnPlan,
    attention_layer,
    attn_param_shapes,
    decode_attention_layer,
    mlp_param_shapes,
    plan_attention,
    rms_norm,
    swiglu,
    write_cache_rows,
)
from .moe import moe_layer, moe_param_shapes
from .ssm import mamba1_block, mamba2_block, ssm_param_shapes, ssm_state_shapes

PyTree = Any


# ---------------------------------------------------------------------------
# parameter inventory
# ---------------------------------------------------------------------------


def _stack(shapes: Dict[str, Tuple[Tuple[int, ...], Tuple]], n: int):
    return {
        k: ((n,) + shape, ("layers",) + tuple(axes))
        for k, (shape, axes) in shapes.items()
    }


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    """Nested pytree of (shape, logical_axes) describing all parameters."""
    D, V, L = cfg.d_model, cfg.vocab, cfg.n_layers
    out: Dict[str, Any] = {
        "embed": ((V, D), ("vocab", "embed")),
        "final_norm": ((D,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ((D, V), ("embed", "vocab"))

    if cfg.family in ("dense", "encoder", "vlm"):
        out["blocks"] = {
            "attn": _stack(attn_param_shapes(cfg), L),
            "mlp": _stack(mlp_param_shapes(cfg), L),
            "ln1": ((L, D), ("layers", "embed")),
            "ln2": ((L, D), ("layers", "embed")),
        }
    elif cfg.family == "moe":
        out["blocks"] = {
            "attn": _stack(attn_param_shapes(cfg), L),
            "moe": _stack(moe_param_shapes(cfg), L),
            "ln1": ((L, D), ("layers", "embed")),
            "ln2": ((L, D), ("layers", "embed")),
        }
    elif cfg.family == "ssm":
        out["blocks"] = {
            "ssm": _stack(ssm_param_shapes(cfg), L),
            "ln": ((L, D), ("layers", "embed")),
        }
    elif cfg.family == "hybrid" and cfg.hybrid.attn_layers:
        # Granite-4.0-H: Mamba2 and attention layers, each with its own MLP
        Lm, La = cfg.hybrid.n_mamba(L), cfg.hybrid.n_attn(L)
        out["blocks"] = {
            "ssm": _stack(ssm_param_shapes(cfg), Lm),
            "ln": ((Lm, D), ("layers", "embed")),
            "mlp": _stack(mlp_param_shapes(cfg), Lm),
            "ln2": ((Lm, D), ("layers", "embed")),
        }
        out["attn_blocks"] = {
            "attn": _stack(attn_param_shapes(cfg), La),
            "mlp": _stack(mlp_param_shapes(cfg), La),
            "ln1": ((La, D), ("layers", "embed")),
            "ln2": ((La, D), ("layers", "embed")),
        }
    elif cfg.family == "hybrid":
        h = cfg.hybrid
        out["blocks"] = {
            "ssm": _stack(ssm_param_shapes(cfg), L),
            "ln": ((L, D), ("layers", "embed")),
        }
        shared_attn = attn_param_shapes(
            cfg, n_heads=h.shared_n_heads, n_kv=h.shared_n_kv_heads
        )
        out["shared"] = {
            "attn": shared_attn,
            "mlp": mlp_param_shapes(cfg, d_ff=h.shared_d_ff),
            "ln1": ((D,), ("embed",)),
            "ln2": ((D,), ("embed",)),
        }
    else:
        raise ValueError(cfg.family)
    return out


def _leaf_is_spec(x) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) == 2
        and isinstance(x[0], tuple)
        and all(isinstance(d, int) for d in x[0])
    )


def abstract_params(cfg: ModelConfig, mesh: Optional[Mesh]) -> PyTree:
    """ShapeDtypeStruct pytree with resolved shardings (dry-run stand-in)."""
    rules = rules_for(cfg, mesh) if mesh is not None else None
    dt = cfg.compute_dtype

    def mk(spec):
        shape, axes = spec
        if rules is None:
            return jax.ShapeDtypeStruct(shape, dt)
        return jax.ShapeDtypeStruct(shape, dt, sharding=rules.named(axes, shape))

    return jax.tree.map(mk, param_shapes(cfg), is_leaf=_leaf_is_spec)


def init_params(cfg: ModelConfig, key: jax.Array, mesh: Optional[Mesh] = None) -> PyTree:
    """Real parameter init (smoke tests / examples — small configs only)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_leaf_is_spec)
    keys = jax.random.split(key, len(leaves))
    rules = rules_for(cfg, mesh) if mesh is not None else None
    dt = cfg.compute_dtype

    vals = []
    for k, (shape, axes) in zip(keys, leaves):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        if len(shape) == 1 or shape[-1] == 1:
            v = jnp.ones(shape, dt) if len(shape) <= 2 else jnp.zeros(shape, dt)
        else:
            v = (jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5) * 0.5).astype(dt)
        # norms / biases / special ssm params
        vals.append(v)
    params = jax.tree.unflatten(treedef, vals)

    # fix up special leaves (norm scales = 1, A_log sensible, dt_bias small)
    def fixup(path, spec, val):
        name = path[-1] if path else ""
        shape, _ = spec
        if name in ("ln", "ln1", "ln2", "final_norm", "norm", "q_norm", "k_norm"):
            return jnp.ones(shape, dt)
        if name == "A_log":
            return jnp.log(jnp.linspace(1.0, 8.0, int(np.prod(shape)))).reshape(shape).astype(dt)
        if name == "dt_bias":
            return jnp.full(shape, -1.0, dt)
        if name == "D":
            return jnp.ones(shape, dt)
        if name in ("conv_b",):
            return jnp.zeros(shape, dt)
        return val

    def walk(sh, pr, path=()):
        if _leaf_is_spec(sh):
            return fixup(path, sh, pr)
        return {k: walk(sh[k], pr[k], path + (k,)) for k in sh}

    params = walk(shapes, params)
    if mesh is not None:
        def put(spec, val):
            _, axes = spec
            return jax.device_put(val, rules.named(axes, val.shape))
        params = jax.tree.map(put, shapes, params, is_leaf=_leaf_is_spec)
    return params


# ---------------------------------------------------------------------------
# shared forward plumbing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelBuild:
    """Everything a step function needs beyond params+batch."""

    cfg: ModelConfig
    mesh: Optional[Mesh]
    remat: str = "full"  # "full" | "none"

    @property
    def rules(self) -> Optional[ShardingRules]:
        return rules_for(self.cfg, self.mesh) if self.mesh is not None else None

    @property
    def plan(self) -> AttnPlan:
        return plan_attention(self.cfg, self.mesh)


def _constrain(x, build: ModelBuild, axes):
    if build.mesh is None:
        return x
    return lax.with_sharding_constraint(x, build.rules.named(axes, x.shape))


def _constrain_hidden(x, build: ModelBuild):
    """Inter-block activation layout.  Default: replicated over the model
    axis (pure Megatron TP).  With ``seq_shard_acts`` (§Perf hillclimb) the
    sequence axis is sharded over the model axis between blocks — activation
    residency and HBM traffic drop by the TP width, and GSPMD converts each
    block's entry/exit psum into all-gather + reduce-scatter (same wire
    bytes, 1/TP the activation footprint)."""
    if build.cfg.seq_shard_acts:
        return _constrain(x, build, ["batch", "seq_model", None])
    return _constrain(x, build, ["batch", None, None])


def _scaled(x, m: float):
    """``x * m``, and ``x`` itself where ``m`` is 1 (no op in the program)."""
    return x if m == 1.0 else x * m


def _embed(params, tokens, build: ModelBuild):
    x = params["embed"][tokens].astype(build.cfg.compute_dtype)
    x = _scaled(x, build.cfg.embedding_multiplier)
    return _constrain(x, build, ["batch", None, None])


def _logits(params, x, build: ModelBuild):
    cfg = build.cfg
    head = params["embed"] if cfg.tie_embeddings or "lm_head" not in params else None
    if head is not None:
        logits = jnp.einsum("bsd,vd->bsv", x, head)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return _constrain(logits, build, ["batch", None, "vocab"])


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def token_loss(params, x, labels, build: ModelBuild):
    """Mean next-token NLL from final hidden states ``x`` (B, S, D).

    With ``cfg.loss_chunk`` set (§Perf hillclimb), the (B, S, V) logits are
    never materialized: a remat'd scan walks sequence chunks, computing each
    chunk's logits + NLL and discarding them — HBM traffic for the loss head
    drops from O(S·V) tensors x several passes to O(chunk·V) working set,
    and the backward pass recomputes per-chunk under ``jax.checkpoint``.
    """
    cfg = build.cfg
    B, S, _D = x.shape
    c = cfg.loss_chunk
    if not c or S % c or S == c:
        return cross_entropy(_logits(params, x, build), labels)

    n = S // c
    xc = x.reshape(B, n, c, x.shape[-1]).swapaxes(0, 1)        # (n, B, c, D)
    lc = labels.reshape(B, n, c).swapaxes(0, 1)                # (n, B, c)

    @jax.checkpoint
    def body(acc, args):
        xi, li = args
        logits = _logits(params, xi, build)                    # (B, c, V)
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return acc + (lse - gold).sum(), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc),
                        unroll=cfg.scan_unroll)
    return total / (B * S)


def _attn_mlp_block(x, bp, build: ModelBuild, *, positions=None, return_kv=False,
                    causal=None):
    cfg = build.cfg
    h, kv = attention_layer(
        rms_norm(x, bp["ln1"], cfg.rms_eps), bp["attn"], cfg, build.plan,
        build.mesh, build.rules, positions=positions, causal=causal,
        return_kv=return_kv,
    )
    x = x + h
    hn = rms_norm(x, bp["ln2"], cfg.rms_eps)
    if cfg.family == "moe" and "moe" in bp:
        m, aux = moe_layer(hn, bp["moe"], cfg, build.mesh)
    else:
        m, aux = swiglu(hn, bp["mlp"]["wi"], bp["mlp"]["wg"], bp["mlp"]["wo"]), 0.0
    x = x + m
    x = _constrain_hidden(x, build)
    return x, kv, aux


# ---------------------------------------------------------------------------
# forward passes (train / prefill)
# ---------------------------------------------------------------------------


def _maybe_remat(fn, build: ModelBuild):
    return jax.checkpoint(fn) if build.remat == "full" else fn


def forward_transformer(params, x, build: ModelBuild, *, positions=None,
                        collect_kv=False, causal=None):
    """dense/moe/encoder/vlm backbone.  x: (B,S,D) embedded input."""
    def body(carry, bp):
        h, aux = carry
        h, kv, aux_l = _attn_mlp_block(
            h, bp, build, positions=positions, return_kv=collect_kv, causal=causal
        )
        return (h, aux + aux_l), kv

    body = _maybe_remat(body, build)
    (x, aux), kvs = lax.scan(body, (x, 0.0), params["blocks"],
                             unroll=build.cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"], build.cfg.rms_eps)
    return x, aux, kvs


def forward_ssm(params, x, build: ModelBuild, *, states=None, collect_state=False,
                length=None):
    """ssm backbone.  states: stacked (L, ...) pytree or None; ``length``
    (B,): real lengths of a padded prefill (Mamba2 only)."""
    cfg = build.cfg
    block = mamba1_block if cfg.ssm.version == 1 else mamba2_block
    kw = {} if length is None else {"length": length}

    def body(h, layer):
        bp, st = layer
        out, new_st = block(rms_norm(h, bp["ln"], cfg.rms_eps), bp["ssm"], cfg, st, **kw)
        h = _constrain_hidden(h + out, build)
        return h, (new_st if collect_state else None)

    body = _maybe_remat(body, build)
    x, new_states = lax.scan(body, x, (params["blocks"], states),
                             unroll=build.cfg.scan_unroll)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, new_states


def hybrid_walk(params, x, build: ModelBuild, attention, *, states=None,
                collect_state=False, length=None, decode=False):
    """The hybrid family's layers in order (``HybridConfig.segments``): each
    run of Mamba2 layers is one scan over its layers' indices, and each
    attention application calls
    ``attention(normed x, attention weights, application) -> (out, kv)``.
    Zamba2's shared block is the same weights at every application;
    Granite's attention layers have their own, and every layer its own MLP.

    A scan reads each layer's weights and state out of the whole stacks and,
    with ``collect_state``, writes the new state back into the carried
    stack in place: slicing a span's stack before its scan would copy it
    (all the Mamba2 weights, 5.5 GB at granite-4.0-h-micro's size).

    Prefill and training (``decode`` False) constrain the hidden layout
    between blocks and remat the Mamba2 layers as ``build`` says; decode
    steps the states one token.  Returns (x before the final norm, the kv
    of each application, the new states stacked over the Mamba2 layers or
    None)."""
    cfg = build.cfg
    eps, r = cfg.rms_eps, cfg.residual_multiplier
    constrain = (lambda h: h) if decode else (lambda h: _constrain_hidden(h, build))

    def take(tree, i):
        return jax.tree.map(lambda v: lax.dynamic_index_in_dim(v, i, keepdims=False), tree)

    def mlp(h, bp):
        return swiglu(rms_norm(h, bp["ln2"], eps),
                      bp["mlp"]["wi"], bp["mlp"]["wg"], bp["mlp"]["wo"])

    def mamba_layer(carry, i):
        h, st = carry
        bp = take(params["blocks"], i)
        out, new = mamba2_block(rms_norm(h, bp["ln"], eps), bp["ssm"], cfg,
                                None if st is None else take(st, i), length=length)
        h = h + _scaled(out, r)
        if "mlp" in bp:
            h = h + _scaled(mlp(h, bp), r)
        if collect_state:
            st = jax.tree.map(lambda a, n: lax.dynamic_update_index_in_dim(
                a, n.astype(a.dtype), i, 0), st, new)
        return (constrain(h), st), None

    if not decode:
        mamba_layer = _maybe_remat(mamba_layer, build)
    kvs = []
    for kind, a, b in cfg.hybrid.segments(cfg.n_layers):
        if kind == "attn":
            bp = (jax.tree.map(lambda v: v[a], params["attn_blocks"])
                  if "attn_blocks" in params else params["shared"])
            out, kv = attention(rms_norm(x, bp["ln1"], eps), bp["attn"], a)
            x = x + _scaled(out, r)
            x = constrain(x + _scaled(mlp(x, bp), r))
            kvs.append(kv)
        else:
            (x, states), _ = lax.scan(mamba_layer, (x, states), jnp.arange(a, b),
                                      unroll=cfg.scan_unroll)
    return x, kvs, (states if collect_state else None)


def forward_hybrid(params, x, build: ModelBuild, *, positions=None,
                   collect_kv=False, states=None, collect_state=False, length=None):
    """Hybrid backbone (:func:`hybrid_walk`) over a whole sequence."""
    cfg = build.cfg

    def attention(h, p, _g):
        return attention_layer(h, p, cfg, build.plan, build.mesh, build.rules,
                               positions=positions, return_kv=collect_kv)

    x, kvs, stacked_states = hybrid_walk(params, x, build, attention, states=states,
                                         collect_state=collect_state, length=length)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    stacked_kv = None
    if collect_kv:
        stacked_kv = (jnp.stack([kv[0] for kv in kvs]), jnp.stack([kv[1] for kv in kvs]))
    return x, stacked_kv, stacked_states


# ---------------------------------------------------------------------------
# public step functions
# ---------------------------------------------------------------------------


def make_loss_fn(cfg: ModelConfig, mesh: Optional[Mesh], remat: str = "full",
                 aux_weight: float = 0.01):
    """Returns loss_fn(params, batch) -> scalar."""
    build = ModelBuild(cfg, mesh, remat)

    def loss_fn(params, batch):
        if cfg.family == "vlm":
            tok_x = _embed(params, batch["tokens"], build)
            x = jnp.concatenate(
                [batch["patches"].astype(cfg.compute_dtype), tok_x], axis=1
            )
            x = _constrain(x, build, ["batch", None, None])
            x, aux, _ = forward_transformer(params, x, build)
            n_img = batch["patches"].shape[1]
            return token_loss(params, x[:, n_img:], batch["labels"], build) \
                + aux_weight * aux
        if cfg.family == "encoder":
            x = batch["frames"].astype(cfg.compute_dtype)
            x = _constrain(x, build, ["batch", None, None])
            x, aux, _ = forward_transformer(params, x, build, causal=False)
            return token_loss(params, x, batch["labels"], build)
        if cfg.family == "ssm":
            x = _embed(params, batch["tokens"], build)
            x, _ = forward_ssm(params, x, build)
            return token_loss(params, x, batch["labels"], build)
        if cfg.family == "hybrid":
            x = _embed(params, batch["tokens"], build)
            x, _, _ = forward_hybrid(params, x, build)
            return token_loss(params, x, batch["labels"], build)
        # dense / moe
        x = _embed(params, batch["tokens"], build)
        x, aux, _ = forward_transformer(params, x, build)
        return token_loss(params, x, batch["labels"], build) + aux_weight * aux

    return loss_fn


def _constrain_cache(kv, build: ModelBuild):
    k, v = kv
    axes = ["layers", "batch", "kv_seq", None, None]
    return (_constrain(k, build, axes), _constrain(v, build, axes))


def make_prefill_fn(cfg: ModelConfig, mesh: Optional[Mesh], remat: str = "full",
                    pad_to: Optional[int] = None):
    """Returns prefill(params, batch) -> (last_logits (B,V), cache pytree).

    The returned cache is the XDT ephemeral object: sequence-sharded KV (and
    SSM states), ready for a decode pod to pull.  ``pad_to`` grows the KV
    sequence axis to the decode context budget.

    A model with Mamba2 layers also takes ``batch["length"]`` (B,): the real
    lengths of prompts padded at their ends.  The states are those of the
    real tokens (padded steps have ``dt = 0``), ``pos`` is the length, and
    the logits are those of the last real position.  Attention is causal, so
    the pads change no real position; their KV lies beyond ``pos``, masked,
    and decode overwrites it.
    """
    build = ModelBuild(cfg, mesh, remat)

    def _pad_kv(kv):
        if pad_to is None:
            return kv
        k, v = kv
        extra = pad_to - k.shape[2]
        if extra <= 0:
            return kv
        pad = [(0, 0)] * k.ndim
        pad[2] = (0, extra)
        return jnp.pad(k, pad), jnp.pad(v, pad)

    def prefill(params, batch):
        cache: Dict[str, Any] = {}
        length = batch.get("length")
        if cfg.family in ("dense", "moe", "vlm", "encoder"):
            if cfg.family == "vlm":
                tok_x = _embed(params, batch["tokens"], build)
                x = jnp.concatenate(
                    [batch["patches"].astype(cfg.compute_dtype), tok_x], axis=1
                )
            elif cfg.family == "encoder":
                x = batch["frames"].astype(cfg.compute_dtype)
            else:
                x = _embed(params, batch["tokens"], build)
            x, _, kvs = forward_transformer(
                params, x, build, collect_kv=True,
                causal=None if cfg.causal else False,
            )
            cache["k"], cache["v"] = _constrain_cache(_pad_kv(kvs), build)
        elif cfg.family == "ssm":
            x = _embed(params, batch["tokens"], build)
            zero = _zero_states(cfg, x.shape[0], build)
            x, states = forward_ssm(params, x, build, states=zero, collect_state=True,
                                    length=length)
            cache.update(states)
        else:  # hybrid
            x = _embed(params, batch["tokens"], build)
            zero = _zero_states(cfg, x.shape[0], build)
            x, kvs, states = forward_hybrid(
                params, x, build, collect_kv=True, states=zero, collect_state=True,
                length=length,
            )
            cache["k"], cache["v"] = _constrain_cache(_pad_kv(kvs), build)
            cache["conv"], cache["ssm"] = states["conv"], states["ssm"]
        B = x.shape[0]
        S = x.shape[1]
        if length is None:
            cache["pos"] = jnp.full((B,), S, jnp.int32)
            last = x[:, -1:]
        else:
            # a padded prompt: the first token is read at its last real position
            cache["pos"] = length.astype(jnp.int32)
            last = jnp.take_along_axis(x, (length - 1)[:, None, None], axis=1)
        logits = _logits(params, last, build)[:, 0]
        return logits, cache

    return prefill


def _state_layers(cfg: ModelConfig) -> int:
    """Layers that hold an SSM state: every layer but attention layers."""
    return cfg.hybrid.n_mamba(cfg.n_layers) if cfg.hybrid else cfg.n_layers


def _zero_states(cfg: ModelConfig, batch: int, build: ModelBuild):
    shapes = ssm_state_shapes(cfg, batch)
    out = {}
    for k, (shape, axes) in shapes.items():
        full = (_state_layers(cfg),) + shape
        z = jnp.zeros(full, jnp.float32 if k == "ssm" else cfg.compute_dtype)
        out[k] = _constrain(z, build, ["layers"] + list(axes))
    return out


def make_decode_fn(cfg: ModelConfig, mesh: Optional[Mesh]):
    """Returns decode(params, cache, tokens (B,1)) -> (logits (B,V), cache).

    This is ``serve_step``: one new token against the resident cache.  The
    layers read the KV cache and return each slot's new row;
    :func:`write_cache_rows` writes them in after the last layer, so that a
    caller that donates the cache updates it in place (under a mesh, each
    device in its own shard of the cache).
    """
    build = ModelBuild(cfg, mesh, remat="none")

    def decode(params, cache, tokens):
        pos = cache["pos"]  # (B,)
        x = _embed(params, tokens, build)

        if cfg.family in ("dense", "moe", "vlm"):
            def body(carry, layer):
                h = carry
                bp, ck, cv = layer
                hn = rms_norm(h, bp["ln1"], cfg.rms_eps)
                a, k_row, v_row = decode_attention_layer(hn, bp["attn"], cfg, ck, cv, pos)
                h = h + a
                hn = rms_norm(h, bp["ln2"], cfg.rms_eps)
                if cfg.family == "moe":
                    m, _ = moe_layer(hn, bp["moe"], cfg, build.mesh)
                else:
                    m = swiglu(hn, bp["mlp"]["wi"], bp["mlp"]["wg"], bp["mlp"]["wo"])
                return h + m, (k_row, v_row)

            x, (k_rows, v_rows) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]),
                                           unroll=cfg.scan_unroll)
            new_cache = dict(cache, k=write_cache_rows(cache["k"], k_rows, pos, build.rules),
                             v=write_cache_rows(cache["v"], v_rows, pos, build.rules),
                             pos=pos + 1)
        elif cfg.family == "ssm":
            def body(carry, layer):
                h = carry
                bp, st = layer
                out, new_st = (mamba1_block if cfg.ssm.version == 1 else mamba2_block)(
                    rms_norm(h, bp["ln"], cfg.rms_eps), bp["ssm"], cfg, st
                )
                return h + out, new_st

            states = {"conv": cache["conv"], "ssm": cache["ssm"]}
            x, new_states = lax.scan(body, x, (params["blocks"], states),
                                     unroll=cfg.scan_unroll)
            new_cache = dict(cache, pos=pos + 1, **new_states)
        else:  # hybrid
            def attention(h, p, g):
                out, k, v = decode_attention_layer(h, p, cfg, cache["k"][g],
                                                   cache["v"][g], pos)
                return out, (k, v)

            states = {"conv": cache["conv"], "ssm": cache["ssm"]}
            x, rows, merged = hybrid_walk(params, x, build, attention, states=states,
                                          collect_state=True, decode=True)
            k_rows = jnp.stack([k for k, _ in rows])
            v_rows = jnp.stack([v for _, v in rows])
            new_cache = dict(cache, k=write_cache_rows(cache["k"], k_rows, pos, build.rules),
                             v=write_cache_rows(cache["v"], v_rows, pos, build.rules),
                             pos=pos + 1, **merged)

        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        logits = _logits(params, x, build)[:, 0]
        return logits, new_cache

    return decode


# ---------------------------------------------------------------------------
# cache shape inventory (dry-run stand-ins for decode cells)
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, batch: int, seq_len: int) -> Dict[str, Tuple]:
    """(shape, logical_axes, dtype) per cache leaf for serve_step lowering."""
    out: Dict[str, Tuple] = {}
    dt = cfg.compute_dtype
    if cfg.family in ("dense", "moe", "vlm"):
        kv = (cfg.n_layers, batch, seq_len, cfg.n_kv_heads, cfg.hd)
        axes = ("layers", "batch", "kv_seq", None, None)
        out["k"] = (kv, axes, dt)
        out["v"] = (kv, axes, dt)
    elif cfg.family == "ssm":
        for k, (shape, axes) in ssm_state_shapes(cfg, batch).items():
            out[k] = ((cfg.n_layers,) + shape, ("layers",) + tuple(axes),
                      jnp.float32 if k == "ssm" else dt)
    else:  # hybrid
        h = cfg.hybrid
        n_kv = cfg.n_kv_heads if h.attn_layers else h.shared_n_kv_heads
        kv = (h.n_attn(cfg.n_layers), batch, seq_len, n_kv, cfg.hd)
        axes = ("layers", "batch", "kv_seq", None, None)
        out["k"] = (kv, axes, dt)
        out["v"] = (kv, axes, dt)
        for k, (shape, saxes) in ssm_state_shapes(cfg, batch).items():
            out[k] = ((_state_layers(cfg),) + shape, ("layers",) + tuple(saxes),
                      jnp.float32 if k == "ssm" else dt)
    out["pos"] = ((batch,), ("batch",), jnp.int32)
    return out

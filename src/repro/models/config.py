"""Unified architecture configuration covering all assigned model families."""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    capacity_factor: float = 1.25
    # "replicated_ep": activations replicated over the model axis, experts
    #   sharded on it; combine folds into one psum (baseline).
    # "dense": every expert computed for every token (tiny-config oracle).
    dispatch: str = "replicated_ep"


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int
    version: int = 1            # 1 = Mamba (S6), 2 = Mamba2 (SSD)
    expand: int = 2
    conv_width: int = 4
    head_dim: int = 64          # Mamba2 only
    dt_rank: Optional[int] = None  # Mamba1; default ceil(d_model/16)
    chunk: int = 256            # scan chunk length


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Mamba2 layers with attention among them, in one of two patterns.

    * Zamba2 (``attn_layers`` empty): one *shared* attention+MLP block, one
      set of weights of the ``shared_*`` widths, applied before each run of
      ``attn_every`` Mamba2 layers; every one of the ``n_layers`` is Mamba2.
    * Granite-4.0-H (``attn_layers`` given): the layers at those indices are
      attention layers with weights of their own (``n_heads`` over
      ``n_kv_heads``) in place of Mamba2, and every layer of either kind is
      followed by its own SwiGLU MLP of width ``d_ff``.
    """

    attn_every: int = 6
    shared_d_ff: int = 8192
    shared_n_heads: int = 32
    shared_n_kv_heads: int = 32
    attn_layers: Tuple[int, ...] = ()

    def n_attn(self, n_layers: int) -> int:
        """Attention applications: KV caches a sequence holds."""
        return len(self.attn_layers) if self.attn_layers else n_layers // self.attn_every

    def n_mamba(self, n_layers: int) -> int:
        """Mamba2 layers: SSM and conv states a sequence holds."""
        return n_layers - len(self.attn_layers)

    def segments(self, n_layers: int) -> List[Tuple[str, int, int]]:
        """The layers in order: ``("attn", g, g + 1)`` is attention
        application ``g``; ``("mamba", a, b)`` the Mamba2 layers ``a`` to
        ``b - 1`` of the stacked Mamba2 weights and states."""
        out: List[Tuple[str, int, int]] = []
        if not self.attn_layers:
            e = self.attn_every
            for g in range(n_layers // e):
                out += [("attn", g, g + 1), ("mamba", g * e, (g + 1) * e)]
            if n_layers % e:
                out.append(("mamba", n_layers - n_layers % e, n_layers))
            return out
        m = g = 0
        for i in range(n_layers):
            if i in self.attn_layers:
                out.append(("attn", g, g + 1))
                g += 1
            else:
                if out and out[-1][0] == "mamba":
                    out[-1] = ("mamba", out[-1][1], m + 1)
                else:
                    out.append(("mamba", m, m + 1))
                m += 1
        return out


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qk_norm: bool = False
    rope: bool = True                   # False: no position embedding (NoPE)
    rope_theta: float = 1e4
    attn_scale: Optional[float] = None  # softmax scale; None: head_dim ** -0.5
    # Granite-style multipliers (the hybrid family): the embedding is scaled
    # by ``embedding_multiplier``, each layer's mixer and MLP outputs by
    # ``residual_multiplier`` before their residual adds, and the logits are
    # divided by ``logits_scaling``
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_eps: float = 1e-5
    causal: bool = True
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    frontend: Optional[str] = None      # None | "audio" | "vlm"
    frontend_seq: int = 0               # patch/frame positions in the sequence
    dtype: str = "bfloat16"
    # capability flags (drive shape-cell applicability)
    has_decode: bool = True
    subquadratic: bool = False          # can run long_500k
    attn_chunk: int = 512               # q-block for chunked attention
    scan_unroll: bool = False           # unroll layer scans (dry-run cost probes)
    # ---- performance knobs (EXPERIMENTS.md §Perf hillclimb) ----
    loss_chunk: int = 0                 # tokens/chunk for streamed CE (0 = off):
                                        # never materializes the (B,S,V) logits
    seq_shard_acts: bool = False        # Megatron-style sequence parallelism:
                                        # inter-block activations sharded over
                                        # the model axis (AG/RS replace psum)
    fsdp_params: bool = False           # shard params' d_model dim over the
                                        # data axis (ZeRO-3/FSDP via GSPMD):
                                        # per-layer weight all-gathers replace
                                        # per-layer activation psums
    # note for DESIGN §Arch-applicability when a shape cell is skipped
    skip_note: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // max(1, self.n_heads)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def n_params(self) -> int:
        """Total parameter count (embedding included once if tied)."""
        d, f, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        hd = self.hd
        n = V * d  # embedding
        if not self.tie_embeddings:
            n += V * d
        per_layer = 0
        if self.family in ("dense", "moe", "encoder", "vlm"):
            per_layer += d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
            per_layer += 2 * d  # norms
            if self.qk_norm:
                per_layer += 2 * hd
        if self.family == "moe":
            m = self.moe
            per_layer += d * m.n_experts  # router
            per_layer += m.n_experts * 3 * d * m.d_ff_expert
        elif self.family in ("dense", "encoder", "vlm"):
            per_layer += 3 * d * f
        elif self.family in ("ssm", "hybrid"):
            s = self.ssm
            d_in = s.expand * d
            if s.version == 1:
                dtr = s.dt_rank or -(-d // 16)
                per_layer += d * 2 * d_in               # in_proj
                per_layer += d_in * s.conv_width        # conv
                per_layer += d_in * (dtr + 2 * s.d_state) + dtr * d_in
                per_layer += d_in * s.d_state + d_in    # A, D
                per_layer += d_in * d                   # out_proj
            else:
                n_h = d_in // s.head_dim
                per_layer += d * (2 * d_in + 2 * s.d_state + n_h)  # in_proj(z,x,B,C,dt)
                per_layer += (d_in + 2 * s.d_state) * (s.conv_width + 1)  # conv, bias
                per_layer += 3 * n_h + d_in             # dt_bias, A, D, norm
                per_layer += d_in * d
            per_layer += d  # norm
        h = self.hybrid
        if self.family == "hybrid" and h.attn_layers:
            n_attn = h.n_attn(L)
            mlp = 3 * d * f + d                         # every layer's MLP, its norm
            attn = (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                    + self.n_heads * hd * d + d)
            n += (L - n_attn) * (per_layer + mlp) + n_attn * (attn + mlp)
        else:
            n += L * per_layer
        if self.family == "hybrid" and not h.attn_layers:
            shd = self.hd
            shared = (
                d * h.shared_n_heads * shd
                + 2 * d * h.shared_n_kv_heads * shd
                + h.shared_n_heads * shd * d
                + 3 * d * h.shared_d_ff
                + 2 * d
            )
            n += shared
        n += d  # final norm
        return n

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: only top_k experts count)."""
        if self.family != "moe":
            return self.n_params()
        m = self.moe
        total = self.n_params()
        all_experts = self.n_layers * m.n_experts * 3 * self.d_model * m.d_ff_expert
        active = self.n_layers * m.top_k * 3 * self.d_model * m.d_ff_expert
        return total - all_experts + active

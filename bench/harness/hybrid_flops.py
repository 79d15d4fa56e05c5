"""Operations and bytes that a Granite-4.0-H hybrid's steps need (Mamba2
layers and GQA attention layers, each followed by a SwiGLU MLP), from its
shapes and the traffic's lengths.

As in :mod:`bench.harness.flops`, these count the algorithm's own work,
whatever implements it: prefill over the prompt's real tokens (not the pad
tokens a program adds to reach whole SSD chunks), the SSD in its chunked
form at the published chunk over those tokens, causal attention in the
attention layers; for a decode step, the weights read once plus, for each
live sequence, its SSM and conv states read and written and the KV
positions it holds.  A program that pads, or reads the whole KV cache,
spends more than this; its share then reads lower.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class HybridShape:
    mamba_layers: int
    attn_layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    m_heads: int
    m_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    chunk: int
    tied_embeddings: bool = True
    #: bytes of one weight, conv-state or KV element as served
    elem_bytes: int = 2
    #: bytes of one SSM-state element (kept in float32)
    state_bytes: int = 4

    @classmethod
    def from_hf(cls, c: dict, elem_bytes: int = 2) -> "HybridShape":
        """From a Hugging Face ``GraniteMoeHybrid``-style ``config.json``."""
        kinds, heads = c["layer_types"], c["num_attention_heads"]
        return cls(
            mamba_layers=kinds.count("mamba"), attn_layers=kinds.count("attention"),
            d_model=c["hidden_size"], heads=heads, kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            d_ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
            m_heads=c["mamba_n_heads"], m_head_dim=c["mamba_d_head"],
            d_state=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
            d_conv=c["mamba_d_conv"], chunk=c["mamba_chunk_size"],
            tied_embeddings=c.get("tie_word_embeddings", False), elem_bytes=elem_bytes)

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def _in_proj(self) -> int:
        return self.d_model * (2 * self.d_inner + 2 * self.n_groups * self.d_state
                               + self.m_heads)

    def _attn_proj(self) -> int:
        return (self.d_model * (self.heads + 2 * self.kv_heads) * self.head_dim
                + self.heads * self.head_dim * self.d_model)

    def params(self) -> int:
        D = self.d_model
        mlp = 3 * D * self.d_ff + D                              # its norm
        mamba = (self._in_proj() + self.conv_dim * (self.d_conv + 1)   # conv, bias
                 + 3 * self.m_heads + self.d_inner               # dt_bias, A, D, norm
                 + self.d_inner * D + D)                         # out_proj, norm
        attn = self._attn_proj() + D
        embed = self.vocab * D * (1 if self.tied_embeddings else 2)
        return (embed + self.mamba_layers * (mamba + mlp)
                + self.attn_layers * (attn + mlp) + D)

    def matmul_flops_per_token(self) -> int:
        """Projections, conv and MLP of every layer, for one token."""
        mlp = 3 * self.d_model * self.d_ff
        mamba = self._in_proj() + self.d_inner * self.d_model + self.d_conv * self.conv_dim
        return 2 * (self.mamba_layers * (mamba + mlp)
                    + self.attn_layers * (self._attn_proj() + mlp))

    def ssm_state_bytes(self) -> int:
        """One sequence's SSM state in every Mamba2 layer."""
        return (self.mamba_layers * self.m_heads * self.m_head_dim * self.d_state
                * self.state_bytes)

    def conv_state_bytes(self) -> int:
        """One sequence's conv state (the last ``d_conv - 1`` inputs)."""
        return self.mamba_layers * (self.d_conv - 1) * self.conv_dim * self.elem_bytes

    def kv_bytes_per_position(self) -> int:
        """K and V of one position in every attention layer."""
        return 2 * self.attn_layers * self.kv_heads * self.head_dim * self.elem_bytes

    def cache_bytes(self, max_len: int) -> int:
        """One sequence's whole cache, as a handoff moves it: states, KV
        over ``max_len`` positions, and the int32 position."""
        return (self.ssm_state_bytes() + self.conv_state_bytes()
                + max_len * self.kv_bytes_per_position() + 4)


def ssd_flops(s: HybridShape, tokens: int) -> int:
    """The SSD of one Mamba2 layer over ``tokens`` in chunks of ``s.chunk``:
    within a chunk, C.B^T and the weighted sum of inputs over causal pairs;
    across chunks, the carried state's output and its update."""
    N, HP = s.n_groups * s.d_state, s.d_inner * s.d_state
    total, left = 0, tokens
    while left > 0:
        c = min(s.chunk, left)
        pairs = c * (c + 1) // 2
        total += 2 * pairs * (N + s.d_inner) + 2 * 2 * c * HP
        left -= c
    return total


def prefill_flops(s: HybridShape, prompt_tokens: int) -> int:
    """One prompt of ``prompt_tokens`` real tokens; logits for its last
    position only."""
    S = prompt_tokens
    attn = 2 * s.attn_layers * s.heads * s.head_dim * S * (S + 1)   # QK^T + PV, causal
    return (S * s.matmul_flops_per_token() + attn + s.mamba_layers * ssd_flops(s, S)
            + 2 * s.d_model * s.vocab)


def decode_step_flops(s: HybridShape, contexts: Iterable[int]) -> int:
    """One decode step; ``contexts`` holds, for each live sequence, the
    positions its new token attends to (itself included)."""
    per_token = (s.matmul_flops_per_token() + 2 * s.d_model * s.vocab
                 + s.mamba_layers * 2 * 3 * s.d_inner * s.d_state)  # decay, input, output
    return sum(per_token + 4 * s.attn_layers * s.heads * s.head_dim * c for c in contexts)


def decode_step_bytes(s: HybridShape, contexts: Iterable[int]) -> int:
    """Weights read once; each live sequence's states read and written, its
    earlier KV positions read and its new one written."""
    states = 2 * (s.ssm_state_bytes() + s.conv_state_bytes())
    return s.params() * s.elem_bytes + sum(states + c * s.kv_bytes_per_position()
                                           for c in contexts)

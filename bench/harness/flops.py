"""Operations and bytes that a Llama-style decoder's steps need, from its
shapes and the traffic's lengths.

These count the work the algorithm needs, whatever implements it: prefill
over the prompt's own tokens (not any padding), causal attention over the
positions each query may see, and for a decode step the weights read once
plus the cache positions the live sequences hold.  A program that pads, or
reads the whole cache, spends more than this; its share of the roofline then
reads lower, and a change that stops the waste raises it without moving the
yardstick.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable


@dataclasses.dataclass(frozen=True)
class DecoderShape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied_embeddings: bool = True
    #: bytes of one weight or cache element as served
    elem_bytes: int = 2

    @classmethod
    def from_hf(cls, c: dict, elem_bytes: int = 2) -> "DecoderShape":
        """From a Hugging Face ``LlamaForCausalLM``-style ``config.json``."""
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"], heads=heads,
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            tied_embeddings=c.get("tie_word_embeddings", False),
            elem_bytes=elem_bytes,
        )

    def params(self) -> int:
        D, hd = self.d_model, self.head_dim
        per_layer = (D * (self.heads + 2 * self.kv_heads) * hd   # q, k, v
                     + self.heads * hd * D                      # o
                     + 3 * D * self.d_ff                        # gate, up, down
                     + 2 * D)                                   # two norms
        embed = self.vocab * D * (1 if self.tied_embeddings else 2)
        return embed + self.layers * per_layer + D

    def matmul_flops_per_token(self) -> int:
        """Projections and MLP of every layer, for one token."""
        D, hd = self.d_model, self.head_dim
        return 2 * self.layers * (D * (self.heads + 2 * self.kv_heads) * hd
                                  + self.heads * hd * D + 3 * D * self.d_ff)

    def kv_bytes_per_position(self) -> int:
        """K and V of one position in every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.elem_bytes


def prefill_flops(s: DecoderShape, prompt_tokens: int) -> int:
    """One prompt of ``prompt_tokens``; logits for its last position only."""
    S = prompt_tokens
    attn = 2 * s.layers * s.heads * s.head_dim * S * (S + 1)   # QK^T + PV, causal
    return S * s.matmul_flops_per_token() + attn + 2 * s.d_model * s.vocab


def decode_step_flops(s: DecoderShape, contexts: Iterable[int]) -> int:
    """One decode step; ``contexts`` holds, for each live sequence, the
    positions its new token attends to (itself included)."""
    total = 0
    for c in contexts:
        total += (s.matmul_flops_per_token()
                  + 4 * s.layers * s.heads * s.head_dim * c
                  + 2 * s.d_model * s.vocab)
    return total


def decode_step_bytes(s: DecoderShape, contexts: Iterable[int]) -> int:
    """Weights read once; each live sequence's earlier positions read and its
    new position written."""
    total = s.params() * s.elem_bytes
    for c in contexts:
        total += c * s.kv_bytes_per_position()
    return total


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip needs: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])

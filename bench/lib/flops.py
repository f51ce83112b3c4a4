"""Operations and bytes that an architecture's algorithm needs, computed
from the configuration's shapes.

These count the work of the algorithm, not of an implementation: the
live context (not the cache's capacity), the causal half of attention
and of the SSD's intra-chunk product, and no recomputation.  So any
implementation is read against the same work.  Embedding lookups are
not counted; the output head is.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Counts:
    """Per-token work of one forward pass.

    matmul_params: parameters that take part in a matrix product per
        token (2 FLOPs each), the head included, the embedding lookup not.
    attn_flops_per_key: FLOPs per token per attended key position, all
        layers (QK^T and PV): 4 * heads * head_dim * layers.
    seq_flops_per_token: FLOPs per token of sequence mixing that does not
        scale with attended keys (the SSD), all layers.
    weight_bytes: bytes of every weight a decode call reads once.
    kv_bytes_per_pos: bytes one cached position holds, all layers, K+V.
    """

    matmul_params: int
    attn_flops_per_key: int = 0
    seq_flops_per_token: int = 0
    weight_bytes: int = 0
    kv_bytes_per_pos: int = 0

    def forward_flops(self, tokens: int, keys: int = 0) -> float:
        """FLOPs of a forward pass over ``tokens`` tokens that attend
        ``keys`` key positions in all (summed over the tokens)."""
        return (2.0 * self.matmul_params * tokens
                + self.seq_flops_per_token * tokens
                + self.attn_flops_per_key * keys)

    def train_flops_per_token(self, seq: int) -> float:
        """Forward plus backward (twice the forward), no recomputation,
        for causal sequences of length ``seq``."""
        causal_keys = (seq + 1) / 2.0
        return 3.0 * (2.0 * self.matmul_params + self.seq_flops_per_token
                      + self.attn_flops_per_key * causal_keys)


def causal_keys(start: int, stop: int) -> int:
    """Keys attended by the tokens at positions ``start..stop-1`` under a
    causal mask: position p attends p + 1 keys."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def dense_decoder(*, d_model: int, n_heads: int, n_kv_heads: int,
                  head_dim: int, d_ff: int, n_layers: int, vocab: int,
                  gated_mlp: bool, layernorm: bool,
                  bytes_per_param: int = 2,
                  bytes_per_cache_elem: int = 2) -> Counts:
    attn = d_model * head_dim * (2 * n_heads + 2 * n_kv_heads)
    mlp = d_model * d_ff * (3 if gated_mlp else 2)
    layer = attn + mlp
    head = vocab * d_model
    norm = d_model * (2 if layernorm else 1)
    params = n_layers * layer + head
    norms = (2 * n_layers + 1) * norm
    return Counts(
        matmul_params=params,
        attn_flops_per_key=4 * n_heads * head_dim * n_layers,
        weight_bytes=(params + norms) * bytes_per_param,
        kv_bytes_per_pos=n_layers * 2 * n_kv_heads * head_dim
        * bytes_per_cache_elem)


def mamba2(*, d_model: int, n_layers: int, vocab: int, d_state: int,
           expand: int, head_dim: int, chunk: int,
           bytes_per_param: int = 2) -> Counts:
    d_inner = expand * d_model
    heads = d_inner // head_dim
    layer = (2 * d_model * d_inner + 2 * d_model * d_state
             + d_model * heads + d_inner * d_model)
    params = n_layers * layer + vocab * d_model
    # SSD, chunked: per token, the causal half of the chunk's C.B^T and
    # (scores . dt x) products, then the chunk state B x^T and its read
    # C . h -- 2 FLOPs per multiply-add
    intra = (chunk + 1) * (d_state + heads * head_dim)
    inter = 4 * d_state * heads * head_dim
    return Counts(matmul_params=params,
                  seq_flops_per_token=n_layers * (intra + inter),
                  weight_bytes=params * bytes_per_param)

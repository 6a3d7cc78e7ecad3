"""Operations and bytes the model requires, counted from the configuration
file's shapes — what the algorithm needs, not what a program does, so a
change that removes waste does not move this yardstick.

FLOPs count multiply-adds as two.  Per token and layer: the q/k/v/o
projections, the attention scores and the weighted sum over the visible
context (causal, inside the sliding window), the router, and ``top_k``
SwiGLU experts; the head once per token whose logits are used (every
decoded token, and the last prompt position of each request).

Bytes, per program step (a decode step over its rows, or one prompt
chunk): every weight once at the precision the configuration stores it
(``quant``: experts at their bits, ``wo`` at its bits, 16 bits for q/k/v,
the embedding and the head, 32 for the router), the embedding rows read,
and per row the KV it reads (its live context, inside the window) and
writes.  Of the experts, a step reads at least ``top_k`` distinct ones
per layer, which is all that is counted: a lower bound.  Quantization
scales are left out too.
"""
from __future__ import annotations


def _m(config):
    return config["model"]


def _bits(config, name: str) -> int:
    return int(config["quant"][name]["bits"])


def visible(model, pos: int) -> int:
    """Keys a query at position ``pos`` attends to."""
    w = model.get("sliding_window")
    return min(pos + 1, w) if w else pos + 1


def flops_token(config, pos: int, head: bool) -> float:
    """FLOPs of one token at position ``pos`` (0-based)."""
    m = _m(config)
    D, F, L = m["d_model"], m["d_ff"], m["n_layers"]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    proj = 2 * D * hd * (2 * H + 2 * Hkv)
    attn = 4 * H * hd * visible(m, pos)
    moe = 2 * D * m["num_experts"] + m["top_k"] * 6 * D * F
    f = L * (proj + attn + moe)
    if head:
        f += 2 * D * m["vocab_size"]
    return float(f)


def weight_bytes(config) -> float:
    """Bytes of every weight one step reads once (experts: ``top_k`` of
    them per layer, the lower bound)."""
    m = _m(config)
    D, F, L, V = m["d_model"], m["d_ff"], m["n_layers"], m["vocab_size"]
    H, Hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    qkv = D * hd * (H + 2 * Hkv) * 16 / 8
    wo = H * hd * D * _bits(config, "wo") / 8
    router = D * m["num_experts"] * 4
    experts = m["top_k"] * 3 * D * F * _bits(config, "experts") / 8
    norms = 2 * D * 2
    head = V * D * 2
    return float(L * (qkv + wo + router + experts + norms) + head + D * 2)


def kv_bytes_per_position(config) -> float:
    m = _m(config)
    return float(m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * 2)


def decode_step(config, positions):
    """(flops, bytes) of one decode step whose rows write at ``positions``."""
    m = _m(config)
    kv = kv_bytes_per_position(config)
    flops = sum(flops_token(config, p, True) for p in positions)
    byts = weight_bytes(config) + sum(
        (visible(m, p) + 1) * kv + m["d_model"] * 2 for p in positions)
    return flops, byts


def prefill_chunk(config, lo: int, hi: int, last: bool):
    """(flops, bytes) of one prompt chunk covering positions [lo, hi);
    ``last`` marks the chunk whose final position's logits are sampled."""
    m = _m(config)
    kv = kv_bytes_per_position(config)
    flops = sum(flops_token(config, p, False) for p in range(lo, hi))
    if last:
        flops += 2 * m["d_model"] * m["vocab_size"]
    byts = (weight_bytes(config) + visible(m, hi - 1) * kv
            + (hi - lo) * (kv + m["d_model"] * 2))
    return flops, byts

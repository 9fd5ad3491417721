"""FLOPs and bytes from shapes, and the table of peaks. The yardstick: what the
ALGORITHM needs, whatever implements it, so a rewritten kernel is read on the same scale
and no share of a roofline can pass 100 %. Recomputed work (remat, flash's backward
recompute of the scores) is never counted. The program's ``telemetry/derived.py`` keeps a
peaks table of its own; this copy is the benchmark's, which a later PR cannot edit.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product per token: every projection of every
    layer and the output head. The embedding is a lookup and is left out."""
    D, F, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return c["num_hidden_layers"] * per_layer + D * c["vocab_size"]


def mean_keys(seq: int, window: int) -> float:
    """Mean number of keys a query attends to under the causal mask AND the window."""
    w = min(window or seq, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_token(c: dict, seq: int) -> float:
    """6 × matmul parameters + 12·L·(H·hd)·mean keys (QKᵀ and PV, forward + backward)."""
    attn = 12 * c["num_hidden_layers"] * c["num_attention_heads"] * c["head_dim"]
    return 6 * matmul_params(c) + attn * mean_keys(seq, c["sliding_window"])


def serve_flops_per_token(c: dict) -> float:
    """2 × matmul parameters per token processed (prompt or output); attention over the
    cache is left out, so the share errs low."""
    return 2.0 * matmul_params(c)


def flash_work(c: dict, batch: int, seq: int) -> tuple:
    """(FLOPs, bytes) one train step's attention needs over all layers: two products
    forward (QKᵀ, PV) and four backward (dV, dP, dQ, dK), each 2·B·H·hd·Σkeys; q, k, v, o
    read or written once forward and q, k, v, o, do, dq, dk, dv once backward, bfloat16."""
    H, K, hd, L = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                   c["num_hidden_layers"])
    pairs = batch * seq * mean_keys(seq, c["sliding_window"])
    flops = L * 6 * 2 * H * hd * pairs
    q_like, kv_like = batch * seq * H * hd * 2, batch * seq * K * hd * 2
    return flops, L * (6 * q_like + 6 * kv_like)


def paged_attn_work(c: dict, lens, page_size: int) -> tuple:
    """(FLOPs, bytes) ONE decode step's attention needs over all layers for lanes holding
    ``lens`` tokens: the K and V pages each lane's own length (within the window) fills,
    bfloat16, plus q and o."""
    H, K, hd, L = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                   c["num_hidden_layers"])
    W = c["sliding_window"]
    keys = [min(int(n), W) if W else int(n) for n in lens]
    paged = sum(-(-k // page_size) * page_size for k in keys)
    return L * 4 * H * hd * sum(keys), L * (paged * 2 * K * hd * 2 + len(keys) * 2 * H * hd * 2)


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of FLOPs/peak and bytes/bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])

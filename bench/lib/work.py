"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick of every roofline and `mfu` metric: the work is
what the algorithm requires, whatever implements it, so padding, masked
rows, recomputation and layout copies are not counted.
"""
from __future__ import annotations

F32 = 4
I32 = 4
BOOL = 1

#: per (query, record) of an ELO replay step: expected score (difference,
#: scale, power of ten, add, reciprocal), the update (difference, K, mask)
#: and the two rating writes
ELO_FLOPS_PER_RECORD = 10
#: per (query, model) of the selection epilogue: combine (2), budget mask,
#: running max
SELECT_FLOPS_PER_MODEL = 4


def retrieval(q: int, c: int, d: int, n: int):
    """Cosine top-n over a (c, d) f32 panel for q live queries:
    2·q·c·d FLOPs; the panel read once, the queries, and the (q, n)
    scores and row ids written. Returns (flops, bytes)."""
    flops = 2.0 * q * c * d
    nbytes = c * d * F32 + q * d * F32 + q * n * (F32 + I32)
    return flops, float(nbytes)


def replay(q: int, t: int, m: int):
    """ELO replay of t records per query over m models, with the budget
    selection: records read once (model a, model b, outcome, valid),
    ratings and choices written. Returns (flops, bytes)."""
    flops = float(ELO_FLOPS_PER_RECORD * q * t + SELECT_FLOPS_PER_MODEL * q * m)
    nbytes = (q * t * (I32 + I32 + F32 + BOOL)   # records
              + q * F32                           # budgets
              + 3 * m * F32                       # prior, global, costs
              + q * m * F32 + q * I32)            # ratings, choices out
    return flops, float(nbytes)


def route_step(q: int, c: int, d: int, n: int, records: int, m: int):
    """The whole routing step of q live queries. Returns (flops, bytes)."""
    f1, b1 = retrieval(q, c, d, n)
    f2, b2 = replay(q, n * records, m)
    return f1 + f2, b1 + b2


def merge_exchange(q: int, n: int, records: int, shards: int) -> float:
    """Bytes the cross-shard merge gathers onto each device a dispatch:
    every shard's n candidates per query, each a score, a row id and
    its records (model a, model b, outcome, valid)."""
    per_candidate = F32 + I32 + records * (I32 + I32 + F32 + BOOL)
    return float(shards * q * n * per_candidate)


# ---------------------------------------------------------------------------
# dense decoder (OLMo-style: MHA, gated MLP, tied embeddings)
# ---------------------------------------------------------------------------

def dense_layer_params(d: int, heads: int, head_dim: int, kv_heads: int,
                       d_ff: int) -> int:
    """Matmul weights of one block: q, k, v, o and the gated MLP."""
    attn = d * heads * head_dim + 2 * d * kv_heads * head_dim \
        + heads * head_dim * d
    return attn + 3 * d * d_ff


def dense_prefill_flops(cfg: dict, s: int) -> float:
    """Required FLOPs of one prompt of s tokens: every block at every
    position, causal attention over the prefix, and the head at the
    last position only (prefill returns that position's logits)."""
    lp = dense_layer_params(cfg["d_model"], cfg["n_heads"], cfg["head_dim"],
                            cfg["n_kv_heads"], cfg["d_ff"])
    layers = cfg["n_layers"]
    width = cfg["n_heads"] * cfg["head_dim"]
    attn = 2 * 2 * (s * (s + 1) / 2) * width          # QK^T and AV, causal
    return layers * (2.0 * lp * s + attn) + 2.0 * cfg["d_model"] * cfg["vocab"]


def dense_decode_flops(cfg: dict, pos: int) -> float:
    """Required FLOPs of one decoded token at position pos (it attends
    to pos + 1 keys), head included."""
    lp = dense_layer_params(cfg["d_model"], cfg["n_heads"], cfg["head_dim"],
                            cfg["n_kv_heads"], cfg["d_ff"])
    width = cfg["n_heads"] * cfg["head_dim"]
    return cfg["n_layers"] * (2.0 * lp + 4.0 * (pos + 1) * width) \
        + 2.0 * cfg["d_model"] * cfg["vocab"]


def dense_request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """One served request: the prefill gives the first token, each of
    the other new_tokens - 1 comes from one decode step."""
    f = dense_prefill_flops(cfg, prompt_len)
    for i in range(1, new_tokens):
        f += dense_decode_flops(cfg, prompt_len + i - 1)
    return f

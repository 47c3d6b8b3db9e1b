"""Operations the algorithm needs for one chip's share of a DeepSeek-V3
block model (Moonlight's expert-parallel share), from the shapes in the
served configuration's `fleet.reference`.

Counted: every matmul of the share at every position it serves, with
multiply and add as two operations. MLA is counted in the cheaper of its
two exact forms at each shape: expanded over a prompt (the latent is
expanded to keys and values once per position, attention over 192-wide
queries and 128-wide values), absorbed in a decode step (W_uk folded
into the query, W_uv into the output, attention over the 576-wide latent
cache). Not counted: norms, softmax, RoPE, the router's top-k, the
sort and scatter of assignments, padding rows, and logits at prompt
positions other than the last (prefill returns that position's).

The routed experts' work is an expectation: each token makes k
assignments over n_experts, of which k * experts_held / n_experts land
on this chip's experts when the router spreads them evenly. The sum of
a run's actual assignments is what the program counts
(`serve_moe_assignments_total`).
"""
from __future__ import annotations


def mla_params(cfg: dict) -> int:
    """Matmul weights of one MLA layer without q-LoRA."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def routed_per_token(cfg: dict) -> float:
    """Expected assignments per token to the experts held here."""
    return cfg["experts_per_tok"] * cfg["experts_held"] / cfg["n_experts"]


def ffn_token_flops(cfg: dict, moe: bool) -> float:
    """FFN FLOPs per token of one layer: the dense SwiGLU, or the
    router, the shared experts and the expected routed assignments."""
    d = cfg["d_model"]
    if not moe:
        return 2.0 * 3 * d * cfg["d_ff"]
    ff = cfg["moe_d_ff"]
    return (2.0 * d * cfg["n_experts"]
            + 2.0 * 3 * d * ff * cfg["n_shared_experts"]
            + routed_per_token(cfg) * 2.0 * 3 * d * ff)


def _layers(cfg: dict):
    kd = cfg["first_k_dense"]
    return [False] * kd + [True] * (cfg["n_layers"] - kd)


def prefill_flops(cfg: dict, s: int) -> float:
    """One prompt of s tokens: every layer at every position (MLA
    expanded, causal attention over the prefix) and the head at the
    last position."""
    h = cfg["n_heads"]
    qk = cfg["qk_nope_dim"] + cfg["qk_rope_dim"]
    attn = 2.0 * (s * (s + 1) / 2) * h * (qk + cfg["v_head_dim"])
    f = sum(s * (2.0 * mla_params(cfg) + ffn_token_flops(cfg, moe)) + attn
            for moe in _layers(cfg))
    return f + 2.0 * cfg["d_model"] * cfg["vocab"]


def decode_flops(cfg: dict, pos: int) -> float:
    """One decoded token at position pos (it attends to pos + 1
    positions), MLA absorbed, head included."""
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_dim"], cfg["qk_rope_dim"], cfg["v_head_dim"]
    proj = 2.0 * (d * h * (dn + dr) + d * (r + dr) + h * dv * d)
    absorb = 2.0 * h * r * (dn + dv)
    attn = 2.0 * h * (pos + 1) * ((r + dr) + r)
    return sum(proj + absorb + attn + ffn_token_flops(cfg, moe)
               for moe in _layers(cfg)) + 2.0 * d * cfg["vocab"]


def request_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """One served request: the prefill gives the first token, each of
    the other new_tokens - 1 comes from one decode step."""
    f = prefill_flops(cfg, prompt_len)
    for i in range(1, new_tokens):
        f += decode_flops(cfg, prompt_len + i - 1)
    return f

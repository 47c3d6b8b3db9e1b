"""Operations of the decode step of a served DeepSeek-V3 block member
(Moonlight's expert-parallel share), recognised in the device trace by
program and array shapes, which follow from the configuration.

FleetModel's decode step is the program `jit_decode_step`; the stand-in
members run programs of that name too, at reduced widths that give none
of the shapes below. TPU ops carry no scope paths, so the `moe.experts`
and `mla.decode` scopes are found by the arrays an op reads or writes
(every `type[dims]` in its HLO text), with w the window's rows, k the
experts per token and t the cache length:

- the held experts: the grouped matmuls (XLA's `ragged-dot` custom
  calls and their metadata), every op on the (w * k)-row sorted
  assignments (their sort, gather, weighting, and the scatter back onto
  the window's tokens), and the per-step cast of the held experts'
  (held, d, ff) and (held, ff, d) weights;
- absorbed MLA: every op on the latent and rope caches (t with
  kv_lora_rank or the rope width: their update and cast), on the
  scores (t with the window's rows and the heads), on the latent queries
  and outputs (rows, heads, kv_lora_rank or v_head_dim), and the
  per-step cast of W_uk and W_uv.

The layer scan's `while` op, whose operands hold all of these, is left
out: the profiler reports its body's ops one by one.
"""
from __future__ import annotations

import re

DECODE_MODULE = "jit_decode_step"
_OPCODE = re.compile(r"^%\S+ = .+? ([a-zA-Z][\w\-]*)\(")
_ARRAY = re.compile(r"(?:pred|[a-z]+\d+)\[([\d,]*)\]")


def _arrays(name):
    """The dims of every array type in an op's HLO text, 1s dropped."""
    return [tuple(int(x) for x in m.group(1).split(",") if x and x != "1")
            for m in _ARRAY.finditer(name)]


def decode_shapes(ctx):
    ref = ctx["cfg"]["fleet"]["reference"]
    return dict(w=ctx["traffic"]["window"], k=ref["experts_per_tok"],
                held=ref["experts_held"], d=ref["d_model"],
                ff=ref["moe_d_ff"], h=ref["n_heads"],
                r=ref["kv_lora_rank"], dr=ref["qk_rope_dim"],
                dn=ref["qk_nope_dim"], dv=ref["v_head_dim"],
                t=ctx["cfg"]["fleet"]["max_len"])


def _matcher(is_part, tag=None):
    def match(name, module):
        if not module.startswith(DECODE_MODULE):
            return False
        m = _OPCODE.match(name)
        if m and m.group(1) == "while":
            return False
        return (tag is not None and tag in name) or any(
            is_part(a) for a in _arrays(name) if a)
    return match


def expert_ops(ctx):
    s = decode_shapes(ctx)
    rows = s["w"] * s["k"]
    weights = {(s["held"], s["d"], s["ff"]), (s["held"], s["ff"], s["d"])}
    return _matcher(lambda a: a[0] == rows or a[-3:] in weights,
                    tag="ragged-dot")


def mla_decode_ops(ctx):
    s = decode_shapes(ctx)
    w, h, t, r = s["w"], s["h"], s["t"], s["r"]
    absorb = {(r, h, s["dn"]), (r, h, s["dv"])}

    def rows_heads(a):
        return a.count(w) >= 2 if w == h else (w in a and h in a)

    def is_part(a):
        if t in a:
            return r in a or s["dr"] in a or rows_heads(a)
        return (rows_heads(a) and (r in a or s["dv"] in a)) \
            or a[-3:] in absorb
    return _matcher(is_part)


def per_decode_step(ctx, match):
    """Device ms of the matching ops per decode step of the checked
    member (its `serve.decode_step.<member>` markers in the trace);
    None where there are no such steps or no matching ops."""
    tr = ctx["trace"]
    step = "serve.decode_step." + ctx["cfg"]["fleet"]["checked"]
    steps = sum(1 for _, _, n in tr.spans if n == step)
    secs, n = tr.op_seconds(match)
    if not steps or not n:
        return None
    return 1e3 * secs / steps

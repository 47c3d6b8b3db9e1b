"""Helpers shared by the per-layer metric readers in bench/metrics/.

On the TPU the profiler names each device operation by its HLO text
(`%name = result opcode(operands), attributes`) and each program run by
its XLA module (`jit_<function>`); Trace attaches the module to every
op. Readers recognise ops by module, opcode and shapes, which follow
from the configuration."""
from __future__ import annotations

import re

import numpy as np

_HLO = re.compile(r"^%\S+ = (.+?) ([a-zA-Z][\w\-]*)\(")
ROUTE_MODULE = "jit_route_batch_choices"       # also ..._sharded
SCATTER_MODULE = "jit__scatter_rows"
#: the sharded commit's owner-scatter (state._sharded_scatter): a jit of
#: shard_map over its inner `body`
SHARD_SCATTER_MODULE = "jit_body"


def router_shapes(ctx):
    r, db, fleet = ctx["cfg"]["router"], ctx["cfg"]["db"], ctx["cfg"]["fleet"]
    return dict(c=db["capacity"], d=r["embed_dim"], n=r["n_neighbors"],
                r=db["records_per_prompt"], m=len(fleet["names"]),
                shards=db.get("shards", 1))


def score_panel(ctx) -> str:
    """HLO type of one shard's similarity score panel: the window's
    queries padded to the kernel's 128-row blocks, by the shard's rows
    padded to its 256-row blocks."""
    s = router_shapes(ctx)
    q = ctx["counters"]["window_rows"]
    c = s["c"] // s["shards"]
    return f"f32[{-(-q // 128) * 128},{-(-c // 256) * 256}]"


def _parts(name):
    m = _HLO.match(name)
    return (m.group(1), m.group(2), name[m.end():]) if m else ("", "", "")


def similarity_kernel(ctx):
    panel = score_panel(ctx)

    def match(name, module):
        result, _, _ = _parts(name)
        return (module.startswith(ROUTE_MODULE) and "tpu_custom_call" in name
                and result.startswith(panel))
    return match


def panel_reducers(ctx):
    """Ops of the route that read the score panel: the live-row mask
    and the top-k (XLA fuses both)."""
    panel = score_panel(ctx)

    def match(name, module):
        _, _, operands = _parts(name)
        return (module.startswith(ROUTE_MODULE)
                and "tpu_custom_call" not in name and panel in operands)
    return match


def replay_kernel(ctx):
    panel = score_panel(ctx)

    def match(name, module):
        result, _, _ = _parts(name)
        return (module.startswith(ROUTE_MODULE) and "tpu_custom_call" in name
                and not result.startswith(panel))
    return match


def merge_ops(ctx):
    """Ops of the sharded route's cross-shard merge: its all-gathers, in
    whatever form XLA emits them, and the top-k over the (Q, S*N) pool
    of gathered candidates (a TopK, or the sort XLA makes of it)."""
    s = router_shapes(ctx)
    pool = f"(f32[{ctx['counters']['window_rows']},{s['shards'] * s['n']}]"

    def match(name, module):
        if not module.startswith(ROUTE_MODULE):
            return False
        result, op, rest = _parts(name)
        if op in ("all-gather", "all-gather-start", "all-gather-done"):
            return True
        return result.startswith(pool) and (
            op in ("sort", "topk")
            or (op == "custom-call" and '"TopK"' in rest))
    return match


def in_module(prefix):
    return lambda name, module: module.startswith(prefix)


def percent(x):
    return None if x is None else 100.0 * float(x)


def engine_spans(ctx, prefix):
    """(total seconds, count) of the engine's spans whose name starts
    with `prefix`, over the measured window."""
    spans = ctx["counters"]["spans"]
    durs = [dur for _, name, _, dur, _, _ in spans if name.startswith(prefix)]
    return float(np.sum(durs)) / 1e9, len(durs)

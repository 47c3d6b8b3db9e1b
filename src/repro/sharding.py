"""Sharding rules: logical param/activation/cache axes -> mesh axes.

MaxText-style name-based rules. Specs are written for the TRAILING dims of
each leaf and right-aligned, so layer-stacked parameters (leading L or
(G, per) dims from scan stacking) pick up `None` on the stack dims
automatically.

Scheme (see DESIGN.md §5):
  * weights: tensor-parallel over "model" (heads / d_ff / experts / vocab);
    kv projections replicate when kv_heads doesn't divide the model axis.
  * activations: batch over ("pod","data"); embed replicated; vocab-dim
    over "model".
  * KV caches: batch over "data", SEQUENCE over "model" (flash-decoding
    style) — memory scales with the full axis regardless of kv_heads.
  * SSM caches: head/channel dims over "model" (no sequence dim to shard).
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig

Pytree = Any


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def batch_axes(mesh: Mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    """Largest prefix of (pod, data) that divides global_batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen = []
    size = 1
    for a in axes:
        if global_batch % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    return tuple(chosen) or None


def _right_align(spec: Sequence, ndim: int) -> P:
    # canonicalize 1-tuples of mesh axes to the bare axis name:
    # P(("data",)) and P("data") place identically but compare unequal
    spec = [s[0] if isinstance(s, tuple) and len(s) == 1 else s
            for s in spec]
    assert len(spec) <= ndim, (spec, ndim)
    return P(*([None] * (ndim - len(spec)) + spec))


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def param_rules(cfg: ModelConfig, mesh: Mesh, experts_2d: bool = False):
    """Ordered (regex, trailing-spec) rules; first match wins.

    Every sharded dim is divisibility-guarded: jit-boundary shardings must
    divide exactly (no GSPMD padding on arguments), so e.g. whisper's 20
    heads or its 51866 vocab fall back to replication on a 16-wide model
    axis while its d_ff=5120 still tensor-shards.
    """
    msz = mesh.shape["model"]

    def ax(n):  # "model" iff divisible, else replicate
        return "model" if (n and n % msz == 0) else None

    m_h = ax(cfg.n_heads)
    m_kv = ax(cfg.n_kv_heads)
    m_ff = ax(cfg.d_ff)
    m_eff = ax(cfg.expert_ff * max(cfg.n_shared_experts, 1))
    m_v = ax(cfg.vocab)
    m_e = ax(cfg.n_experts)
    if experts_2d and cfg.n_experts and \
            cfg.n_experts % (msz * mesh.shape.get("data", 1)) == 0:
        # serving layout: one expert shard per chip — the storage win that
        # fits 256-expert MoEs on 16 GiB chips (EXPERIMENTS §Perf-C)
        m_e = ("model", "data")
    m_di = ax(cfg.d_inner) if cfg.ssm_state else None
    m_sh = ax(cfg.ssm_nheads) if cfg.ssm_state else None

    rules = [
        # MoE (expert-stacked 3D) — must precede generic ffn rules.
        (r"ffn/shared/w_(gate|up)$", [None, m_eff]),
        (r"ffn/shared/w_down$", [m_eff, None]),
        (r"ffn/router$", [None, None]),
        (r"moe_blocks/ffn/w_(gate|up)$", [m_e, None, None]),
        (r"moe_blocks/ffn/w_down$", [m_e, None, None]),
        # attention (GQA) + cross
        (r"(attn|cross)/wq$", [None, m_h, None]),
        (r"(attn|cross)/w[kv]$", [None, m_kv, None]),
        (r"(attn|cross)/wo$", [m_h, None, None]),
        # MLA
        (r"attn/w_u?q$", [None, m_h, None]),
        (r"attn/w_(uk|uv)$", [None, m_h, None]),
        (r"attn/w_(dq|dkv|kr)$", [None, None]),
        # dense ffn
        (r"ffn/w_(gate|up)$", [None, m_ff]),
        (r"ffn/w_down$", [m_ff, None]),
        # mamba2: head-structured streams sharded, ngroups streams replicated
        (r"mamba/in_(z|x)$", [None, m_di]),
        (r"mamba/in_dt$", [None, m_sh]),
        (r"mamba/in_[BC]$", [None, None]),
        (r"mamba/conv_x_w$", [None, m_di]),
        (r"mamba/conv_x_b$", [m_di]),
        (r"mamba/(A_log|D|dt_bias)$", [m_sh]),
        (r"mamba/norm_scale$", [m_di]),
        (r"mamba/out_proj$", [m_di, None]),
        # embeddings / head
        (r"^embed$", [m_v, None]),
        (r"^lm_head$", [None, m_v]),
        (r"^mtp/proj$", [None, None]),
    ]
    return [(re.compile(rx), spec) for rx, spec in rules]


def param_specs(cfg: ModelConfig, mesh: Mesh, params_shape: Pytree,
                fsdp: bool = False, experts_2d: bool = False) -> Pytree:
    """Per-leaf PartitionSpec from the rules. With ``fsdp=True`` every
    matrix additionally shards one replicated weight dim over "data"
    (ZeRO-3 style: storage drops ~data-axis-fold; GSPMD inserts per-layer
    just-in-time all-gathers, which show up honestly in the collective
    term — see EXPERIMENTS §Perf)."""
    rules = param_rules(cfg, mesh, experts_2d=experts_2d)
    dsz = mesh.shape.get("data", 1)

    def spec_of(path, leaf):
        s = _path_str(path)
        spec = None
        for rx, sp in rules:
            if rx.search(s):
                spec = _right_align(sp, leaf.ndim)
                break
        if spec is None:
            spec = P(*([None] * leaf.ndim))
        if fsdp and leaf.ndim >= 2 and dsz > 1:
            spec_l = list(spec)
            n_stack = leaf.ndim - len([_ for _ in spec_l])  # always 0 here
            # choose the largest None dim (skipping the layer-stack dim 0
            # of stacked leaves, which scan slices) divisible by data
            best, best_size = None, 0
            start = 1 if leaf.ndim >= 3 else 0   # dim0 of stacked = stack
            for i in range(start, leaf.ndim):
                if spec_l[i] is None and leaf.shape[i] % dsz == 0 \
                        and leaf.shape[i] > best_size:
                    best, best_size = i, leaf.shape[i]
            if best is not None and best_size >= dsz:
                spec_l[best] = "data"
                spec = P(*spec_l)
        return spec

    return jax.tree_util.tree_map_with_path(spec_of, params_shape)


def opt_state_specs(param_spec_tree: Pytree) -> Dict[str, Any]:
    return {"m": param_spec_tree, "v": param_spec_tree, "step": P()}


# ---------------------------------------------------------------------------
# cache / batch specs
# ---------------------------------------------------------------------------

def make_cache_specs(cfg: ModelConfig, mesh: Mesh, cache_shape: Pytree,
                     global_batch: int, seq_axis: str = "model") -> Pytree:
    b = batch_axes(mesh, global_batch)
    msz = mesh.shape["model"]
    sa = seq_axis if mesh.shape.get(seq_axis, 1) > 1 else None
    m_di = "model" if (cfg.ssm_state and cfg.d_inner % msz == 0) else None
    m_sh = "model" if (cfg.ssm_state and cfg.ssm_nheads % msz == 0) else None

    def spec_of(path, leaf):
        s = _path_str(path)
        nd = leaf.ndim

        def seq_ax(seq_dim_size):
            return sa if (sa and seq_dim_size % msz == 0) else None

        if re.search(r"cross", s):
            # (L,B,F,Hk,hd): F=1500 doesn't divide the axis; shard batch only
            return _right_align([b, None, None, None], nd)
        if re.search(r"(^|/)[kv]$", s):
            return _right_align([b, seq_ax(leaf.shape[-3]), None, None], nd)
        if re.search(r"(c_kv|k_rope)$", s):
            return _right_align([b, seq_ax(leaf.shape[-2]), None], nd)
        if re.search(r"moe_stats$", s):  # the expert layers' counters
            return P(None)
        if re.search(r"(^|/)pos$", s):   # ring-buffer position tags (B, W)
            return _right_align([b, None], nd)
        if re.search(r"conv_x$", s):
            return _right_align([b, None, m_di], nd)
        if re.search(r"conv_[BC]$", s):
            return _right_align([b, None, None], nd)
        if re.search(r"(^|/)ssm$", s):
            return _right_align([b, m_sh, None, None], nd)
        return _right_align([b], nd) if nd else P()

    return jax.tree_util.tree_map_with_path(spec_of, cache_shape)


def batch_specs(cfg: ModelConfig, mesh: Mesh, batch_shape: Pytree,
                global_batch: int) -> Pytree:
    b = batch_axes(mesh, global_batch)

    def spec_of(path, leaf):
        return _right_align([b] + [None] * (leaf.ndim - 1), leaf.ndim)

    return jax.tree_util.tree_map_with_path(spec_of, batch_shape)


# ---------------------------------------------------------------------------
# activation constrainer
# ---------------------------------------------------------------------------

def make_constrainer(mesh: Mesh, global_batch: int, seq_axis=None,
                     vocab: int = 0, n_experts: int = 0,
                     experts_2d: bool = False):
    """Returns constrain(x, logical_axes) placing with_sharding_constraint."""
    b = batch_axes(mesh, global_batch)
    msz = mesh.shape.get("model", 1)
    dsz = mesh.shape.get("data", 1)
    if experts_2d and n_experts and n_experts % (msz * dsz) == 0:
        e_ax = ("model", "data")
    elif n_experts and msz > 1 and n_experts % msz == 0:
        e_ax = "model"
    else:
        e_ax = None
    table = {
        "batch": b,
        "seq": seq_axis,
        "embed": None,
        "vocab": "model" if (msz > 1 and vocab % msz == 0) else None,
        "heads": "model" if msz > 1 else None,
        "experts": e_ax,
    }

    def constrain(x, axes):
        spec = [table.get(a) for a in axes]
        dims = x.shape[-len(axes):]
        # guard divisibility on every constrained dim and resolve duplicate
        # mesh-axis claims: "seq" has the LOWEST priority (a seq-sharded
        # residual stream yields to heads/vocab sharding inside blocks)
        used = set()
        order = sorted(range(len(axes)), key=lambda i: axes[i] == "seq")
        for i in order:
            ax = spec[i]
            if ax is None:
                continue
            names = (ax,) if isinstance(ax, str) else tuple(ax)
            sz = 1
            for a in names:
                sz *= mesh.shape[a]
            if dims[i] % sz != 0 or any(a in used for a in names):
                spec[i] = None
            else:
                used.update(names)
        full = [None] * (x.ndim - len(axes)) + spec
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*full)))

    return constrain


def tree_shardings(mesh: Mesh, spec_tree: Pytree) -> Pytree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# router-DB capacity-axis sharding (DESIGN.md §12)
# ---------------------------------------------------------------------------

#: mesh axis the RouterState DB panels partition over. Deliberately NOT
#: "data"/"model": the routing DB shards on its own 1-D mesh so the
#: router can scale independently of the fleet's serving meshes.
DB_AXIS = "db"


def db_state_specs() -> Dict[str, P]:
    """PartitionSpec per RouterState field for the capacity partition:
    every (C, ...) DB panel splits dim 0 over DB_AXIS into CONTIGUOUS
    row ranges (shard s owns global rows [s*C/S, (s+1)*C/S)); the (M,)
    ratings and the scalar live-row count replicate. Contiguity is
    load-bearing: the cross-shard top-k merge orders its candidate pool
    (shard, local rank), which is ascending-global-row order among
    equal scores only under a contiguous split — that is what keeps
    tie-breaking bit-identical to the single-device oracle."""
    return dict(global_ratings=P(), emb=P(DB_AXIS), model_a=P(DB_AXIS),
                model_b=P(DB_AXIS), outcome=P(DB_AXIS), valid=P(DB_AXIS),
                size=P())


def db_shard_count(mesh: Mesh) -> int:
    return mesh.shape[DB_AXIS]


def check_db_mesh(mesh: Mesh, capacity: int) -> int:
    """Validate a DB mesh against a state capacity; returns the shard
    count. Capacity must divide exactly — jit-boundary shardings take
    no GSPMD padding, and the power-of-two capacity/bucket policy
    (VectorDB._grow doubles) preserves divisibility for free."""
    if DB_AXIS not in mesh.axis_names:
        raise ValueError(
            f"DB mesh must carry a {DB_AXIS!r} axis, got {mesh.axis_names}")
    shards = db_shard_count(mesh)
    if capacity % shards != 0:
        raise ValueError(
            f"capacity {capacity} does not divide over {shards} DB shards")
    return shards

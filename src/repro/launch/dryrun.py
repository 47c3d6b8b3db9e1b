import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

For each combination this lowers the right step (train_step / prefill /
decode_step) with full-size ShapeDtypeStruct inputs on the production mesh,
compiles it, and records:

  * memory_analysis()  — per-device argument/output/temp bytes (fits HBM?)
  * cost_analysis()    — per-device HLO FLOPs and bytes accessed
  * collective bytes   — parsed from the post-SPMD compiled HLO, summed per
    collective kind (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute)

Results land in results/dryrun/<arch>__<shape>__<mesh>.json; the roofline
report (benchmarks/roofline.py) reads them. Failures write an error JSON —
they are bugs in the sharding config and must be fixed, not skipped.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # full sweep, resumable
"""
import argparse
import json
import re
import time
import traceback
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as SH
from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, input_specs, supports
from repro.launch.mesh import make_production_mesh
from repro.analysis.roofline_model import analytic_costs
from repro.models import transformer as T
from repro.training.optim import AdamW

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_DT_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
             "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
             "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


_WHILE_RE = re.compile(
    r"while\(.*?\)\s*,\s*condition=%?([\w\.\-]+)\s*,\s*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(
    r"(?:to_apply|body|condition|branch_computations|called_computations)="
    r"[%{]?([\w\.\- ,%]+)}?")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _split_computations(hlo_text: str):
    """Map computation-name -> text block (top-level HLO computations)."""
    comps = {}
    cur, lines = None, []
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and "{" in line and "->" in line:
            if cur:
                comps[cur] = "\n".join(lines)
            head = line.split("(")[0].strip()
            cur = head.replace("ENTRY", "").strip().lstrip("%")
            lines = [line]
        elif cur is not None:
            lines.append(line)
    if cur:
        comps[cur] = "\n".join(lines)
    return comps


def _line_bytes(shapes_str: str) -> int:
    nbytes = 0
    for dt, dims in _SHAPE_RE.findall(shapes_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _DT_BYTES[dt]
    return nbytes


def collective_bytes(hlo_text: str):
    """Sum result-shape bytes of every collective op, keyed by kind.

    Scan-aware: jax.lax.scan lowers to `while`, whose body appears ONCE in
    the HLO regardless of trip count. Each computation's collective bytes
    are multiplied by the product of the trip counts of its enclosing
    while loops (trip count = the s32[] constant in the loop condition),
    so per-layer collectives (e.g. the MoE psum) count L times.
    """
    comps = _split_computations(hlo_text)

    trip = {}          # body computation -> trip count
    callees = {c: set() for c in comps}
    names = sorted(comps, key=len, reverse=True)
    ref_re = re.compile(r"%([\w\.\-]+)")
    for cname, ctext in comps.items():
        for m in _WHILE_RE.finditer(ctext):
            cond, body = m.group(1), m.group(2)
            consts = [int(x) for x in _CONST_RE.findall(comps.get(cond, ""))]
            trip[body] = max(consts) if consts else 1
        # call edge = any %name reference to another computation
        for ref in set(ref_re.findall(ctext)):
            if ref in comps and ref != cname:
                callees[cname].add(ref)

    # propagate multipliers from the entry through the call graph
    entry = None
    for cname, ctext in comps.items():
        if ctext.lstrip().startswith("ENTRY"):
            entry = cname
    mult = {c: 0 for c in comps}
    stack = [(entry or next(iter(comps), None), 1)]
    seen = set()
    while stack:
        cname, m_in = stack.pop()
        if cname is None or cname not in comps:
            continue
        m_here = m_in * trip.get(cname, 1)
        key = (cname, m_here)
        if key in seen:
            continue
        seen.add(key)
        mult[cname] = max(mult[cname], m_here)
        for cal in callees.get(cname, ()):
            stack.append((cal, m_here))

    out = {}
    f32_act_bytes = 0  # f32 collectives: XLA:CPU upcasts bf16 activations
    for cname, ctext in comps.items():
        k = mult.get(cname, 1) or 1
        for line in ctext.splitlines():
            m = _COLL_RE.search(line)
            if not m:
                continue
            shapes_str, kind = m.group(1), m.group(2)
            nbytes = _line_bytes(shapes_str)
            out[kind] = out.get(kind, 0) + nbytes * k
            out[kind + "_count"] = out.get(kind + "_count", 0) + k
            for dt, dims in _SHAPE_RE.findall(shapes_str):
                if dt == "f32":
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    f32_act_bytes += n * 4 * k
    out["total_bytes"] = sum(v for kk, v in out.items()
                             if not kk.endswith("_count"))
    # TPU estimate: bf16 activations halve every f32 collective payload
    out["total_bytes_tpu_bf16_est"] = out["total_bytes"] - f32_act_bytes // 2
    return out


def model_flops(cfg, kind: str, global_batch: int, seq_len: int) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (inference fwd), N = active
    non-embedding params, D = tokens processed this step."""
    n = cfg.active_params() - cfg.vocab * cfg.d_model
    if kind == "train":
        return 6.0 * n * global_batch * seq_len
    if kind == "prefill":
        return 2.0 * n * global_batch * seq_len
    return 2.0 * n * global_batch * 1  # decode: one token per sequence


def _mem_dict(mem):
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    d = {}
    for k in keys:
        try:
            d[k] = int(getattr(mem, k))
        except Exception:
            pass
    return d


def build_step(cfg, mesh, spec, *, fsdp=False, bf16_params=False,
               opt_state_bf16=False, experts_2d=False, seq_shard=False,
               window_cache=False):
    """Returns (jitted_fn, example_args) for the combo.

    fsdp: additionally shard weight dims over "data" (ZeRO-3 storage).
    bf16_params: serve/train with bf16 parameters (inference-standard).
    opt_state_bf16: AdamW moments in bf16 (halves optimizer memory)."""
    import dataclasses as _dc
    if bf16_params:
        cfg = _dc.replace(cfg, param_dtype="bfloat16")
    if window_cache:
        cfg = _dc.replace(cfg, window_cache=True)
    kind = spec["kind"]
    gb = spec["global_batch"]
    seq_ax = "model" if (seq_shard and spec["seq_len"] %
                         mesh.shape["model"] == 0) else None
    constrain = SH.make_constrainer(mesh, gb, seq_axis=seq_ax,
                                    vocab=cfg.vocab,
                                    n_experts=cfg.n_experts,
                                    experts_2d=experts_2d)
    params_shape = jax.eval_shape(partial(T.init_params, cfg),
                                  jax.random.key(0))
    pspec = SH.param_specs(cfg, mesh, params_shape, fsdp=fsdp,
                           experts_2d=experts_2d)
    pshard = SH.tree_shardings(mesh, pspec)

    if kind == "train":
        import jax.numpy as _jnp
        opt = AdamW(state_dtype=_jnp.bfloat16 if opt_state_bf16
                    else _jnp.float32)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        ospec = SH.opt_state_specs(pspec)
        oshard = SH.tree_shardings(mesh, ospec)
        bshard = SH.tree_shardings(
            mesh, SH.batch_specs(cfg, mesh, spec["batch"], gb))

        def train_step(params, opt_state, batch):
            def lfn(p):
                return T.loss_fn(cfg, p, batch, mesh=mesh, constrain=constrain)
            (loss, metrics), grads = jax.value_and_grad(
                lfn, has_aux=True)(params)
            params, opt_state = opt.update(grads, opt_state, params)
            return params, opt_state, loss

        fn = jax.jit(train_step,
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard,
                                    NamedSharding(mesh, P())),
                     donate_argnums=(0, 1))
        return fn, (params_shape, opt_shape, spec["batch"])

    if kind == "prefill":
        bshard = SH.tree_shardings(
            mesh, SH.batch_specs(cfg, mesh, spec["batch"], gb))
        max_len = spec["seq_len"]
        cache_shape = jax.eval_shape(
            lambda: T.init_cache(cfg, gb, max_len, jnp.bfloat16))
        cshard = SH.tree_shardings(
            mesh, SH.make_cache_specs(cfg, mesh, cache_shape, gb))
        b_ax = SH.batch_axes(mesh, gb)
        v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
        lshard = NamedSharding(mesh, P(b_ax, v_ax))

        def prefill_step(params, batch):
            return T.prefill(cfg, params, batch, max_len, mesh=mesh,
                             constrain=constrain)

        fn = jax.jit(prefill_step, in_shardings=(pshard, bshard),
                     out_shardings=((lshard, cshard)))
        return fn, (params_shape, spec["batch"])

    # decode
    cshard = SH.tree_shardings(
        mesh, SH.make_cache_specs(cfg, mesh, spec["cache"], gb))
    b_ax = SH.batch_axes(mesh, gb)
    v_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
    tshard = NamedSharding(mesh, P(b_ax, None))
    ishard = NamedSharding(mesh, P())
    lshard = NamedSharding(mesh, P(b_ax, v_ax))

    def dstep(params, cache, tokens, index):
        return T.decode_step(cfg, params, cache, tokens, index, mesh=mesh,
                             constrain=constrain)

    fn = jax.jit(dstep, in_shardings=(pshard, cshard, tshard, ishard),
                 out_shardings=(lshard, cshard), donate_argnums=(1,))
    return fn, (params_shape, spec["cache"], spec["tokens"], spec["index"])


def run_combo(arch: str, shape: str, mesh_name: str, out_dir: Path,
              force: bool = False, keep_hlo: bool = False,
              fsdp: bool = False, bf16_params: bool = False,
              opt_state_bf16: bool = False, experts_2d: bool = False,
              seq_shard: bool = False, window_cache: bool = False,
              tag: str = ""):
    out = out_dir / f"{arch}__{shape}__{mesh_name}{tag}.json"
    if out.exists() and not force:
        print(f"[skip] {out.name} exists")
        return json.loads(out.read_text())
    cfg = get_config(arch)
    ok, why = supports(cfg, shape)
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "timestamp": time.time()}
    if not ok:
        rec.update(status="skipped", reason=why)
        out.write_text(json.dumps(rec, indent=1))
        print(f"[skip-by-design] {arch} x {shape}: {why}")
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        n_dev = mesh.devices.size
        if window_cache:
            import dataclasses as _dc2
            cfg = _dc2.replace(cfg, window_cache=True)
        spec = input_specs(cfg, shape)
        fn, args = build_step(cfg, mesh, spec, fsdp=fsdp,
                              bf16_params=bf16_params,
                              opt_state_bf16=opt_state_bf16,
                              experts_2d=experts_2d, seq_shard=seq_shard)
        # window_cache already applied to cfg above
        with mesh:
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            # older jax returns a per-device LIST of cost dicts
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update(
            status="ok",
            n_devices=int(n_dev),
            kind=spec["kind"],
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory=_mem_dict(mem),
            flops_per_device=float(cost.get("flops", -1.0)),
            bytes_per_device=float(cost.get("bytes accessed", -1.0)),
            transcendentals=float(cost.get("transcendentals", -1.0)),
            collectives=coll,
            model_flops_global=model_flops(cfg, spec["kind"],
                                           spec["global_batch"],
                                           spec["seq_len"]),
            analytic=analytic_costs(cfg, spec["kind"],
                                    spec["global_batch"], spec["seq_len"]),
            hlo_chars=len(hlo),
        )
        if keep_hlo:
            (out_dir / f"{arch}__{shape}__{mesh_name}.hlo.txt").write_text(hlo)
        print(f"[ok] {arch} x {shape} x {mesh_name}: "
              f"compile {t_compile:.1f}s, "
              f"temp/device {rec['memory'].get('temp_size_in_bytes', 0)/2**30:.2f} GiB, "
              f"coll {coll['total_bytes']/2**20:.1f} MiB")
    except Exception as e:  # a failure here is a sharding bug to fix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL] {arch} x {shape} x {mesh_name}: {e}")
    rec["elapsed_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--bf16-params", action="store_true")
    ap.add_argument("--opt-state-bf16", action="store_true")
    ap.add_argument("--experts-2d", action="store_true")
    ap.add_argument("--window-cache", action="store_true",
                    help="ring-buffer local-layer KV caches (gemma3-style)")
    ap.add_argument("--seq-shard", action="store_true",
                    help="Megatron-SP style: residual stream sequence-"
                         "sharded over the model axis between blocks")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        combos = [(a, s, m) for a in ARCH_IDS for s in SHAPES
                  for m in ("single", "multi")]
        n_err = 0
        for a, s, m in combos:
            rec = run_combo(a, s, m, out_dir, force=args.force,
                            keep_hlo=args.keep_hlo)
            n_err += rec.get("status") == "error"
        print(f"sweep done; {n_err} errors")
        raise SystemExit(1 if n_err else 0)

    assert args.arch and args.shape, "--arch/--shape or --all"
    rec = run_combo(args.arch, args.shape, args.mesh, out_dir,
                    force=args.force, keep_hlo=args.keep_hlo,
                    fsdp=args.fsdp, bf16_params=args.bf16_params,
                    opt_state_bf16=args.opt_state_bf16,
                    experts_2d=args.experts_2d, seq_shard=args.seq_shard,
                    window_cache=args.window_cache, tag=args.tag)
    raise SystemExit(0 if rec.get("status") in ("ok", "skipped") else 1)


if __name__ == "__main__":
    main()

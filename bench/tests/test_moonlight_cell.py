"""The Moonlight cell's parts at a tiny size on the CPU: a rehearsal of
bench/run.py over a Moonlight-shaped expert-parallel share (2 of 8
experts held, MLA without q-LoRA) from bench/tests/data_moe, the fp8
control and a planted expert fault failing its checks, the work
functions of bench/lib/work_moe.py, and the three new readers."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.lib import work_moe as W
from bench.lib.tracing import Trace

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data_moe"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
CELL = "tiny.moonlight"


def _run(script, *args):
    p = subprocess.run([sys.executable, script, *args], cwd=ROOT, env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _bench(*extra, seed=11, script="bench/run.py", fault=()):
    return _run(script, *fault, "--workload", CELL, "--seed", str(seed),
                "--seconds", "3", "--rehearse", "--data-dir", str(DATA),
                *extra)


def _load(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_traced_rehearsal_is_correct():
    r = _bench("--trace", "1")
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert "metrics" not in r            # CPU readings are never metrics
    got = r["rehearsal_readings"]
    for name in ("serve_mfu.moe", "decode_step_ms.serve",
                 "generate_share.serve", "e2e_p50_ms.serve"):
        assert name in got, got
    # the CPU's ops are not named by their HLO text: the device-trace
    # readers find nothing there and report nothing
    assert "expert_ffn_ms.moe" not in got and "mla_decode_ms.moe" not in got


def test_control_fails_where_the_program_passes():
    rec = _run("bench/calibrate.py", "--workload", CELL, "--seeds", "21",
               "--seconds", "3", "--rehearse", "--data-dir", str(DATA),
               "--out", os.devnull)
    lim = rec["limits"]
    assert all(rec["program"][k] <= v for k, v in lim.items()), rec
    assert rec["control"]["logit_gap"] > lim["logit_gap"], rec


def test_held_expert_left_out_is_not_correct():
    r = _bench(seed=5, script="bench/tests/_fault_moe.py",
               fault=("expert_left_out",))
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["logit_gap"]["value"] > \
        r["checks"]["logit_gap"]["limit"]


# -- work ---------------------------------------------------------------------

REF = json.loads((ROOT / "bench" / "configs" /
                  "eagle-moonlight-ep8-served.json").read_text())["fleet"][
    "reference"]


def test_work_counts_the_published_share():
    # MLA 13.76M and 8 held experts' 69.2M a layer, at published widths
    assert W.mla_params(REF) == 13_762_560
    assert W.routed_per_token(REF) == 0.75           # 6 of 64, 8 held
    d, ff = 2048, 1408
    moe = 2 * d * 64 + 2 * 3 * d * ff * 2 + 0.75 * 2 * 3 * d * ff
    assert W.ffn_token_flops(REF, True) == moe
    assert W.ffn_token_flops(REF, False) == 2 * 3 * d * 11264


def test_work_of_a_request_adds_its_steps():
    tiny = dict(REF, n_layers=2, first_k_dense=1, d_model=8, n_heads=2,
                kv_lora_rank=4, qk_nope_dim=2, qk_rope_dim=2, v_head_dim=2,
                d_ff=16, moe_d_ff=4, n_experts=8, experts_per_tok=2,
                experts_held=2, n_shared_experts=1, vocab=32)
    mla = 8 * 2 * 4 + 8 * 6 + 4 * 2 * 4 + 2 * 2 * 8       # 200 weights
    assert W.mla_params(tiny) == mla
    dense, moe = 2 * 3 * 8 * 16, 2 * 8 * 8 + 2 * 3 * 8 * 4 + 0.5 * 2 * 3 * 8 * 4
    # prompt of 3: every position, causal attention over 6 (q, k) pairs
    attn = 2 * 6 * 2 * (4 + 2)
    head = 2 * 8 * 32
    want = 3 * (2 * mla + dense) + attn + 3 * (2 * mla + moe) + attn + head
    assert W.prefill_flops(tiny, 3) == want
    # a decode step at position 3 attends to 4 latent positions
    proj = 2 * (8 * 2 * 4 + 8 * 6 + 2 * 2 * 8)
    absorb = 2 * 2 * 4 * 4
    lat = 2 * 2 * 4 * (6 + 4)
    step = (proj + absorb + lat) * 2 + dense + moe + head
    assert W.decode_flops(tiny, 3) == step
    assert W.request_flops(tiny, 3, 3) == want + step + W.decode_flops(tiny, 4)


# -- readers --------------------------------------------------------------------

CFG = {"fleet": {"reference": REF, "max_len": 1152,
                 "checked": "moonlight-16b-a3b-ep8"}}
TRAFFIC = {"window": 16, "prompt_len": 1024}
DECODE, PREFILL = "jit_decode_step", "jit_prefill"
#: ops of the decode step as the v5e compiler names them (HLO text)
OPS = [
    ("expert", DECODE, "%ragged-dot-none = f32[96,1408]{1,0} custom-call("
     "bf16[96,2048]{1,0} %fusion.418, bf16[8,2048,1408]{2,1,0} %fusion.419)"),
    ("expert", DECODE, "%ragged-dot-metadata = (s32[9]{0}, s32[10]{0}) "
     "custom-call(s32[8]{0} %fusion.417)"),
    ("expert", DECODE, "%sort.29 = (s32[96]{0}, s32[96]{0}) sort(s32[96]{0} "
     "%fusion.415, s32[96]{0} %iota.40)"),
    ("expert", DECODE, "%fusion.420 = bf16[8,2048,1408]{2,1,0} fusion("
     "f32[8,8,2048,1408]{3,2,1,0} %p)"),
    ("expert", DECODE, "%fusion.426 = f32[16,2048]{1,0} fusion(f32[16,2048]"
     "{1,0} %x, f32[96,2048]{1,0} %ragged-dot-none.2)"),
    ("mla", DECODE, "%dynamic_update_slice.40 = f32[16,1152,512]{2,1,0} "
     "dynamic-update-slice(f32[16,1152,512]{2,1,0} %c, f32[16,1,512] %n)"),
    ("mla", DECODE, "%fusion.408 = (f32[16,16]{1,0}, f32[16,16,1,1152]"
     "{3,2,1,0}) fusion(f32[16,1152,16]{2,1,0} %fusion.406)"),
    ("mla", DECODE, "%fusion.410 = bf16[16,16,512]{2,1,0} fusion("
     "f32[16,1152,512]{2,1,0} %c)"),
    ("mla", DECODE, "%fusion.411 = bf16[16,128,16]{2,1,0} fusion("
     "bf16[512,16,128]{2,1,0} %w, bf16[16,16,512]{2,1,0} %fusion.410)"),
    ("mla", DECODE, "%dynamic_update_slice.41 = f32[16,1152,64]{2,1,0} "
     "dynamic-update-slice(f32[16,1152,64]{2,1,0} %r, f32[16,1,64] %n)"),
    # neither: the layer scan, the shared experts, the router, the
    # prefill's ops, and a stand-in member's cache
    (None, DECODE, "%while.2 = (s32[], bf16[16,1,2048], f32[8,16,1152,512]"
     ", f32[8,8,2048,1408]) while((s32[]) %t)"),
    (None, DECODE, "%fusion.428 = bf16[16,2816]{1,0} fusion(bf16[16,2048]"
     "{1,0} %h, f32[2048,2816]{1,0} %w)"),
    (None, DECODE, "%sort.28 = (f32[16,64]{1,0}, s32[16,64]{1,0}) sort("
     "f32[16,64]{1,0} %s)"),
    (None, PREFILL, "%ragged-dot-none = f32[98304,1408]{1,0} custom-call("
     "bf16[98304,2048]{1,0} %x, bf16[8,2048,1408]{2,1,0} %w)"),
    (None, PREFILL, "%fusion.9 = f32[16,1152,512]{2,1,0} fusion("
     "f32[16,1024,512]{2,1,0} %c)"),
    (None, DECODE, "%dynamic_update_slice.3 = f32[16,1152,4,32]{3,2,1,0} "
     "dynamic-update-slice(f32[16,1152,4,32]{3,2,1,0} %k, f32[16,1,4,32] %n)"),
]


def _trace(steps=4):
    """Each op runs once a step for 1 ms, op i starting at i ms of its
    step, the member's decode-step markers around each step."""
    ops, spans = [], []
    for s in range(steps):
        t0 = s * 100_000_000
        spans.append((t0, t0 + len(OPS) * 1_000_000,
                      "serve.decode_step.moonlight-16b-a3b-ep8"))
        for i, (_, mod, name) in enumerate(OPS):
            ops.append((t0 + i * 1_000_000, t0 + (i + 1) * 1_000_000, name,
                        mod))
    return Trace({0: ops}, spans, steps * 0.1)


@pytest.mark.parametrize("reader,part", [("expert_ffn_ms.moe", "expert"),
                                         ("mla_decode_ms.moe", "mla")])
def test_device_readers_count_their_ops_per_decode_step(reader, part):
    ctx = dict(trace=_trace(), cfg=CFG, traffic=TRAFFIC)
    want = sum(1 for p, _, _ in OPS if p == part)     # ms a step
    assert _load(reader).read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("reader", ["expert_ffn_ms.moe", "mla_decode_ms.moe"])
def test_device_readers_without_steps_or_ops_read_nothing(reader):
    no_steps = Trace(_trace().ops, [], 0.4)
    assert _load(reader).read(dict(trace=no_steps, cfg=CFG,
                                   traffic=TRAFFIC)) is None
    only_prefill = Trace({0: [o for o in _trace().ops[0] if o[3] == PREFILL]},
                         _trace().spans, 0.4)
    assert _load(reader).read(dict(trace=only_prefill, cfg=CFG,
                                   traffic=TRAFFIC)) is None


def test_serve_mfu_moe_counts_route_and_requests():
    from types import SimpleNamespace
    from bench.lib import work
    cfg = dict(CFG, router={"embed_dim": 1536, "n_neighbors": 20},
               db={"capacity": 1 << 18, "records_per_prompt": 8},
               fleet=dict(CFG["fleet"], names=["a", "b", "c", "d"]))
    served = {0: SimpleNamespace(model="moonlight-16b-a3b-ep8"),
              1: SimpleNamespace(model="qwen3-8b")}
    ctx = dict(cfg=cfg, traffic=TRAFFIC, peaks={"flops_per_s": 197e12},
               counters={"serve_s": 2.0, "flush_sizes": [2],
                         "served": served, "max_new": [5, 9]})
    flops = work.route_step(2, 1 << 18, 1536, 20, 8, 4)[0] \
        + W.request_flops(REF, 1024, 5)
    got = _load("serve_mfu.moe").read(ctx)
    assert got == pytest.approx(100 * flops / (2.0 * 197e12))
    assert 0 < got < 100

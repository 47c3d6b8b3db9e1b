"""The per-layer readers of route.paper4m.4chip on a made-up trace of 4
devices whose operations carry the HLO text the TPU compiler gives the
sharded route and the owner-scatter commit at the cell's shapes (a
described-v5e compile): two dispatches and two commits."""
import importlib.util
import json
from pathlib import Path

import pytest

from bench.lib import readers as R
from bench.lib import work
from bench.lib.tracing import Trace

ROOT = Path(__file__).resolve().parents[2]
CFG = json.loads(
    (ROOT / "bench/configs/eagle-paper-4m-sharded.json").read_text())
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROUTE = "jit_route_batch_choices_sharded"
MS = 1_000_000      # ns

SIM = ('%eagle_similarity.1 = f32[256,1048576]{1,0:T(8,128)} custom-call('
       'f32[256,1536]{1,0} %param.22, f32[1048576,1536]{1,0} %param.16), '
       'custom_call_target="tpu_custom_call"')
REDUCE = ('%select_reduce_fusion = f32[32,8192,8]{2,1,0:T(8,128)} fusion('
          's32[] %copy.142, s32[] %mul.19, s32[8192] %iota_multiply_fusion, '
          'f32[256,1048576]{1,0} %eagle_similarity.1), kind=kLoop')
GATHER_S = ('%all-gather.28 = f32[1024,20]{0,1:T(8,128)S(1)} all-gather('
            'f32[256,20]{0,1} %copy-done.7), channel_id=2, dimensions={0}')
GATHER_START = ('%all-gather-start.3 = (s32[256,20,8], s32[1024,20,8]) '
                'all-gather-start(s32[256,20,8] %gte.38), dimensions={0}')
GATHER_DONE = ('%all-gather-done.3 = s32[1024,20,8]{1,0,2} all-gather-done('
               '(s32[256,20,8], s32[1024,20,8]) %all-gather-start.3)')
MERGE = ('%top_k.34 = (f32[256,80]{0,1:T(8,128)S(1)}, s32[256,80]{0,1}) '
         'sort(f32[256,80]{0,1} %reshape.78, s32[256,80]{0,1} %top_k.33), '
         'dimensions={1}, is_stable=true')
LOCAL_TOPK = ('%top_k.19 = (f32[256,20]{0,1:T(8,128)}, s32[256,20]{0,1}) '
              'custom-call(f32[256,8192]{0,1} %copy.105), '
              'custom_call_target="TopK"')
ELO = ('%elo.1 = f32[256,10]{1,0} custom-call(s32[256,160]{1,0} %a), '
       'custom_call_target="tpu_custom_call"')
SCATTER = ('%scatter.1 = f32[1048576,1536]{1,0} scatter(f32[1048576,1536] '
           '%param.0, s32[64] %param.5, f32[64,1536] %param.6)')


def _trace():
    """Device d: two dispatches of (kernel 28 ms, reduce 1.77 ms, gathers
    0.1 + 0.02 + 0.03 ms, merge sort 0.05 ms, local TopK 0.14 ms, ELO
    0.016 ms), then a commit of 0.3 ms (0.6 ms on device 3)."""
    ops = {}
    for d in range(4):
        t, v = 0, []
        for _ in range(2):
            for name, ms in ((SIM, 28.0), (REDUCE, 1.77), (LOCAL_TOPK, 0.14),
                             (GATHER_S, 0.1), (GATHER_START, 0.02),
                             (GATHER_DONE, 0.03), (MERGE, 0.05),
                             (ELO, 0.016)):
                dur = int(ms * MS)
                v.append((t, t + dur, name, ROUTE))
                t += dur
            t += 5 * MS
            dur = int((0.6 if d == 3 else 0.3) * MS)
            v.append((t, t + dur, SCATTER, R.SHARD_SCATTER_MODULE))
            t += dur + 5 * MS
        ops[d] = v
    return Trace(ops, [], window_s=0.1)


@pytest.fixture(scope="module")
def ctx():
    return dict(trace=_trace(), cfg=CFG, peaks=PEAKS,
                counters={"windows": 2, "window_rows": 256})


def _read(name, ctx):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_merge_reads_the_gathers_and_the_pool_topk(ctx, capsys):
    # per dispatch: 0.1 + 0.02 + 0.03 ms of gathers, 0.05 ms of merge
    assert _read("merge_ms.shard", ctx) == pytest.approx(0.2, abs=1e-9)
    assert "2293760 B gathered" in capsys.readouterr().err


def test_merge_bytes_at_the_cell_shapes():
    # 4 shards x 256 queries x 20 candidates x (8 + 8 x 13) B
    assert work.merge_exchange(256, 20, 8, 4) == 2_293_760


def test_commit_reads_the_busiest_device(ctx):
    assert _read("commit_device_ms.shard", ctx) == pytest.approx(0.6)


def test_route_mfu_counts_every_chip(ctx):
    flops, _ = work.route_step(256, 1 << 22, 1536, 20, 8, 10)
    want = 100 * flops * 2 / (0.1 * 4 * 197e12)
    assert _read("route_mfu", ctx) == pytest.approx(want)


def test_route_mfu_of_one_shard_is_one_chip_s():
    """Unsharded, the peak is one chip's, as before the reader counted
    shards: the one-chip cell's reading is unchanged to the bit."""
    cfg = dict(CFG, db=dict(CFG["db"], capacity=1 << 20, shards=1))
    trace = Trace({0: [(0, 28 * MS, SIM, "jit_route_batch_choices")]}, [],
                  window_s=0.1)
    one = dict(trace=trace, cfg=cfg, peaks=PEAKS,
               counters={"windows": 1, "window_rows": 256})
    flops, _ = work.route_step(256, 1 << 20, 1536, 20, 8, 10)
    assert _read("route_mfu", one) == R.percent(flops * 1 / (0.1 * 197e12))


def test_idle_readers_read_the_first_device_of_four():
    """A host stall idles every chip: the idle readers split the first
    device's gaps by the host span open across them, whatever the other
    devices hold."""
    ops = {d: [(0, 10 * MS, SIM, ROUTE), (15 * MS, 25 * MS, SIM, ROUTE)]
           for d in range(4)}
    spans = [(0, 1 * MS, "dispatch.launch"), (10 * MS, 12 * MS,
                                              "dispatch.readout"),
             (12 * MS, 15 * MS, "state.commit")]
    ctx = dict(trace=Trace(ops, spans, window_s=0.025), cfg=CFG, peaks=PEAKS,
               counters={"windows": 1, "window_rows": 256})
    assert _read("dispatch_idle_ms.route", ctx) == pytest.approx(2.0)
    assert _read("commit_idle_ms.route", ctx) == pytest.approx(3.0)


def test_joined_readers_read_per_shard_work(ctx):
    """The existing readers the cell joins read each device's own work
    and time, averaged over the devices."""
    flops, nbytes = work.retrieval(256, 1 << 20, 1536, 20)
    t_min = max(flops / 197e12, nbytes / 819e9)
    assert _read("retrieval_roofline.route", ctx) == pytest.approx(
        100 * t_min / ((28.0 + 1.77) * 1e-3), rel=1e-6)
    assert _read("topk_device_ms.route", ctx) == pytest.approx(1.77)
    assert _read("replay_roofline.route", ctx) > 0
    assert _read("idle_share.route", ctx) == pytest.approx(
        100 * (1 - _trace().busy_s() / 0.1))


def test_merge_reads_nothing_without_a_merge(ctx):
    one_device = dict(ctx, trace=Trace({0: [
        (0, 28 * MS, SIM, "jit_route_batch_choices")]}, [], window_s=0.1))
    assert _read("merge_ms.shard", one_device) is None
    assert _read("commit_device_ms.shard", one_device) is None

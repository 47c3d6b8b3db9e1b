"""Spans on the profiler's clock (DESIGN.md §9): the routing chain's
device scopes in the lowered HLO, the program's host spans in a CPU
profiler trace with the default (disabled) scope, the decode loop's
ring-free markers, and the benchmark's program-span readers on a small
recorded trace and in traced rehearsals.

Re-record the trace fixture with `python tests/test_trace_spans.py`."""
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from bench.lib.readers import SCATTER_MODULE  # noqa: E402
from bench.lib.tracing import Trace  # noqa: E402
from repro import obs as OBS  # noqa: E402
from repro.configs import get_reduced_config  # noqa: E402
from repro.core import state as STATE  # noqa: E402
from repro.core.dispatch import RouteDispatcher  # noqa: E402
from repro.core.router import EagleConfig, EagleRouter  # noqa: E402
from repro.serving.engine import FleetModel  # noqa: E402

FIXTURE = REPO / "bench" / "tests" / "data" / "trace_cpu_spans.xplane.pb"
STAGES = ("eagle.similarity", "eagle.topk", "eagle.gather", "eagle.replay")
#: the program-span readers, with the tiny cell each reads
READERS = {"topk_device_ms.route": "route", "dispatch_idle_ms.route": "route",
           "commit_idle_ms.route": "route", "decode_step_ms.serve": "serve",
           "decode_idle_ms.serve": "serve"}


def _router(backend="reference", dim=8, capacity=256, n=64, seed=0):
    rng = np.random.default_rng(seed)
    m = 4
    r = EagleRouter([f"m{i}" for i in range(m)], np.arange(1.0, m + 1),
                    EagleConfig(embed_dim=dim, backend=backend, n_neighbors=4),
                    db_capacity=capacity)
    r.fit(rng.normal(size=(n, dim)).astype(np.float32),
          rng.integers(0, m, n), rng.integers(0, m, n),
          rng.choice([0.0, 0.5, 1.0], n), query_id=np.arange(n))
    return r, rng


def _window(r, dbuf, d, q, bud):
    """One closed-loop window: dispatch, fold a comparison, commit."""
    choices, _ = d.route_result(dbuf.front, q, bud)
    a = choices[:4].astype(np.int32)
    r.feedback(q[:4], a, (a + 1) % r.n_models, np.ones(4, np.float32))
    dbuf.commit(r.global_ratings)


def _profile(fn):
    """Run fn under a CPU profiler session; the trace as Trace reads it."""
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return Trace.load(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_route_hlo_carries_stage_scopes(backend):
    r, _ = _router(backend)
    st = STATE.state_from_buffer(r.db, r.global_ratings)
    q = np.zeros((8, 8), np.float32)
    txt = STATE.route_batch_choices.lower(
        st, q, np.ones(8, np.float32), r.costs,
        backend=backend).as_text(debug_info=True)
    for scope in STAGES:
        assert scope in txt, scope


def test_scatter_hlo_carries_commit_scope():
    r, _ = _router()
    st = STATE.state_from_buffer(r.db, r.global_ratings)
    rows = np.arange(4, dtype=np.int32)
    lowered = STATE._scatter_rows.lower(
        st.emb, st.model_a, st.model_b, st.outcome, st.valid,
        STATE._pack_rows(r.db, rows, rows))
    assert "eagle.commit_scatter" in lowered.as_text(debug_info=True)
    # bench/lib/readers.py finds commit_device_ms.route by this name
    assert lowered.compile().as_text().split()[1].rstrip(",") == \
        SCATTER_MODULE


# ---------------------------------------------------------------------------
# host spans on the profiler's clock
# ---------------------------------------------------------------------------

def test_disabled_scope_spans_reach_the_profiler_not_the_ring():
    assert not OBS.DEFAULT.enabled
    r, rng = _router()
    dbuf = STATE.DoubleBuffer(r.db, r.global_ratings)
    d = RouteDispatcher.for_router(r)
    d.warmup(dbuf.front, [16])
    q = rng.normal(size=(16, 8)).astype(np.float32)
    bud = np.full(16, 8.0, np.float32)
    _window(r, dbuf, d, q, bud)          # compiles outside the trace
    tr = _profile(lambda: [_window(r, dbuf, d, q, bud) for _ in range(2)])
    names = [n for _, _, n in tr.spans]
    for name in ("dispatch.route_result", "dispatch.put", "dispatch.launch",
                 "dispatch.readout", "router.feedback",
                 "router.feedback.add", "router.feedback.fold",
                 "state.commit", "state.commit.gather",
                 "state.commit.upload", "state.commit.scatter"):
        assert names.count(name) == 2, (name, names)
    # nesting: every part lies inside its parent span
    spans = {n: [(s, e) for s, e, m in tr.spans if m == n] for n in names}
    for child, parent in (("dispatch.launch", "dispatch.route_result"),
                          ("state.commit.upload", "state.commit"),
                          ("router.feedback.fold", "router.feedback")):
        for (s, e), (ps, pe) in zip(spans[child], spans[parent]):
            assert ps <= s <= e <= pe, child
    assert OBS.DEFAULT.tracer.recorded == 0
    assert r.obs is None and dbuf.obs is OBS.DEFAULT


def test_ring_free_marker_is_a_no_op_outside_a_session():
    ob = OBS.Observability(enabled=True)
    assert ob.span("x", ring=False) is OBS.NULL_SPAN
    assert ob.tracer.span("x", ring=False) is OBS.NULL_SPAN
    assert OBS.DEFAULT.span("x") is OBS.NULL_SPAN
    with ob.span("y"):
        with ob.span("z", ring=False):
            pass
    assert [s[1] for s in ob.tracer.spans()] == ["y"]


@pytest.fixture(scope="module")
def olmo():
    return FleetModel(get_reduced_config("olmo-1b"), seed=0, max_len=32)


def test_generate_ring_records_do_not_grow_with_tokens(olmo):
    toks = np.zeros((2, 8), np.int32)
    counts = []
    for max_new in (2, 8):
        olmo.obs = ob = OBS.Observability(enabled=True)
        out = olmo.generate(toks, max_new)
        assert out.shape == (2, max_new)
        names = [s[1] for s in ob.tracer.spans()]
        assert names == ["serve.prefill.olmo-1b", "serve.decode.olmo-1b"]
        counts.append(ob.tracer.recorded)
    assert counts[0] == counts[1]


@pytest.mark.parametrize("max_new", [2, 8])
def test_generate_leaves_one_marker_per_decode_step(olmo, max_new):
    toks = np.zeros((2, 8), np.int32)
    olmo.obs = ob = OBS.Observability(enabled=True)
    olmo.generate(toks, max_new)         # compiles outside the trace
    ob.reset()
    tr = _profile(lambda: olmo.generate(toks, max_new))
    names = [n for _, _, n in tr.spans]
    # the first token comes from prefill; each further one is a step
    assert names.count("serve.decode_step.olmo-1b") == max_new - 1
    assert names.count("serve.readout.olmo-1b") == max_new - 1
    assert names.count("serve.prefill.olmo-1b") == 1
    assert names.count("serve.decode.olmo-1b") == 1
    assert ob.tracer.recorded == 2 and ob.tracer.dropped == 0


# ---------------------------------------------------------------------------
# the program-span readers
# ---------------------------------------------------------------------------

def _record_fixture(path=FIXTURE):
    """Three route windows (dispatch, feedback, commit) on the default
    disabled scope, then one reduced OLMo-1B generate of 4 tokens, under
    a CPU profiler session."""
    r, rng = _router()
    dbuf = STATE.DoubleBuffer(r.db, r.global_ratings)
    d = RouteDispatcher.for_router(r)
    d.warmup(dbuf.front, [16])
    q = rng.normal(size=(16, 8)).astype(np.float32)
    bud = np.full(16, 8.0, np.float32)
    m = FleetModel(get_reduced_config("olmo-1b"), seed=0, max_len=32)
    toks = np.zeros((2, 8), np.int32)
    _window(r, dbuf, d, q, bud)
    m.generate(toks, 4)
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # as bench/lib/tracing.Tracer
    opts.enable_hlo_proto = False      # keeps the fixture small
    jax.profiler.start_trace(logdir, profiler_options=opts)
    for _ in range(3):
        _window(r, dbuf, d, q, bud)
    m.generate(toks, 4)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[-1]
    shutil.copy(src, path)
    shutil.rmtree(logdir, ignore_errors=True)


def _reader(name):
    """A metric's reader, loaded as bench/run.py loads it."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", REPO / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return Trace.from_profile(ProfileData.from_file(str(FIXTURE)))


def test_fixture_holds_the_program_spans(recorded):
    names = [n for _, _, n in recorded.spans]
    assert names.count("dispatch.launch") == 3
    assert names.count("state.commit") == 3
    assert names.count("serve.decode_step.olmo-1b") == 3
    assert recorded.ops


def test_idle_by_span_splits_gaps_at_span_boundaries():
    from bench.lib.idle import idle_by_span
    ms = 1_000_000
    ops = [(0, 10 * ms, "a", "m"), (20 * ms, 30 * ms, "b", "m"),
           (30 * ms + 500, 40 * ms, "c", "m")]     # a 500-ns gap: skipped
    spans = [(0, 40 * ms, "outer"), (8 * ms, 14 * ms, "x"),
             (14 * ms, 19 * ms, "y"), (15 * ms, 16 * ms, "y.in")]
    got = idle_by_span(Trace({0: ops}, spans, 0.04))
    # the 10 ms gap [10, 20): x 4, y 4 (y.in 1 inside it), outer 1
    want = {"x": 4e-3, "y": 4e-3, "y.in": 1e-3, "outer": 1e-3}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k]), k
    # midpoint naming gives the whole gap to the span open at 15 ms
    assert dict(Trace({0: ops}, spans, 0.04).idle_gaps()) == \
        pytest.approx({"y.in": 10e-3})


def test_idle_by_span_adds_up_to_the_idle_gaps(recorded):
    from jax.profiler import ProfileData
    from bench.lib.idle import idle_by_span
    old = Trace.from_profile(ProfileData.from_file(
        str(FIXTURE.parent / "trace_cpu_small.xplane.pb")))
    for tr in (recorded, old):
        gaps = tr.idle_gaps(k=len(tr.spans) + 1)
        assert sum(idle_by_span(tr).values()) == \
            pytest.approx(sum(v for _, v in gaps))


def test_span_readers_on_the_recorded_trace(recorded):
    from bench.lib.idle import idle_by_span
    ctx = {"trace": recorded, "cfg": {"fleet": {"checked": "olmo-1b"}}}
    gaps = idle_by_span(recorded)
    disp = sum(v for n, v in gaps.items() if n.startswith("dispatch."))
    com = sum(v for n, v in gaps.items() if n.startswith("state.commit"))
    dec = sum(v for n, v in gaps.items()
              if n in ("serve.decode_step.olmo-1b", "serve.readout.olmo-1b"))
    assert _reader("dispatch_idle_ms.route").read(ctx) == \
        pytest.approx(1e3 * disp / 3)
    assert _reader("commit_idle_ms.route").read(ctx) == \
        pytest.approx(1e3 * com / 3)
    assert _reader("decode_idle_ms.serve").read(ctx) == \
        pytest.approx(1e3 * dec / 3)
    steps = [e - s for s, e, n in recorded.spans
             if n == "serve.decode_step.olmo-1b"]
    step_ms = _reader("decode_step_ms.serve").read(ctx)
    assert step_ms == pytest.approx(1e-6 * sum(steps) / 3) and step_ms > 0
    # the CPU trace has no scope metadata on its ops: no device reading
    assert _reader("topk_device_ms.route").read(ctx) is None


#: two TPU "XLA Ops" events of a route dispatch, as a v5e names them
#: (HLO text, layouts shortened): the named similarity kernel and the
#: fused live-row mask + top-k that reads its panel
TPU_KERNEL = ("%eagle_similarity.1 = f32[256,65536]{1,0} custom-call("
              "f32[256,1536]{1,0} %query_embs.1, f32[65536,1536]{1,0} "
              "%state_emb.1), custom_call_target=\"tpu_custom_call\"")
TPU_TOPK = ("%fusion.6 = (f32[256,20]{1,0}, s32[256,20]{1,0}) fusion("
            "f32[256,65536]{1,0} %eagle_similarity.1, f32[]{:T(128)} "
            "%constant.18, pred[65536]{0} %iota_compare_fusion), "
            "kind=kCustom, calls=%fused_computation.4")


def test_topk_reader_on_tpu_op_names():
    mod = "jit_route_batch_choices"
    ops = []
    for w in range(3):      # three dispatches: kernel 2 ms, top-k 1 ms
        t = w * 10_000_000
        ops += [(t, t + 2_000_000, TPU_KERNEL, mod),
                (t + 2_000_000, t + 3_000_000, TPU_TOPK, mod),
                (t + 3_000_000, t + 3_100_000, "%rev.4 = s32[256,20] "
                 "reverse(s32[256,20] %get-tuple-element.11)", mod)]
    ops.append((40_000_000, 40_500_000, TPU_TOPK, "jit_other"))
    tr = Trace({0: ops}, [], 0.04)
    assert _reader("topk_device_ms.route").read({"trace": tr}) == \
        pytest.approx(1.0)


def test_span_readers_read_nothing_without_the_spans():
    from jax.profiler import ProfileData
    old = Trace.from_profile(ProfileData.from_file(
        str(FIXTURE.parent / "trace_cpu_small.xplane.pb")))
    ctx = {"trace": old, "cfg": {"fleet": {"checked": "olmo-1b"}}}
    for name in READERS:
        assert _reader(name).read(ctx) is None, name


@pytest.mark.parametrize("cell", ["route", "serve"])
def test_traced_rehearsal_reads_the_program_spans(tmp_path, cell):
    """The tiny cells with the five readers added to their manifest."""
    data = REPO / "bench" / "tests" / "data"
    shutil.copytree(data / "bench", tmp_path / "bench")
    man = json.loads((data / "BENCHMARK.json").read_text())
    moves = {"route": "route_rps", "serve": "e2e_p50_ms"}
    for name, c in READERS.items():
        man["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "test", "moves": moves[c],
            "workloads": [f"tiny.{c}"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", f"tiny.{cell}",
         "--seed", "11", "--seconds", "3" if cell == "serve" else "2",
         "--rehearse", "--trace", "1", "--data-dir", str(tmp_path)],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is True
    got = r["rehearsal_readings"]
    want = [n for n, c in READERS.items()
            if c == cell and n != "topk_device_ms.route"]
    for name in want:
        assert got[name]["value"] >= 0, name
    assert "topk_device_ms.route" not in got      # no scopes on CPU ops
    assert got["decode_step_ms.serve" if cell == "serve"
               else "dispatch_idle_ms.route"]["value"] > 0
    names = [n for n, _ in r["breakdown"]["idle_gaps"]]
    prefix = "dispatch." if cell == "route" else "serve."
    assert any(n.startswith(prefix) for n in names), names


if __name__ == "__main__":
    _record_fixture()
    print(FIXTURE, FIXTURE.stat().st_size, "bytes")

"""Per-kernel validation: Pallas (interpret=True on CPU) vs pure-jnp ref,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.elo_scan import elo_scan_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.similarity_topk import (TOPK_CHUNK, panel_topk,
                                           similarity_pallas, two_stage_topk)
from repro.kernels import ops


def _rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ---------------------------------------------------------------------------
# similarity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q_n,db_n,d", [(4, 64, 32), (128, 256, 256),
                                        (130, 300, 1536), (1, 17, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_similarity_kernel(q_n, db_n, d, dtype):
    rng = np.random.default_rng(0)
    q = _rand(rng, (q_n, d), dtype)
    db = _rand(rng, (db_n, d), dtype)
    got = similarity_pallas(q, db, block_q=128, block_n=128, interpret=True)
    want = ref.similarity_ref(q.astype(jnp.float32), db.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_similarity_topk_matches_bruteforce():
    rng = np.random.default_rng(1)
    q = _rand(rng, (8, 64), jnp.float32)
    db = _rand(rng, (200, 64), jnp.float32)
    s_ref = np.asarray(ref.similarity_ref(q, db))
    _, idx = ops.similarity_topk(q, db, 10, backend="pallas_interpret")
    for i in range(8):
        want = set(np.argsort(-s_ref[i])[:10].tolist())
        assert set(np.asarray(idx[i]).tolist()) == want


# ---------------------------------------------------------------------------
# two-stage top-k over the score panel
# ---------------------------------------------------------------------------

B = TOPK_CHUNK
WIDE = 96 * B      # 12,288 rows: two stages at n = 20 (needs 80 chunks)

# id: (q, c, n, scores, size, offset, two_stage)
PANEL_CASES = {
    "gaussian_all_live": (16, WIDE, 20, "gauss", WIDE, 0, True),
    "integer_ties_all_live": (16, WIDE, 20, "ints", WIDE, 0, True),
    "integer_ties_part_live": (16, WIDE, 20, "ints", 7_001, 0, True),
    "peaked_ties_all_live": (16, WIDE, 20, "peaked", WIDE, 0, True),
    "peaked_ties_part_live": (16, WIDE, 20, "peaked", 9_000, 0, True),
    "size_0": (8, WIDE, 20, "gauss", 0, 0, True),
    "size_at_chunk_boundary": (8, WIDE, 20, "ints", 40 * B, 0, True),
    "size_inside_chunk": (8, WIDE, 20, "gauss", 40 * B + 77, 0, True),
    "fewer_than_n_live": (8, WIDE, 20, "ints", 13, 0, True),
    "one_live_chunk_ties": (8, WIDE, 20, "ints", B, 0, True),
    "offset_shard_live": (8, WIDE, 20, "ints", 3 * WIDE - 500, 2 * WIDE, True),
    "offset_shard_dead": (8, WIDE, 20, "gauss", 2 * WIDE - 1, 2 * WIDE, True),
    "offset_size_inside": (8, WIDE, 20, "gauss", WIDE + 4_321, WIDE, True),
    "q_not_multiple_of_8": (13, WIDE, 20, "ints", 9_999, 0, True),
    "q_1": (1, WIDE, 20, "gauss", WIDE, 0, True),
    "at_threshold": (8, 80 * B, 20, "ints", 80 * B - 3, 0, True),
    "below_threshold": (8, 79 * B, 20, "gauss", 79 * B - 3, 0, False),
    "small_n_narrow_panel": (8, 4 * B, 1, "ints", 4 * B - 1, 0, True),
    "c_not_multiple_of_chunk": (8, WIDE + 5, 20, "gauss", WIDE, 0, False),
}


@pytest.mark.parametrize("case", list(PANEL_CASES))
def test_panel_topk_bitwise_equals_lax_top_k(case):
    """panel_topk against lax.top_k over the live-masked panel: scores
    (as bits) and row ids equal, -inf rows and ties included; each case
    takes the path its shape says."""
    q, c, n, kind, size, offset, two_stage = PANEL_CASES[case]
    assert two_stage_topk(c, n) is two_stage
    rng = np.random.default_rng(sum(map(ord, case)))
    if kind == "gauss":
        s = rng.normal(size=(q, c)).astype(np.float32)
    else:   # few distinct values: every top n is decided by ties
        s = rng.integers(-3, 4, (q, c)).astype(np.float32)
    if kind == "peaked":    # a high row late in the live range ranks its
        # chunk first, ahead of lower chunks tied at the next value
        live_c = size - offset
        s[np.arange(q), rng.integers(live_c // 2, live_c, q)] = 10.0
    s = jnp.asarray(s)
    live = (jnp.arange(c) + offset) < size
    want_s, want_i = jax.lax.top_k(jnp.where(live[None, :], s, -jnp.inf), n)
    got_s, got_i = jax.jit(panel_topk, static_argnums=1)(s, n, size, offset)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s).view(np.uint32),
                                  np.asarray(want_s).view(np.uint32))


# ---------------------------------------------------------------------------
# elo scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,t,m", [(4, 20, 10), (130, 7, 32), (1, 1, 4)])
def test_elo_scan_kernel(q, t, m):
    rng = np.random.default_rng(2)
    ratings = jnp.asarray(1000 + 50 * rng.normal(size=(q, m)), jnp.float32)
    a = jnp.asarray(rng.integers(0, m, (q, t)), jnp.int32)
    b = jnp.asarray((np.asarray(a) + 1 + rng.integers(0, m - 1, (q, t))) % m,
                    jnp.int32)
    s = jnp.asarray(rng.choice([0.0, 0.5, 1.0], (q, t)), jnp.float32)
    v = jnp.asarray(rng.random((q, t)) > 0.2)
    got = elo_scan_pallas(ratings, a, b, s, v, k=32.0, block_q=128,
                          interpret=True)
    want = ref.elo_scan_ref(ratings, a, b, s, v, k=32.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_elo_scan_kernel_matches_core_scan():
    """Kernel == the production lax.scan implementation in core.elo."""
    from repro.core import elo as core_elo
    rng = np.random.default_rng(3)
    q, t, m = 16, 20, 8
    g = jnp.asarray(1000 + 30 * rng.normal(size=(m,)), jnp.float32)
    a = jnp.asarray(rng.integers(0, m, (q, t)), jnp.int32)
    b = jnp.asarray((np.asarray(a) + 1) % m, jnp.int32)
    s = jnp.asarray(rng.choice([0.0, 1.0], (q, t)), jnp.float32)
    v = jnp.ones((q, t), bool)
    want = core_elo.local_elo(g, a, b, s, v, k=32.0)
    got = elo_scan_pallas(jnp.broadcast_to(g, (q, m)), a, b, s, v, k=32.0,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,hk,dh", [(1, 256, 4, 4, 64),
                                         (2, 256, 4, 2, 32),
                                         (1, 512, 8, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, s, h, hk, dh, dtype):
    rng = np.random.default_rng(4)
    q = _rand(rng, (b, s, h, dh), dtype)
    k = _rand(rng, (b, s, hk, dh), dtype)
    v = _rand(rng, (b, s, hk, dh), dtype)
    got = flash_attention_pallas(q, k, v, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_flash_attention_sliding_window():
    rng = np.random.default_rng(5)
    b, s, h, dh, w = 1, 512, 2, 64, 128
    q = _rand(rng, (b, s, h, dh), jnp.float32)
    k = _rand(rng, (b, s, h, dh), jnp.float32)
    v = _rand(rng, (b, s, h, dh), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=w,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,hk,dh", [(2, 512, 4, 4, 64),
                                         (1, 1024, 8, 2, 128),
                                         (3, 256, 2, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(b, t, h, hk, dh, dtype):
    rng = np.random.default_rng(6)
    q = _rand(rng, (b, h, dh), dtype)
    k = _rand(rng, (b, t, hk, dh), dtype)
    v = _rand(rng, (b, t, hk, dh), dtype)
    kv_len = jnp.asarray(rng.integers(1, t, (b,)), jnp.int32)
    got = decode_attention_pallas(q, k, v, kv_len, block_k=256,
                                  interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_len)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)


def test_decode_matches_flash_last_row():
    """decode kernel over a full cache == last row of prefill flash."""
    rng = np.random.default_rng(7)
    b, s, h, dh = 1, 256, 4, 64
    q = _rand(rng, (b, s, h, dh), jnp.float32)
    k = _rand(rng, (b, s, h, dh), jnp.float32)
    v = _rand(rng, (b, s, h, dh), jnp.float32)
    full = flash_attention_pallas(q, k, v, causal=True, interpret=True)
    dec = decode_attention_pallas(q[:, -1], k, v,
                                  jnp.full((b,), s, jnp.int32),
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=2e-3, atol=2e-3)

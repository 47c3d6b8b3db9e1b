"""Property tests on model-substrate invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.configs import get_reduced_config
from repro.models import transformer as T
from repro.models import layers as L

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_reduced_config("olmo-1b")
    params = T.init_params(cfg, jax.random.key(0))
    return cfg, params


def test_causality_future_tokens_do_not_leak(dense_setup):
    """Changing token t must not change logits at positions < t."""
    cfg, params = dense_setup
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (1, 12)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, 8] = (toks2[0, 8] + 7) % cfg.vocab
    l1, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    l2, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks2)})
    np.testing.assert_allclose(np.asarray(l1[:, :8], np.float32),
                               np.asarray(l2[:, :8], np.float32),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(l1[:, 8:], np.float32),
                           np.asarray(l2[:, 8:], np.float32))


def test_ssm_causality():
    cfg = get_reduced_config("mamba2-780m")
    params = T.init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab, (1, 10)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, 6] = (toks2[0, 6] + 3) % cfg.vocab
    l1, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    l2, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks2)})
    np.testing.assert_allclose(np.asarray(l1[:, :6], np.float32),
                               np.asarray(l2[:, :6], np.float32),
                               rtol=1e-4, atol=1e-4)


def test_batch_elements_independent(dense_setup):
    """Row b of the batch must not influence row b'."""
    cfg, params = dense_setup
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
    la, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    toks_mut = toks.copy()
    toks_mut[1] = rng.integers(0, cfg.vocab, 8)
    lb, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks_mut)})
    np.testing.assert_allclose(np.asarray(la[0], np.float32),
                               np.asarray(lb[0], np.float32),
                               rtol=1e-5, atol=1e-5)


def test_sliding_window_limits_context():
    """With window w, logits at position t only see the last w tokens."""
    cfg = get_reduced_config("gemma3-12b", n_layers=2, local_global_ratio=0,
                             sliding_window=4)
    # make ALL layers local (pattern disabled -> kinds 'attn'; force window
    # by reinstating the pattern with ratio high enough to avoid globals)
    cfg = dataclasses.replace(cfg, local_global_ratio=5, n_layers=2)
    params = T.init_params(cfg, jax.random.key(3))
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, (1, 12)).astype(np.int32)
    toks2 = toks.copy()
    toks2[0, 0] = (toks2[0, 0] + 5) % cfg.vocab   # outside any 4-window
    l1, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks)})
    l2, _, _, _ = T.forward(cfg, params, {"tokens": jnp.asarray(toks2)})
    # both layers local with window 4: position 11 sees tokens 8..11 at
    # layer 1, and indirectly 5..11 through layer stacking — token 0 is
    # beyond the receptive field (2 layers x window 4).
    np.testing.assert_allclose(np.asarray(l1[:, 11], np.float32),
                               np.asarray(l2[:, 11], np.float32),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=5, deadline=None)
def test_rope_relative_shift_invariance(seed):
    """RoPE attention scores depend only on relative positions."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 2, 16)), jnp.float32)
    def score(offset):
        qp = jnp.asarray([[3 + offset]], jnp.int32)
        kp = jnp.asarray([[1 + offset]], jnp.int32)
        qr = L.apply_rope(q, qp, 10000.0)
        kr = L.apply_rope(k, kp, 10000.0)
        return np.asarray(jnp.einsum("bshd,bthd->bhst", qr, kr))
    np.testing.assert_allclose(score(0), score(1000), rtol=1e-4, atol=1e-4)


def test_loss_mask_ignores_negative_targets(dense_setup):
    cfg, params = dense_setup
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
    tgt = rng.integers(0, cfg.vocab, (1, 8)).astype(np.int32)
    loss_full, _ = T.loss_fn(cfg, params, {"tokens": toks,
                                           "targets": jnp.asarray(tgt)})
    tgt_masked = tgt.copy()
    tgt_masked[0, :4] = -100
    loss_half, _ = T.loss_fn(cfg, params, {"tokens": toks,
                                           "targets": jnp.asarray(tgt_masked)})
    assert not np.isclose(float(loss_full), float(loss_half))
    assert np.isfinite(float(loss_half))


def test_moe_small_and_shardmap_paths_agree():
    """The serving path's dropless grouped matmul must match the
    training path's capacity buffer where the capacity drops nothing."""
    from repro.models import moe as MOE
    cfg = get_reduced_config("phi3.5-moe-42b-a6.6b", capacity_factor=8.0)
    params = MOE.init_moe(cfg, jax.random.key(5))
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 4, cfg.d_model)) * 0.1, jnp.float32)
    y_serve, aux_s, st_s = MOE.apply_moe(cfg, params, x)
    y_train, aux_t, st_t = MOE.apply_moe(cfg, params, x, train=True)
    # reduced phi has no shared experts: both are the routed part alone
    np.testing.assert_allclose(np.asarray(y_serve), np.asarray(y_train),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_s), float(aux_t), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(st_s), np.asarray(st_t))
    assert int(st_s[0]) == 8 * cfg.experts_per_tok and int(st_s[1]) == 0


def test_windowed_ring_cache_matches_full_cache():
    """Ring-buffer local caches (window_cache=True) must produce EXACTLY the
    same decode logits as full-length caches: the ring holds precisely the
    tokens the sliding-window mask admits."""
    base = get_reduced_config("gemma3-12b", n_layers=6, local_global_ratio=2,
                              sliding_window=4)
    cfg_full = dataclasses.replace(base, window_cache=False)
    cfg_ring = dataclasses.replace(base, window_cache=True)
    params = T.init_params(cfg_full, jax.random.key(7))
    rng = np.random.default_rng(7)
    prompt = jnp.asarray(rng.integers(0, base.vocab, (1, 8)), jnp.int32)

    lf, cf = T.prefill(cfg_full, params, {"tokens": prompt}, 16,
                       cache_dtype=jnp.float32)
    lr, cr = T.prefill(cfg_ring, params, {"tokens": prompt}, 16,
                       cache_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(lf, np.float32),
                               np.asarray(lr, np.float32),
                               rtol=1e-4, atol=1e-4)
    tok = jnp.argmax(lf, -1).astype(jnp.int32)[:, None]
    for i in range(8, 14):  # crosses the ring wrap boundary (W=4)
        lf, cf = T.decode_step(cfg_full, params, cf, tok, i)
        lr, cr = T.decode_step(cfg_ring, params, cr, tok, i)
        np.testing.assert_allclose(np.asarray(lf, np.float32),
                                   np.asarray(lr, np.float32),
                                   rtol=1e-4, atol=1e-4)
        tok = jnp.argmax(lf, -1).astype(jnp.int32)[:, None]

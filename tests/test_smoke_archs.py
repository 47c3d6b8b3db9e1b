"""Per-architecture smoke tests: REDUCED variants (2 layers, d_model<=512,
<=4 experts) run one forward + one train-grad step and one prefill+decode
step on CPU, asserting output shapes and the absence of NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_IDS, get_reduced_config
from repro.models import transformer as T

jax.config.update("jax_platform_name", "cpu")


def _batch_for(cfg, b=2, s=16):
    rng = np.random.default_rng(0)
    batch = {}
    if cfg.arch_type == "vlm":
        s_text = s - cfg.n_image_tokens
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (b, s_text)), jnp.int32)
        batch["img_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_image_tokens, cfg.d_model)), jnp.float32)
        batch["targets"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (b, s_text)), jnp.int32)
    elif cfg.arch_type == "encdec":
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_audio_frames, cfg.d_model)), jnp.float32)
        batch["targets"] = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
        batch["targets"] = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    return batch


@pytest.fixture(scope="module")
def rngkey():
    return jax.random.key(0)


@pytest.mark.parametrize("arch", ALL_IDS)
def test_forward_and_loss(arch, rngkey):
    cfg = get_reduced_config(arch)
    params = T.init_params(cfg, rngkey)
    batch = _batch_for(cfg)
    b = batch["tokens"].shape[0]
    s_total = batch["tokens"].shape[1] + (
        cfg.n_image_tokens if cfg.arch_type == "vlm" else 0)

    logits, aux, _, _ = T.forward(cfg, params, batch)
    assert logits.shape == (b, s_total, cfg.vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any()), "NaN logits"

    loss, metrics = T.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss)), f"non-finite loss {loss}"
    assert float(metrics["ce"]) > 0


@pytest.mark.parametrize("arch", ALL_IDS)
def test_train_grad_step(arch, rngkey):
    cfg = get_reduced_config(arch)
    params = T.init_params(cfg, rngkey)
    batch = _batch_for(cfg)

    def lfn(p):
        return T.loss_fn(cfg, p, batch)[0]

    loss, grads = jax.value_and_grad(lfn)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert flat, "no grads"
    for g in flat:
        assert np.all(np.isfinite(np.asarray(g, np.float32))), "non-finite grad"
    # SGD step changes the loss
    new_params = jax.tree.map(lambda p, g: p - 1e-2 * g.astype(p.dtype),
                              params, grads)
    loss2 = float(lfn(new_params))
    assert np.isfinite(loss2)


@pytest.mark.parametrize("arch", ALL_IDS)
def test_prefill_then_decode(arch, rngkey):
    cfg = get_reduced_config(arch)
    params = T.init_params(cfg, rngkey)
    batch = _batch_for(cfg)
    batch.pop("targets")
    b = batch["tokens"].shape[0]
    s_total = batch["tokens"].shape[1] + (
        cfg.n_image_tokens if cfg.arch_type == "vlm" else 0)
    max_len = s_total + 4

    last_logits, cache = T.prefill(cfg, params, batch, max_len,
                                   cache_dtype=jnp.float32)
    assert last_logits.shape == (b, cfg.vocab)
    assert not bool(jnp.isnan(last_logits.astype(jnp.float32)).any())

    tok = jnp.argmax(last_logits, -1).astype(jnp.int32)[:, None]
    logits, cache = T.decode_step(cfg, params, cache, tok, s_total)
    assert logits.shape == (b, cfg.vocab)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())
    logits2, _ = T.decode_step(
        cfg, params, cache, jnp.argmax(logits, -1).astype(jnp.int32)[:, None],
        s_total + 1)
    assert not bool(jnp.isnan(logits2.astype(jnp.float32)).any())


def test_decode_matches_prefill_dense(rngkey):
    """Teacher-forced decode must reproduce the prefill logits (dense)."""
    cfg = get_reduced_config("olmo-1b")
    params = T.init_params(cfg, rngkey)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)

    full_logits, _, _, _ = T.forward(cfg, params, {"tokens": toks})

    _, cache = T.prefill(cfg, params, {"tokens": toks[:, :4]}, 8,
                         cache_dtype=jnp.float32)
    outs = []
    for i in range(4, 8):
        lg, cache = T.decode_step(cfg, params, cache, toks[:, i:i + 1], i)
        outs.append(lg)
    # logits at step i correspond to full_logits[:, i]
    for j, lg in enumerate(outs):
        np.testing.assert_allclose(
            np.asarray(lg, np.float32),
            np.asarray(full_logits[:, 4 + j], np.float32),
            rtol=2e-2, atol=2e-2)


def test_decode_matches_prefill_ssm(rngkey):
    """Recurrent decode must match the chunked SSD scan (mamba2)."""
    cfg = get_reduced_config("mamba2-780m")
    params = T.init_params(cfg, rngkey)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)

    full_logits, _, _, _ = T.forward(cfg, params, {"tokens": toks})
    _, cache = T.prefill(cfg, params, {"tokens": toks[:, :4]}, 8,
                         cache_dtype=jnp.float32)
    for i in range(4, 8):
        lg, cache = T.decode_step(cfg, params, cache, toks[:, i:i + 1], i)
        np.testing.assert_allclose(
            np.asarray(lg, np.float32),
            np.asarray(full_logits[:, i], np.float32),
            rtol=5e-2, atol=5e-2)

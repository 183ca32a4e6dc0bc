"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step + one decode step on CPU; asserts shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.models.model import get_model


def _smoke_batch(cfg, rng, B=2, S=32):
    ks = jax.random.split(rng, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
    }
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = jax.random.normal(
            ks[2], (B, cfg.max_encoder_len, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_train_step_smoke(arch):
    cfg = get_config(arch).smoke()
    api = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = api.init(rng)
    batch = _smoke_batch(cfg, jax.random.PRNGKey(1))

    loss, metrics = jax.jit(lambda p, b: api.loss(p, b))(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss {loss}"
    assert float(loss) > 0.0
    # sane CE for random init: close to ln(vocab)
    assert float(metrics["ce"]) < np.log(cfg.vocab_size) + 2.0

    # gradients flow and are finite
    g, _ = jax.grad(lambda p: api.loss(p, batch)[0], has_aux=False)(params), None
    leaves = jax.tree.leaves(g)
    assert leaves, "no grads"
    for leaf in leaves:
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32))), f"{arch}: NaN grad"


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_decode_step_smoke(arch):
    cfg = get_config(arch).smoke()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    B, max_len = 2, 16
    cache = api.init_cache(B, max_len)
    token = jnp.array([1, 2], jnp.int32)
    step = jax.jit(lambda p, t, c: api.decode(p, t, c))
    logits, cache = step(params, token, cache)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))
    # second step advances position
    logits2, cache2 = step(params, token, cache)
    assert np.all(np.isfinite(np.asarray(logits2)))
    pos = jax.tree.leaves(cache2)[-1] if not hasattr(cache2, "pos") else cache2.pos
    assert int(cache2.pos) == 2


def test_param_counts_match_analytic():
    """Full-size analytic param counts are in the right ballpark."""
    expect = {
        "yi-6b": (5.5e9, 7.5e9),
        "qwen2.5-32b": (30e9, 36e9),
        "mamba2-130m": (0.10e9, 0.16e9),
        # the published config.json: 27 layers, MLA, 64 experts -> 15.96B
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_param_pytree_finite(arch):
    cfg = get_config(arch).smoke()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(42))
    for leaf in jax.tree.leaves(params):
        assert np.all(np.isfinite(np.asarray(leaf, dtype=np.float32)))

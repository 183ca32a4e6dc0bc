"""Cross-path model consistency: prefill vs decode, shard_map MoE vs pjit
MoE, deferred vs eager cache commit, hybrid state handoff."""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.partitioning import use_partitioning
from repro.launch.shardings import rules_for
from repro.models import tuning
from repro.models.model import get_model


def _greedy_rollout(api, params, prompt, n, max_len):
    """prefill + n decode steps, greedy."""
    logits, cache = api.prefill(params, prompt, max_len)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    toks = [tok]
    for _ in range(n - 1):
        logits, cache = api.decode(params, tok, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(tok)
    return jnp.stack(toks, axis=1)


@pytest.mark.parametrize("arch", ["yi-6b", "qwen2-moe-a2.7b"])
def test_prefill_decode_matches_teacher_forcing(arch):
    """Greedy decode continuation must match re-prefilling the full prefix."""
    cfg = get_config(arch).smoke()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompt = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)

    out = _greedy_rollout(api, params, prompt, 4, max_len=16)
    # teacher-forced check: prefill(prompt + out[:-1]) must predict out[-1]
    full = jnp.concatenate([prompt, out[:, :-1]], axis=1)
    logits2, _ = api.prefill(params, full, 16)
    pred = jnp.argmax(logits2[:, -1], axis=-1)
    assert int(pred[0]) == int(out[0, -1]), "decode path diverges from prefill"


def test_deferred_commit_multi_step_equivalence():
    cfg = get_config("qwen2.5-3b").smoke()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(1))
    toks = jnp.asarray([[2, 9, 4]], jnp.int32)

    def run():
        cache = api.init_cache(1, 8)
        outs = []
        for i in range(3):
            logits, cache = api.decode(params, toks[:, i], cache)
            outs.append(logits)
        return jnp.stack(outs), cache

    with tuning.tuned(decode_deferred_commit=True):
        o_def, c_def = run()
    with tuning.tuned(decode_deferred_commit=False):
        o_eager, c_eager = run()
    np.testing.assert_allclose(np.asarray(o_def), np.asarray(o_eager),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(c_def.k), np.asarray(c_eager.k),
                               atol=1e-5, rtol=1e-5)


def test_moe_shardmap_matches_pjit_path_on_unit_mesh():
    """On a 1x1 mesh the token-motion-free path must equal the pjit path
    (same routing, same capacity semantics at dp=1, m=1)."""
    cfg = get_config("qwen2-moe-a2.7b").smoke()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(2))
    batch = {
        "tokens": jnp.asarray(np.arange(1, 33)[None, :], jnp.int32),
        "labels": jnp.asarray(np.arange(2, 34)[None, :], jnp.int32),
    }
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    rules = rules_for(cfg, mesh)

    with tuning.tuned(moe_shardmap=False):
        loss_a, _ = jax.jit(lambda p, b: api.loss(p, b))(params, batch)
    with tuning.tuned(moe_shardmap=True), use_partitioning(mesh, rules):
        loss_b, _ = jax.jit(lambda p, b: api.loss(p, b))(params, batch)
    assert float(loss_a) == pytest.approx(float(loss_b), rel=2e-3)


def test_hybrid_prefill_then_decode_state_handoff():
    """Zamba2: decode after prefill must match a pure-decode rollout."""
    cfg = get_config("zamba2-1.2b").smoke()
    from repro.models import hybrid

    params = hybrid.init_params(jax.random.PRNGKey(3), cfg)
    prompt = jnp.asarray(np.arange(1, 7)[None, :], jnp.int32)

    # path A: prefill prompt, decode 1
    logits_p, cache = hybrid.prefill(params, prompt, cfg, max_len=16)
    tok = jnp.argmax(logits_p, axis=-1).astype(jnp.int32)
    la, _ = hybrid.decode_step(params, tok, cache, cfg)

    # path B: feed prompt token-by-token through decode
    cache_b = hybrid.init_cache(cfg, 1, 16)
    for i in range(prompt.shape[1]):
        lb, cache_b = hybrid.decode_step(params, prompt[:, i], cache_b, cfg)
    # logits after consuming the prompt should match prefill's last logits
    np.testing.assert_allclose(
        np.asarray(lb), np.asarray(logits_p), atol=5e-3, rtol=5e-3
    )
    lb2, _ = hybrid.decode_step(params, tok, cache_b, cfg)
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb2), atol=5e-3, rtol=5e-3)


def test_ssm_prefill_then_decode_state_handoff():
    cfg = get_config("mamba2-130m").smoke()
    from repro.models import ssm_lm

    params = ssm_lm.init_params(jax.random.PRNGKey(4), cfg)
    prompt = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)
    logits_p, cache = ssm_lm.prefill(params, prompt, cfg)

    cache_b = ssm_lm.init_cache(cfg, 1)
    for i in range(prompt.shape[1]):
        lb, cache_b = ssm_lm.decode_step(params, prompt[:, i], cache_b, cfg)
    np.testing.assert_allclose(
        np.asarray(lb), np.asarray(logits_p), atol=5e-3, rtol=5e-3
    )

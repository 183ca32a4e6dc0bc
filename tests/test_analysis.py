"""Unit tests for the HLO cost parser and roofline math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_cost as H
from repro.analysis.roofline import PEAK_FLOPS, compute_terms, model_flops_per_step
from repro.configs import get_config, get_shape


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


class TestHloCost:
    def test_scan_trip_count_multiplies_flops(self):
        def make(L):
            def f(x, w):
                def body(c, _):
                    return jnp.tanh(c @ w), None
                return jax.lax.scan(body, x, None, length=L)[0]
            return f

        x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        for L in (1, 3, 7):
            mc = H.module_cost(_compile(make(L), x, w).as_text())
            assert mc.flops == pytest.approx(2 * 64 * 128 * 128 * L, rel=1e-6), L

    def test_nested_scan_trip_counts_compose(self):
        def f(x, w):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ w, None
                c2, _ = jax.lax.scan(inner, c, None, length=4)
                return c2, None
            return jax.lax.scan(outer, x, None, length=3)[0]

        x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        mc = H.module_cost(_compile(f, x, w).as_text())
        assert mc.flops == pytest.approx(2 * 32 * 64 * 64 * 12, rel=1e-6)

    def test_dot_flops_from_contracting_dims(self):
        def f(a, b):
            return jnp.einsum("bij,bjk->bik", a, b)

        a = jax.ShapeDtypeStruct((4, 8, 16), jnp.float32)
        b = jax.ShapeDtypeStruct((4, 16, 32), jnp.float32)
        mc = H.module_cost(_compile(f, a, b).as_text())
        assert mc.flops == pytest.approx(2 * 4 * 8 * 32 * 16, rel=1e-6)

    def test_shape_parsing_tuple_with_index_comments(self):
        # the bug that broke instruction parsing: /*index=5*/ inside tuples
        comps, entry = H.parse_module(
            "ENTRY %main (p: f32[4]) -> f32[4] {\n"
            "  %t = (f32[2,4]{1,0}, s32[]{}, /*index=2*/f32[8]{0}) tuple(%a, %b, %c)\n"
            "  ROOT %r = f32[4]{0} add(%p, %p)\n"
            "}\n"
        )
        assert entry == "main"
        kinds = [i.kind for i in comps["main"].instrs]
        assert kinds == ["tuple", "add"]

    def test_bytes_slicing_semantics(self):
        elems, nbytes = H.shape_elems_bytes("bf16[8,128]{1,0}")
        assert elems == 1024 and nbytes == 2048


class TestRoofline:
    def test_terms_and_dominance(self):
        cfg = get_config("yi-6b")
        shape = get_shape("train_4k")
        t = compute_terms(cfg, shape, 256, flops_per_device=1e15,
                          bytes_per_device=1e13, collective_bytes_dev=1e11)
        assert t.compute_s == pytest.approx(1e15 / PEAK_FLOPS)
        assert t.dominant == "memory"
        assert 0 < t.roofline_fraction <= 1

    def test_model_flops_train_scales_with_tokens(self):
        cfg = get_config("qwen2.5-3b")
        f_train = model_flops_per_step(cfg, get_shape("train_4k"), 256)
        f_decode = model_flops_per_step(cfg, get_shape("decode_32k"), 256)
        # train processes 1M tokens with fwd+bwd; decode 128 tokens fwd-only
        assert f_train > 1000 * f_decode
        # 6·N·D lower bound (attention term only adds)
        n = cfg.active_param_count()
        assert f_train >= 6.0 * n * 256 * 4096

    def test_moe_uses_active_params(self):
        cfg = get_config("moonlight-16b-a3b")
        assert cfg.active_param_count() < 0.3 * cfg.param_count()

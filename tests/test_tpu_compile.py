"""The main path's Pallas kernels compile for a TPU v5e at their real widths.

Nothing runs: the TPU compiler installed with JAX compiles each kernel for a
described (not attached) v5e chip, which refuses what interpret mode
accepts — block shapes off the (8, 128) tiling, slices inside a tile. The
topology is described inside a fixture, never while a module is imported,
so every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.page_copy import page_copy, page_move

QWEN = get_config("qwen2.5-3b")
KV_ROWS = QWEN.num_layers * 1024  # one row per (layer, slot) of a 1024-slot pool
MOON = dataclasses.replace(get_config("moonlight-16b-a3b"), experts_held=8)
MOON_SLOTS = 12_288  # bench/configs/moonlight_ep8.json's KV pool
DATA_PLANE_ROWS = 458_752 + 65_536 + 1  # paper geometry: slow + fast + trash


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args):
    text = fn.lower(*args, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
    # the pool is aliased in place: no relayout copy of it around the kernel
    assert " copy(" not in text


@pytest.mark.parametrize("name,row,dtype,m", [
    ("data_plane", (128,), jnp.float32, 2048),
    ("qwen_kv", (16, QWEN.num_kv_heads, QWEN.d_head), jnp.bfloat16, QWEN.num_layers * 64),
    ("qwen_quest_summary", (QWEN.num_kv_heads, QWEN.d_head), jnp.float32, QWEN.num_layers * 64),
    ("moonlight_latent", (16, 640), jnp.bfloat16, MOON.num_layers * 64),
])
def test_page_move_compiles(one_chip, name, row, dtype, m):
    rows = {"data_plane": DATA_PLANE_ROWS,
            "moonlight_latent": MOON.num_layers * MOON_SLOTS}.get(name, KV_ROWS)
    pool = jax.ShapeDtypeStruct((rows, *row), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    _compile(page_move, (pool, ids, ids))


def test_mla_decode_step_updates_the_latent_pool_in_place(one_chip, monkeypatch):
    """moonlight_ep8's decode step (32 lanes, 512-page tables, 8 experts
    held, a 12,288-page latent pool) compiles for the v5e with the pool
    aliased, the paged kernel reading it in place, no [B, n_p * page, W]
    gather of the table's pages, and temporaries below 0.1 GB (the gathered
    pages alone were 0.34 GB). A pool whose rows are not whole 128-lane
    tiles, or a scatter into it by two index arrays, makes XLA copy all
    6.8 GB of it."""
    from repro.kernels import ops
    from repro.kvcache.paged import lane_width
    from repro.models.model import get_model
    from repro.serving.paged_model import paged_decode_step

    monkeypatch.setattr(ops, "interpret", lambda: False)  # the host's backend is the CPU

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          jax.eval_shape(get_model(MOON).init, jax.random.PRNGKey(0)))
    B, n_p, W = 32, 512, lane_width(MOON.latent_dim)
    pool = spec((MOON.num_layers, MOON_SLOTS, 16, W), jnp.bfloat16)
    table = spec((B, n_p), jnp.int32)
    lane = spec((B,), jnp.int32)
    compiled = paged_decode_step.lower(
        params, lane, lane, table, table, spec((B,), jnp.bool_), (pool,),
        num_logical_pages=MOON_SLOTS, cfg=MOON, quest_pages=n_p).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text
    assert f"bf16[{B},{n_p * 16},{W}]" not in text and f"bf16[{B},{n_p},16,{W}]" not in text
    pool_bytes = MOON.num_layers * MOON_SLOTS * 16 * W * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < 0.1e9


def test_latent_paged_attention_compiles_at_the_cells_shapes(one_chip):
    """The kernel alone: 32 lanes of 16 absorbed heads over 512-page tables
    into the [27, 12288, 16, 640] bf16 pool, which stays where it is."""
    from repro.kernels.paged_attention import paged_attention

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B, nh, W = 32, MOON.num_heads, 640
    pool = spec((MOON.num_layers, MOON_SLOTS, 16, W), jnp.bfloat16)
    compiled = paged_attention.lower(
        spec((B, nh, W), jnp.bfloat16), pool, spec((), jnp.int32), spec((B, 512), jnp.int32),
        spec((B,), jnp.int32), spec((B, W), jnp.bfloat16), sm_scale=0.072,
        v_width=MOON.kv_lora_rank, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(rf"= bf16\[{MOON.num_layers},{MOON_SLOTS},16,{W}\]\S* copy\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_page_copy_compiles_at_data_plane_width(one_chip):
    m = 2048
    staging = jax.ShapeDtypeStruct((m, 128), jnp.float32, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((DATA_PLANE_ROWS, 128), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    _compile(page_copy, (staging, pool, ids, ids))


def test_policy_tick_compiles_gather_free_at_paper_geometry(one_chip):
    """The fused policy tick at the benchmark's shape (458,752 pages, 16
    tenant slots, the 4,096-entry queue, owner segments on) compiles for the
    v5e with no gather that outputs a page-length array (the owner-segment
    permutation's, about 7 ns an element on the chip) and no [T, P] buffer
    outside a fusion: its temporaries stay below one [16, P] bool."""
    from repro.core import policy
    from repro.core.manager import CentralManager

    P, T = 458_752, 16
    m = CentralManager(num_pages=P, fast_capacity=65_536, migration_budget=2048, max_tenants=T,
                       sample_period=1, queue_size=4096, migration_bandwidth=2048)
    m._ensure_segs()
    shape = lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)  # noqa: E731
                       if hasattr(x, "shape") else x)
    compiled = policy._jitted_epoch_step().lower(
        jax.tree.map(shape, m._state), jax.tree.map(shape, m.params), max_tenants=T,
        plan_size=m.plan_size, exact_sampling=False, count_clamp=policy.COUNT_CLAMP,
    ).compile()
    assert not re.search(rf"= \w+\[{P}\]\S* gather\(", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < T * P

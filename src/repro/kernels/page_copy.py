"""Page-migration copy kernels — Pallas TPU (the I/OAT DMA-engine analogue).

A pool is an array ``[P, *row]`` whose leading axis indexes pages; a page is
one row ``pool[i]`` of any shape. The pools stay in HBM (``memory_space=ANY``)
and every planned row is one DMA issued by the kernel: no VMEM round trip,
no vector compute. Slicing only the leading axis keeps each DMA off the TPU
tiling of the row's last two dims, so any row shape works — except a 2-D
pool whose row is a single tiled sublane, which Mosaic accepts only for a
32-bit row exactly one lane tile (128) wide. Give wider or 16-bit rows a
second row axis (``[P, r, c]``).

Contract: ids must be in-range. Fixed-size plans pad with a reserved trash
row (by convention the LAST row of the destination pool), mirroring how the
MaxMem migration planner emits fixed-size plans. The trash row's content
afterwards is unspecified; only its isolation is guaranteed.

The destination pool is donated (input_output_aliased): the copy is in-place,
like the DMA engine the paper offloads to.

Both entry points take ``interpret`` without a default; ``kernels/ops.py``
chooses it once from the backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _for_each(n, fn):
    def body(i, carry):
        fn(i)
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _move_kernel(src_ids, dst_ids, pool_hbm, out_hbm, stage_hbm, sem):
    # pool_hbm and out_hbm alias one buffer; every access goes through out
    del pool_hbm
    m = src_ids.shape[0]

    def gather(i):
        return pltpu.make_async_copy(out_hbm.at[src_ids[i]], stage_hbm.at[i], sem.at[0])

    def scatter(i):
        return pltpu.make_async_copy(stage_hbm.at[i], out_hbm.at[dst_ids[i]], sem.at[1])

    # every read lands before the first write: gather semantics
    _for_each(m, lambda i: gather(i).start())
    _for_each(m, lambda i: gather(i).wait())
    _for_each(m, lambda i: scatter(i).start())
    _for_each(m, lambda i: scatter(i).wait())


def _copy_kernel(src_ids, dst_ids, src_hbm, dst_hbm, out_hbm, sem):
    del dst_hbm  # aliased with out_hbm
    m = src_ids.shape[0]

    def copy(i):
        return pltpu.make_async_copy(src_hbm.at[src_ids[i]], out_hbm.at[dst_ids[i]], sem.at[0])

    _for_each(m, lambda i: copy(i).start())
    _for_each(m, lambda i: copy(i).wait())


_ANY = pl.BlockSpec(memory_space=pl.ANY)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def page_move(
    pool: jax.Array,  # [P, *row] (donated; in-place moves)
    src_ids: jax.Array,  # [M] int32
    dst_ids: jax.Array,  # [M] int32
    *,
    interpret: bool,
) -> jax.Array:
    """Intra-pool page moves: pool[dst_ids[i]] = pool[src_ids[i]].

    GATHER semantics: every read sees the pre-plan pool, so a swap
    (src=[a, b], dst=[b, a]) is exact and a plan may write a row an earlier
    entry read (slot reuse). The rows are staged through an HBM buffer of
    M rows: all M gathers complete before the first scatter starts."""
    m = src_ids.shape[0]
    row = pool.shape[1:]
    out, _ = pl.pallas_call(
        _move_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[_ANY],
            out_specs=[_ANY, _ANY],
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
            jax.ShapeDtypeStruct((m, *row), pool.dtype),  # staging rows
        ],
        input_output_aliases={2: 0},  # pool (after 2 scalar args) -> out
        interpret=interpret,
        name="page_move",
    )(src_ids, dst_ids, pool)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def page_copy(
    src_pool: jax.Array,  # [Ps, *row]
    dst_pool: jax.Array,  # [Pd, *row] (donated)
    src_ids: jax.Array,  # [M] int32
    dst_ids: jax.Array,  # [M] int32
    *,
    interpret: bool,
) -> jax.Array:
    """Cross-pool copies: dst_pool[dst_ids[i]] = src_pool[src_ids[i]], one
    DMA per entry straight from the source pool into the destination."""
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[_ANY, _ANY],
            out_specs=_ANY,
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={3: 0},  # dst_pool (arg idx incl. 2 scalar args) -> out
        interpret=interpret,
        name="page_copy",
    )(src_ids, dst_ids, src_pool, dst_pool)

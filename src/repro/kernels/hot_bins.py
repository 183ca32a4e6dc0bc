"""Hotness accumulate + bin kernel — Pallas TPU (MaxMem §3.2 hot path).

Turns a batch of sampled page ids into per-page counters and heat-bin ids.
Scatter-add is pathological on TPU, so the bincount is computed densely: the
grid tiles the page axis; each tile compares the whole id vector against its
page range (broadcast compare -> one-hot) and row-reduces. The compare+reduce
feeds the VPU/MXU instead of a serial scatter unit — this is the paper's
"binning" mechanism restated as dense linear algebra (DESIGN.md §2).

Fused in the same pass: counts_out = counts_in + bincount(ids) and
bin id = clip(floor(log2(count)) + 1, 0, num_bins-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hot_bins_kernel(
    ids_ref,  # [N, 1] int32 (whole sample vector, every tile)
    counts_ref,  # [tile] int32
    out_counts_ref,  # [tile] int32
    out_bins_ref,  # [tile] int32
    *,
    tile: int,
    num_bins: int,
    n_chunk: int,
):
    t = pl.program_id(0)
    base = t * tile
    N = ids_ref.shape[0]
    page_idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)  # [1, tile]

    def body(c, acc):
        ids = ids_ref[pl.ds(c * n_chunk, n_chunk), :]  # [chunk, 1]
        onehot = (ids == page_idx).astype(jnp.int32)  # [chunk, tile]
        return acc + onehot.sum(axis=0)

    nchunks = N // n_chunk
    hist = jax.lax.fori_loop(0, nchunks, body, jnp.zeros((tile,), jnp.int32))
    counts = counts_ref[...] + hist
    out_counts_ref[...] = counts
    # bin = clip(floor(log2(count)) + 1, 0, num_bins-1); count==0 -> 0
    fl = 31 - jax.lax.clz(jnp.maximum(counts, 1))
    bins = jnp.clip(jnp.where(counts > 0, fl + 1, 0), 0, num_bins - 1)
    out_bins_ref[...] = bins.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_bins", "tile", "n_chunk", "interpret"))
def hot_bins(
    page_ids: jax.Array,  # [N] int32; entries < 0 ignored
    counts_in: jax.Array,  # [P] int32
    *,
    num_bins: int = 6,
    tile: int = 512,
    n_chunk: int = 1024,
    interpret: bool,
):
    """Returns (counts_out [P] i32, bins [P] i32)."""
    P = counts_in.shape[0]
    N = page_ids.shape[0]
    pad_p = (-P) % tile
    if pad_p:
        counts_in = jnp.pad(counts_in, (0, pad_p))
    pad_n = (-N) % n_chunk
    ids = jnp.where(page_ids >= 0, page_ids, -1)
    if pad_n:
        ids = jnp.pad(ids, (0, pad_n), constant_values=-1)
    ids2d = ids[:, None]

    kernel = functools.partial(
        _hot_bins_kernel, tile=tile, num_bins=num_bins, n_chunk=min(n_chunk, ids.shape[0])
    )
    counts, bins_arr = pl.pallas_call(
        kernel,
        grid=((P + pad_p) // tile,),
        in_specs=[
            pl.BlockSpec((ids2d.shape[0], 1), lambda t: (0, 0)),  # full ids each tile
            pl.BlockSpec((tile,), lambda t: (t,)),
        ],
        out_specs=[
            pl.BlockSpec((tile,), lambda t: (t,)),
            pl.BlockSpec((tile,), lambda t: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((P + pad_p,), jnp.int32),
            jax.ShapeDtypeStruct((P + pad_p,), jnp.int32),
        ],
        interpret=interpret,
    )(ids2d, counts_in)
    return counts[:P], bins_arr[:P]

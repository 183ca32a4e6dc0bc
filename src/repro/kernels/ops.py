"""Public kernel entry points.

Dispatch policy, decided here and nowhere else: on a TPU backend the Pallas
kernels compile natively; on the CPU backend (tests, CI) they run in the
Pallas interpreter, which executes the kernel body in Python with bit-level
semantics. Any other backend is an error — there is no silent fallback to
the interpreter or to the pure-jnp references in ``ref.py``, which stay the
tests' correctness oracles.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.hot_bins import hot_bins as _hot_bins
from repro.kernels.page_copy import page_copy as _page_copy
from repro.kernels.page_copy import page_move as _page_move
from repro.kernels.paged_attention import paged_attention as _paged


def interpret() -> bool:
    """False on TPU (compiled kernels), True on CPU (interpreter)."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"Pallas kernels need a TPU or the CPU interpreter, not {backend!r}")
    return backend == "cpu"


def flash_attention(q, k, v, *, causal=True, sliding_window=0, q_blk=256, kv_blk=256):
    return _flash(
        q, k, v, causal=causal, sliding_window=sliding_window,
        q_blk=q_blk, kv_blk=kv_blk, interpret=interpret(),
    )


def paged_attention(q, pool, layer, tables, lens, new, *, sm_scale, v_width, block_pages=32):
    """Latent decode attention read in place from the paged pool."""
    return _paged(q, pool, layer, tables, lens, new, sm_scale=sm_scale, v_width=v_width,
                  block_pages=block_pages, interpret=interpret())


def hot_bins(page_ids, counts_in, *, num_bins=6, tile=512, n_chunk=1024):
    return _hot_bins(
        page_ids, counts_in, num_bins=num_bins, tile=tile, n_chunk=n_chunk,
        interpret=interpret(),
    )


def page_copy(src_pool, dst_pool, src_ids, dst_ids):
    """Cross-pool row copies (staging -> pool writes)."""
    return _page_copy(src_pool, dst_pool, src_ids, dst_ids, interpret=interpret())


def page_move(pool, src_ids, dst_ids):
    """Intra-pool in-place moves (MaxMem migration executor path)."""
    return _page_move(pool, src_ids, dst_ids, interpret=interpret())

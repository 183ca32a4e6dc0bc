"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs ref.py oracles.

Kernels run through ``ops`` (the interpreter on the CPU); the oracle is pure jnp.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # clean checkout: deterministic fallback sweep
    from _hypothesis_fallback import given, settings, st

from repro.kernels import ref
from repro.kernels.ops import flash_attention, hot_bins, page_copy, page_move, paged_attention

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[dt]


class TestFlashAttention:
    @pytest.mark.parametrize("B,nh,nkv,Sq,Skv,dh", [
        (2, 4, 2, 128, 128, 64),
        (1, 8, 8, 96, 96, 128),   # MHA, non-multiple of block
        (2, 4, 1, 64, 192, 64),   # MQA, Sq < Skv
        (1, 2, 2, 300, 300, 64),  # ragged padding path
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep(self, B, nh, nkv, Sq, Skv, dh, dtype, causal):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, nh, Sq, dh), dtype)
        k = jax.random.normal(ks[1], (B, nkv, Skv, dh), dtype)
        v = jax.random.normal(ks[2], (B, nkv, Skv, dh), dtype)
        out = flash_attention(q, k, v, causal=causal, q_blk=64, kv_blk=64)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype),
        )

    def test_sliding_window(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, sliding_window=64, q_blk=64, kv_blk=64)
        want = ref.flash_attention_ref(q, k, v, causal=True, sliding_window=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)

    def test_block_size_invariance(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 2, 128, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32)
        a = flash_attention(q, k, v, q_blk=32, kv_blk=32)
        b = flash_attention(q, k, v, q_blk=128, kv_blk=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


PAGE, W, V = 4, 256, 128  # latent rows two lane tiles wide, values the first tile
BLOCK = 3  # pages per kernel block: 12 tokens


def _latent_case(dtype, lens, *, layer=0, holes=(), n_p=12, seed=0):
    """Queries, new rows, a [3, 64, PAGE, W] pool, tables and lengths. Every
    slot no table names below its lane's length holds NaN (slot 0 among
    them): one read of it, even masked, turns the output NaN."""
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    B, nh, n_slots = len(lens), 4, 64
    q = jax.random.normal(ks[0], (B, nh, W), dtype)
    new = jax.random.normal(ks[2], (B, W), dtype)
    tables = np.zeros((B, n_p), np.int32)  # past the length: slot 0, all NaN
    free = list(rng.permutation(np.arange(1, n_slots)))
    for b, n in enumerate(lens):
        for p in range(-(-n // PAGE)):
            tables[b, p] = free.pop()
    for b, p in holes:
        tables[b, p] = -1
    read = sorted({int(t) for b, n in enumerate(lens) for t in tables[b, :-(-n // PAGE)] if t >= 0})
    pool = np.full((3, n_slots, PAGE, W), np.nan, np.float32)
    pool[:, read] = np.asarray(jax.random.normal(ks[1], (3, len(read), PAGE, W)), np.float32)
    return (q, jnp.asarray(pool, dtype), layer, jnp.asarray(tables),
            jnp.asarray(np.asarray(lens, np.int32)), new)


def _latent(args, block_pages=BLOCK):
    q, pool, layer, tables, lens, new = args
    out = paged_attention(q, pool, jnp.int32(layer), tables, lens, new, sm_scale=0.125,
                          v_width=V, block_pages=block_pages)
    return np.asarray(out, np.float32)


def _latent_ref(args):
    q, pool, layer, tables, lens, new = args
    return np.asarray(ref.paged_attention_ref(q, pool, layer, tables, lens, new, sm_scale=0.125,
                                              v_width=V), np.float32)


class TestPagedAttention:
    """The latent (MQA-form) paged-decode kernel against the plain gather."""

    @pytest.mark.parametrize("lens,layer,holes", [
        ([1, 2], 0, ()),  # one and two tokens in the pool
        ([8, 4, 16], 1, ()),  # each ends on a page boundary
        ([12, 24, 36], 2, ()),  # each ends on a block boundary
        ([30, 17], 1, ((0, 2), (1, 4))),  # a -1 entry below the length
        ([45, 0, 3, 33], 2, ((3, 8),)),  # an idle lane beside ragged ones
    ], ids=["one_token", "page_end", "block_end", "hole", "ragged_idle"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, lens, layer, holes, dtype):
        args = _latent_case(dtype, lens, layer=layer, holes=holes)
        out = _latent(args)
        assert np.isfinite(out).all(), "a page past a length, or a -1 entry, was read"
        np.testing.assert_allclose(out, _latent_ref(args), atol=_tol(dtype), rtol=_tol(dtype))

    def test_idle_lane_reads_only_the_new_token(self):
        """Length 0 reads no page: the lane attends to its new token alone."""
        args = _latent_case(jnp.float32, [0, 5], layer=2)
        out, new = _latent(args), np.asarray(args[-1])
        np.testing.assert_allclose(out[0], np.broadcast_to(new[0, :V], out[0].shape), atol=1e-6)

    def test_new_token_merge_equals_it_in_the_pool(self):
        """The token not yet in the pool, folded into the softmax, gives what
        the same token written into its page slot gives."""
        q, pool, layer, tables, lens, new = args = _latent_case(jnp.float32, [13, 26], layer=1)
        p = np.asarray(pool).copy()
        for b, n in enumerate(np.asarray(lens)):
            p[layer, tables[b, n // PAGE], n % PAGE] = np.asarray(new[b])
        written = _latent_ref((q, jnp.asarray(p), layer, tables, lens + 1, None))
        np.testing.assert_allclose(_latent(args), written, atol=2e-5, rtol=2e-5)

    def test_block_size_invariance(self):
        """One page a block, or 32 (the served size: a -1 entry in its last
        page sets the top bit of the block's hole mask)."""
        args = _latent_case(jnp.float32, [45, 7, 130], layer=1, holes=((2, 31),), n_p=40)
        one, wide = _latent(args, block_pages=1), _latent(args, block_pages=32)
        assert np.isfinite(wide).all()
        np.testing.assert_allclose(one, wide, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(wide, _latent_ref(args), atol=2e-5, rtol=2e-5)


class TestHotBins:
    @pytest.mark.parametrize("N,P,tile", [(100, 64, 64), (1000, 512, 128), (257, 130, 64), (64, 4096, 512)])
    def test_sweep(self, N, P, tile):
        rng = np.random.default_rng(N + P)
        ids = rng.integers(-1, P, N).astype(np.int32)
        cin = rng.integers(0, 40, P).astype(np.int32)
        c, b = hot_bins(jnp.asarray(ids), jnp.asarray(cin), tile=tile, n_chunk=128)
        cr, br = ref.hot_bins_ref(jnp.asarray(ids), jnp.asarray(cin), 6)
        assert (np.asarray(c) == np.asarray(cr)).all()
        assert (np.asarray(b) == np.asarray(br)).all()

    def test_interpret_auto_selects_from_backend(self):
        """ops compiles on TPU and interprets on the CPU; the result must be
        identical to an explicit call either way."""
        from repro.kernels import hot_bins as hb
        from repro.kernels import ops

        expect = jax.default_backend() != "tpu"
        assert ops.interpret() == expect
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(-1, 130, 257), jnp.int32)
        cin = jnp.asarray(rng.integers(0, 40, 130), jnp.int32)
        c_auto, b_auto = hot_bins(ids, cin, tile=64, n_chunk=128)
        c_exp, b_exp = hb.hot_bins(ids, cin, tile=64, n_chunk=128, interpret=expect)
        assert (np.asarray(c_auto) == np.asarray(c_exp)).all()
        assert (np.asarray(b_auto) == np.asarray(b_exp)).all()

    @pytest.mark.parametrize("N,P,tile", [(333, 130, 64), (1023, 777, 256), (65, 513, 512)])
    def test_bincount_parity_non_multiple_of_tile(self, N, P, tile):
        """Exact jnp.bincount parity where neither the page count nor the
        sample count is a multiple of the kernel tiling (padding paths)."""
        rng = np.random.default_rng(N * 31 + P)
        ids = rng.integers(-1, P, N).astype(np.int32)
        cin = rng.integers(0, 40, P).astype(np.int32)
        c, b = hot_bins(jnp.asarray(ids), jnp.asarray(cin), tile=tile, n_chunk=128)
        valid = jnp.asarray(ids[ids >= 0])
        expect = jnp.asarray(cin) + jnp.bincount(valid, length=P).astype(jnp.int32)
        assert (np.asarray(c) == np.asarray(expect)).all()
        # fused bin ids: clip(floor(log2(count)) + 1, 0, num_bins-1)
        ce = np.asarray(expect)
        fl = np.where(ce > 0, np.floor(np.log2(np.maximum(ce, 1))).astype(np.int32), -1)
        assert (np.asarray(b) == np.clip(fl + 1, 0, 5)).all()

    @settings(max_examples=20, deadline=None)
    @given(
        ids=st.lists(st.integers(-1, 63), min_size=1, max_size=200),
        seed=st.integers(0, 100),
    )
    def test_property_matches_numpy_bincount(self, ids, seed):
        P = 64
        rng = np.random.default_rng(seed)
        cin = rng.integers(0, 10, P).astype(np.int32)
        ids_np = np.asarray(ids, np.int32)
        c, _ = hot_bins(jnp.asarray(ids_np), jnp.asarray(cin), tile=64, n_chunk=64)
        expect = cin + np.bincount(ids_np[ids_np >= 0], minlength=P).astype(np.int32)
        assert (np.asarray(c) == expect).all()


class TestPageCopy:
    @pytest.mark.parametrize("Ps,Pd,E,M", [(16, 16, 128, 5), (8, 32, 256, 8), (4, 4, 64, 1)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
    def test_sweep(self, Ps, Pd, E, M, dtype):
        rng = np.random.default_rng(Ps * 7 + M)
        if dtype == jnp.int32:
            src = jnp.asarray(rng.integers(0, 100, (Ps, E)), dtype)
            dst = jnp.asarray(rng.integers(0, 100, (Pd, E)), dtype)
        else:
            src = jnp.asarray(rng.normal(size=(Ps, E)), dtype)
            dst = jnp.asarray(rng.normal(size=(Pd, E)), dtype)
        sid = jnp.asarray(rng.choice(Ps, M, replace=True), jnp.int32)
        did = jnp.asarray(rng.choice(Pd - 1, M, replace=False), jnp.int32)
        want = ref.page_copy_ref(src, dst, sid, did)
        out = page_copy(src, jnp.copy(dst), sid, did)
        assert (np.asarray(out) == np.asarray(want)).all()

    def test_untouched_rows_preserved(self):
        src = jnp.ones((4, 32), jnp.float32)
        dst = jnp.zeros((8, 32), jnp.float32)
        out = page_copy(src, jnp.copy(dst), jnp.asarray([1], jnp.int32), jnp.asarray([3], jnp.int32))
        assert float(out[3].sum()) == 32.0
        assert float(out.sum()) == 32.0  # only one row written

    @pytest.mark.parametrize("Ps,Pd,E,M", [
        (7, 13, 100, 3),    # nothing a multiple of any tile
        (5, 9, 257, 7),     # odd row width beyond one lane tile
        (3, 3, 33, 2),      # tiny pools, narrow rows
        (17, 31, 384, 17),  # M > Pd/2, E a non-128 multiple
    ])
    def test_non_multiple_of_tile_sizes(self, Ps, Pd, E, M):
        """Interpret-mode parity at shapes where neither the pool heights
        nor the row width align with TPU tiling — the data plane uses
        whatever row_elems the caller configured."""
        rng = np.random.default_rng(Ps * 101 + E)
        src = jnp.asarray(rng.normal(size=(Ps, E)), jnp.float32)
        dst = jnp.asarray(rng.normal(size=(Pd, E)), jnp.float32)
        sid = jnp.asarray(rng.choice(Ps, M, replace=True), jnp.int32)
        did = jnp.asarray(rng.choice(Pd, M, replace=False), jnp.int32)
        want = ref.page_copy_ref(src, dst, sid, did)
        out = page_copy(src, jnp.copy(dst), sid, did)
        assert (np.asarray(out) == np.asarray(want)).all()

    def test_trash_row_padding_contract(self):
        """Fixed-size plans pad with the reserved LAST destination row: the
        padded entries must leave every real row untouched, no matter what
        source row the padding names."""
        rng = np.random.default_rng(0)
        src = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
        dst = jnp.asarray(rng.normal(size=(10, 64)), jnp.float32)
        trash = 9
        # 2 real moves + 3 pad entries aimed at the trash row
        sid = jnp.asarray([2, 5, 0, 3, 1], jnp.int32)
        did = jnp.asarray([1, 4, trash, trash, trash], jnp.int32)
        out = np.asarray(page_copy(src, jnp.copy(dst), sid, did))
        src_np, dst_np = np.asarray(src), np.asarray(dst)
        assert (out[1] == src_np[2]).all()
        assert (out[4] == src_np[5]).all()
        keep = [0, 2, 3, 5, 6, 7, 8]
        assert (out[keep] == dst_np[keep]).all()
        # the interpreter issues the DMAs in order, so the trash row holds
        # the LAST padded source; on the chip its content is unspecified by
        # the contract, only its isolation matters
        assert (out[trash] == src_np[1]).all()


class TestPageMove:
    def test_intra_pool_moves_match_ref(self):
        rng = np.random.default_rng(5)
        pool = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
        sid = jnp.asarray([0, 1, 2], jnp.int32)
        did = jnp.asarray([8, 9, 10], jnp.int32)
        want = ref.page_move_ref(pool, sid, did)
        out = page_move(jnp.copy(pool), sid, did)
        assert (np.asarray(out) == np.asarray(want)).all()

    def test_write_after_read_is_safe(self):
        """A plan may WRITE a row that an earlier step READ (slot reuse)."""
        pool = jnp.asarray(np.arange(8 * 4).reshape(8, 4), jnp.float32)
        # demote: row1 -> row6 (reads 1), promote: row5 -> row1 (writes 1)
        sid = jnp.asarray([1, 5], jnp.int32)
        did = jnp.asarray([6, 1], jnp.int32)
        out = page_move(jnp.copy(pool), sid, did)
        assert (np.asarray(out[6]) == np.asarray(pool[1])).all()
        assert (np.asarray(out[1]) == np.asarray(pool[5])).all()

    @pytest.mark.parametrize("Pr,E,M", [(11, 100, 4), (9, 257, 5), (5, 33, 3)])
    def test_non_multiple_of_tile_sizes(self, Pr, E, M):
        rng = np.random.default_rng(Pr * 7 + E)
        pool = jnp.asarray(rng.normal(size=(Pr, E)), jnp.float32)
        sid = jnp.asarray(rng.choice(Pr - 1, M, replace=False), jnp.int32)
        did = jnp.asarray(
            rng.permutation(Pr - 1)[:M], jnp.int32
        )
        want = ref.page_move_ref(pool, sid, did)
        out = page_move(jnp.copy(pool), sid, did)
        assert (np.asarray(out) == np.asarray(want)).all()

    def test_trash_row_padding_contract(self):
        """The data plane pads intra-pool plans with trash->trash self-copy
        entries; real rows must be untouched by the padding."""
        rng = np.random.default_rng(1)
        pool = jnp.asarray(rng.normal(size=(8, 48)), jnp.float32)
        trash = 7
        sid = jnp.asarray([0, trash, trash, trash], jnp.int32)
        did = jnp.asarray([3, trash, trash, trash], jnp.int32)
        out = np.asarray(page_move(jnp.copy(pool), sid, did))
        pool_np = np.asarray(pool)
        assert (out[3] == pool_np[0]).all()
        keep = [0, 1, 2, 4, 5, 6, trash]
        assert (out[keep] == pool_np[keep]).all()

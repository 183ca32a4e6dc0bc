"""The traffic generator: the same seed gives the same schedule, another
seed the same sizes in another order, and arrivals keep the ring consistent."""
import numpy as np
import pytest

import bench_tiny
from bench.generator import (build_schedule, content_rows, content_rows_jnp, law, samples_per_epoch,
                             seed_words)

BIG = 2**31 + 977


@pytest.mark.parametrize("mix", ["growth", "arrivals"])
def test_same_seed_same_schedule(mix):
    cfg, m = bench_tiny.tiny_config(), bench_tiny.tiny_mix(mix)
    a, b = build_schedule(cfg, m, BIG), build_schedule(cfg, m, BIG)
    assert np.array_equal(a.ring, b.ring)
    assert [a.events_at(e) for e in range(200)] == [b.events_at(e) for e in range(200)]
    c = build_schedule(cfg, m, BIG + 1)
    assert not np.array_equal(a.ring, c.ring)
    # another seed changes where the accesses fall, not how many tenants, pages and events there are
    assert [t["pages"] for t in a.tenants] == [t["pages"] for t in c.tenants]
    assert [a.events_at(e) for e in range(200)] == [c.events_at(e) for e in range(200)]
    assert abs(int(a.ring.sum()) - int(c.ring.sum())) < 0.05 * a.ring.sum()


def test_samples_stay_on_owned_pages():
    cfg = bench_tiny.tiny_config()
    s = build_schedule(cfg, bench_tiny.tiny_mix("arrivals"), 3)
    owned = sum(t["pages"] for t in s.tenants)
    assert s.ring[:, owned:].sum() == 0
    for k, ph in enumerate(s.cycle):
        want = sum(samples_per_epoch(t, cfg["access_model"])
                   for t in s.tenants if t["name"] in ph["tenants"])
        per_epoch = s.ring[k * s.draws:(k + 1) * s.draws].sum(axis=1)
        assert np.all(np.abs(per_epoch - want) < 0.1 * want)
        for t in s.tenants:  # an absent tenant reports nothing
            if t["name"] not in ph["tenants"]:
                assert s.ring[k * s.draws:(k + 1) * s.draws, s.pages_of(t["name"])].sum() == 0


def test_access_rate_follows_the_slow_tier():
    """FlexKVS: 4 threads, 16 KiB values at 300 ns + 16,384 B / 30 GB/s, 1 in 100 sampled."""
    model = bench_tiny.load_json("configs", "paper_fig8")["access_model"]
    kvs = {"threads": 4, "value_bytes": 16384}
    assert samples_per_epoch(kvs, model) == pytest.approx(4 / (300e-9 + 16384 / 30e9) / 100)


def test_shift_moves_the_hot_set_to_disjoint_regions():
    """growth: each cycle's 42 GB hot set sits inside the 74 GB one that
    follows it, and on pages no other cycle's hot set uses."""
    cfg = bench_tiny.tiny_config()
    s = build_schedule(cfg, bench_tiny.tiny_mix("growth"), 5)
    pages = s.pages_of("flexkvs")
    hot, touched = [], []
    for k, ph in enumerate(s.cycle):
        c = s.ring[k * s.draws:(k + 1) * s.draws, pages].sum(axis=0, dtype=np.int64)
        n_hot = int(round(ph["tenants"]["flexkvs"]["hot_fraction"] * len(pages)))
        hot.append(set(np.argsort(-c)[: n_hot // 2].tolist()))
        touched.append(set(np.flatnonzero(c).tolist()))
    for k in range(0, len(hot), 2):
        assert len(hot[k] - touched[k + 1]) < 0.1 * len(hot[k])  # the hot set grows, it keeps its pages
        for j in range(k + 2, len(hot), 2):
            assert len(hot[k + 1] & hot[j + 1]) < 0.2 * len(hot[k + 1])


def test_hot_law_nests_and_sums_to_one():
    n, perm = 1000, np.random.default_rng(0).permutation(1000)
    small = law("hot").weights(n, {"hot_fraction": 0.1, "hot_share": 0.9, "offset": 0.25}, perm)
    big = law("hot").weights(n, {"hot_fraction": 0.2, "hot_share": 0.9, "offset": 0.25}, perm)
    assert small.sum() == pytest.approx(1) and big.sum() == pytest.approx(1)
    assert set(np.flatnonzero(small > small.min())) <= set(np.flatnonzero(big > big.min()))
    with pytest.raises(ValueError, match="no access law"):
        law("no_such_law")


def test_churn_generations_follow_the_boundaries():
    s = build_schedule(bench_tiny.tiny_config(), bench_tiny.tiny_mix("arrivals"), 11)
    assert s.initial() == ["flexkvs", "gapbs", "gups"]
    seen = {t["name"]: 0 for t in s.tenants}
    present = set(s.initial())
    for e in range(10 * s.cycle_epochs):
        gone, come = s.events_at(e)
        assert set(gone) <= present and not set(come) & present
        present = (present - set(gone)) | set(come)
        for name in come:
            seen[name] += 1
        assert present == set(s.present(s.phase_of(e)))
        for name in seen:
            assert s.generation(name, e) == seen[name]
    assert seen == {"flexkvs": 0, "gapbs": 0, "gups": 9}
    # GUPS departs at the end of its 24 epochs and arrives again 8 later
    assert s.events_at(24) == (["gups"], []) and s.events_at(32) == ([], ["gups"])


def test_contents_agree_between_host_and_device():
    import jax.numpy as jnp

    pages = np.array([0, 1, 4095, 123457], np.int64)
    parity = np.array([0, 1, 0, 1])
    host = content_rows(BIG, pages, parity, 128)
    dev = np.asarray(content_rows_jnp(jnp.asarray(seed_words(BIG)), jnp.asarray(pages, jnp.int32),
                                      jnp.asarray(parity, jnp.int32), 128))
    assert np.array_equal(host.view(np.uint32), dev.view(np.uint32))
    assert np.all(host == np.floor(host)) and host.max() < 2**24
    assert not np.array_equal(host[0], content_rows(BIG, pages[:1], [1], 128)[0])

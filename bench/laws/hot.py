"""A hot set: ``hot_share`` of the tenant's samples on ``hot_fraction`` of
its pages, the rest spread evenly over the others (``core/simulator.py``'s
one-set ``WorkloadSpec``). The hot pages are scattered by the tenant's
permutation, starting ``offset`` (a fraction of the pages) into it: a hot set
that grows keeps its pages, and another offset puts it on other pages."""
import numpy as np


def weights(n: int, law: dict, perm: np.ndarray) -> np.ndarray:
    h = int(round(law["hot_fraction"] * n))
    first = int(round(law.get("offset", 0.0) * n))
    w = np.full(n, (1.0 - law["hot_share"]) / (n - h))
    w[perm[(first + np.arange(h)) % n]] = law["hot_share"] / h
    return w

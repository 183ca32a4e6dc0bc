"""Every page of the tenant equally likely (GUPS's random updates)."""
import numpy as np


def weights(n: int, law: dict, perm: np.ndarray) -> np.ndarray:
    return np.full(n, 1.0 / n)

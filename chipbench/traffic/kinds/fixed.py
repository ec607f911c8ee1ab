"""Every item the same ``value``."""
import numpy as np


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return np.full(n, int(spec["value"]), np.int32)

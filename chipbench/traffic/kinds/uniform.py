"""Each item's value uniform over ``range(values)``: every value gets
``n // values`` items, the remainder spread by the seed, in a
seed-shuffled order."""
import numpy as np


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    k = int(spec["values"])
    base = np.repeat(np.arange(k), n // k)
    extra = rng.permutation(k)[: n - base.size]
    return rng.permutation(np.concatenate([base, extra])).astype(np.int32)

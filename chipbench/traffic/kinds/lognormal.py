"""The lognormal distribution's ``n`` quantiles (``median``, ``sigma``),
clipped to ``[min, max]`` and rounded up to the next of ``round_up_to``,
in a seed-shuffled order."""
import math
from statistics import NormalDist

import numpy as np


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    raw = np.clip(np.ceil(raw), spec["min"], spec["max"])
    buckets = np.asarray(sorted(spec["round_up_to"]))
    idx = np.searchsorted(buckets, raw, side="left")
    return rng.permutation(buckets[idx]).astype(np.int32)

"""The Poisson draw the program documents for its sampler: step k's
logical batch is every example whose uniform draw is below q, the draws
coming from a Philox generator keyed by (seed, domain 1, k) with the seed
in the high 64 bits of the key and (domain << 56) | k in the low."""
from __future__ import annotations

import numpy as np

POISSON_DOMAIN = 1


def draw(seed: int, step: int, n: int, q: float) -> np.ndarray:
    key = ((int(seed) & ((1 << 64) - 1)) << 64) \
        | (POISSON_DOMAIN << 56) | int(step)
    u = np.random.Generator(np.random.Philox(key=key)).random(n)
    return np.nonzero(u < q)[0]

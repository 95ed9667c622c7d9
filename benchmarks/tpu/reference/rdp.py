"""Renyi DP of the Poisson-subsampled Gaussian mechanism at integer orders
(Mironov, Talwar and Zhang 2019, section 3.3), composed over steps and
turned into (eps, delta) with the bound of Balle et al. 2020 (Canonne,
Kamath and Steinke's form), minimised over the orders.  ``dtype`` sets the
precision of the arithmetic: float64 is the reference, float32 the
control."""
from __future__ import annotations

import math

import numpy as np

ORDERS = tuple(range(2, 65)) + (128, 256, 512)


def _rdp_one(q, sigma, alpha, dtype):
    k = np.arange(alpha + 1, dtype=dtype)
    log_comb = np.array([math.lgamma(alpha + 1) - math.lgamma(i + 1)
                         - math.lgamma(alpha - i + 1)
                         for i in range(alpha + 1)], dtype)
    terms = (log_comb + (alpha - k) * dtype(math.log1p(-q))
             + k * dtype(math.log(q)) + (k * k - k) / dtype(2 * sigma ** 2))
    m = terms.max()
    lse = m + np.log(np.exp(terms - m).sum())
    return max(float(lse), 0.0) / (alpha - 1)


def epsilon(q: float, sigma: float, steps: int, delta: float,
            dtype=np.float64) -> float:
    best = math.inf
    for a in ORDERS:
        r = steps * _rdp_one(q, sigma, a, dtype)
        eps = r + math.log((a - 1) / a) - (math.log(delta) + math.log(a)) \
            / (a - 1)
        best = min(best, eps)
    return float(dtype(best))

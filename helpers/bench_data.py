"""The Higgs-shaped synthetic dataset that ``chip_smoke.py`` trains on.

Stdlib + numpy only.
"""
from __future__ import annotations

import numpy as np


def make_higgs_like(n: int, f: int, seed: int = 7):
    """[n, f] float32 features + binary labels, HIGGS-shaped: 21 unit-
    gaussian "low-level" kinematic features and f-21 derived positive
    "high-level" features (products of low-level pairs plus noise), labels
    from a sparse linear logit. Matches the reference's headline Higgs
    experiment shape (binning/shape-equivalent, synthetic values)."""
    rng = np.random.RandomState(seed)
    X = np.empty((n, f), np.float32)
    low = min(21, f)
    X[:, :low] = rng.randn(n, low).astype(np.float32)
    for j in range(low, f):
        a, b = rng.randint(0, low, 2)
        X[:, j] = np.abs(X[:, a] * X[:, b] + rng.randn(n).astype(np.float32) * 0.5)
    w = rng.randn(f) * (rng.rand(f) > 0.3)
    logits = X @ w * 0.3 + rng.randn(n) * 2.0
    y = (logits > 0).astype(np.float32)
    return X, y

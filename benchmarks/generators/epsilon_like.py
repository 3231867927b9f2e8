"""An Epsilon-shaped set: dense standardised gaussian features, a label from
a linear logit on ``INFORMATIVE`` of them plus noise (the Pascal challenge's
Epsilon set is dense, standardised and nearly linearly separable; its values
are not public here, so only the shape is the source's)."""
import functools
from typing import Tuple

import numpy as np

from benchmarks import datagen

BLOCK = 1 << 14
INFORMATIVE = 200
NOISE = 0.7


@functools.lru_cache(maxsize=8)
def weights(features: int, recipe: int) -> np.ndarray:
    """Unit-length weights on ``INFORMATIVE`` of the features."""
    rng = np.random.default_rng([int(recipe), 0])
    w = np.zeros(features, np.float32)
    on = rng.choice(features, size=min(INFORMATIVE, features), replace=False)
    w[on] = rng.standard_normal(len(on))
    return w / np.linalg.norm(w)


def block(rows: int, features: int, index: int,
          recipe: int = 7) -> Tuple[np.ndarray, np.ndarray]:
    """Rows ``[index * BLOCK, min(rows, (index + 1) * BLOCK))`` of the set,
    in the set's own order."""
    w = weights(features, recipe)
    n = min(rows, (index + 1) * BLOCK) - index * BLOCK
    rng = np.random.default_rng([int(recipe), 2, int(index)])
    X = rng.standard_normal((n, features), dtype=np.float32)
    logits = X @ w + NOISE * rng.standard_normal(n, dtype=np.float32)
    return X, (logits > 0).astype(np.float32)


def make(rows: int, features: int, seed: int, recipe: int = 7,
         workers: int = 8) -> Tuple[np.ndarray, np.ndarray]:
    """[rows, features] float32 and [rows] float32 labels in {0, 1}."""
    return datagen.in_blocks(block, BLOCK, rows, features, seed, recipe, workers)

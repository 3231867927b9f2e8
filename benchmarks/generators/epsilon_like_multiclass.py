"""``epsilon_like``'s table with a label of ``classes`` classes.

The same table, value for value (``epsilon_like.block``, keyed by the
recipe); rows and columns stand where ``datagen.order`` puts them, as every
generator's do. Row ``i``'s label is ``argmax_k (x_i . w_k + NOISE e_ik)``:
the ``classes`` unit-length weight vectors lie on ``epsilon_like.weights``'
informative columns and are drawn from the recipe, and the gaussian noise
``e`` is drawn for each block from a key of its own, so a row's label does not
depend on where the seed puts it. The classes are alike by construction; at
200,000 x 2000 and five classes their shares read 0.2066, 0.1914, 0.1996,
0.2102, 0.1921 (recipe 7; recipes 8 and 9 lie within 0.194-0.210 too).
"""
import functools
from typing import Tuple

import numpy as np

from benchmarks import datagen

epsilon_like = datagen.generator("epsilon_like")
NOISE = epsilon_like.NOISE


@functools.lru_cache(maxsize=8)
def class_weights(features: int, recipe: int, classes: int) -> np.ndarray:
    """[classes, features]: unit-length rows on ``epsilon_like.weights``'
    columns."""
    on = np.flatnonzero(epsilon_like.weights(features, recipe))
    rng = np.random.default_rng([int(recipe), 4])
    w = np.zeros((classes, features), np.float32)
    w[:, on] = rng.standard_normal((classes, len(on)))
    return w / np.linalg.norm(w, axis=1, keepdims=True)


def block(rows: int, features: int, index: int, recipe: int = 7,
          classes: int = 5) -> Tuple[np.ndarray, np.ndarray]:
    """``epsilon_like.block``'s rows and their class, in the set's own order."""
    X, _ = epsilon_like.block(rows, features, index, recipe)
    rng = np.random.default_rng([int(recipe), 5, int(index)])
    logits = X @ class_weights(features, recipe, classes).T
    logits += NOISE * rng.standard_normal(logits.shape, dtype=np.float32)
    return X, np.argmax(logits, axis=1).astype(np.float32)


def make(rows: int, features: int, seed: int, recipe: int = 7, workers: int = 8,
         classes: int = 5) -> Tuple[np.ndarray, np.ndarray]:
    """[rows, features] float32 and [rows] float32 labels in ``[0, classes)``."""
    return datagen.in_blocks(functools.partial(block, classes=classes), epsilon_like.BLOCK,
                             rows, features, seed, recipe, workers)

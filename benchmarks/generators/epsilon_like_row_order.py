"""``epsilon_like``'s set, with a choice of who orders its rows.

The same table, value for value (``epsilon_like.block``, keyed by the
recipe). ``row_order`` ``"seed"`` is ``epsilon_like`` itself: rows and columns
stand where ``datagen.order`` puts them, as every generator's do.
``"recipe"``, which the configuration of sampled boosting gives: the seed
orders the columns alone and the rows stay as the recipe makes them.

Letting the seed place every row moves the unsampled cell's iteration by 0.3%:
another order of the float32 sums, a few near-ties decided the other way. A
booster that *samples* its rows by their gradients is far more sensitive:
those few other splits give other scores, other ranks at the edge of the top
and other rows under the draw's fixed positions, so each later tree is grown
on another sample and takes another number of the grower's steps. On the chip
six seeds' windows of 20 sampled iterations read 0.7074 to 0.7224 s an
iteration, a quartile distance of 1.8% under a bound of 1% (PERF.md section 6,
PR 33), where one order of the rows gives one set of draws and of trees for
every seed. Upstream's table is one file in one order (``bosch_like``'s
``row_order`` is the same choice, for the same reason); a column's place
decides nothing but which of two equal splits is named.
"""
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

from benchmarks import datagen

epsilon_like = datagen.generator("epsilon_like")


def make(rows: int, features: int, seed: int, recipe: int = 7, workers: int = 8,
         row_order: str = "seed") -> Tuple[np.ndarray, np.ndarray]:
    """[rows, features] float32 and [rows] float32 labels in {0, 1}; under
    ``row_order`` ``"recipe"`` row ``i`` is the set's row ``i`` and column
    ``j`` the set's column ``datagen.order``'s ``column_from[j]``."""
    if row_order == "seed":
        return epsilon_like.make(rows, features, seed, recipe, workers)
    if row_order != "recipe":
        raise ValueError("row_order is 'seed' or 'recipe', not %r" % (row_order,))
    size = epsilon_like.BLOCK
    _, column_from = datagen.order(rows, features, seed)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(index: int) -> None:
        xb, yb = epsilon_like.block(rows, features, index, recipe)
        X[index * size: index * size + len(yb)] = xb[:, column_from]
        y[index * size: index * size + len(yb)] = yb

    with ThreadPoolExecutor(max(workers, 1)) as pool:
        list(pool.map(fill, range(-(-rows // size))))
    return X, y

"""``epsilon_like``'s set, with a choice of who orders its columns.

The same table, value for value (``epsilon_like.block``, keyed by the
recipe). ``column_order`` ``"seed"`` is ``epsilon_like`` itself: rows and
columns stand where ``datagen.order`` puts them, as every generator's do.
``"recipe"``, which the configuration of column-sampled boosting gives: the
seed orders the rows alone and column ``j`` of the matrix is the set's column
``j``.

A tree under ``feature_fraction`` is grown on the columns that stand at the
positions its draw names, and the draws come from a stream of the program's
own (``feature_fraction_seed``), the same positions for every seed of the
benchmark. Under another order of the columns the same positions hold other
columns of the set: each seed would grow its trees on other subsets of the
200 informative columns, so other trees and another iteration's time for
every seed (rows drawn anew moved an iteration by 10%, PERF.md, PR 24; another
order of the rows under a row sample by 1.84%, PR 33), where one order of the
columns gives every seed the same draws of the same columns. What the seed
still moves is the order of the rows: another order of the float32 sums, a
near-tie decided the other way now and then. The draws do not depend on the
trees (as a row sample by the gradients does), so that stays small: on the
chip six seeds read 0.47727 to 0.47801 s an iteration, a quartile distance of
0.10% under a bound of 1% (PERF.md section 6, PR 35).
"""
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

from benchmarks import datagen

epsilon_like = datagen.generator("epsilon_like")


def make(rows: int, features: int, seed: int, recipe: int = 7, workers: int = 8,
         column_order: str = "seed") -> Tuple[np.ndarray, np.ndarray]:
    """[rows, features] float32 and [rows] float32 labels in {0, 1}; under
    ``column_order`` ``"recipe"`` row ``i`` of the set stands at
    ``datagen.order``'s ``row_at[i]`` and column ``j`` is the set's column
    ``j``."""
    if column_order == "seed":
        return epsilon_like.make(rows, features, seed, recipe, workers)
    if column_order != "recipe":
        raise ValueError("column_order is 'seed' or 'recipe', not %r" % (column_order,))
    size = epsilon_like.BLOCK
    row_at, _ = datagen.order(rows, features, seed)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(index: int) -> None:
        xb, yb = epsilon_like.block(rows, features, index, recipe)
        at = row_at[index * size: index * size + len(yb)]
        X[at] = xb
        y[at] = yb

    with ThreadPoolExecutor(max(workers, 1)) as pool:
        list(pool.map(fill, range(-(-rows // size))))
    return X, y

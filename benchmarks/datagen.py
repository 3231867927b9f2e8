"""Data from the seed, by the generator a configuration names: the file
``generators/<name>.py``, found by that name as a metric's reader is, with one
function ``make(rows, features, seed, **generator_args)``.

A set (every value and every label) is keyed by the configuration's
``recipe``, as upstream's experiments run on one fixed table. The seed gives
the order: where each row and each column stands in the matrix that is handed
to the program. Trees grown on two seeds' matrices are the same trees up to
the names of rows and columns (and to the order of float32 sums), so an
iteration's work is the same from seed to seed: on the chip, 100K x 2000 rows
drawn anew for each seed moved one iteration's time by 10% between seeds
(PERF.md, PR 24), which no bound could have held. Rows come in fixed blocks,
each from a stream of its own keyed by (recipe, block), so any set of blocks
can be made in any order or in parallel and gives the same rows.
"""
from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def order(rows: int, features: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Where the seed puts the set's rows and columns: row ``i`` of the set
    stands at ``row_at[i]``, and column ``j`` of the matrix is the set's
    column ``column_from[j]``."""
    rng = np.random.default_rng([int(seed), 3])
    return rng.permutation(rows), rng.permutation(features)


def in_blocks(make_block: Callable, block_rows: int, rows: int, features: int,
              seed: int, recipe: int, workers: int) -> Tuple[np.ndarray, np.ndarray]:
    """The set of ``make_block(rows, features, block, recipe)``'s blocks, in
    the seed's order: [rows, features] float32 and [rows] float32 labels."""
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    row_at, column_from = order(rows, features, seed)

    def fill(block: int) -> None:
        xb, yb = make_block(rows, features, block, recipe)
        at = row_at[block * block_rows: block * block_rows + len(yb)]
        X[at] = xb[:, column_from]
        y[at] = yb

    blocks = range(-(-rows // block_rows))
    if workers <= 1:
        for b in blocks:
            fill(b)
    else:
        with ThreadPoolExecutor(workers) as pool:
            list(pool.map(fill, blocks))
    return X, y


def generator(name: str, bench_dir: str = HERE):
    """The module ``generators/<name>.py``."""
    path = os.path.join(bench_dir, "generators", name + ".py")
    if not os.path.isfile(path):
        raise KeyError("generator %r has no file at %s" % (name, path))
    spec = importlib.util.spec_from_file_location("benchmarks_generator_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(config: dict, seed: int, bench_dir: str = HERE) -> Tuple[np.ndarray, np.ndarray]:
    """The configuration's data for this seed, by the generator it names."""
    return generator(config["generator"], bench_dir).make(
        config["rows"], config["features"], seed, **config.get("generator_args", {}))

"""The data generator: a pure function of the seed, the same rows whether
made block by block, in parallel or at once, at any seed the driver may give;
the set is the recipe's, the seed gives the order of its rows and columns."""
import os

import numpy as np
import pytest

from benchmarks import datagen

GENERATORS = sorted(f[:-3] for f in os.listdir(os.path.join(datagen.HERE, "generators"))
                    if f.endswith(".py"))
epsilon = datagen.generator("epsilon_like")


@pytest.mark.parametrize("name", GENERATORS)
@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 32 + 5])
def test_same_seed_same_rows_other_seed_other_rows(name, seed):
    make = datagen.generator(name).make
    X1, y1 = make(5000, 28, seed)
    X2, y2 = make(5000, 28, seed)
    X3, _ = make(5000, 28, seed + 1)
    assert np.array_equal(X1, X2) and np.array_equal(y1, y2)
    assert not np.array_equal(X1, X3)
    assert X1.dtype == np.float32 and y1.dtype == np.float32
    assert set(np.unique(y1)) == {0.0, 1.0}


def in_the_seeds_order(blocks, rows, features, seed):
    row_at, column_from = datagen.order(rows, features, seed)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    X[row_at] = np.concatenate([b[0] for b in blocks])[:, column_from]
    y[row_at] = np.concatenate([b[1] for b in blocks])
    return X, y


@pytest.mark.parametrize("workers", [1, 3, 8])
def test_blockwise_equals_one_shot(workers):
    rows = epsilon.BLOCK * 2 + 77      # two whole blocks and a part of one
    X, y = epsilon.make(rows, 50, 2 ** 31 + 9, workers=workers)
    blocks = [epsilon.block(rows, 50, b) for b in range(3)]
    Xb, yb = in_the_seeds_order(blocks, rows, 50, 2 ** 31 + 9)
    assert np.array_equal(X, Xb) and np.array_equal(y, yb)
    assert len(blocks[2][1]) == 77


@pytest.mark.parametrize("name", GENERATORS)
def test_seed_gives_the_order_and_the_recipe_gives_the_set(name):
    # every seed hands over the same rows and columns in another order, so
    # that every seed's trees, and an iteration's work, are the same
    make = datagen.generator(name).make
    rows, features = 50000, 28
    X1, y1 = make(rows, features, 1)
    X2, y2 = make(rows, features, 2 ** 31 + 2)
    (r1, c1), (r2, c2) = (datagen.order(rows, features, s) for s in (1, 2 ** 31 + 2))
    assert not np.array_equal(r1, r2) and not np.array_equal(c1, c2)
    assert sorted(r1) == list(range(rows)) and sorted(c1) == list(range(features))
    # undo both orders: the same set
    back1 = X1[r1][:, np.argsort(c1)]
    back2 = X2[r2][:, np.argsort(c2)]
    assert np.array_equal(back1, back2) and np.array_equal(y1[r1], y2[r2])
    other, _ = make(rows, features, 1, recipe=8)
    assert not np.array_equal(np.sort(other[:, 0]), np.sort(X1[:, 0]))


def test_generators_are_found_by_file_name():
    assert "epsilon_like" in GENERATORS
    assert callable(datagen.generator("epsilon_like").make)
    with pytest.raises(KeyError):
        datagen.generator("no_such_generator")


def test_epsilon_shape_of_the_set():
    X, y = epsilon.make(50000, 400, 3)
    assert X.shape == (50000, 400) and X.dtype == np.float32
    assert abs(X.mean()) < 0.01 and abs(X.std() - 1) < 0.01
    assert 0.47 < y.mean() < 0.53
    # the label depends on the recipe's columns alone
    w = epsilon.weights(400, 7)
    assert np.count_nonzero(w) == epsilon.INFORMATIVE
    _, column_from = datagen.order(50000, 400, 3)
    assert np.mean((X @ w[column_from] > 0) == (y > 0)) > 0.75


@pytest.mark.parametrize("name", GENERATORS)
def test_every_generator_takes_the_harness_call(name):
    config = {"generator": name, "rows": 1000, "features": 30,
              "generator_args": {"recipe": 7}}
    X, y = datagen.make(config, 2 ** 31 + 1)
    assert X.shape == (1000, 30) and y.shape == (1000,)

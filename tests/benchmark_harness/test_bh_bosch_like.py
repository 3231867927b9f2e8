"""The Bosch-shaped generator: the shares the source's table has (cells that
hold a value, positive labels), stations present or empty together, columns of
a handful of values, a table keyed by (recipe, block) whatever is asked for
beside it, two seeds one table in two orders, NaN and all; and that the label
leans on which stations were visited."""
import numpy as np
import pytest

from benchmarks import datagen

bosch = datagen.generator("bosch_like")


def same(a, b):
    return np.array_equal(a, b, equal_nan=True)


@pytest.mark.parametrize("rows, features, recipe", [
    (20000, 28, 7), (20000, 28, 8), (60000, 28, 9), (30000, 968, 7), (30000, 200, 8)])
def test_the_shares_of_the_sources_table(rows, features, recipe):
    X, y = bosch.make(rows, features, 2 ** 31 + 4, recipe=recipe, empty="nan")
    assert X.shape == (rows, features) and X.dtype == np.float32 and y.dtype == np.float32
    present = ~np.isnan(X)
    assert abs(present.mean() - bosch.PRESENT) < 0.01          # within a point
    assert present.any(axis=0).all()                           # no column without a value
    assert 0.6 * bosch.POSITIVE < y.mean() < 1.4 * bosch.POSITIVE
    assert set(np.unique(y)) == {0.0, 1.0}
    # stations: a row holds a whole station or none of it
    lay = bosch.layout(features, recipe)
    _, column_from = datagen.order(rows, features, 2 ** 31 + 4)
    station = lay.station_of[column_from]
    assert len(set(station)) >= 4 and len(set(np.bincount(station))) > 1   # of uneven width
    for s in set(station):
        cols = present[:, station == s]
        assert (cols.all(axis=1) | ~cols.any(axis=1)).all()
    # some columns take a handful of values, the others as many as they have rows
    distinct = np.array([len(np.unique(X[present[:, j], j])) for j in range(features)])
    few = lay.levels[column_from] > 0
    assert 0 < few.sum() < features and distinct[few].max() <= 9
    assert (distinct[~few] > 0.9 * present[:, ~few].sum(axis=0)).all()


def test_the_default_is_a_table_without_missing_values_and_the_same_values():
    X0, y0 = bosch.make(5000, 28, 11)
    Xn, yn = bosch.make(5000, 28, 11, empty="nan")
    assert not np.isnan(X0).any() and np.array_equal(y0, yn)
    assert same(np.where(np.isnan(Xn), 0.0, Xn), X0)
    assert same(bosch.make(5000, 28, 11, empty=-9.0)[0], np.where(np.isnan(Xn), -9.0, Xn))


@pytest.mark.parametrize("workers", [1, 3, 8])
def test_blocks_are_keyed_by_recipe_and_block(workers):
    rows, features, seed = bosch.BLOCK * 2 + 77, 40, 2 ** 31 + 9
    X, y = bosch.make(rows, features, seed, workers=workers, empty="nan")
    blocks = [bosch.block(rows, features, b, empty=float("nan")) for b in range(3)]
    assert len(blocks[2][1]) == 77
    row_at, column_from = datagen.order(rows, features, seed)
    Xb = np.empty_like(X)
    Xb[row_at] = np.concatenate([b[0] for b in blocks])[:, column_from]
    assert same(X, Xb) and np.array_equal(y[row_at], np.concatenate([b[1] for b in blocks]))
    # a block is the same rows whatever table it is asked for as a part of
    longer = bosch.block(rows * 3, features, 1, empty=float("nan"))
    assert same(longer[0], blocks[1][0]) and np.array_equal(longer[1], blocks[1][1])
    other = bosch.block(rows, features, 1, recipe=8, empty=float("nan"))
    assert not same(other[0], blocks[1][0])


def test_two_seeds_are_one_table_in_two_orders():
    rows, features = 50000, 28
    X1, y1 = bosch.make(rows, features, 1, empty="nan")
    X2, y2 = bosch.make(rows, features, 2 ** 32 + 5, empty="nan")
    (r1, c1), (r2, c2) = (datagen.order(rows, features, s) for s in (1, 2 ** 32 + 5))
    assert not same(X1, X2)
    assert same(X1[r1][:, np.argsort(c1)], X2[r2][:, np.argsort(c2)])
    assert np.array_equal(y1[r1], y2[r2])
    assert same(bosch.make(rows, features, 1, empty="nan")[0], X1)


@pytest.mark.parametrize("workers", [1, 8])
def test_the_cells_seeds_order_the_columns_and_leave_the_rows(workers):
    """``row_order="recipe"``, as the configuration asks: every seed's rows
    stand as the recipe makes them (one sample for the bin edges, one order of
    the float32 sums, so one set of trees); the seed orders the columns."""
    rows, features = bosch.BLOCK * 2 + 77, 40
    seeds = (3, 2 ** 31 + 7)
    (X1, y1), (X2, y2) = (bosch.make(rows, features, s, empty="nan", row_order="recipe",
                                     workers=workers) for s in seeds)
    c1, c2 = (datagen.order(rows, features, s)[1] for s in seeds)
    assert np.array_equal(y1, y2) and not same(X1, X2)
    assert same(X1[:, np.argsort(c1)], X2[:, np.argsort(c2)])
    blocks = [bosch.block(rows, features, b, empty=float("nan")) for b in range(3)]
    assert same(X1, np.concatenate([b[0] for b in blocks])[:, c1])
    assert np.array_equal(y1, np.concatenate([b[1] for b in blocks]))
    # the seed's own order of the rows is this table's rows moved, and nothing else
    Xs, ys = bosch.make(rows, features, seeds[0], empty="nan")
    row_at = datagen.order(rows, features, seeds[0])[0]
    assert same(Xs[row_at], X1) and np.array_equal(ys[row_at], y1)


def test_a_row_order_the_generator_does_not_know_is_refused():
    with pytest.raises(ValueError):
        bosch.make(1000, 28, 1, row_order="sorted")


def test_the_label_leans_on_values_and_on_visits():
    """Where a part was measured tells the label apart, and so does the
    strongest column, low values failing: a split of it has to send the parts
    that were not measured there to the right."""
    rows, features, recipe = 400000, 28, 7
    X, y = bosch.make(rows, features, 5, recipe=recipe, empty="nan")
    lay = bosch.layout(features, recipe)
    _, column_from = datagen.order(rows, features, 5)
    at = {int(c): j for j, c in enumerate(column_from)}          # the set's column -> the matrix's
    assert lay.weight[np.argmax(np.abs(lay.weight))] < 0
    strongest = X[:, at[int(lay.informative[np.argmax(np.abs(lay.weight))])]]
    seen = ~np.isnan(strongest)
    low = seen & (strongest < np.nanquantile(strongest, 0.2))
    assert y[low].mean() > 2 * y[seen & ~low].mean()
    station = int(np.argmax(np.abs(lay.visit_weight)))
    visited = ~np.isnan(X[:, at[int(np.flatnonzero(lay.station_of == station)[0])]])
    rates = sorted([y[visited].mean(), y[~visited].mean()])
    assert rates[1] > 1.3 * rates[0]

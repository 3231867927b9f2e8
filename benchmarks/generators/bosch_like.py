"""A Bosch-shaped set (Kaggle's Bosch Production Line Performance, the numeric
table upstream's GPU benchmark trains on): measurements taken at the stations
of production lines. A part runs down one line and is measured only at the
stations it visits, so most cells are empty (``PRESENT`` of all cells hold a
value), and few parts fail (``POSITIVE`` of the labels are 1). The values are
not public here, so only the shape is the source's: columns in stations of
uneven width, whole stations present or empty together, within a station some
columns continuous and some taking a handful of values, and a label that
depends on a few dozen present values and on which stations were visited, so
that where a split sends the empty cells carries signal.

An empty cell holds ``empty``. The configuration gives ``"nan"``, what a CSV
reader makes of the source's empty cells and what the program's default
``use_missing`` treats as missing. The default, 0.0, is there for the
harness's generator tests alone (``test_bh_datagen.py`` calls every generator
without arguments and compares tables by ``==``, which a NaN never meets): no
configuration runs a table without missing values from this generator.
"""
import functools
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Tuple

import numpy as np

from benchmarks import datagen

BLOCK = 1 << 14
PRESENT = 0.19           # share of all cells that hold a value
POSITIVE = 0.0058        # share of the labels that are 1
COLUMNS_PER_STATION = 18.6   # the source's 968 columns over its 52 stations
DISCRETE = 0.3           # share of a station's columns that take a handful of values
INFORMATIVE = 36         # continuous columns the label's logit reads
VISIT_SIGNAL = 8         # stations whose visit alone moves the logit
VALUE_SIGNAL = 2.0       # length of the logit's weights on the informative columns
VISIT_STEP = 0.7         # spread of the logit's steps for a visit
NOISE = 1.0
CALIBRATION_ROWS = 1 << 18   # rows the label's cut is taken from


class Layout(NamedTuple):
    """One recipe's table, but for its rows."""

    station_of: np.ndarray   # [F] the station of each column
    line_of: np.ndarray      # [S] the line of each station, -1: every line's parts pass it
    line_p: np.ndarray       # [L] share of the parts on each line
    visit_p: np.ndarray      # [S] chance that a part on the station's line is measured there
    loading: np.ndarray      # [F] how much of a column is its station's common factor
    scale: np.ndarray        # [F]
    shift: np.ndarray        # [F]
    levels: np.ndarray       # [F] 0: continuous; else the column takes 2 * levels + 1 values
    informative: np.ndarray  # [K] columns the logit reads
    weight: np.ndarray       # [K]
    visit_weight: np.ndarray  # [S] the logit's step for a visit, 0 at most stations


def present_share(width, line_of, line_p, visit_p) -> float:
    on_line = np.where(line_of < 0, 1.0, line_p[np.maximum(line_of, 0)])
    return float(np.sum(width * on_line * visit_p) / np.sum(width))


@functools.lru_cache(maxsize=8)
def layout(features: int, recipe: int) -> Layout:
    rng = np.random.default_rng([int(recipe), 0])
    S = max(4, int(round(features / COLUMNS_PER_STATION)))
    width = 1 + rng.multinomial(features - S, rng.dirichlet(np.full(S, 2.0)))
    station_of = np.repeat(np.arange(S), width)
    shared = max(1, S // 5)                      # the last stations: every part passes them
    L = min(4, max(2, (S - shared) // 2))
    line_of = np.concatenate([                   # no line without a station
        np.sort(np.concatenate([np.arange(L), rng.integers(0, L, S - shared - L)])),
        np.full(shared, -1)])
    line_p = 0.08 + rng.dirichlet(np.full(L, 1.5)) * (1 - 0.08 * L)
    base = rng.uniform(0.3, 1.0, S)
    lo, hi = 0.0, 1.0 / base.min()               # the factor that gives PRESENT, by bisection
    for _ in range(60):
        k = 0.5 * (lo + hi)
        visit_p = np.clip(k * base, 0.02, 1.0)
        if present_share(width, line_of, line_p, visit_p) < PRESENT:
            lo = k
        else:
            hi = k
    levels = np.where(rng.random(features) < DISCRETE, rng.integers(1, 5, features), 0)
    continuous = np.flatnonzero(levels == 0)
    informative = np.sort(rng.choice(continuous, min(INFORMATIVE, len(continuous) // 2),
                                     replace=False))
    weight = rng.standard_normal(len(informative))
    # the strongest column fails a part where its value is low: a split of it
    # has to send the parts not measured there, the sound majority, to the right
    weight *= -np.sign(weight[np.argmax(np.abs(weight))]) * VALUE_SIGNAL / np.linalg.norm(weight)
    visit_weight = np.zeros(S)
    on = rng.choice(S, min(VISIT_SIGNAL, S // 2), replace=False)
    visit_weight[on] = VISIT_STEP * rng.standard_normal(len(on))
    return Layout(station_of, line_of, line_p, visit_p,
                  rng.uniform(0.2, 0.8, features).astype(np.float32),
                  rng.uniform(0.05, 0.3, features).astype(np.float32),
                  rng.uniform(-0.2, 0.2, features).astype(np.float32),
                  levels, informative, weight.astype(np.float32),
                  visit_weight.astype(np.float32))


def visits(lay: Layout, n: int, rng) -> np.ndarray:
    """[n, S] bool: the stations each part was measured at."""
    line = rng.choice(len(lay.line_p), size=n, p=lay.line_p)
    on_line = (lay.line_of[None, :] < 0) | (lay.line_of[None, :] == line[:, None])
    return on_line & (rng.random((n, len(lay.line_of)), dtype=np.float32) < lay.visit_p)


def standardised(lay: Layout, columns, common: np.ndarray, own: np.ndarray) -> np.ndarray:
    """Unit-variance values of ``columns``: the station's common factor and
    the column's own part."""
    a = lay.loading[columns]
    return a * common[:, lay.station_of[columns]] + np.sqrt(1 - a * a) * own


def logit(lay: Layout, visit: np.ndarray, values: np.ndarray, noise: np.ndarray) -> np.ndarray:
    """From the informative columns' standardised values, which count only
    where the part was measured, and from the visits themselves."""
    seen = visit[:, lay.station_of[lay.informative]]
    return (np.where(seen, values, 0) @ lay.weight + visit.astype(np.float32) @ lay.visit_weight
            + NOISE * noise)


@functools.lru_cache(maxsize=8)
def cut(features: int, recipe: int) -> float:
    """The logit above which a part fails: the ``1 - POSITIVE`` quantile over
    rows of a stream of their own, so that it is one number a recipe whatever
    rows are asked for."""
    lay = layout(features, recipe)
    rng = np.random.default_rng([int(recipe), 1])
    n = CALIBRATION_ROWS
    visit = visits(lay, n, rng)
    common = rng.standard_normal((n, len(lay.line_of)), dtype=np.float32)
    own = rng.standard_normal((n, len(lay.informative)), dtype=np.float32)
    values = standardised(lay, lay.informative, common, own)
    return float(np.quantile(
        logit(lay, visit, values, rng.standard_normal(n, dtype=np.float32)), 1 - POSITIVE))


def block(rows: int, features: int, index: int, recipe: int = 7,
          empty: float = 0.0) -> Tuple[np.ndarray, np.ndarray]:
    """Rows ``[index * BLOCK, min(rows, (index + 1) * BLOCK))`` of the set,
    in the set's own order. The [n, features] passes are made in place: at
    this width a fresh array a pass costs more than the pass."""
    lay = layout(features, recipe)
    n = min(rows, (index + 1) * BLOCK) - index * BLOCK
    rng = np.random.default_rng([int(recipe), 2, int(index)])
    visit = visits(lay, n, rng)
    common = rng.standard_normal((n, len(lay.line_of)), dtype=np.float32)
    X = rng.standard_normal((n, features), dtype=np.float32)
    y = logit(lay, visit, standardised(lay, lay.informative, common, X[:, lay.informative]),
              rng.standard_normal(n, dtype=np.float32)) > cut(features, recipe)
    X *= np.sqrt(1 - lay.loading ** 2) * lay.scale
    shared = np.take(common, lay.station_of, axis=1)   # rows stay contiguous
    shared *= lay.loading * lay.scale
    X += shared
    del shared
    stepped = np.flatnonzero(lay.levels)           # a handful of values: a grid of its own
    level = lay.levels[stepped].astype(np.float32)
    grid = lay.scale[stepped] * 2 / level
    few = np.take(X, stepped, axis=1)
    few /= grid
    np.rint(few, out=few)
    np.clip(few, -level, level, out=few)
    few *= grid
    X[:, stepped] = few
    X += lay.shift
    np.copyto(X, np.float32(empty), where=~np.take(visit, lay.station_of, axis=1))
    return X, y.astype(np.float32)


def make(rows: int, features: int, seed: int, recipe: int = 7, workers: int = 8,
         empty=0.0, row_order: str = "seed") -> Tuple[np.ndarray, np.ndarray]:
    """[rows, features] float32, ``empty`` (a number or ``"nan"``) where a
    part was not measured, and [rows] float32 labels in {0, 1}.

    ``row_order`` ``"seed"``: rows and columns stand where ``datagen.order``
    puts them, as every generator's do. ``"recipe"``, which the configuration
    gives: the seed orders the columns alone and the rows stay as the recipe
    makes them. Another order of the rows is another 200,000-row sample for
    ``lgb.Dataset``'s bin edges, so other edges and other trees (three seeds
    shared 1-3 of a tree's 254 splits), and even under one set of edges
    another order of float32 sums, which with 0.58% positives flips a near-tie
    in one tree of four: ``train_iter_s`` spread by 2.0% and 0.8% over seeds
    that way (PERF.md section 6, PR 28). Upstream's table is one file in one
    order; a column's place decides nothing but which of two equal splits is
    named."""
    cut(features, recipe)                          # once, before the workers ask for it
    make_block = functools.partial(block, empty=float(empty))
    if row_order == "seed":
        return datagen.in_blocks(make_block, BLOCK, rows, features, seed, recipe, workers)
    if row_order != "recipe":
        raise ValueError("row_order is 'seed' or 'recipe', not %r" % (row_order,))
    _, column_from = datagen.order(rows, features, seed)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(index: int) -> None:
        xb, yb = make_block(rows, features, index, recipe)
        X[index * BLOCK: index * BLOCK + len(yb)] = xb[:, column_from]
        y[index * BLOCK: index * BLOCK + len(yb)] = yb

    with ThreadPoolExecutor(max(workers, 1)) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK))))
    return X, y

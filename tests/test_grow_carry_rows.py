"""How a speculative batch reads its rows of the two histogram carries
(``ops/grow._carry_rows``): row by row, and bit for bit what ``buf[idx]``
gives, under every transform the grower runs inside; and a tree grown
through the cache's read-back path equals the sequential grower's. What the
TPU compiler makes of the form is held by
tests/test_hist_pallas_tpu_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu.ops.grow as grow_mod
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import construct_dataset
from lightgbm_tpu.ops.split import SplitParams

KB = 8
SHAPES = [(7, 3, 4), (31, 10, 16), (255, 5, 63)]


def _carry(shape, seed=0):
    M, F, B = shape
    rng = np.random.RandomState(seed)
    buf = rng.standard_normal((M, F, B, 3)).astype(np.float32)
    # the first and the last row, one row twice, the rest anywhere
    idx = np.concatenate([[0, M - 1, M // 2, M // 2], rng.randint(0, M, KB - 4)])
    return buf, idx.astype(np.int32)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_rows_equal_the_gather_under_jit(shape):
    buf, idx = _carry(shape)
    _same_bits(jax.jit(grow_mod._carry_rows)(buf, idx), buf[idx])


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_rows_equal_the_gather_under_vmap(shape):
    # device_chunk_size and the per-class loop batch the grower's carries
    bufs, idxs = zip(*(_carry(shape, seed) for seed in range(3)))
    bufs, idxs = np.stack(bufs), np.stack(idxs)
    got = jax.jit(jax.vmap(grow_mod._carry_rows))(bufs, idxs)
    _same_bits(got, np.stack([b[i] for b, i in zip(bufs, idxs)]))


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_rows_equal_the_gather_in_a_while_loop_carry(shape):
    """As ``body_spec`` does: read the batch's rows of the carry, write them
    back changed, three steps over moving rows."""
    buf, idx = _carry(shape)
    M = shape[0]

    def grown(read):
        def body(c):
            k, carry, seen = c
            rows = (idx + k) % M
            got = read(carry, rows)
            return k + 1, carry.at[rows].set(got * 0.5 + 1.0), seen + got

        init = (jnp.int32(0), jnp.asarray(buf), jnp.zeros((KB,) + buf.shape[1:]))
        return jax.lax.while_loop(lambda c: c[0] < 3, body, init)

    by_rows = jax.jit(lambda: grown(grow_mod._carry_rows))()
    by_gather = jax.jit(lambda: grown(lambda b, i: b[i]))()
    for got, want in zip(by_rows[1:], by_gather[1:]):
        _same_bits(got, want)


@pytest.fixture
def grow_mode(monkeypatch):
    def set_mode(mode):
        monkeypatch.setattr(grow_mod, "_ENV_GROW", mode)
        jax.clear_caches()

    yield set_mode
    jax.clear_caches()


def _grow(mode, leaves=31):
    rng = np.random.RandomState(4)
    n = 3000
    X = rng.randn(n, 8)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.randn(n) > 0).astype(np.float64)
    ds = construct_dataset(
        X, Config.from_params({"max_bin": 63, "objective": "binary"}),
        label=y.astype(np.float32),
    )
    meta = {k: jnp.asarray(v) for k, v in ds.feature_meta_arrays().items()}
    tree, leaf_id = grow_mod.grow_tree(
        jnp.asarray(ds.bins), jnp.asarray((0.5 - y).astype(np.float32)),
        jnp.full((n,), 0.25, jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.ones((X.shape[1],), bool), meta, num_leaves=leaves, max_depth=-1,
        num_bins=ds.max_num_bin,
        params=SplitParams(0.0, 0.0, 0.0, 5, 1e-3, 0.0),
    )
    assert grow_mod._LAST_GROW_MODE == mode
    return tree, np.asarray(leaf_id)


def test_a_tree_read_back_from_the_cache_equals_the_sequential(grow_mode):
    grow_mode("seq")
    seq, seq_leaf = _grow("seq")
    grow_mode("spec")
    spec, spec_leaf = _grow("spec")
    c = dict(zip(grow_mod.COUNTER_NAMES, np.asarray(spec.counters)))
    assert c["splits"] == 30
    # slots were computed and parked, not applied, in their own batch ...
    assert c["splits"] < c["slots_computed"]
    # ... and batches held slots they did not compute: the parked ones, whose
    # rows came back from hist and spec_rhist
    assert c["slots_computed"] < c["steps"] * grow_mod._ENV_SPEC_K
    for name in seq._fields:
        if name != "counters":
            np.testing.assert_array_equal(
                np.asarray(getattr(seq, name)), np.asarray(getattr(spec, name)),
                err_msg=name,
            )
    np.testing.assert_array_equal(seq_leaf, spec_leaf)

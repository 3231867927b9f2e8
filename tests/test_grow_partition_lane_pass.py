"""``make_bucket_kernels``' ``partition_batch`` against a numpy stable
partition of each segment. The flat lane pass reads a slot's rows as a window
of ``order`` (slices chosen by the lane's slot, no gather) and looks the
categorical membership up only on a table that has a categorical column; what
it returns is held here element for element: ``order``, the left counts and
the rows that went by a split's default direction, for one slot and for
eight, every missing type, a categorical table, a bundled one, and the batch
shapes that bite. The split columns are read from ``bins`` row by row; the
take they were read by before is held to the same bytes."""
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.efb import BundleInfo, decode_subbin, encode_subbin
from lightgbm_tpu.ops.grow import make_bucket_kernels
from lightgbm_tpu.ops.split import MISSING_NAN, MISSING_NONE, MISSING_ZERO

N, F, B = 1500, 6, 16
KINDS = {"none": MISSING_NONE, "zero": MISSING_ZERO, "nan": MISSING_NAN}


@functools.lru_cache(maxsize=None)
def table(kind):
    """(bins as the kernels are handed them, the feature table, the sub-bins
    ``[F, N]`` the decision is about, is_categorical ``[F]``)."""
    rng = np.random.RandomState(11)
    num_bin = np.array([B, B - 3, B, 5, B, B - 1], np.int32)
    default_bin = np.array([0, 2, 5, 0, 1, 3], np.int32)
    sub = np.stack([rng.randint(0, nb, N) for nb in num_bin]).astype(np.int32)
    is_cat = np.zeros(F, bool)
    missing = np.full(F, KINDS.get(kind, MISSING_NONE), np.int32)
    meta = {"num_bin": num_bin, "default_bin": default_bin}
    bins = sub
    if kind == "categorical":  # columns of every kind beside the categorical
        is_cat[[1, 3]] = True
        missing = np.array([MISSING_NAN, 0, MISSING_ZERO, 0, MISSING_NONE, MISSING_NAN], np.int32)
        meta["is_categorical"] = is_cat
    if kind == "bundled":  # two groups of three; a row holds one feature's value
        missing = np.array([MISSING_ZERO, MISSING_NONE, MISSING_ZERO, MISSING_NAN, MISSING_NONE, MISSING_ZERO],
                           np.int32)
        info = BundleInfo([[0, 2, 4], [1, 3, 5]], num_bin)
        owner = rng.randint(0, 3, (2, N))
        bins = np.zeros((2, N), np.int32)
        for g, members in enumerate(info.groups):
            for i, f in enumerate(members):
                mine = owner[g] == i
                sub[f] = np.where(mine, sub[f], default_bin[f])
                hold = mine & (sub[f] != default_bin[f])
                bins[g, hold] = encode_subbin(sub[f], default_bin[f], info.bin_offset[f])[hold]
        for f in range(F):  # the table says what the kernels will decode
            g = info.group_id[f]
            assert np.array_equal(decode_subbin(bins[g], info.bin_offset[f], default_bin[f], num_bin[f]), sub[f])
        meta["group_id"], meta["bin_offset"] = info.group_id, info.bin_offset
    meta["missing_type"] = missing
    return bins.astype(np.uint8), meta, sub, is_cat


@functools.lru_cache(maxsize=None)
def partition(kind, W):
    bins, meta, _, _ = table(kind)
    kern = make_bucket_kernels(
        jnp.asarray(bins), {k: jnp.asarray(v) for k, v in meta.items()}, B,
        num_group_bins=64, kb=W if W > 1 else 0)
    return jax.jit(kern.partition_batch)


def stable_partition(kind, order, begin, pcnt, feat, thr, dleft, member):
    _, meta, sub, is_cat = table(kind)
    out, lefts, by_default = order.copy(), [], 0
    for k in range(len(begin)):
        f, seg = feat[k], order[begin[k]: begin[k] + pcnt[k]]
        col = sub[f, seg]
        if is_cat[f]:
            go_left, missing = member[k, col], np.zeros(len(seg), bool)
        else:
            missing = {
                MISSING_NONE: np.zeros(len(seg), bool),
                MISSING_ZERO: col == meta["default_bin"][f],
                MISSING_NAN: col == meta["num_bin"][f] - 1,
            }[int(meta["missing_type"][f])]
            go_left = np.where(missing, dleft[k], col <= thr[k])
        out[begin[k]: begin[k] + pcnt[k]] = np.concatenate([seg[go_left], seg[~go_left]])
        lefts.append(int(go_left.sum()))
        by_default += int(missing.sum())
    return out, np.asarray(lefts, np.int32), by_default


def batch(shape, W, rng):
    """(begin, pcnt) of W disjoint segments of ``[0, N)``."""
    if W == 1:
        return {
            "zero_width": ([700], [0]), "starts_at_0": ([0], [300]), "ends_at_N": ([N - 517], [517]),
            "whole_table": ([0], [N]), "any_order": ([411], [258]),
        }[shape]
    if shape == "whole_table":  # in one slot, not the first; the others hold nothing, anywhere
        begin, pcnt = rng.randint(0, N + 1, W), np.zeros(W, int)
        begin[3], pcnt[3] = 0, N
        return begin, pcnt
    cuts = np.sort(rng.choice(np.arange(1, N), 2 * W - 1, replace=False))
    begin, pcnt = cuts[0::2][:W - 1], cuts[1::2] - cuts[0::2][:W - 1]  # W - 1 segments, gaps between
    begin, pcnt = np.append(begin, cuts[-1]), np.append(pcnt, 0)
    if shape == "starts_at_0":
        pcnt[0] += begin[0]
        begin[0] = 0
    if shape == "ends_at_N":
        pcnt[-1] = N - begin[-1]
    if shape == "zero_width":  # the first, the last and one between hold nothing; their begin is anything
        for k in (0, 4, W - 1):
            begin[k], pcnt[k] = (0, N, 911)[k % 3], 0
    if shape == "any_order":
        turn = rng.permutation(W)
        begin, pcnt = begin[turn], pcnt[turn]
    return begin, pcnt


@pytest.mark.parametrize("shape", ["zero_width", "starts_at_0", "ends_at_N", "whole_table", "any_order"])
@pytest.mark.parametrize("kind", ["none", "zero", "nan", "categorical", "bundled"])
@pytest.mark.parametrize("W", [1, 8])
def test_the_lane_pass_is_a_stable_partition_of_each_segment(W, kind, shape):
    rng = np.random.RandomState(zlib.crc32(f"{W} {kind} {shape}".encode()))
    meta = table(kind)[1]
    for i in range(4):
        begin, pcnt = (np.asarray(a, np.int32) for a in batch(shape, W, rng))
        assert (pcnt >= 0).all() and (begin + pcnt <= N).all()
        order = rng.permutation(N).astype(np.int32)
        feat = rng.randint(0, F, W).astype(np.int32)
        if kind == "categorical":  # a categorical split and a numerical one in every batch, or in turn
            feat[: 2] = (3, 0)[i % 2:][: W]
        thr = rng.randint(0, meta["num_bin"][feat]).astype(np.int32)
        dleft = rng.rand(W) < 0.5
        member = rng.rand(W, B) < 0.5
        want = stable_partition(kind, order, begin, pcnt, feat, thr, dleft, member)
        got = partition(kind, W)(*(jnp.asarray(a) for a in (order, begin, pcnt, feat, thr, dleft, member)))
        assert np.array_equal(np.asarray(got[0]), want[0])
        assert np.array_equal(np.asarray(got[1]), want[1])
        assert int(got[2]) == want[2]


@pytest.mark.parametrize("feats", ["one_feature", "dead_slots"])
@pytest.mark.parametrize("kind", ["nan", "categorical", "bundled"])
@pytest.mark.parametrize("W", [1, 8])
def test_the_split_columns_read_row_by_row_give_the_take_s_bytes(W, kind, feats):
    """The W split columns are read from ``bins`` as W slices of one row;
    ``jnp.take(bins, rows_of, axis=0)``, which read them before and still
    reads a matrix sharded along the features (``feature_sharded``), is the
    reference here, and what the pass returns is its own byte for byte:
    every slot on one feature, and slots of no rows whose feature is what
    the grower leaves there (0 on the top-k's filler rows, -1 where a leaf's
    scan found no split), out of range or not."""
    rng = np.random.RandomState(zlib.crc32(f"{W} {kind} {feats}".encode()))
    bins, meta, _, _ = table(kind)
    shape = "zero_width" if feats == "dead_slots" else "any_order"
    cases = []
    for i in range(3):
        begin, pcnt = (np.asarray(a, np.int32) for a in batch(shape, W, rng))
        feat = np.full(W, rng.randint(0, F), np.int32)
        if feats == "dead_slots":
            feat = rng.randint(0, F, W).astype(np.int32)
            feat[pcnt == 0] = (-1, 0, -1)[i]
        thr = rng.randint(0, meta["num_bin"][feat]).astype(np.int32)
        order = rng.permutation(N).astype(np.int32)
        cases.append((order, begin, pcnt, feat, thr, rng.rand(W) < 0.5, rng.rand(W, B) < 0.5))
    took = jax.jit(make_bucket_kernels(
        jnp.asarray(bins), {k: jnp.asarray(v) for k, v in meta.items()}, B,
        num_group_bins=64, feature_sharded=True, kb=W if W > 1 else 0).partition_batch)
    for case in cases:
        mine = partition(kind, W)(*map(jnp.asarray, case))
        want = took(*map(jnp.asarray, case))
        for a, b in zip(mine, want):
            assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
        if (case[3] >= 0).all():  # and the partition is the stable one
            assert np.array_equal(np.asarray(mine[0]), stable_partition(kind, *case)[0])


def test_the_membership_is_read_only_on_a_table_with_a_categorical_column():
    """What the meta shows decides the trace: without ``is_categorical`` no
    operation of the pass depends on ``member``; with it the lookup is there."""

    def reads_member(kind):
        bins, meta, _, _ = table(kind)
        kern = make_bucket_kernels(jnp.asarray(bins), {k: jnp.asarray(v) for k, v in meta.items()}, B, kb=8)
        i32, W = jnp.int32, 8
        args = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((N,), i32), ((W,), i32), ((W,), i32), ((W,), i32), ((W,), i32), ((W,), bool), ((W, B), bool))]
        jaxpr = jax.make_jaxpr(kern.partition_batch)(*args)
        member = jaxpr.jaxpr.invars[-1]
        cond = next(e for e in jaxpr.jaxpr.eqns if e.primitive.name == "cond")
        return any(v is member for v in cond.invars)

    assert not reads_member("nan")
    assert reads_member("categorical")

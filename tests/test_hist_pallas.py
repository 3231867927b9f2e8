"""Differential tests for the radix-packed Pallas histogram kernel.

Runs the kernel in pallas interpret mode on CPU against the numpy oracle and
the XLA fallback (the same cross-check discipline as the reference's
GPU_DEBUG_COMPARE histogram diff, gpu_tree_learner.cpp:996-1019).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops.hist_pallas import histogram_pallas, supported
from lightgbm_tpu.ops.histogram import histogram_reference, leaf_histogram


@pytest.mark.parametrize("num_bins", [64, 255, 256])
@pytest.mark.parametrize("n", [1000, 1024])
def test_pallas_matches_oracle_f32(rng, num_bins, n):
    F = 3
    bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, num_bins)
    out = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(vals), num_bins,
            chunk=512, dtype_name="float32", interpret=True,
        )
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_pallas_bf16_close(rng):
    F, n, B = 2, 2048, 256
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, B)
    out = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(vals), B,
            chunk=1024, dtype_name="bfloat16", interpret=True,
        )
    )
    # bf16 rounds each operand to ~2^-8 relative; sums stay close
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


def test_pallas_masked_rows_contribute_nothing(rng):
    F, n, B = 2, 1024, 32
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    mask = (rng.rand(n) > 0.5).astype(np.float32)
    g = rng.randn(n).astype(np.float32)
    h = np.abs(rng.randn(n)).astype(np.float32)
    vals = np.stack([g * mask, h * mask, mask], axis=1)
    ref = histogram_reference(bins, vals, B)
    out = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(vals), B,
            chunk=512, dtype_name="float32", interpret=True,
        )
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)
    # count channel equals mask total
    np.testing.assert_allclose(out[:, :, 2].sum(axis=1), mask.sum(), rtol=1e-6)


def _pallas_call_eqn(n, chunk, dtype_name, F=8, num_bins=255):
    """The ``pallas_call`` equation of the routed kernel's wrapper at
    ``[F, n]`` bins (traced on shapes; nothing runs)."""
    import functools

    import jax

    from lightgbm_tpu.ops.hist_pallas import _histogram_pallas_fb

    fn = functools.partial(
        _histogram_pallas_fb.__wrapped__, num_bins=num_bins, chunk=chunk,
        dtype_name=dtype_name, interpret=True,
    )
    jaxpr = jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((F, n), jnp.uint8),
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
    )
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    return eqn


def _block_shape(eqn, i):
    """Block ``i``'s sizes, a squeezed axis (the out block's slot) left out."""
    block = eqn.params["grid_mapping"].block_mappings[i].block_shape
    sizes = [getattr(d, "block_size", d) for d in block]
    return tuple(int(d) for d in sizes if isinstance(d, int))


def _split_cases():
    r = np.random.RandomState(7)
    wide = (
        r.uniform(1.0, 2.0, 200000) * 10.0 ** r.uniform(-30, 30, 200000)
    ).astype(np.float32)
    # every one of the 24 significand bits set, at many exponents
    full = np.float32(2.0 - 2.0 ** -23) * np.float32(2.0) ** np.arange(-90, 90)
    return {
        "positive_1e-30_to_1e30": wide,
        "negative_1e-30_to_1e30": -wide,
        "zeros_and_signed_zero": np.array([0.0, -0.0, 0.0], np.float32),
        "all_24_bits_set": np.concatenate([full, -full]).astype(np.float32),
        "halfway_to_the_next_bf16": (
            np.float32(1.0 + 2.0 ** -8) * np.float32(2.0) ** np.arange(-60, 60)
        ).astype(np.float32),
        "gradients": r.randn(100000).astype(np.float32),
    }


@pytest.mark.parametrize("case", sorted(_split_cases()))
def test_three_piece_split_is_exact(case):
    """The float32 operand contract: three bf16 pieces that sum back to the
    float32 value bit for bit, each exactly representable in bfloat16."""
    from lightgbm_tpu.ops.hist_pallas import split_bf16

    v = _split_cases()[case]
    pieces = [np.asarray(p) for p in split_bf16(jnp.asarray(v), 3)]
    assert len(pieces) == 3
    for p in pieces:
        assert p.dtype == np.float32
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(p).astype(jnp.bfloat16).astype(jnp.float32)), p
        )
    np.testing.assert_array_equal((pieces[0] + pieces[1]) + pieces[2], v)
    # one piece is the rounding the caller asked for, nothing else
    (one,) = split_bf16(jnp.asarray(v), 1)
    np.testing.assert_array_equal(
        np.asarray(one), np.asarray(jnp.asarray(v).astype(jnp.bfloat16).astype(jnp.float32))
    )


@pytest.mark.parametrize("num_bins", [63, 255])
@pytest.mark.parametrize("values", ["gradients_1e-6_to_1e3", "integers"])
def test_f32_kernel_against_float64_oracle(rng, num_bins, values):
    """Rows no multiple of the chunk, F no multiple of FB. Gradients over
    nine decades at the tolerance the kernel has always been held to;
    integer values whose sums fit 24 bits come out exactly."""
    F, n = 11, 9001  # three chunks of 3072 under histogram_pallas' 4096
    bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
    if values == "integers":
        vals = rng.randint(-300, 300, (n, 3)).astype(np.float32)
    else:
        vals = (
            rng.randn(n, 3) * 10.0 ** rng.uniform(-6, 3, (n, 3))
        ).astype(np.float32)
    ref = histogram_reference(bins, vals, num_bins)
    out = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(vals), num_bins,
            chunk=4096, dtype_name="float32", interpret=True,
        )
    )
    if values == "integers":
        np.testing.assert_array_equal(out, ref)
        return
    # today's tolerance (rtol=1e-5, atol=1e-4) where it can hold at all;
    # float32 accumulation itself errs by a few ulps of a bin's sum of |v|
    # (1.5e-4 where thousands cancel), so the bound everywhere is 1e-6 of
    # that sum, the tighter of the two wherever both apply: a dropped
    # third piece would read 8e-6 of it, a dropped second 2e-3
    mass = histogram_reference(bins, np.abs(vals), num_bins)
    err = np.abs(out - ref)
    assert (err <= 1e-6 * mass).all()
    assert (err <= np.maximum(1e-5 * np.abs(ref) + 1e-4, 1e-6 * mass)).all()


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_vmapped_lanes_equal_single_calls_bitwise(rng, dtype_name):
    """The contract the grower's ``lanes`` form rests on (ops/grow.py,
    _ENV_SPEC_HIST): a vmapped lane runs the kernel verbatim."""
    import jax

    W, F, n, B = 8, 10, 5000, 255  # two chunks of 2560
    bins = rng.randint(0, B, (W, F, n)).astype(np.uint8)
    vals = (rng.randn(W, n, 3) * 10.0 ** rng.uniform(-3, 2, (W, n, 3))).astype(
        np.float32
    )
    one = lambda b, v: histogram_pallas(
        b, v, B, chunk=4096, dtype_name=dtype_name, interpret=True
    )
    lanes = np.asarray(jax.vmap(one)(jnp.asarray(bins), jnp.asarray(vals)))
    for w in range(W):
        single = np.asarray(one(jnp.asarray(bins[w]), jnp.asarray(vals[w])))
        np.testing.assert_array_equal(lanes[w], single)


def _flat_batch(rng, cnts, F, B, chunk, tail_chunks=2):
    """``cnts`` segments laid end to end, each padded with zero values to
    whole ``chunk``s, and ``tail_chunks`` more that belong to no segment
    (a lattice's round-up). Pad and tail bins are 7s, not zeros, to show
    that nobody's sums read them. The segments are drawn first, so one
    seed lays the same segments out at any chunk."""
    segments = [
        (rng.randint(0, B, (F, c)).astype(np.uint8),
         (rng.randn(c, 3) * 10.0 ** rng.uniform(-3, 2, (c, 3))).astype(np.float32))
        for c in cnts
    ]
    padded = [-(-c // chunk) * chunk for c in cnts]
    ends = np.cumsum(padded)
    L = int(ends[-1]) + tail_chunks * chunk
    bins = np.full((F, L), 7, np.uint8)
    vals = np.zeros((L, 3), np.float32)
    for end, pad, (b, v) in zip(ends, padded, segments):
        at = end - pad
        bins[:, at:at + len(v)] = b
        vals[at:at + len(v)] = v
    return bins, vals, ends.astype(np.int32), segments


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_slots_equal_single_calls_bitwise_at_any_chunk(dtype_name):
    """The contract the grower's ``flat`` form rests on (ops/grow.py
    ``segment_histogram_flat``): a segment's histogram does not depend on the
    company it was computed in, nor on the block size. Eight segments, two
    of them empty, one over several chunks, one of a single row; a slot
    that owns no chunk is never written by the kernel and reads zeros."""
    from lightgbm_tpu.ops.hist_pallas import histogram_pallas_slots

    cnts = [1300, 0, 1, 5000, 0, 700, 512, 33]
    F, B = 11, 255
    out = {}
    for chunk in (512, 1536):
        bins, vals, ends, segments = _flat_batch(
            np.random.RandomState(5), cnts, F, B, chunk)
        out[chunk] = np.asarray(histogram_pallas_slots(
            jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(ends), B,
            chunk=chunk, dtype_name=dtype_name, interpret=True,
        ))
        assert np.isfinite(out[chunk]).all()
        for w, (b, v) in enumerate(segments):
            if not len(v):
                assert (out[chunk][w] == 0).all()
                continue
            single = np.asarray(histogram_pallas(
                jnp.asarray(b), jnp.asarray(v), B, chunk=4096,
                dtype_name=dtype_name, interpret=True,
            ))
            np.testing.assert_array_equal(out[chunk][w], single)
    # the same RandomState drew the same segments at both chunks
    np.testing.assert_array_equal(out[512], out[1536])


def test_slots_against_float64_oracle(rng):
    """The flat batch under the file's tolerance for the float32 kernel."""
    from lightgbm_tpu.ops.hist_pallas import histogram_pallas_slots

    F, B = 11, 255
    bins, vals, ends, segments = _flat_batch(rng, [3000, 0, 9, 1025], F, B, 1024)
    out = np.asarray(histogram_pallas_slots(
        jnp.asarray(bins), jnp.asarray(vals), jnp.asarray(ends), B,
        chunk=1024, dtype_name="float32", interpret=True,
    ))
    for w, (b, v) in enumerate(segments):
        ref = histogram_reference(b, v, B)
        mass = histogram_reference(b, np.abs(v), B)
        err = np.abs(out[w] - ref)
        assert (err <= 1e-6 * mass).all()
        assert (err <= np.maximum(1e-5 * np.abs(ref) + 1e-4, 1e-6 * mass)).all()


def test_slots_refuse_a_chunk_the_kernel_cannot_take():
    from lightgbm_tpu.ops.hist_pallas import histogram_pallas_slots

    bins, vals = jnp.zeros((8, 2048), jnp.uint8), jnp.zeros((2048, 3))
    for chunk in (768, 1536):  # no multiple of 512; does not divide the rows
        with pytest.raises(ValueError, match="chunk"):
            histogram_pallas_slots(bins, vals, jnp.asarray([2048]), 255,
                                   chunk=chunk, interpret=True)


@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_feature_batched_matches_v1(rng, dtype_name):
    """The default (feature-batched) kernel against the per-feature-grid v1
    at the same chunking — same radix math, different grid/factor layout.
    bfloat16 is the one-piece case of the same body: the same rounded
    operands as v1's, so the same tolerance."""
    from lightgbm_tpu.ops.hist_pallas import histogram_pallas_v1

    F, n, B = 5, 4096, 255
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    kw = dict(chunk=1024, dtype_name=dtype_name, interpret=True)
    h2 = np.asarray(histogram_pallas(jnp.asarray(bins), jnp.asarray(vals), B, **kw))
    h1 = np.asarray(histogram_pallas_v1(jnp.asarray(bins), jnp.asarray(vals), B, **kw))
    np.testing.assert_allclose(h1, h2, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize(
    "dtype_name,pieces", [("float32", 3), ("bfloat16", 1)]
)
def test_accumulator_block_follows_the_operand_dtype(dtype_name, pieces):
    """One body, one parameter it observes: the accumulator block is
    [FB, HI, pieces*K*LO], so bfloat16 keeps the 24 columns it always had
    and float32 carries its three pieces side by side."""
    from lightgbm_tpu.ops.hist_pallas import FB, LO, _pieces_for

    assert _pieces_for(dtype_name) == pieces
    eqn = _pallas_call_eqn(2048, 1024, dtype_name, F=5)
    assert _block_shape(eqn, -1) == (FB, 32, pieces * 3 * LO)
    assert eqn.outvars[0].aval.dtype == jnp.float32
    # operands reach the MXU as bfloat16 only through the split: the
    # kernel's inputs are the chunks' slot table and live count (scalar
    # prefetch), the u8 bins and the float32 values as passed
    assert [str(v.aval.dtype) for v in eqn.invars] == [
        "int32", "int32", "uint8", "float32"]


@pytest.mark.parametrize(
    "n,chunk,want",
    [
        (8192, 6144, (2, 4096)),      # a fixed C=6144 padded this to 12288
        (24576, 16384, (2, 12288)),
        (98304, 16384, (6, 16384)),
        (200000, 16384, (13, 15872)),
        (9001, 4096, (3, 3072)),
        (1000, 512, (2, 512)),
        (300, 4096, (1, 512)),
    ],
)
def test_equal_chunks_under_the_cap(n, chunk, want):
    """Rows are cut into equal chunks of at most ``chunk``, so the grower's
    lattice sizes are never padded by a chunk's remainder."""
    eqn = _pallas_call_eqn(n, chunk, "float32")
    grid = eqn.params["grid_mapping"].grid
    assert (int(grid[1]), _block_shape(eqn, 0)[1]) == want


def test_feature_batched_many_features(rng):
    """F larger than a VMEM-friendly block still chunks correctly (the
    fori feature loop + [F, C] block cap)."""
    F, n, B = 67, 1536, 63
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, B)
    out = np.asarray(
        histogram_pallas(
            jnp.asarray(bins), jnp.asarray(vals), B,
            chunk=512, dtype_name="float32", interpret=True,
        )
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_packed4_matches_oracle(rng):
    """Nibble-packed kernel (B <= 16) against the numpy oracle — the
    measurement vehicle for the 4-bit-bin question (dense_nbits_bin.hpp)."""
    from lightgbm_tpu.ops.hist_pallas import histogram_pallas_packed4, pack4

    F, n, B = 9, 3001, 16  # odd n exercises the pad row
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, B)
    bp, vp = pack4(jnp.asarray(bins), jnp.asarray(vals))
    out = np.asarray(
        histogram_pallas_packed4(
            bp, vp, B, chunk=512, dtype_name="float32", interpret=True
        )
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)


def test_packed4_via_leaf_histogram(rng):
    """pallas_packed4 is part of leaf_histogram's routed impl vocabulary
    (ISSUE 13): the router packs the raw [F, N] bins itself and the result
    matches the numpy oracle AND the XLA one-hot differential baseline."""
    F, n, B = 7, 2001, 16  # odd n exercises the pack4 pad row
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, B)
    out = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), B,
                       impl="pallas_packed4", chunk=1024, interpret=True)
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)
    base = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), B,
                       impl="xla", chunk=1024)
    )
    np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-5)


def test_packed4_supported_gating():
    """supported_packed4 is the router's gate: <= 16 bins, TPU backend
    (shape-only under ignore_backend, the forced-interpret test mode)."""
    from lightgbm_tpu.ops.hist_pallas import supported_packed4

    assert supported_packed4(16, backend="tpu")
    assert not supported_packed4(17, backend="tpu")
    assert not supported_packed4(16, backend="cpu")
    assert supported_packed4(16, ignore_backend=True)
    assert not supported_packed4(17, ignore_backend=True)
    from lightgbm_tpu.ops.histogram import impl_supported

    assert impl_supported("pallas_packed4", 16, "tpu")
    assert not impl_supported("pallas_packed4", 32, "tpu")
    assert not impl_supported("pallas_packed4", 16, "cpu")
    assert impl_supported("xla", 256, "cpu")


def test_packed4_over16_falls_back_to_xla(rng):
    """A forced pallas_packed4 at B > 16 must fall back to the XLA one-hot
    (warn_once + counter) instead of mis-lowering — same contract as the
    radix kernel's num_bins bound."""
    F, n, B = 3, 512, 32
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    out = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), B,
                       impl="pallas_packed4")
    )
    base = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), B, impl="xla")
    )
    np.testing.assert_array_equal(out, base)


@pytest.mark.parametrize("num_bins", [16, 63, 255])
def test_xla_radix_matches_oracle(rng, num_bins):
    """The plain-XLA radix factorization against the numpy oracle and the
    one-hot contraction (the routing bake-off's third contender)."""
    F, n = 6, 3000
    bins = rng.randint(0, num_bins, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    ref = histogram_reference(bins, vals, num_bins)
    out = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), num_bins,
                       impl="xla_radix", chunk=512)
    )
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)
    base = np.asarray(
        leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), num_bins,
                       impl="xla", chunk=512)
    )
    np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-5)


def test_xla_fallback_selected_on_cpu(rng):
    # on the CPU test platform, impl="auto" must route to the XLA contraction
    assert not supported(256, backend="cpu")
    assert supported(256, backend="tpu")
    assert not supported(512, backend="tpu")  # beyond the radix M budget
    F, n, B = 2, 512, 16
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    vals = rng.randn(n, 3).astype(np.float32)
    out = np.asarray(leaf_histogram(jnp.asarray(bins), jnp.asarray(vals), B))
    ref = histogram_reference(bins, vals, B)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)

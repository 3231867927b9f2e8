"""The routed Pallas histogram kernel compiled for a v5e that is described,
not attached: Mosaic accepts the lane-dense body at the largest chunk the
``_BYTES_PER_COL`` table allows (what interpret mode cannot show); and the
speculative grower's step, which must copy neither histogram carry nor the
bin matrix. Nothing runs, so nothing here is a device number. The topology
is described inside a fixture: one worker loads the TPU compiler, and only
when it is given this file."""
import os
import re

import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu.ops.grow as grow_mod
import lightgbm_tpu.ops.histogram as hist_mod
from lightgbm_tpu.ops import hist_pallas
from lightgbm_tpu.ops.split import SplitParams


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here, or another process has it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "dtype_name,num_bins,lanes",
    [("float32", 255, 0), ("bfloat16", 255, 0), ("float32", 63, 8)],
)
def test_mosaic_accepts_the_largest_chunk(one_chip, dtype_name, num_bins, lanes):
    cap = hist_pallas._max_chunk_for("pallas")
    F, N = 16, 2 * cap
    lead = (lanes,) if lanes else ()
    bins = jax.ShapeDtypeStruct(lead + (F, N), jnp.uint8, sharding=one_chip)
    vals = jax.ShapeDtypeStruct(lead + (N, 3), jnp.float32, sharding=one_chip)

    def one(b, v):
        return hist_pallas._histogram_pallas_fb(
            b, v, num_bins, chunk=cap, dtype_name=dtype_name
        )

    fn = jax.vmap(one) if lanes else one
    compiled = jax.jit(fn).lower(bins, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("F", [64, 968])
@pytest.mark.parametrize("dtype_name", ["float32", "bfloat16"])
def test_mosaic_accepts_the_slots_at_the_largest_chunk(one_chip, F, dtype_name):
    """The slot-grouped entry (scalar prefetch, the out block's slot from
    the chunk table, the body's 512-row slices at a dynamic offset) at the
    largest chunk the grower's rule can choose, narrow and wide."""
    cap = grow_mod.flat_chunk(10 ** 9, 8)
    group = hist_pallas._UNROLL * hist_pallas.SUB
    assert cap == hist_pallas._max_chunk_for("pallas") // group * group
    bins = jax.ShapeDtypeStruct((F, 3 * cap), jnp.uint8, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((3 * cap, 3), jnp.float32, sharding=one_chip)
    ends = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda b, v, e: hist_pallas.histogram_pallas_slots(
            b, v, e, 255, chunk=cap, dtype_name=dtype_name)
    ).lower(bins, vals, ends).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture
def spec_pallas(monkeypatch):
    """The cells' grower on a process whose default backend is the CPU: both
    choices are read at import, so they are steered here, in the test."""
    monkeypatch.setattr(grow_mod, "_ENV_GROW", "spec")
    monkeypatch.setattr(hist_mod, "_ENV_IMPL", "pallas")
    jax.clear_caches()
    yield
    jax.clear_caches()


B = M = 255  # the cells' bins and leaves


def _compile_grower(one_chip, F, N, with_bins_nf=False):
    """``grow_tree`` lowered and compiled for the described chip at 255 bins
    and 255 leaves, the two ``[255, F, 255, 3]`` carries donated."""

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {
        "num_bin": arg((F,), jnp.int32),
        "missing_type": arg((F,), jnp.int32),
        "default_bin": arg((F,), jnp.int32),
        "monotone": arg((F,), jnp.int8),
    }
    compiled = grow_mod.grow_tree.lower(
        arg((F, N), jnp.uint8), arg((N,), jnp.float32), arg((N,), jnp.float32),
        arg((N,), jnp.float32), arg((F,), jnp.bool_), meta,
        num_leaves=M, max_depth=-1, num_bins=B,
        params=SplitParams(0.0, 0.0, 0.0, 1, 100.0, 0.0), chunk=16384,
        hist_buf=arg((M, F, B, 3), jnp.float32),
        spec_buf=arg((M, F, B, 3), jnp.float32),
        bins_nf=arg((N, F), jnp.uint8) if with_bins_nf else None,
    ).compile()
    assert grow_mod._LAST_GROW_MODE == "spec"
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the cells' histogram, not the one-hot
    return compiled, text


@pytest.mark.parametrize("F", [64, 968, 1600])
def test_a_grower_step_copies_no_histogram_carry(one_chip, spec_pallas, F):
    """A speculative batch reads 8 rows of each carry
    (``grow._carry_rows``). Read by ``buf[idx]``, the TPU compiler cut a
    gather whose result outgrew its fast memory into column pieces
    (``mini-gather-slice``: at 968 columns, none at 64) and materialised every
    piece, a copy of the whole carry a step; read by a stacked unroll of
    dynamic slices, it relaid the whole carry out instead (a ``copy`` to
    ``{1,0,3,2}``). Neither may come back, at a narrow table, a wide one or
    the drawn columns of one (1600 of 2000: ``feature_fraction`` 0.8).
    The loop body does not depend on the rows, so 4096 do. Nothing runs, and
    nothing here is a device number."""
    compiled, text = _compile_grower(one_chip, F, 4096)
    assert text.count("mini-gather-slice") == 0
    carry = re.escape(f"f32[{M},{F},{B},3]")
    assert not re.search(rf"= {carry}\S* copy\(", text)
    if F == 968:
        # 704.6 MB with the gather, 189.0 MB without (compiled here, PR 29)
        carry_bytes = M * F * B * 3 * 4
        assert compiled.memory_analysis().temp_size_in_bytes < carry_bytes // 2


_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\((?:%([^\s,)]+))?"
)


def _loop_computations(text):
    """The computations of an optimised HLO module by name, each a list of
    its lines, and the names of those some ``while`` runs: its body or
    condition, and whatever those call (branches, fusions, inner loops)."""
    comps, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    in_loop = set(re.findall(r"(?:body|condition)=%([^\s,)}]+)", text))
    todo = list(in_loop)
    while todo:
        for ref in re.findall(r"%([^\s,)}]+)", "\n".join(comps[todo.pop()])):
            if ref in comps and ref not in in_loop:
                in_loop.add(ref)
                todo.append(ref)
    return comps, in_loop


def _loop_copies_of_a_handed_in_matrix(text, shape_a, shape_b):
    """The ``copy`` instructions of an optimised HLO module that (1) lie in a
    computation some ``while`` runs, (2) give a ``u8`` array of
    either shape, and (3) read, through ``get-tuple-element`` and ``bitcast``
    alone, a parameter of their computation: a value the loop carries or is
    handed, not one a step computes (a gathered segment of as many rows as
    the table has the matrix's shape too, and its relayout is the
    histogram's own work)."""
    comps, in_loop = _loop_computations(text)
    shapes = "|".join(re.escape("u8[%d,%d]" % s) for s in (shape_a, shape_b))
    whole = re.compile(rf"= (?:{shapes})\S* copy\(")
    found = []
    for name in in_loop:
        defs = {}
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if m:
                defs[m.group(1)] = (m.group(2), m.group(3), line)
        for op, src, line in defs.values():
            if op != "copy" or not whole.search(line):
                continue
            while src in defs and defs[src][0] in ("get-tuple-element", "bitcast"):
                src = defs[src][1]
            if src in defs and defs[src][0] == "parameter":
                found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("F", [64, 968])
@pytest.mark.parametrize("both_layouts", [True, False])
def test_a_grower_step_copies_no_bin_matrix(one_chip, spec_pallas, F, both_layouts):
    """The grower's loop carries the bin matrix, and a carried value has one
    layout. The histogram's segment gathers want it rows major, the
    partition's take of the 8 split features' columns wants it columns major,
    and the compiler sinks that take into every branch of the partition's
    lattice switch: with one matrix for both, every grower step copies the
    whole of it to the other layout (on a v5e 2.5 ms a step at 968 x 750K,
    a ninth of an iteration there; PERF.md, PR 31). Handed both layouts as
    two values, as the serial learner hands them, the loop copies neither;
    handed one, it shows the copy once a partition branch, so this test is
    known to see what it guards. 5000 rows, so that most segment buckets
    have another shape than the table. Nothing runs, and nothing here is a
    device number."""
    N = 5000
    _, text = _compile_grower(one_chip, F, N, with_bins_nf=both_layouts)
    found = _loop_copies_of_a_handed_in_matrix(text, (F, N), (N, F))
    if both_layouts:
        assert not found, found
    else:
        branches = grow_mod._branch_steps(-(-N // 256) + grow_mod._ENV_SPEC_K)
        assert len(found) == len(branches), found


def _bucket_kernels(F, N):
    """The grower's bucket kernels for an ``[F, N]`` table: the lattices
    they were built over (``sizes``, ``root_sizes``)."""
    zeros = jnp.zeros((F,), jnp.int32)
    return grow_mod.make_bucket_kernels(
        jnp.zeros((F, N), jnp.uint8),
        {"num_bin": zeros, "missing_type": zeros, "default_bin": zeros}, B)


def test_one_grower_serves_sampled_and_unsampled_trees(one_chip, spec_pallas):
    """The row sample is an operand (``bag_mask``), the root segment's length
    a traced value: the one program compiled for the chip holds the sampled
    tree's own work (the stable partition of the rows by the mask, the root's
    pass over its segment at the few sizes of ``root_sizes``, the
    out-of-bag rows' leaves by the finished tree, scope ``oob_leaf``) under
    conditionals beside the whole-table root pass, none of it in the grower's
    loop, and the loop copies no bin matrix (PR 31's guard, kept). The CPU
    witness that one executable runs both kinds of tree:
    tests/test_grow_rooted_at_sample.py. Nothing runs, and nothing here is a
    device number."""
    F, N = 64, 5000
    compiled, text = _compile_grower(one_chip, F, N, with_bins_nf=True)
    assert not _loop_copies_of_a_handed_in_matrix(text, (F, N), (N, F))
    comps, in_loop = _loop_computations(text)
    outside = "\n".join(l for name, lines in comps.items() if name not in in_loop
                        for l in lines)
    inside = "\n".join(l for name in in_loop for l in comps[name])
    assert "oob_leaf" in outside and "oob_leaf" not in inside
    assert re.search(r" sort\(", outside)           # the stable partition by the mask
    assert " conditional(" in outside
    kern = _bucket_kernels(F, N)
    assert kern.root_sizes == (4096, N) and set(kern.root_sizes) <= set(kern.sizes)


def _switches(comps):
    """(computation, its branches' names) of every index conditional."""
    for name, lines in comps.items():
        for line in lines:
            m = re.search(r" conditional\(.*branch_computations=\{([^}]*)\}", line)
            if m:
                yield name, re.findall(r"%([^\s,]+)", m.group(1))


def _switches_over_the_kernel(text):
    """The index conditionals of an optimised HLO module whose branches run
    the histogram kernel (``hist_pallas_fb`` in a custom call's metadata,
    in the branch or in what it calls): (in a loop?, number of branches)."""
    comps, in_loop = _loop_computations(text)

    def runs_kernel(name, seen=()):
        body = "\n".join(comps[name])
        if "tpu_custom_call" in body and "hist_pallas_fb" in body:
            return True
        return any(
            runs_kernel(ref, seen + (name,))
            for ref in set(re.findall(r"%([^\s,)}]+)", body))
            if ref in comps and ref != name and ref not in seen
        )

    return [
        (name in in_loop, len(branches)) for name, branches in _switches(comps)
        if any(runs_kernel(b) for b in branches)
    ]


def test_the_histogram_switch_has_no_more_branches_than_the_lanes_form(
        one_chip, spec_pallas):
    """A switch branch is seconds of the cold build. Under ``pallas`` the
    grower's loop holds ONE switch over the kernel, the flat form's, with as
    many branches as ``flat_branches`` says and no more than the lanes form's
    switch over the bucket lattice had (the parent's); outside the loop, the
    sampled root's few sizes under the conditional that keeps the whole
    table's pass for an unsampled tree. Nothing runs, and nothing here is a
    device number."""
    F, N = 64, 5000
    _, text = _compile_grower(one_chip, F, N, with_bins_nf=True)
    assert grow_mod._LAST_SPEC_HIST == "flat"
    kern = _bucket_kernels(F, N)
    found = _switches_over_the_kernel(text)
    in_loop = [n for looped, n in found if looped]
    assert in_loop == [len(grow_mod.flat_branches(N, grow_mod._ENV_SPEC_K))]
    assert in_loop[0] <= len(kern.sizes)
    assert sorted(n for looped, n in found if not looped) == sorted(
        [len(kern.root_sizes), 2])


def _lane_gathers_by_branch(text):
    """Of an optimised HLO module that holds one index conditional: for each
    of its branches, the result types of the gathers that give a value a
    lane (more than a hundred elements; the left counts' read of 8 lanes is
    none), with the element type of what each gathers from."""
    comps, _ = _loop_computations(text)
    switches = [branches for _, branches in _switches(comps)]
    assert len(switches) == 1, switches

    def gathers(name, seen=()):
        found = []
        for line in comps[name]:
            m = re.search(r"= (\w+)\[(\d+)\]\S* gather\(%(\S+?),", line)
            if m and int(m.group(2)) > 100:
                src = next(l for l in comps[name] if re.search(rf"%{re.escape(m.group(3))} = ", l))
                found.append((m.group(1), int(m.group(2)), re.search(r"= (\w+)\[", src).group(1)))
            for ref in re.findall(r"calls=%([^\s,)}]+)", line):
                if ref in comps and ref not in seen:
                    found += gathers(ref, seen + (name,))
        return found

    return [gathers(b) for b in switches[0]]


@pytest.mark.parametrize("categorical", [False, True])
def test_a_partition_lane_gathers_only_its_bin(one_chip, categorical):
    """``partition_batch`` alone at the Bosch cell's width and batch: a lane
    of the flat pass reads its row as a window of ``order`` (eight slices
    chosen by the lane's slot, fused into one loop) and gathers one value,
    its bin in its slot's split column (``u8``). On a table that has no
    categorical column (no ``is_categorical`` in the meta) no branch holds
    the membership lookup, a ``pred`` gather from the ``[8, 255]`` bitset
    that cost a v5e 10.3 ns a lane (PERF.md, PR 36); with the key the lookup
    is there, so this test is known to see what it guards. 5000 rows: the
    branches differ by their lanes alone. Nothing runs, and nothing here is
    a device number."""
    F, N, W = 968, 5000, 8

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = {k: arg((F,), jnp.int32) for k in ("num_bin", "missing_type", "default_bin")}
    if categorical:
        meta["is_categorical"] = arg((F,), jnp.bool_)

    def fn(bins, meta, order, begin, pcnt, feat, thr, dleft, member):
        kern = grow_mod.make_bucket_kernels(bins, meta, B, kb=W)
        assert kern.part_sizes[-1] == -(-N // 256) * 256 + W * 256
        return kern.partition_batch(order, begin, pcnt, feat, thr, dleft, member)

    i32 = jnp.int32
    text = jax.jit(fn).lower(
        arg((F, N), jnp.uint8), meta, arg((N,), i32), arg((W,), i32), arg((W,), i32), arg((W,), i32),
        arg((W,), i32), arg((W,), jnp.bool_), arg((W, B), jnp.bool_),
    ).compile().as_text()
    by_branch = _lane_gathers_by_branch(text)
    sizes = grow_mod._branch_steps(-(-N // 256) + W)
    assert len(by_branch) == len(sizes)
    for units, found in zip(sizes, by_branch):
        want = [("u8", units * 256, "u8")]
        if categorical:
            want.append(("pred", units * 256, "pred"))
        assert sorted(found) == sorted(want), (units, found)

"""Every device op of the grower under a named phase, and the grower's counts
of the work its padded passes pay.

The phases are ``obs/trace.SCOPES``: ``grow_tree`` compiled on the CPU in
each of its forms (the speculative step's flat and lanes histograms, the
sequential grower, the histogram pool, masked mode, a categorical column
in bundles) is read instruction by
instruction in its optimised HLO, and every op the device runs has to lie
under one of them. The counters (ops/grow.py ``COUNTER_NAMES``): the rows the
histogram's gather reads against the rows its kernel streams and the rows
the tree needed, and the rows the partition's column take asks for. Nothing
here is a device number."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
import lightgbm_tpu.ops.grow as grow_mod
from lightgbm_tpu.obs import trace
from lightgbm_tpu.ops.split import SplitParams

#: what is no op of its own: the values a computation is handed and makes,
#: and the control flow, whose bodies are read
EXEMPT = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
          "while", "conditional"}

#: The allow-list: instructions XLA makes itself carry no ``op_name`` at all,
#: so that no scope can reach them. Each opcode, and where it comes from:
XLA_OWN = {
    # copy insertion around loop carries and branch operands, and layouts
    "copy",
    # a constant broadcast to a shape, materialised by a pass
    "broadcast",
    # the CPU's split of a long cumulative sum into a tree of windows
    "reduce-window",
    # the TPU's asynchronous moves between memory spaces and its slices
    "copy-start", "copy-done", "slice-start", "slice-done",
    # the TPU's buffer allocations and the concatenation of its own slices
    # (AllocateBuffer, ConcatBitcast)
    "custom-call",
    # the TPU's loop and layout passes: a trip count's test, the choice it
    # drives, a reshape between tilings
    "compare", "select", "reshape",
    # a fusion XLA roots in an instruction of its own (a bitcast of a layout
    # pass, the bounds check of a scatter it expands): judged by the named
    # instructions it holds, which have to lie under a phase like any other
    "fusion",
}

_COMP = re.compile(r"^(?:ENTRY )?%(\S+) \(.*\) -> .* \{$")
_INST = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _computations(text):
    """{name: [instruction lines]} of an optimised HLO module, and the name
    of its entry."""
    comps, name, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            entry = name if line.startswith("ENTRY") else entry
        elif name is not None and line.startswith(" "):
            comps[name].append(line)
    return comps, entry


def _run_on_the_device(comps, entry):
    """The computations whose instructions the device runs one by one: the
    entry, and what a ``while``, a ``conditional`` or a ``call`` runs; not a
    fusion's body, nor a reduction's or a sort's comparator. -> {name: the
    phase of the op XLA made it for, None for the program's own}: a loop XLA
    expands a named scatter into runs under that scatter's name, where a
    branch of the program's own ``cond`` or body of its ``while`` holds
    instructions that carry their own."""
    seen, todo = {entry: None}, [entry]
    while todo:
        name = todo.pop()
        for line in comps[name]:
            m = _INST.match(line)
            if not m:
                continue
            refs = re.findall(r"(?:body|condition|true_computation|"
                              r"false_computation)=%([^\s,)}]+)", line)
            branches = re.search(r"branch_computations=\{([^}]*)\}", line)
            if branches:
                refs += re.findall(r"%([^\s,]+)", branches.group(1))
            if m.group(2) == "call":
                refs += re.findall(r"(?:to_apply|calls)=%([^\s,)}]+)", line)
            op = _OP_NAME.search(line)
            phase = seen[name]
            if op and op.group(1).rsplit("/", 1)[-1] not in ("while", "cond"):
                phase = trace.scope_of(op.group(1)) or phase
            for ref in refs:
                if ref in comps and ref not in seen:
                    seen[ref] = phase
                    todo.append(ref)
    return seen


def _unnamed_phase(op_name):
    """True where an op carries a name that no phase covers. A name whose
    last part is ``while`` or ``cond`` is the control flow's own, which XLA
    gives what it adds for a loop (its induction variable)."""
    if trace.scope_of(op_name) is not None:
        return False
    return op_name.rsplit("/", 1)[-1] not in ("while", "cond")


def unscoped_instructions(text):
    """The instructions of an optimised HLO module that the device runs and
    that lie under no phase of ``trace.SCOPES``, with the reason: those that
    carry an op_name outside every phase, and those that carry none and are
    neither XLA's own (``XLA_OWN``) nor in a computation XLA made for a
    named op."""
    comps, entry = _computations(text)
    found = []
    for name, made_for in _run_on_the_device(comps, entry).items():
        for line in comps[name]:
            m = _INST.match(line)
            if not m or m.group(2) in EXEMPT:
                continue
            op = _OP_NAME.search(line)
            if op:
                if _unnamed_phase(op.group(1)):
                    found.append(("outside every phase", line.strip()[:240]))
            elif made_for:
                continue
            elif m.group(2) not in XLA_OWN:
                found.append(("no op_name", line.strip()[:240]))
            elif m.group(2) == "fusion":
                body = comps[re.search(r"calls=%([^\s,)}]+)", line).group(1)]
                names = [_OP_NAME.search(l) for l in body]
                if any(n and _unnamed_phase(n.group(1)) for n in names):
                    found.append(("a fused op outside every phase",
                                  line.strip()[:240]))
    return found


F, N, B, M = 6, 3000, 31, 15


def _grower_hlo(monkeypatch, grow, spec_hist="", meta_keys=(), **kw):
    """``grow_tree``'s optimised HLO on the CPU for a small table, in the
    form the steering asks for; ``meta_keys``: a categorical column
    (``is_categorical``), one bundle a column (``group_id``)."""
    monkeypatch.setattr(grow_mod, "_ENV_GROW", grow)
    monkeypatch.setattr(grow_mod, "_ENV_SPEC_HIST", spec_hist)
    jax.clear_caches()
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, (F, N)).astype(np.uint8))
    meta = {"num_bin": jnp.full((F,), B, jnp.int32),
            "missing_type": jnp.zeros((F,), jnp.int32),
            "default_bin": jnp.zeros((F,), jnp.int32),
            "monotone": jnp.zeros((F,), jnp.int8)}
    if "is_categorical" in meta_keys:
        meta["is_categorical"] = jnp.arange(F) == 0
    if "group_id" in meta_keys:
        meta["group_id"] = jnp.arange(F, dtype=jnp.int32)
        meta["bin_offset"] = jnp.ones((F,), jnp.int32)
        kw["num_group_bins"] = B
    kw.setdefault("bins_nf", None if kw.get("hist_mode") == "masked" else bins.T)
    try:
        return grow_mod.grow_tree.lower(
            bins, jnp.asarray(rng.randn(N).astype(np.float32)),
            jnp.ones((N,), jnp.float32), jnp.ones((N,), jnp.float32),
            jnp.ones((F,), bool), meta, num_leaves=M, max_depth=-1,
            num_bins=B, params=SplitParams(0.0, 0.0, 0.0, 1, 1e-3, 0.0),
            chunk=1024, **kw,
        ).compile().as_text()
    finally:
        jax.clear_caches()


FORMS = {
    "speculative-flat": dict(grow="spec", spec_hist="flat"),
    "speculative-lanes": dict(grow="spec", spec_hist="lanes"),
    "sequential": dict(grow="seq"),
    "sequential-pooled": dict(grow="seq", hist_pool_slots=4),
    "masked": dict(grow="seq", hist_mode="masked"),
    "speculative-categorical-bundled": dict(
        grow="spec", meta_keys=("is_categorical", "group_id")),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_op_of_the_grower_lies_under_a_phase(monkeypatch, form):
    text = _grower_hlo(monkeypatch, **FORMS[form])
    want = {"speculative-flat": ("spec", "flat"), "speculative-lanes": ("spec", "lanes"),
            "speculative-categorical-bundled": ("spec", "lanes")}
    assert (grow_mod._LAST_GROW_MODE, grow_mod._LAST_SPEC_HIST) == want.get(
        form, ("seq", None))
    found = unscoped_instructions(text)
    assert not found, found[:10]
    # and the phases are there: the work of a step under its own names
    names = set(filter(None, map(trace.scope_of, _OP_NAME.findall(text))))
    assert {"tree_init", "tree_out", "apply_split", "split_find",
            "split_find/prep", "carry_write", "partition/split_columns",
            "partition/lanes", "hist_build/feed", "hist_build/kernel"} <= names
    if FORMS[form].get("hist_mode") != "masked":
        assert {"partition/scatter", "leaf_ids", "hist_build/root"} <= names


def test_the_reader_sees_an_unscoped_op(monkeypatch):
    """The check is known to see what it guards: an op added outside every
    phase is found."""
    text = _grower_hlo(monkeypatch, grow="seq")
    assert not unscoped_instructions(text)
    # the sampled root's stable partition by the mask: a sort of its own
    line = next(l for l in text.splitlines()
                if re.search(r" sort\(", l) and "/tree_init/" in l)
    planted = text.replace(line, line.replace("/tree_init/", "/nowhere/"))
    assert [r for r, _ in unscoped_instructions(planted)] == ["outside every phase"]
    unnamed = re.sub(r", metadata=\{[^}]*\}", "", line)
    assert unnamed != line
    assert [r for r, _ in unscoped_instructions(text.replace(line, unnamed))] == [
        "no op_name"]


def test_scope_of_takes_the_innermost_phase():
    path = "jit(grow_tree)/while/body/apply_split/partition/cond/branch_3_fun/"
    assert trace.scope_of(path + "partition/lanes/gather") == "partition/lanes"
    assert trace.scope_of(path + "partition/scatter/scatter") == "partition/scatter"
    assert trace.scope_of("jit(grow_tree)/while/body/apply_split/top_k") == "apply_split"
    assert trace.scope_of("jit(grow_tree)/tree_init/hist_build/root/dot") == "hist_build/root"
    assert trace.scope_of("jit(grow_tree)/tree_init/mul") == "tree_init"
    assert trace.scope_of("jit(grow_tree)/while/body/add") is None
    assert trace.scope_of("") is None
    # every sub-phase lies under a phase of the list
    assert all(s.split("/")[0] in trace.SCOPES for s in trace.SCOPES)


# -- the counters of padded work ---------------------------------------------

def _table(n=2500, f=8, seed=5):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    X[rng.rand(n, f) < 0.1] = np.nan
    y = (X[:, 0] + np.nan_to_num(X[:, 1] * X[:, 2]) + 0.3 * rng.randn(n) > 0)
    return X, y.astype(float)


def _counted(monkeypatch, grow, spec_hist="", **params):
    """The grow.counters of three trees grown in the form asked for."""
    monkeypatch.setattr(grow_mod, "_ENV_GROW", grow)
    monkeypatch.setattr(grow_mod, "_ENV_SPEC_HIST", spec_hist)
    jax.clear_caches()
    try:
        X, y = _table()
        trace.reset()
        bst = lgb.train(dict({"objective": "binary", "num_leaves": 15,
                              "verbosity": -1}, **params),
                        lgb.Dataset(X, label=y), 3)
        bst.model_to_string()  # materialises the trees: one event each
        return [e["args"] for e in trace.events() if e["name"] == "grow.counters"]
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("form", ["speculative-flat", "speculative-lanes",
                                  "sequential", "masked"])
def test_the_counts_of_padded_work(monkeypatch, form):
    spec = form.startswith("speculative")
    params = {"tpu_hist_mode": "masked"} if form == "masked" else {}
    counted = _counted(monkeypatch, "spec" if spec else "seq",
                       form.split("-")[-1] if spec else "", **params)
    assert len(counted) == 3
    rows = 2500
    for c in counted:
        assert c["hist_rows_gathered"] >= c["hist_rows_streamed"] >= c["hist_rows_needed"] > 0
        if form != "speculative-flat":
            # the lanes and the W = 1 pass stream the rows they gather
            assert c["hist_rows_gathered"] == c["hist_rows_streamed"]
        # the take of the split columns: W x N a partition call, one a step
        width = grow_mod._ENV_SPEC_K if spec else 1
        assert c["part_rows_taken"] == width * rows * c["steps"]
        assert c["part_rows_streamed"] >= c["part_rows_needed"]
        if form == "masked":
            assert c["hist_rows_gathered"] == rows * (1 + c["splits"])
            assert c["part_rows_streamed"] == rows * c["splits"]


def test_the_flat_pallas_pass_gathers_more_than_it_streams(monkeypatch):
    """Under the Pallas kernel (interpreted here) the flat pass streams the
    chunks that hold a slot's rows and gathers its switch branch's: the
    round-up the kernel skips is what the gather pays over it."""
    import functools

    import lightgbm_tpu.ops.histogram as hist_mod
    from lightgbm_tpu.ops import hist_pallas

    def interpreted(real):
        @functools.wraps(real)
        def call(*args, **kwargs):
            return real(*args, **dict(kwargs, interpret=True))
        return call

    monkeypatch.setattr(hist_mod, "_ENV_IMPL", "pallas")
    for name in ("histogram_pallas", "histogram_pallas_slots"):
        monkeypatch.setattr(hist_pallas, name, interpreted(getattr(hist_pallas, name)))
    # 63 leaves: batches of 5 and 7 computing slots, whose chunks fall
    # between two switch branches
    counted = _counted(monkeypatch, "spec", num_leaves=63)
    assert grow_mod._LAST_SPEC_HIST == "flat"
    for c in counted:
        assert c["hist_rows_gathered"] >= c["hist_rows_streamed"] >= c["hist_rows_needed"]
    assert sum(c["hist_rows_gathered"] for c in counted) > sum(
        c["hist_rows_streamed"] for c in counted)

"""graftlint self-tests: per-rule golden fixtures + the baseline gate.

Three layers:
  * per-rule true-positive / true-negative fixtures (tests/golden/lint/):
    every JX rule must fire on its ``_bad`` fixture and stay silent on its
    ``_good`` fixture;
  * the shipped baseline regression: linting ``lightgbm_tpu/`` must produce
    EXACTLY the findings recorded in tools/graftlint/baseline.txt — a new
    violation fails tier-1, and so does a fixed-but-not-removed entry;
  * CLI smoke via ``python -m tools.graftlint``.

No test here is marked slow: this IS the tier-1 lint gate.
"""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint import RULES, load_baseline, run_lint  # noqa: E402
from tools.graftlint.cli import DEFAULT_BASELINE, main as cli_main  # noqa: E402
from tools.graftlint.engine import compare_to_baseline  # noqa: E402

LINT_DIR = os.path.join(REPO, "tests", "golden", "lint")
ALL_RULES = ("JX001", "JX002", "JX003", "JX004",
             "JX005", "JX006", "JX007", "JX008", "JX009", "JX010",
             "JX011", "JX012", "JX013")

#: the default scan scope the check.sh gate and the baseline test share —
#: lightgbm_tpu/ plus the orchestration surface (helpers/) whose
#: bugs burn bringup rounds just as surely (ISSUE 11 satellite)
SCAN_SCOPE = ("lightgbm_tpu", "helpers")


def _fixture(rule_id, kind):
    """Fixture path for a rule: directory-scoped rules (JX009, JX010) keep
    their fixtures under golden/lint/<scope-dir>/ so the scope gate sees the
    required path segment; everything else lives flat in golden/lint/."""
    name = "%s_%s.py" % (rule_id.lower(), kind)
    for scope in ("ops", "obs", "lightgbm_tpu"):
        scoped = os.path.join(LINT_DIR, scope, name)
        if os.path.exists(scoped):
            return scoped
    return os.path.join(LINT_DIR, name)


def _lint(path, rule_id):
    return run_lint([path], root=REPO, select=[rule_id])


# ---------------------------------------------------------------------------
# per-rule golden fixtures
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rule_id", ALL_RULES)
def test_rule_fires_on_bad_fixture(rule_id):
    path = _fixture(rule_id, "bad")
    findings = _lint(path, rule_id)
    assert findings, "%s produced no findings on its bad fixture" % rule_id
    assert all(f.rule == rule_id for f in findings)
    # every finding carries a location and a content-stable key
    for f in findings:
        assert f.line > 0
        assert f.key.startswith(rule_id + ":")
        assert f.key.count(":") >= 3  # RULE:path:qualname:detail


@pytest.mark.parametrize("rule_id", ALL_RULES)
def test_rule_silent_on_good_fixture(rule_id):
    path = _fixture(rule_id, "good")
    findings = _lint(path, rule_id)
    assert findings == [], (
        "%s false positives: %s" % (rule_id, [f.format() for f in findings])
    )


def test_jx001_counts():
    path = os.path.join(LINT_DIR, "jx001_bad.py")
    assert len(_lint(path, "JX001")) == 3  # float(), np.asarray(), .item()


def test_jx004_counts_and_params():
    path = os.path.join(LINT_DIR, "jx004_bad.py")
    findings = _lint(path, "JX004")
    assert sorted(f.detail for f in findings) == [
        "param=callbacks", "param=extra", "param=seen",
    ]


def test_jx006_hot_path_factory(tmp_path):
    """The untyped-factory check is scoped to ops/ and parallel/ dirs:
    the same file is clean outside and flagged inside a hot-path dir."""
    outside = _lint(os.path.join(LINT_DIR, "jx006_bad.py"), "JX006")
    ops_dir = tmp_path / "ops"
    ops_dir.mkdir()
    for name in ("jx006_bad.py", "jx006_good.py"):
        shutil.copy(os.path.join(LINT_DIR, name), ops_dir / name)
    inside = run_lint([str(ops_dir / "jx006_bad.py")],
                      root=str(tmp_path), select=["JX006"])
    assert len(inside) == len(outside) + 1  # + the untyped jnp.zeros
    good = run_lint([str(ops_dir / "jx006_good.py")],
                    root=str(tmp_path), select=["JX006"])
    assert good == []


def test_jx009_scoped_to_ops_and_models(tmp_path):
    """JX009 polices only ops/ and models/ directories: the same file is
    clean under helpers/ (smoke scripts print their protocol lines) and
    flagged under models/."""
    src = open(_fixture("JX009", "bad")).read()
    for dirname, expected in (("helpers", 0), ("models", 3)):
        d = tmp_path / dirname
        d.mkdir()
        p = d / "timed.py"
        p.write_text(src)
        findings = run_lint([str(p)], root=str(tmp_path), select=["JX009"])
        assert len(findings) == expected, (dirname, [
            f.format() for f in findings
        ])


def test_jx009_counts():
    findings = _lint(_fixture("JX009", "bad"), "JX009")
    # two time.time() calls + one print()
    assert len(findings) == 3
    msgs = " ".join(f.message for f in findings)
    assert "perf_counter" in msgs and "print()" in msgs


def test_jx010_counts_and_scope(tmp_path):
    """Five artifact-write findings in the bad fixture (plain "w"/"wb",
    vopen, exclusive-create "x", keyword-only file=/mode=); the same file is
    CLEAN outside a lightgbm_tpu/ directory (helpers and tests legitimately
    write model files directly, e.g. golden-fixture generators)."""
    findings = _lint(_fixture("JX010", "bad"), "JX010")
    assert len(findings) == 5
    assert all("atomic" in f.message for f in findings)
    src = open(_fixture("JX010", "bad")).read()
    outside = tmp_path / "helpers"
    outside.mkdir()
    (outside / "gen.py").write_text(src)
    assert run_lint([str(outside / "gen.py")], root=str(tmp_path),
                    select=["JX010"]) == []


def test_jx010_atomic_writer_module_exempt(tmp_path):
    """The publisher's own temp-file open must not flag itself."""
    pkg = tmp_path / "lightgbm_tpu" / "resil"
    pkg.mkdir(parents=True)
    (pkg / "atomic.py").write_text(
        "def atomic_write_text(path, text):\n"
        "    with open(path + '.tmp', 'w') as fh:  # model_path upstream\n"
        "        fh.write(text)\n"
    )
    assert run_lint([str(pkg / "atomic.py")], root=str(tmp_path),
                    select=["JX010"]) == []


def test_jx007_axis_index_first_positional(tmp_path):
    """axis_index takes the axis name as its FIRST argument — the rule must
    check args[0] there, not the reduction collectives' args[1]."""
    src = (
        "import jax\nimport numpy as np\n"
        "from jax.sharding import Mesh\n\n"
        "def make_mesh(devices):\n"
        "    return Mesh(np.array(devices), ('data',))\n\n"
        "def rank():\n"
        "    return jax.lax.axis_index('dtaa')\n"  # typo'd axis
    )
    p = tmp_path / "axis_index.py"
    p.write_text(src)
    findings = run_lint([str(p)], root=str(tmp_path), select=["JX007"])
    assert len(findings) == 1 and "dtaa" in findings[0].message


def test_jx007_shard_map_specs_and_splatted_partition_specs():
    """The ISSUE-8 extension: shard_map in_specs/out_specs string literals
    and — in parallel/ files — the build-a-spec-then-splat idiom
    (``spec[i] = "axis"; P(*spec)``) are policed against declared axes.
    Fixtures live under golden/lint/parallel/ so the dir scope engages."""
    bad = os.path.join(LINT_DIR, "parallel", "jx007_specs_bad.py")
    findings = _lint(bad, "JX007")
    assert sorted(f.detail for f in findings) == ["axis=model", "axis=rows"]
    good = os.path.join(LINT_DIR, "parallel", "jx007_specs_good.py")
    assert _lint(good, "JX007") == []


def test_jx007_shard_map_specs_no_double_report(tmp_path):
    """Strings INSIDE P() calls within shard_map spec kwargs are reported
    once (by the PartitionSpec branch), not twice."""
    src = (
        "import numpy as np\n"
        "from jax.sharding import Mesh, PartitionSpec as P\n"
        "from jax import shard_map\n\n"
        "def make_mesh(devices):\n"
        "    return Mesh(np.array(devices), ('data',))\n\n"
        "def wrap(f, mesh):\n"
        "    return shard_map(f, mesh=mesh, in_specs=(P('rows'),),\n"
        "                     out_specs=P('rows'))\n"
    )
    p = tmp_path / "parallel"
    p.mkdir()
    f = p / "dup.py"
    f.write_text(src)
    findings = run_lint([str(f)], root=str(tmp_path), select=["JX007"])
    assert len(findings) == 2, [x.format() for x in findings]  # one per P()


def test_jx007_needs_a_mesh_declaration(tmp_path):
    """Without any Mesh() in scope the axis check cannot validate and
    stays silent instead of guessing."""
    src = 'import jax\n\ndef f(x):\n    return jax.lax.psum(x, "data")\n'
    p = tmp_path / "no_mesh.py"
    p.write_text(src)
    assert run_lint([str(p)], root=str(tmp_path), select=["JX007"]) == []


def test_jx001_tolist_on_static_arg_is_legal(tmp_path):
    """.tolist() on a static argument is a trace-time constant, not a
    device sync — the no-false-positive-on-statics contract applies."""
    src = (
        "import functools\nimport jax\n\n"
        "@functools.partial(jax.jit, static_argnames=('bins',))\n"
        "def f(x, bins):\n"
        "    edges = bins.tolist()\n"
        "    return x * len(edges)\n"
    )
    p = tmp_path / "static_tolist.py"
    p.write_text(src)
    assert run_lint([str(p)], root=str(tmp_path), select=["JX001"]) == []


@pytest.mark.parametrize("header,dec", [
    ("import numba", "@numba.jit"),           # dotted non-jax
    ("from numba import jit", "@jit"),        # bare name from non-jax
])
def test_non_jax_jit_decorators_are_not_jit_scope(tmp_path, header, dec):
    """numba's jit (dotted or from-imported) is not a jax tracing scope —
    Python branches and float() are legal there."""
    src = (
        "%s\n\n"
        "%s\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return float(x)\n"
        "    return 0.0\n" % (header, dec)
    )
    p = tmp_path / "numba_fn.py"
    p.write_text(src)
    findings = run_lint([str(p)], root=str(tmp_path))
    assert findings == [], [f.format() for f in findings]


def test_bare_jit_from_jax_still_counts(tmp_path):
    """``from jax import jit`` keeps the bare decorator a tracing scope."""
    src = (
        "from jax import jit\n\n"
        "@jit\n"
        "def f(x):\n"
        "    return float(x.sum())\n"
    )
    p = tmp_path / "jax_bare.py"
    p.write_text(src)
    findings = run_lint([str(p)], root=str(tmp_path), select=["JX001"])
    assert len(findings) == 1


def test_nonexistent_path_is_an_error(capsys):
    """A typo'd path must be a usage error, not a vacuous clean pass."""
    rc = cli_main(["no_such_dir_xyz/", "--root", REPO])
    err = capsys.readouterr().err
    assert rc == 2
    assert "no such file or directory" in err


def test_overlapping_paths_lint_each_file_once():
    """A file reachable through two path arguments must produce each
    finding once, or the multiset baseline would see phantom duplicates."""
    grow = os.path.join(REPO, "lightgbm_tpu", "ops", "grow.py")
    once = run_lint([grow], root=REPO)
    twice = run_lint([os.path.join(REPO, "lightgbm_tpu", "ops"), grow],
                     root=REPO)
    assert [f.key for f in twice if f.path.endswith("grow.py")] == [
        f.key for f in once
    ]


def test_static_argnames_are_not_traced():
    """The jit model must honor static_argnames: int()/branching on a
    static argument is legal and must not fire JX001/JX002."""
    path = os.path.join(LINT_DIR, "jx001_good.py")
    assert _lint(path, "JX001") == []
    assert _lint(path, "JX002") == []


# ---------------------------------------------------------------------------
# JX011/JX012/JX013 (the graftsan wave, ISSUE 11)
# ---------------------------------------------------------------------------
def test_jx011_counts_and_kinds():
    """Every contract violation in the bad fixture is reported exactly once,
    with a content-stable detail naming the violated contract."""
    findings = _lint(_fixture("JX011", "bad"), "JX011")
    details = sorted(f.detail for f in findings)
    assert details == sorted([
        "_kernel:program_id=2",       # axis 2 against a rank-2 grid
        "_kernel:store_dtype",        # .astype(bfloat16) into a f32 out ref
        "in_specs_count",             # 1 spec, 2 operands
        "in_specs[0]:index_map_arity",  # 1-arg lambda, rank-2 grid
        "out_specs[0]:index_map_rank",  # 3 coords, 2-dim block
        "in_specs[0]:vmem",           # 64 MiB static block
        "out[0]:block_rank",          # rank-2 block, rank-3 out_shape
        "out_specs_count",            # 2 out_specs, 1 out_shape
        "out[0]:dtype_missing",       # ShapeDtypeStruct without dtype
    ]), [f.format() for f in findings]


def test_jx011_vmem_budget_from_chip_peaks(tmp_path):
    """The VMEM bound reads the smallest ``vmem_bytes`` from a CHIP_PEAKS
    table in the scanned set (obs/costs.py's chip-detection table) instead
    of hardcoding a chip: the same 1 MiB block passes under the default
    16 MiB budget and fails when a table declares a tighter chip."""
    kernel_src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n\n"
        "def run(x):\n"
        "    return pl.pallas_call(\n"
        "        lambda x_ref, o_ref: None,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((512, 512), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((512, 512), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((2048, 512), jnp.float32),\n"
        "    )(x)\n"
    )
    k = tmp_path / "kern.py"
    k.write_text(kernel_src)
    assert run_lint([str(k)], root=str(tmp_path), select=["JX011"]) == []
    (tmp_path / "peaks.py").write_text(
        "CHIP_PEAKS = {\n"
        '    "tiny": {"peak_flops": 1e12, "vmem_bytes": 512 * 1024},\n'
        '    "big": {"peak_flops": 9e12, "vmem_bytes": 64 * 2 ** 20},\n'
        "}\n"
    )
    findings = run_lint([str(tmp_path)], root=str(tmp_path), select=["JX011"])
    assert len(findings) == 2, [f.format() for f in findings]  # in + out spec
    assert all("524288-byte" in f.message for f in findings)


def test_jx011_helper_built_specs_are_unknown_not_one(tmp_path):
    """``in_specs=build_specs(3)`` is a helper returning an unknown number
    of specs — the count check must SKIP, not assume a single BlockSpec and
    flag correct code."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n\n"
        "def build_specs(n):\n"
        "    return [pl.BlockSpec((8, 128), lambda i: (i, 0))] * n\n\n"
        "def run(x, y, z):\n"
        "    return pl.pallas_call(\n"
        "        lambda a_ref, b_ref, c_ref, o_ref: None,\n"
        "        grid=(4,),\n"
        "        in_specs=build_specs(3),\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),\n"
        "    )(x, y, z)\n"
    )
    p = tmp_path / "helper_specs.py"
    p.write_text(src)
    assert run_lint([str(p)], root=str(tmp_path), select=["JX011"]) == []


def test_jx011_scratch_refs_not_mistaken_for_out_refs(tmp_path):
    """scratch_shapes refs trail the out refs in a pallas kernel signature;
    a correct bf16 store into the SCRATCH ref must not be flagged against
    the f32 out_shape dtype."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "from jax.experimental.pallas import tpu as pltpu\n\n"
        "def _kernel(x_ref, o_ref, acc_ref):\n"
        "    acc_ref[:] = x_ref[:].astype(jnp.bfloat16)\n"
        "    o_ref[:] = acc_ref[:].astype(jnp.float32)\n\n"
        "def run(x):\n"
        "    return pl.pallas_call(\n"
        "        _kernel,\n"
        "        grid=(4,),\n"
        "        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),\n"
        "        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),\n"
        "        scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],\n"
        "    )(x)\n"
    )
    p = tmp_path / "scratch.py"
    p.write_text(src)
    assert run_lint([str(p)], root=str(tmp_path), select=["JX011"]) == []


def test_jx011_packed4_fixture():
    """The promoted packed4 histogram idiom (ISSUE 13) is provably inside
    the lint gate's sight: a nibble-packed call with seeded violations is
    flagged per contract, and the faithful mirror of the real
    ``histogram_pallas_packed4`` invocation is clean."""
    findings = _lint(os.path.join(LINT_DIR, "jx011_packed4_bad.py"), "JX011")
    details = sorted(f.detail for f in findings)
    assert details == sorted([
        "_kernel_p4:program_id=2",       # axis 2 against the rank-2 grid
        "_kernel_p4:store_dtype",        # bf16 store into a f32 out ref
        "in_specs[0]:index_map_arity",   # 1-arg lambda, rank-2 grid
        "in_specs_count",                # 1 spec, 2 operands
        "out[0]:block_rank",             # rank-2 block, rank-3 out_shape
    ]), [f.format() for f in findings]
    assert _lint(os.path.join(LINT_DIR, "jx011_packed4_good.py"),
                 "JX011") == []


def test_jx011_onehot_fixture():
    """The dense one-hot-tile idiom (ISSUE 17) is provably inside the lint
    gate's sight — including its rank-3 (feature, bin-tile, chunk) grid: a
    seeded call is flagged per contract, and the faithful mirror of the
    real ``histogram_pallas_onehot`` invocation is clean."""
    findings = _lint(os.path.join(LINT_DIR, "jx011_onehot_bad.py"), "JX011")
    details = sorted(f.detail for f in findings)
    assert details == sorted([
        "_kernel_onehot:program_id=3",   # axis 3 against the rank-3 grid
        "_kernel_onehot:store_dtype",    # bf16 store into a f32 out ref
        "in_specs[0]:index_map_arity",   # 2-arg lambda, rank-3 grid
        "in_specs_count",                # 1 spec, 2 operands
        "out[0]:block_rank",             # rank-2 block, rank-3 out_shape
    ]), [f.format() for f in findings]
    assert _lint(os.path.join(LINT_DIR, "jx011_onehot_good.py"),
                 "JX011") == []


def test_jx011_bitplane_fixture():
    """The bit-plane idiom (ISSUE 17) is provably inside the lint gate's
    sight, with a violation mix the other histogram fixtures don't cover
    (second in_spec arity, out index_map rank, missing out dtype)."""
    findings = _lint(os.path.join(LINT_DIR, "jx011_bitplane_bad.py"),
                     "JX011")
    details = sorted(f.detail for f in findings)
    assert details == sorted([
        "_kernel_bitplane:program_id=2",  # axis 2 against the rank-2 grid
        "in_specs[1]:index_map_arity",    # 1-arg lambda, rank-2 grid
        "out_specs[0]:index_map_rank",    # 2 coords, 3-dim block
        "out[0]:dtype_missing",           # ShapeDtypeStruct without dtype
    ]), [f.format() for f in findings]
    assert _lint(os.path.join(LINT_DIR, "jx011_bitplane_good.py"),
                 "JX011") == []


def test_jx011_scalar_prefetch_fixture():
    """A ``grid_spec=pltpu.PrefetchScalarGridSpec(...)`` carries the grid and
    the specs (ops/hist_pallas.py's slot-grouped call since PR 34): the rule
    reads them there, and each scalar-prefetch operand is one more index_map
    argument and one more operand of the invocation."""
    findings = _lint(os.path.join(LINT_DIR, "jx011_prefetch_bad.py"), "JX011")
    details = sorted(f.detail for f in findings)
    assert details == sorted([
        "_kernel:program_id=2",         # axis 2 against the rank-2 grid
        "in_specs[0]:index_map_arity",  # 2-arg lambda: rank 2 + 2 prefetch
        "in_specs_count",               # one scalar operand short
    ]), [f.format() for f in findings]
    assert _lint(os.path.join(LINT_DIR, "jx011_prefetch_good.py"),
                 "JX011") == []


def test_jx011_real_pallas_seams_clean():
    """The shipped kernels must satisfy their own hygiene rule — the Pallas
    PR grows from these seams under JX011's gate (including the ISSUE 17
    onehot/bitplane kernels in hist_pallas.py)."""
    for mod in ("hist_pallas.py", "split_pallas.py"):
        path = os.path.join(REPO, "lightgbm_tpu", "ops", mod)
        assert _lint(path, "JX011") == [], mod


def test_jx012_counts_and_scope(tmp_path):
    """Five hazards in the bad fixture; the identical file is CLEAN outside
    ops//models/ (serve and helpers code has no bitwise-identity contract),
    and every message cites the PR 8 FMA find."""
    findings = _lint(_fixture("JX012", "bad"), "JX012")
    assert len(findings) == 5, [f.format() for f in findings]
    fma = [f for f in findings if "FMA" in f.message]
    assert len(fma) >= 4  # 3 inline-mult-adds + the barrier message
    assert sum("PR 8" in f.message for f in findings) >= 4
    src = open(_fixture("JX012", "bad")).read()
    outside = tmp_path / "helpers"
    outside.mkdir()
    (outside / "jx012_bad.py").write_text(src)
    assert run_lint([str(outside / "jx012_bad.py")], root=str(tmp_path),
                    select=["JX012"]) == []


def test_jx013_counts_and_scope(tmp_path):
    """Four findings in the bad fixture (3 unguarded mutations + 1
    undeclared nesting); the identical file is CLEAN outside serve//obs/."""
    findings = _lint(_fixture("JX013", "bad"), "JX013")
    assert sorted(f.detail for f in findings) == [
        "attr=_items", "attr=_n", "attr=_n", "nest=_a>_b",
    ], [f.format() for f in findings]
    src = open(_fixture("JX013", "bad")).read()
    outside = tmp_path / "models"
    outside.mkdir()
    (outside / "jx013_bad.py").write_text(src)
    assert run_lint([str(outside / "jx013_bad.py")], root=str(tmp_path),
                    select=["JX013"]) == []


def test_jx013_pragma_needs_a_reason(tmp_path):
    """A bare ``# unlocked:`` with no justification must NOT suppress — the
    pragma is an in-place baseline entry and carries the same obligation."""
    src = (
        "import threading\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._v = 0\n\n"
        "    def set_empty(self, v):\n"
        "        self._v = v  # unlocked:\n\n"
        "    def set_reason(self, v):\n"
        "        self._v = v  # unlocked: single-writer rebind\n"
    )
    d = tmp_path / "obs"
    d.mkdir()
    (d / "c.py").write_text(src)
    findings = run_lint([str(d / "c.py")], root=str(tmp_path),
                        select=["JX013"])
    assert len(findings) == 1 and findings[0].line == 9, [
        f.format() for f in findings
    ]


def test_jx013_sanitize_make_lock_counts_as_lock(tmp_path):
    """A class building its lock through obs/sanitize.py's make_lock factory
    owns a lock exactly like a raw threading.Lock one."""
    src = (
        "from ..obs import sanitize\n\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = sanitize.make_lock('c')\n"
        "        self._v = 0\n\n"
        "    def bump(self):\n"
        "        self._v += 1\n"
    )
    d = tmp_path / "serve"
    d.mkdir()
    (d / "c.py").write_text(src)
    findings = run_lint([str(d / "c.py")], root=str(tmp_path),
                        select=["JX013"])
    assert len(findings) == 1 and findings[0].detail == "attr=_v"


# ---------------------------------------------------------------------------
# registry + docs
# ---------------------------------------------------------------------------
def test_rule_registry_complete():
    assert set(RULES) == set(ALL_RULES)
    for r in RULES.values():
        assert r.title
        assert r.doc, "rule %s has no documentation" % r.id


def test_rules_documented_in_docs():
    doc = open(os.path.join(REPO, "docs", "StaticAnalysis.md")).read()
    for rule_id in ALL_RULES:
        assert rule_id in doc, "%s missing from docs/StaticAnalysis.md" % rule_id


# ---------------------------------------------------------------------------
# the shipped baseline is exact: no new findings, no stale suppressions
# ---------------------------------------------------------------------------
def test_baseline_matches_current_findings_exactly():
    findings = run_lint(
        [os.path.join(REPO, p) for p in SCAN_SCOPE], root=REPO
    )
    baseline, notes = load_baseline(DEFAULT_BASELINE)
    new, stale = compare_to_baseline(findings, baseline)
    assert not new, (
        "new graftlint findings (fix them or baseline with a "
        "justification):\n" + "\n".join(f.format() for f in new)
    )
    assert not stale, (
        "stale baseline entries (the finding is gone — delete the line):\n"
        + "\n".join(sorted(stale))
    )


def test_baseline_entries_are_justified():
    baseline, notes = load_baseline(DEFAULT_BASELINE)
    assert baseline, "baseline unexpectedly empty"
    for key in baseline:
        note = notes.get(key, "")
        assert note and "TODO" not in note, (
            "baseline entry lacks a real justification: %s" % key
        )


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_in_process_clean(capsys):
    rc = cli_main(
        [os.path.join(REPO, p) for p in SCAN_SCOPE] + ["--root", REPO]
    )
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "clean" in out


def test_cli_reports_findings(capsys):
    rc = cli_main([
        os.path.join(LINT_DIR, "jx004_bad.py"), "--no-baseline",
        "--root", REPO,
    ])
    out = capsys.readouterr().out
    assert rc == 1
    assert "JX004" in out


def test_cli_subprocess_entrypoint():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.graftlint"] + list(SCAN_SCOPE),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rejects_unknown_rule_id(capsys):
    rc = cli_main([
        os.path.join(LINT_DIR, "jx004_bad.py"), "--select", "JX0O1",
        "--root", REPO,
    ])
    err = capsys.readouterr().err
    assert rc == 2
    assert "unknown rule id" in err


def test_write_baseline_preserves_unscanned_entries(tmp_path, capsys):
    """A partial-path --write-baseline must not delete suppressions (and
    their justifications) belonging to files the run never parsed."""
    (tmp_path / "clean.py").write_text("def f(x):\n    return x\n")
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "JX004:somewhere/else.py:train:param=callbacks  # kept on purpose\n"
    )
    rc = cli_main([
        str(tmp_path / "clean.py"), "--write-baseline",
        "--baseline", str(bl), "--root", str(tmp_path),
    ])
    capsys.readouterr()
    assert rc == 0
    content = bl.read_text()
    assert "somewhere/else.py" in content
    assert "kept on purpose" in content


def test_cli_list_rules(capsys):
    rc = cli_main(["--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rule_id in ALL_RULES:
        assert rule_id in out


def test_prune_baseline_drops_stale_entries(tmp_path, capsys):
    """--prune-baseline rewrites the baseline dropping suppressions for
    findings that no longer exist in the scanned set, printing each pruned
    line — while keeping live suppressions (with their justifications) and
    entries for files the run never parsed."""
    src = (
        "import jax\nimport jax.numpy as jnp\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.astype(jnp.float64)\n"
    )
    p = tmp_path / "leak.py"
    p.write_text(src)
    findings = run_lint([str(p)], root=str(tmp_path))
    assert findings, "fixture must produce a real finding to keep"
    live_key = findings[0].key
    bl = tmp_path / "baseline.txt"
    bl.write_text(
        "%s  # live suppression, must survive\n"
        "JX001:leak.py:ghost:print  # stale, must be pruned\n"
        "JX004:somewhere/else.py:train:param=callbacks  # unscanned, kept\n"
        % live_key
    )
    rc = cli_main([
        str(p), "--prune-baseline", "--baseline", str(bl),
        "--root", str(tmp_path),
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pruned stale baseline entry: JX001:leak.py:ghost:print" in out
    content = bl.read_text()
    assert "ghost" not in content
    assert live_key in content and "live suppression" in content
    assert "somewhere/else.py" in content and "unscanned, kept" in content
    # a normal gate re-run over the same narrow path set reports ONLY the
    # intentionally-preserved unscanned-file entry as stale (pre-existing
    # strictness for partial runs); ghost and the live key are settled
    rc2 = cli_main([
        str(p), "--baseline", str(bl), "--root", str(tmp_path),
    ])
    out2 = capsys.readouterr().out
    assert rc2 == 1
    assert "ghost" not in out2
    assert "somewhere/else.py" in out2
    assert "0 new finding(s)" in out2


def test_prune_baseline_still_fails_on_new_findings(tmp_path, capsys):
    """Pruning never launders NEW findings: stale entries are dropped but
    an unsuppressed finding still exits 1."""
    src = (
        "import jax\nimport jax.numpy as jnp\n\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.astype(jnp.float64)\n"
    )
    p = tmp_path / "leak.py"
    p.write_text(src)
    bl = tmp_path / "baseline.txt"
    bl.write_text("JX001:leak.py:ghost:print  # stale\n")
    rc = cli_main([
        str(p), "--prune-baseline", "--baseline", str(bl),
        "--root", str(tmp_path),
    ])
    out = capsys.readouterr().out
    assert rc == 1
    assert "pruned stale baseline entry" in out
    assert "ghost" not in bl.read_text()


def test_prune_baseline_rejects_select(tmp_path, capsys):
    """--prune-baseline with --select would see every unselected rule's
    suppression as stale and mass-delete it — refused as a usage error."""
    p = tmp_path / "x.py"
    p.write_text("def f():\n    return 1\n")
    rc = cli_main([
        str(p), "--prune-baseline", "--select", "JX001",
        "--baseline", str(tmp_path / "bl.txt"),
    ])
    err = capsys.readouterr().err
    assert rc == 2
    assert "--prune-baseline with --select" in err


def test_chip_peaks_ast_view_matches_live_table():
    """The ONE shared CHIP_PEAKS extraction (engine.chip_peaks_from_ast)
    must agree with the live obs/costs table — the static JX011 VMEM
    budget and irscan's runtime costs.chip_peaks() read the same source of
    truth and cannot drift."""
    import ast as _ast

    from lightgbm_tpu.obs import costs
    from tools.graftlint.engine import (
        FileContext, ProjectContext, chip_peaks_from_ast,
    )

    path = os.path.join(REPO, "lightgbm_tpu", "obs", "costs.py")
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    got = chip_peaks_from_ast(_ast.parse(src))
    live_int = {
        chip: {
            k: v for k, v in fields.items()
            if isinstance(v, int) and not isinstance(v, bool)
        }
        for chip, fields in costs.CHIP_PEAKS.items()
    }
    assert set(got) == set(live_int)
    for chip in live_int:
        assert got[chip] == live_int[chip], chip
        assert "vmem_bytes" in got[chip], chip
    # the JX011 budget resolves from the REAL table (the pre-refactor
    # Assign-only walker missed the annotated assignment and silently fell
    # back to the default forever)
    ctx = FileContext(path, "lightgbm_tpu/obs/costs.py", src)
    budget = ProjectContext([ctx]).vmem_budget
    assert budget == min(
        f["vmem_bytes"] for f in live_int.values() if "vmem_bytes" in f
    )

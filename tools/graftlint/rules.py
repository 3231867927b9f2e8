"""The JX rule set. Each rule is registered with @rule and yields Findings.

Rules lean on the engine's jit-scope model (FileContext.enclosing_jit /
JitInfo.traced_params) so that static arguments — ``static_argnames`` /
``static_argnums`` — never produce traced-value false positives. See
docs/StaticAnalysis.md for a bad/good example per rule.
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, Optional, Set

from .engine import (
    FileContext,
    Finding,
    ProjectContext,
    const_int,
    dotted_name,
    rule,
)

# attribute reads that are static metadata even on a traced array
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type", "sharding"}

# numpy module aliases as they appear in this codebase
_NP_BASES = {"np", "numpy", "onp"}
_JNP_BASES = {"jnp", "jax.numpy"}


def _first_arg(call: ast.Call) -> Optional[ast.AST]:
    return call.args[0] if call.args else None


def _none_guard_subtrees(test: ast.AST) -> Set[int]:
    """ids of Compare subtrees that are pure ``x is (not) None`` guards —
    trace-time control on pytree *structure*, legal under jit."""
    skip: Set[int] = set()
    for node in ast.walk(test):
        if not isinstance(node, ast.Compare):
            continue
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops) and any(
            isinstance(c, ast.Constant) and c.value is None
            for c in [node.left] + node.comparators
        ):
            for sub in ast.walk(node):
                skip.add(id(sub))
    return skip


def _references_traced(
    ctx: FileContext, node: ast.AST, traced: frozenset,
    skip: Optional[Set[int]] = None,
) -> Optional[str]:
    """Name of the first traced parameter *used as a value* in ``node``.

    Static-metadata reads (``x.shape``, ``len(x)``, ``isinstance(x, ...)``)
    and subtrees listed in ``skip`` do not count.
    """
    skip = skip or set()
    for sub in ast.walk(node):
        if id(sub) in skip:
            continue
        if not (isinstance(sub, ast.Name) and sub.id in traced):
            continue
        parent = ctx.parent(sub)
        if (
            isinstance(parent, ast.Attribute)
            and parent.value is sub
            and parent.attr in _STATIC_ATTRS
        ):
            continue
        if (
            isinstance(parent, ast.Call)
            and isinstance(parent.func, ast.Name)
            and parent.func.id in ("len", "isinstance", "type")
        ):
            continue
        return sub.id
    return None


# --------------------------------------------------------------------------
@rule("JX001", "host-device sync inside a jit/pjit function")
def jx001_host_sync(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """``float(x)``/``int(x)``/``bool(x)``, ``np.asarray(x)``, ``.item()``,
    ``.tolist()`` or ``jax.device_get`` on a traced value inside compiled
    code forces the host to block on the device — a silent serialization
    point that defeats async dispatch. Compute with jnp/lax primitives
    instead, or hoist the conversion out of the jitted function.
    """
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        traced = info.traced_params()
        func = node.func
        # float(x) / int(x) / bool(x) on a traced value
        if isinstance(func, ast.Name) and func.id in ("float", "int", "bool"):
            arg = _first_arg(node)
            if arg is not None:
                name = _references_traced(ctx, arg, traced)
                if name is not None:
                    yield ctx.finding(
                        "JX001", node,
                        "%s() on traced value %r blocks on the device inside "
                        "jit; use jnp casts or hoist to the host side"
                        % (func.id, name),
                    )
            continue
        fname = dotted_name(func)
        base, _, attr = fname.rpartition(".")
        # np.asarray / np.array on a traced value materializes on host
        if base in _NP_BASES and attr in ("asarray", "array"):
            arg = _first_arg(node)
            if arg is not None:
                name = _references_traced(ctx, arg, traced)
                if name is not None:
                    yield ctx.finding(
                        "JX001", node,
                        "%s(%s) inside jit copies the traced value to host "
                        "memory; use jnp.asarray or keep it on device"
                        % (fname, name),
                    )
            continue
        # .item()/.tolist(): a host sync when the receiver is traced. A
        # receiver referencing only STATIC params is a trace-time constant
        # and legal; unknown receivers (locals) are flagged — locals inside
        # jit are almost always traced values.
        if isinstance(func, ast.Attribute) and func.attr in ("item", "tolist"):
            static = frozenset(info.param_names()) - traced
            if (
                _references_traced(ctx, func.value, traced) is not None
                or _references_traced(ctx, func.value, static) is None
            ):
                yield ctx.finding(
                    "JX001", node,
                    ".%s() inside jit is a host-device sync; return the "
                    "array and convert outside the compiled function"
                    % func.attr,
                )
            continue
        if attr == "device_get" and base.rsplit(".", 1)[-1] == "jax":
            yield ctx.finding(
                "JX001", node,
                "jax.device_get inside jit forces a transfer; move it to "
                "the caller",
            )


# --------------------------------------------------------------------------
@rule("JX002", "Python branch on a traced value")
def jx002_traced_branch(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """A Python ``if``/``while`` whose condition reads a traced value raises
    a ConcretizationTypeError at trace time — or, when it sneaks through via
    a host round-trip, re-traces per branch. Use ``lax.cond`` /
    ``lax.while_loop`` / ``jnp.where``. Conditions on static arguments,
    ``x.shape``-style metadata, and ``x is None`` pytree-structure guards
    are trace-time constants and are not flagged.
    """
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.If, ast.While)):
            continue
        info = ctx.enclosing_jit(node)
        if info is None:
            continue
        traced = info.traced_params()
        skip = _none_guard_subtrees(node.test)
        name = _references_traced(ctx, node.test, traced, skip)
        if name is not None:
            kind = "if" if isinstance(node, ast.If) else "while"
            yield ctx.finding(
                "JX002", node,
                "Python `%s` on traced value %r inside jit; use lax.cond/"
                "lax.while_loop (or jnp.where) for data-dependent control"
                % (kind, name),
                detail=ctx.detail_for(node.test),
            )


# --------------------------------------------------------------------------
def _is_const_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, float, complex, bool))
    if isinstance(node, ast.UnaryOp) and isinstance(
        node.op, (ast.USub, ast.UAdd)
    ):
        return _is_const_literal(node.operand)
    if isinstance(node, (ast.List, ast.Tuple)):
        return bool(node.elts) and all(_is_const_literal(e) for e in node.elts)
    return False


@rule("JX003", "device constant rebuilt on every call/trace")
def jx003_const_rebuild(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """``jnp.array([ ... literal ... ])`` inside a function body rebuilds
    (and re-uploads) the same device constant on every call — and every
    re-trace constant-folds it again, a hidden recompile cost. Hoist the
    constant to module level as a *numpy* array (np constants don't touch
    the backend at import, jnp ones would) so it is built once.
    Module-level constants, arrays built from runtime values, and scalar
    wraps like ``jnp.asarray(False)`` (idiomatic for lax.cond predicates,
    no build cost) are fine.
    """
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        base, _, attr = fname.rpartition(".")
        if base not in _JNP_BASES or attr not in ("array", "asarray"):
            continue
        if not ctx.enclosing_functions(node):
            continue  # module level: built once, fine
        arg = _first_arg(node)
        if (
            arg is not None
            and isinstance(arg, (ast.List, ast.Tuple))
            and _is_const_literal(arg)
        ):
            yield ctx.finding(
                "JX003", node,
                "jnp.%s of a Python constant inside a function is rebuilt "
                "every call (and folded every trace); hoist it to module "
                "scope" % attr,
            )


# --------------------------------------------------------------------------
_MUTABLE_CALLS = {"list", "dict", "set", "defaultdict", "OrderedDict"}


def _is_mutable_default(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        name = dotted_name(node.func)
        return name.rsplit(".", 1)[-1] in _MUTABLE_CALLS
    return False


@rule("JX004", "mutable default argument in a public function")
def jx004_mutable_default(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """A mutable default (``[]``, ``{}``, ``set()``, ``dict()``...) is
    created once at def time and shared across calls — mutations leak
    between callers. Default to ``None`` and materialize inside the body.
    Private helpers (leading underscore) are exempt; the public API is not.
    """
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_"):
            continue
        a = node.args
        pos = a.posonlyargs + a.args
        for param, default in zip(pos[len(pos) - len(a.defaults):], a.defaults):
            if _is_mutable_default(default):
                yield ctx.finding(
                    "JX004", node,
                    "mutable default for %r is shared across calls; use "
                    "None and create it in the body" % param.arg,
                    detail="param=%s" % param.arg,
                )
        for param, default in zip(a.kwonlyargs, a.kw_defaults):
            if default is not None and _is_mutable_default(default):
                yield ctx.finding(
                    "JX004", node,
                    "mutable default for %r is shared across calls; use "
                    "None and create it in the body" % param.arg,
                    detail="param=%s" % param.arg,
                )


# --------------------------------------------------------------------------
# parameter names that denote large reusable accumulator/output buffers in
# this codebase (histogram carries, score vectors, donated scratch)
_BUFFER_RE = re.compile(
    r"(^|_)(hist\w*|score\w*|\w*buf(fer)?\w*|scratch\w*|carry)($|_)"
)


@rule("JX005", "large-buffer jit argument without donation")
def jx005_missing_donate(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """A jit function that takes a large accumulator/output buffer
    (histogram carry, score vector, scratch) without
    ``donate_argnums``/``donate_argnames`` forces XLA to keep the input
    alive across the call — doubling peak HBM for buffers that the caller
    immediately overwrites. Donate the buffer (and have the caller re-adopt
    the aliased output), or baseline with a justification when the caller
    genuinely reuses the input. Spelling out ``donate_argnums=()`` (this
    codebase's explicit "considered, nothing donatable" marker) opts the
    function out.
    """
    for info in ctx.jit_fns.values():
        if info.donate_declared:
            # any donate_argnums/argnames spelling (empty included) means
            # the author made a donation decision — nothing left to flag
            continue
        for name in info.traced_params():
            if _BUFFER_RE.search(name):
                yield ctx.finding(
                    "JX005", info.fn,
                    "jit function %r takes buffer-like argument %r without "
                    "donate_argnums/donate_argnames; donating avoids a "
                    "duplicate device allocation" % (info.fn.name, name),
                    detail="param=%s" % name,
                )


# --------------------------------------------------------------------------
_FACTORY_DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}
_HOT_PATH_DIRS = ("ops", "parallel")


def _in_hot_path(ctx: FileContext) -> bool:
    # whole path segments, so loops/ or devops/ never match ops
    return any(seg in _HOT_PATH_DIRS for seg in ctx.rel_path.split("/")[:-1])


@rule("JX006", "dtype drift in hot-path compiled code")
def jx006_dtype_drift(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """Two flavors of accumulator dtype drift inside jit code:
    (a) ``float64``/``double`` references — TPUs have no f64; with x64
    disabled they silently downcast, with it enabled they double bandwidth
    and break bf16/f32 accumulator contracts; (b) in the hot-path dirs
    (``ops/``, ``parallel/``), jnp array factories without an explicit
    dtype — the result dtype then flips with the x64 flag, so f32
    accumulators can silently widen. Always pass dtype in hot-path code.
    """
    for node in ast.walk(ctx.tree):
        if ctx.enclosing_jit(node) is None:
            continue
        if isinstance(node, ast.Attribute):
            base = dotted_name(node.value)
            if node.attr in ("float64", "double") and (
                base in _NP_BASES or base in _JNP_BASES
            ):
                yield ctx.finding(
                    "JX006", node,
                    "%s.%s inside jit: TPU-hostile 64-bit dtype (silent "
                    "downcast with x64 off, bandwidth/precision drift with "
                    "it on); use float32/bfloat16 explicitly"
                    % (base, node.attr),
                )
            continue
        if not isinstance(node, ast.Call) or not _in_hot_path(ctx):
            continue
        fname = dotted_name(node.func)
        base, _, attr = fname.rpartition(".")
        if base not in _JNP_BASES or attr not in _FACTORY_DTYPE_POS:
            continue
        has_dtype = len(node.args) > _FACTORY_DTYPE_POS[attr] or any(
            kw.arg == "dtype" for kw in node.keywords
        )
        if not has_dtype:
            yield ctx.finding(
                "JX006", node,
                "jnp.%s without an explicit dtype in hot-path jit code; "
                "the result dtype follows the x64 flag — pass the "
                "accumulator dtype explicitly" % attr,
            )


# --------------------------------------------------------------------------
_COLLECTIVES = {
    "psum", "pmean", "pmax", "pmin", "ppermute", "all_gather",
    "all_to_all", "psum_scatter", "axis_index",
}


@rule("JX007", "collective/sharding axis name not declared on any mesh")
def jx007_undeclared_axis(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """Axis-name strings in ``psum``/``axis_name=``/``PartitionSpec`` must
    match an axis declared on a ``Mesh`` (parallel/mesh.py). A typo'd axis
    fails only at run time — deep inside shard_map, on the hardware — so
    catch it at review time. Skipped when no Mesh declaration is in scope.

    Beyond the direct call forms, two indirect spellings are policed:

      * ``shard_map(..., in_specs=..., out_specs=...)`` — every string
        literal inside the spec expressions (PartitionSpec members are
        already covered by the P() branch; bare strings outside a P call
        are caught here);
      * in ``parallel/`` files, the build-a-spec-then-splat idiom
        ``spec[i] = "axis"; P(*spec)`` — the assignment's string is an axis
        name even though no P() call contains it.
    """
    declared = project.declared_axes
    if not declared:
        return

    def check_strings(node: ast.AST, where: str, skip_p: bool = False) -> Iterator[Finding]:
        skipped: set = set()
        if skip_p:
            # strings inside nested PartitionSpec/P calls are reported by
            # the dedicated branch below — avoid double findings
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    nm = dotted_name(sub.func)
                    if nm and nm.rsplit(".", 1)[-1] in ("PartitionSpec", "P"):
                        for inner in ast.walk(sub):
                            skipped.add(id(inner))
        for sub in ast.walk(node):
            if id(sub) in skipped:
                continue
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                if sub.value not in declared:
                    yield ctx.finding(
                        "JX007", sub,
                        "axis name %r in %s is not declared on any mesh "
                        "(declared: %s)"
                        % (sub.value, where, ", ".join(sorted(declared))),
                        detail="axis=%s" % sub.value,
                    )

    # names splatted into PartitionSpec calls (P(*spec)): subscript
    # assignments of string literals into those names are axis names
    splatted: set = set()
    in_parallel_dir = "parallel" in ctx.rel_path.split("/")[:-1]
    if in_parallel_dir:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            nm = dotted_name(node.func)
            if not nm or nm.rsplit(".", 1)[-1] not in ("PartitionSpec", "P"):
                continue
            for arg in node.args:
                if isinstance(arg, ast.Starred) and isinstance(
                    arg.value, ast.Name
                ):
                    splatted.add(arg.value.id)

    for node in ast.walk(ctx.tree):
        if (
            in_parallel_dir
            and isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Subscript)
            and isinstance(node.targets[0].value, ast.Name)
            and node.targets[0].value.id in splatted
        ):
            yield from check_strings(
                node.value, "a PartitionSpec built via %s[...] = ..."
                % node.targets[0].value.id,
            )
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        attr = fname.rsplit(".", 1)[-1] if fname else ""
        if attr == "Mesh":
            continue  # the declaration site itself
        for kw in node.keywords:
            if kw.arg in ("axis_name", "axis_names"):
                yield from check_strings(kw.value, "%s(%s=...)" % (attr, kw.arg))
            elif attr == "shard_map" and kw.arg in ("in_specs", "out_specs"):
                yield from check_strings(
                    kw.value, "shard_map(%s=...)" % kw.arg, skip_p=True
                )
        if attr in _COLLECTIVES:
            # axis_index(axis_name) takes the axis first; the reduction
            # collectives take (operand, axis_name)
            pos = 0 if attr == "axis_index" else 1
            if len(node.args) > pos:
                yield from check_strings(node.args[pos], "%s(...)" % attr)
        if attr in ("PartitionSpec", "P"):
            for arg in node.args:
                yield from check_strings(arg, "PartitionSpec")


# --------------------------------------------------------------------------
_BROAD_EXC = {"Exception", "BaseException"}


def _is_broad(handler_type: Optional[ast.AST]) -> bool:
    if handler_type is None:
        return True  # bare except:
    if isinstance(handler_type, (ast.Name, ast.Attribute)):
        return dotted_name(handler_type).rsplit(".", 1)[-1] in _BROAD_EXC
    if isinstance(handler_type, ast.Tuple):
        return any(_is_broad(el) for el in handler_type.elts)
    return False


_OBS_ROUTED_DIRS = ("ops", "models")


@rule("JX009", "raw wall-clock / print in observability-routed packages")
def jx009_raw_host_io(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """In ``lightgbm_tpu/ops/`` and ``lightgbm_tpu/models/`` every timing
    and log line must route through the observability layer: ``time.time()``
    is wall-clock (an NTP step corrupts phase totals — use
    ``time.perf_counter`` via utils/timer.py or obs/trace.py spans), and a
    bare ``print()`` bypasses the log levels, the ISO timestamps and the
    pluggable callback (use utils/log.py, or ``log.warn_once`` for
    recurring warnings). Scoped to those directories: helpers and bench
    scripts legitimately print their own protocol lines.
    """
    if not any(seg in _OBS_ROUTED_DIRS for seg in ctx.rel_path.split("/")[:-1]):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        if fname == "time.time":
            yield ctx.finding(
                "JX009", node,
                "time.time() is wall-clock (NTP steps corrupt intervals); "
                "use time.perf_counter via utils/timer.py or an obs/trace "
                "span",
            )
        elif isinstance(node.func, ast.Name) and node.func.id == "print":
            yield ctx.finding(
                "JX009", node,
                "bare print() bypasses log levels/timestamps/callback; "
                "route through utils/log.py (warn_once for recurring "
                "warnings)",
            )


# --------------------------------------------------------------------------
# artifact-naming heuristic for JX010: identifiers/strings that denote a
# persisted model or training checkpoint in this codebase
_ARTIFACT_RE = re.compile(r"(model|checkpoint|ckpt|snapshot)", re.I)
_ATOMIC_WRITER_SUFFIX = "resil/atomic.py"


def _write_mode(call: ast.Call) -> Optional[str]:
    """The call's literal mode string when it opens for writing, else None."""
    mode = None
    if len(call.args) >= 2:
        a = call.args[1]
        if isinstance(a, ast.Constant) and isinstance(a.value, str):
            mode = a.value
    for kw in call.keywords:
        if kw.arg == "mode" and isinstance(kw.value, ast.Constant) and isinstance(
            kw.value.value, str
        ):
            mode = kw.value.value
    # 'x' (exclusive create) publishes at the final name just like 'w' —
    # a kill mid-write leaves the same truncated artifact
    if mode and mode.startswith(("w", "a", "x")):
        return mode
    return None


def _path_arg(call: ast.Call) -> Optional[ast.AST]:
    """The file-path expression: first positional arg, or ``file=`` /
    ``path=`` keyword (open/vopen accept the path by keyword too)."""
    if call.args:
        return call.args[0]
    for kw in call.keywords:
        if kw.arg in ("file", "path"):
            return kw.value
    return None


def _mentions_artifact(node: ast.AST) -> Optional[str]:
    """First identifier/attribute/string in ``node`` matching the artifact
    vocabulary (the path expression names what it writes)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and _ARTIFACT_RE.search(sub.id):
            return sub.id
        if isinstance(sub, ast.Attribute) and _ARTIFACT_RE.search(sub.attr):
            return sub.attr
        if (
            isinstance(sub, ast.Constant)
            and isinstance(sub.value, str)
            and _ARTIFACT_RE.search(sub.value)
        ):
            return sub.value
    return None


@rule("JX010", "model/checkpoint artifact written without the atomic publisher")
def jx010_raw_artifact_write(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """A direct ``open(path, "w")`` / ``vopen(path, "w")`` of a model,
    checkpoint or snapshot artifact can be killed mid-write and leave a
    TRUNCATED published file — which a later load trusts. Route artifact
    writes through ``resil/atomic.py`` (temp file + fsync + rename: readers
    see the old complete file or the new complete file, never a prefix).
    Scoped to ``lightgbm_tpu/``; the atomic writer module itself is exempt,
    and so are paths whose expression/enclosing function names no artifact
    (prediction outputs, traces, datasets have their own formats and
    rewrite-from-source recovery).
    """
    if "lightgbm_tpu" not in ctx.rel_path.split("/")[:-1]:
        return
    if ctx.rel_path.endswith(_ATOMIC_WRITER_SUFFIX):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        if fname.rsplit(".", 1)[-1] not in ("open", "vopen"):
            continue
        path_arg = _path_arg(node)
        mode = _write_mode(node)
        if mode is None or path_arg is None:
            continue
        hit = _mentions_artifact(path_arg)
        if hit is None:
            for fn in ctx.enclosing_functions(node):
                if _ARTIFACT_RE.search(fn.name):
                    hit = fn.name
                    break
        if hit is not None:
            if mode.startswith("a"):
                # append has no atomic equivalent (rename replaces the whole
                # file) — the right fix is a different artifact design, not
                # a drop-in helper call
                msg = (
                    "append-mode %s(..., %r) of artifact %r is not "
                    "crash-safe (a kill mid-append leaves a torn record); "
                    "rewrite the whole artifact through resil/atomic.py or "
                    "use a format that tolerates a truncated tail"
                    % (fname, mode, hit)
                )
            else:
                msg = (
                    "direct %s(..., %r) of artifact %r can publish a "
                    "truncated file on crash; route through resil/atomic.py "
                    "(atomic_write_text/bytes)" % (fname, mode, hit)
                )
            yield ctx.finding("JX010", node, msg, detail="artifact=%s" % hit)


# --------------------------------------------------------------------------
@rule("JX008", "broad exception handler silently swallows")
def jx008_silent_swallow(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """``except Exception: pass`` (or a bare ``except:``) with nothing in
    the body hides real failures — on this codebase that has masked device
    errors as silent CPU fallbacks. Catch the specific exception you
    expect, or at least log before continuing. Narrow handlers
    (``except OSError: pass``) are allowed.
    """
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not _is_broad(node.type):
            continue
        if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
            type_txt = (
                ast.unparse(node.type) if node.type is not None else "<bare>"
            )
            yield ctx.finding(
                "JX008", node,
                "broad `except %s` with a pass-only body swallows every "
                "failure; catch the specific exception or log it"
                % type_txt,
                detail="except=%s" % type_txt,
            )


# --------------------------------------------------------------------------
# JX011 helpers: static model of a pl.pallas_call site
# --------------------------------------------------------------------------
def _last_attr(name: str) -> str:
    return name.rsplit(".", 1)[-1] if name else ""


def _is_blockspec(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _last_attr(
        dotted_name(node.func)
    ) == "BlockSpec"


def _spec_list(node: Optional[ast.AST], is_leaf=None):
    """BlockSpec expressions of an in_specs/out_specs kwarg: a literal
    list/tuple, a single spec, or the ``[spec] * N`` replication idiom.
    Returns None when the count cannot be known statically — including a
    bare Call that is NOT itself a spec (``in_specs=build_specs(3)`` is a
    helper returning an unknown number of specs, not one spec)."""
    if node is None:
        return None
    if is_leaf is None:
        is_leaf = _is_blockspec
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    if (
        isinstance(node, ast.BinOp)
        and isinstance(node.op, ast.Mult)
        and isinstance(node.left, (ast.List, ast.Tuple))
        and isinstance(node.right, ast.Constant)
        and type(node.right.value) is int
    ):
        return list(node.left.elts) * node.right.value
    if isinstance(node, ast.Call) and is_leaf(node):
        return [node]  # a single bare BlockSpec(...) / ShapeDtypeStruct(...)
    return None


def _blockspec_parts(spec: ast.Call):
    """(block_shape tuple node or None, index_map lambda node or None)."""
    shape = spec.args[0] if spec.args else None
    index_map = spec.args[1] if len(spec.args) > 1 else None
    for kw in spec.keywords:
        if kw.arg == "block_shape":
            shape = kw.value
        elif kw.arg == "index_map":
            index_map = kw.value
    if not isinstance(shape, (ast.Tuple, ast.List)):
        shape = None
    if not isinstance(index_map, ast.Lambda):
        index_map = None
    return shape, index_map


def _is_sds(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _last_attr(
        dotted_name(node.func)
    ) == "ShapeDtypeStruct"


def _sds_list(node: Optional[ast.AST]):
    """ShapeDtypeStruct expressions of an out_shape kwarg (same shapes of
    spelling as _spec_list)."""
    return _spec_list(node, is_leaf=_is_sds)


def _sds_parts(sds: ast.Call):
    """(shape tuple node or None, dtype expr or None) of a ShapeDtypeStruct."""
    shape = sds.args[0] if sds.args else None
    dtype = sds.args[1] if len(sds.args) > 1 else None
    for kw in sds.keywords:
        if kw.arg == "shape":
            shape = kw.value
        elif kw.arg == "dtype":
            dtype = kw.value
    if not isinstance(shape, (ast.Tuple, ast.List)):
        shape = None
    return shape, dtype


def _resolve_kernel(ctx: FileContext, call: ast.Call):
    """FunctionDef of the kernel a pallas_call dispatches, resolved through
    the ``kernel = functools.partial(_body, ...)`` idiom. Innermost binding
    in the call's enclosing-function chain wins."""
    if not call.args:
        return None

    def fn_name_of(expr: ast.AST) -> Optional[str]:
        if isinstance(expr, ast.Call):
            name = dotted_name(expr.func)
            if _last_attr(name) == "partial" and expr.args:
                inner = dotted_name(expr.args[0])
                return _last_attr(inner) if inner else None
            return None
        name = dotted_name(expr)
        return _last_attr(name) if name else None

    target = fn_name_of(call.args[0])
    if target is None and isinstance(call.args[0], ast.Name):
        target = call.args[0].id
    if target is None:
        return None
    # follow one level of local rebinding: kernel = partial(_body, ...)
    scopes = ctx.enclosing_functions(call) + [ctx.tree]
    for scope in scopes:
        for node in ast.walk(scope):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == target
            ):
                resolved = fn_name_of(node.value)
                if resolved is not None and resolved != target:
                    target = resolved
                break
        else:
            continue
        break
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.FunctionDef) and node.name == target:
            return node
    return None


@rule("JX011", "pallas kernel violates its grid/BlockSpec/VMEM contract")
def jx011_pallas_hygiene(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """Static contract checks on every ``pl.pallas_call`` site — the
    mistakes that otherwise surface as Mosaic lowering errors (or silent
    garbage) on real TPU silicon only:

      * an ``index_map`` lambda whose arity differs from the grid rank
        (plus the scalar-prefetch operands, where the grid and the specs
        come in a ``grid_spec=pltpu.PrefetchScalarGridSpec(...)``);
      * an ``index_map`` returning a different number of block coordinates
        than the BlockSpec's block_shape has dimensions;
      * ``in_specs`` count != the number of operands the wrapped call is
        invoked with;
      * ``out_specs`` count != ``out_shape`` count, or an out BlockSpec
        whose block rank differs from its ShapeDtypeStruct's rank;
      * ``pl.program_id(axis)`` / ``pl.num_programs(axis)`` with a literal
        axis outside the grid's rank (resolved through the
        ``kernel = functools.partial(_body, ...)`` idiom);
      * a ShapeDtypeStruct without an explicit dtype, or a kernel that
        stores ``.astype(<dtype>)`` into an out ref whose declared
        out_shape dtype differs;
      * a fully-static block whose byte footprint (4 B/elem assumed when
        the dtype is dynamic) exceeds the per-chip VMEM budget — the
        smallest ``vmem_bytes`` in obs/costs.py's CHIP_PEAKS table, so the
        tightest supported chip gates every kernel.

    Dynamic shapes/specs are skipped, never guessed at.
    """
    budget = project.vmem_budget
    consts = ctx.module_int_consts
    for node in ast.walk(ctx.tree):
        if not (
            isinstance(node, ast.Call)
            and _last_attr(dotted_name(node.func)) == "pallas_call"
        ):
            continue
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        # a grid spec object (pltpu.PrefetchScalarGridSpec) carries the grid
        # and the specs; each scalar-prefetch operand is one more leading
        # operand and one more trailing index_map argument
        prefetch: Optional[int] = 0
        grid_spec = kwargs.get("grid_spec")
        if isinstance(grid_spec, ast.Call):
            spec_kw = {kw.arg: kw.value for kw in grid_spec.keywords if kw.arg}
            kwargs = {**spec_kw, **kwargs}
            if "num_scalar_prefetch" in spec_kw:
                prefetch = const_int(spec_kw["num_scalar_prefetch"], consts)
        # -- grid rank ----------------------------------------------------
        grid_node = kwargs.get("grid")
        grid_rank: Optional[int] = None
        if grid_node is not None:
            if isinstance(grid_node, (ast.Tuple, ast.List)):
                grid_rank = len(grid_node.elts)
            elif const_int(grid_node, consts) is not None:
                grid_rank = 1

        in_specs = _spec_list(kwargs.get("in_specs"))
        out_specs = _spec_list(kwargs.get("out_specs"))
        out_shape = _sds_list(kwargs.get("out_shape"))

        # -- per-spec index_map/shape consistency -------------------------
        for where, specs in (("in_specs", in_specs), ("out_specs", out_specs)):
            for i, spec in enumerate(specs or ()):
                if not _is_blockspec(spec):
                    continue
                shape, index_map = _blockspec_parts(spec)
                if (
                    index_map is not None
                    and grid_rank is not None
                    and prefetch is not None
                ):
                    arity = len(index_map.args.args)
                    if arity != grid_rank + prefetch:
                        yield ctx.finding(
                            "JX011", spec,
                            "%s[%d] index_map takes %d argument(s) but the "
                            "grid has rank %d%s — every grid axis indexes "
                            "every block" % (
                                where, i, arity, grid_rank,
                                " and %d scalar-prefetch operand(s) follow "
                                "it" % prefetch if prefetch else "",
                            ),
                            detail="%s[%d]:index_map_arity" % (where, i),
                        )
                if (
                    index_map is not None
                    and shape is not None
                    and isinstance(index_map.body, (ast.Tuple, ast.List))
                    and len(index_map.body.elts) != len(shape.elts)
                ):
                    yield ctx.finding(
                        "JX011", spec,
                        "%s[%d] index_map returns %d block coordinate(s) for "
                        "a %d-dimensional block_shape"
                        % (where, i, len(index_map.body.elts), len(shape.elts)),
                        detail="%s[%d]:index_map_rank" % (where, i),
                    )
                # -- VMEM budget on fully-static blocks -------------------
                if shape is not None:
                    dims = [const_int(d, consts) for d in shape.elts]
                    if all(d is not None for d in dims):
                        nbytes = 4  # f32 unless the spec says otherwise
                        for d in dims:
                            nbytes *= d
                        if nbytes > budget:
                            yield ctx.finding(
                                "JX011", spec,
                                "%s[%d] static block is %d bytes (f32), over "
                                "the %d-byte per-core VMEM budget (smallest "
                                "vmem_bytes in CHIP_PEAKS); tile the block "
                                "or shrink the chunk" % (where, i, nbytes, budget),
                                detail="%s[%d]:vmem" % (where, i),
                            )

        # -- in_specs count vs the immediate invocation -------------------
        parent = ctx.parent(node)
        if (
            in_specs is not None
            and prefetch is not None
            and isinstance(parent, ast.Call)
            and parent.func is node
            and not any(isinstance(a, ast.Starred) for a in parent.args)
        ):
            if len(parent.args) != len(in_specs) + prefetch:
                yield ctx.finding(
                    "JX011", node,
                    "pallas_call declares %d in_specs but is invoked with "
                    "%d operand(s)"
                    % (len(in_specs), len(parent.args) - prefetch),
                    detail="in_specs_count",
                )

        # -- out_specs vs out_shape ---------------------------------------
        if out_specs is not None and out_shape is not None:
            if len(out_specs) != len(out_shape):
                yield ctx.finding(
                    "JX011", node,
                    "pallas_call declares %d out_specs for %d out_shape "
                    "entr%s" % (
                        len(out_specs), len(out_shape),
                        "y" if len(out_shape) == 1 else "ies",
                    ),
                    detail="out_specs_count",
                )
            else:
                for i, (spec, sds) in enumerate(zip(out_specs, out_shape)):
                    if not (_is_blockspec(spec) and _is_sds(sds)):
                        continue
                    bshape, _ = _blockspec_parts(spec)
                    sshape, _ = _sds_parts(sds)
                    if (
                        bshape is not None
                        and sshape is not None
                        and len(bshape.elts) != len(sshape.elts)
                    ):
                        yield ctx.finding(
                            "JX011", spec,
                            "out_specs[%d] block has rank %d but its "
                            "out_shape entry has rank %d"
                            % (i, len(bshape.elts), len(sshape.elts)),
                            detail="out[%d]:block_rank" % i,
                        )

        # -- out_shape dtype discipline -----------------------------------
        out_dtypes: List[Optional[str]] = []
        for i, sds in enumerate(out_shape or ()):
            if not _is_sds(sds):
                out_dtypes.append(None)
                continue
            _, dtype = _sds_parts(sds)
            if dtype is None:
                yield ctx.finding(
                    "JX011", sds,
                    "out_shape[%d] ShapeDtypeStruct has no explicit dtype; "
                    "the accumulator dtype must be pinned, not inferred" % i,
                    detail="out[%d]:dtype_missing" % i,
                )
                out_dtypes.append(None)
            else:
                name = dotted_name(dtype)
                out_dtypes.append(_last_attr(name) if name else None)

        # -- kernel-side checks: program_id range + stored dtype ----------
        kernel = _resolve_kernel(ctx, node)
        if kernel is None:
            continue
        if grid_node is None and grid_spec is None:
            grid_rank = 0
        a = kernel.args
        params = [p.arg for p in a.posonlyargs + a.args]
        n_out = len(out_shape) if out_shape is not None else None
        # scratch refs trail the out refs in a pallas kernel signature:
        # kernel(in..., out..., scratch...). A non-literal scratch_shapes
        # makes the out-ref positions unknowable — skip the dtype check.
        scratch_node = kwargs.get("scratch_shapes")
        n_scratch: Optional[int] = 0
        if scratch_node is not None:
            if isinstance(scratch_node, (ast.List, ast.Tuple)):
                n_scratch = len(scratch_node.elts)
            else:
                n_scratch = None
        out_params = set()
        if n_out and n_scratch is not None:
            end = len(params) - n_scratch
            out_params = set(params[end - n_out:end])
        for sub in ast.walk(kernel):
            if not isinstance(sub, ast.Call):
                continue
            attr = _last_attr(dotted_name(sub.func))
            if (
                attr in ("program_id", "num_programs")
                and sub.args
                and grid_rank is not None
            ):
                axis = const_int(sub.args[0], consts)
                if axis is not None and not (0 <= axis < max(grid_rank, 0)):
                    yield ctx.finding(
                        "JX011", sub,
                        "%s(%d) in kernel %r but the pallas_call grid has "
                        "rank %d" % (attr, axis, kernel.name, grid_rank),
                        detail="%s:program_id=%d" % (kernel.name, axis),
                    )
        if n_out == 1 and out_dtypes and out_dtypes[0] is not None:
            declared = out_dtypes[0]
            (out_param,) = out_params or (None,)
            for sub in ast.walk(kernel):
                if not (
                    isinstance(sub, (ast.Assign, ast.AugAssign))
                    and isinstance(
                        tgt := (
                            sub.targets[0]
                            if isinstance(sub, ast.Assign) and sub.targets
                            else getattr(sub, "target", None)
                        ),
                        ast.Subscript,
                    )
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == out_param
                ):
                    continue
                v = sub.value
                if (
                    isinstance(v, ast.Call)
                    and isinstance(v.func, ast.Attribute)
                    and v.func.attr == "astype"
                    and v.args
                ):
                    stored = _last_attr(dotted_name(v.args[0]))
                    if stored and stored != declared:
                        yield ctx.finding(
                            "JX011", sub,
                            "kernel %r stores .astype(%s) into out ref %r "
                            "declared %s in out_shape — the write will be "
                            "recast" % (kernel.name, stored, out_param, declared),
                            detail="%s:store_dtype" % kernel.name,
                        )


# --------------------------------------------------------------------------
# JX012: float-exactness hazards on score/carry paths
# --------------------------------------------------------------------------
_SCORE_RE = re.compile(r"(^|_)(scores?\w*|carry|carries)($|_)")

_PR8_CITE = (
    "(PR 8: XLA CPU loop fusion FMA-contracted the shrink-multiply into the "
    "score add in one program but not the other — a 1-ulp drift found only "
    "by hand)"
)

_LOCAL_REDUCERS = {"sum", "mean", "dot", "matmul", "einsum", "tensordot"}


def _names_in(node: ast.AST) -> Iterator[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _has_inline_mult_add(node: ast.AST) -> Optional[ast.AST]:
    """The first Add BinOp one of whose direct operands is a Mult — the
    shape LLVM contracts into an FMA when XLA fuses the two."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Add):
            for side in (sub.left, sub.right):
                if isinstance(side, ast.BinOp) and isinstance(side.op, ast.Mult):
                    return sub
    return None


def _subscript_base_name(node: ast.AST) -> Optional[str]:
    """'scores' for scores[...], scores.at[...], self.scores.at[...]."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        if isinstance(node, ast.Attribute):
            if node.attr not in ("at",):
                return node.attr
            node = node.value
        else:
            node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


@rule("JX012", "float-exactness hazard on a score/carry path")
def jx012_float_exactness(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """Three hazards that break the bitwise-identity contracts the chunked /
    sharded / segmented trainers are proven against, scoped to ``ops/`` and
    ``models/`` jit code:

      * an inline multiply feeding an add on a score/carry assignment
        (``scores = scores + leaf * rate``, ``scores.at[k].add(v * rate)``)
        — whether XLA's fusion hands LLVM the contractible pattern depends
        on the surrounding program, so two program shapes computing the
        same math can drift by 1 ulp (the PR 8 find); materialize the
        product as its own value (or a program output) first;
      * ``jax.lax.optimization_barrier`` used as a fusion fence — it is
        stripped before XLA's fusion pass (measured, PR 8) and guarantees
        nothing about contraction; pin exactness by materializing the value
        as a program output instead;
      * a local f32 reduction nested directly inside a cross-shard
        collective (``psum(x.sum(...), axis)``) — the accumulation grouping
        then depends on the shard count, so results vary across mesh sizes;
        reduce into a shard-invariant layout first or document the
        tolerance at the call site.
    """
    if not any(
        seg in ("ops", "models") for seg in ctx.rel_path.split("/")[:-1]
    ):
        return
    for node in ast.walk(ctx.tree):
        # (b) optimization_barrier anywhere in these packages
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            attr = _last_attr(fname)
            if attr == "optimization_barrier":
                yield ctx.finding(
                    "JX012", node,
                    "optimization_barrier is stripped before XLA fusion and "
                    "does NOT prevent FMA contraction %s; materialize the "
                    "value as a program output instead" % _PR8_CITE,
                    detail="optimization_barrier",
                )
                continue
            # (c) psum/pmean of a directly-nested local reduction
            if attr in ("psum", "pmean") and node.args:
                operand = node.args[0]
                if (
                    isinstance(operand, ast.Call)
                    and _last_attr(dotted_name(operand.func)) in _LOCAL_REDUCERS
                ):
                    yield ctx.finding(
                        "JX012", node,
                        "%s of an inline %s: the f32 accumulation grouping "
                        "(local partials, then the collective tree) changes "
                        "with the shard count, so results differ across "
                        "mesh sizes; hoist the local reduction and prove "
                        "(or document) shard-invariance at the call site"
                        % (attr, _last_attr(dotted_name(operand.func))),
                        detail="%s_of_reduction" % attr,
                    )
                continue
        # (a) inline multiply-add on a score/carry assignment, jit code only
        if not isinstance(node, (ast.Assign, ast.AugAssign)):
            continue
        if ctx.enclosing_jit(node) is None:
            continue
        targets = node.targets if isinstance(node, ast.Assign) else [node.target]
        names: List[str] = []
        for t in targets:
            names.extend(_names_in(t))
        if not any(_SCORE_RE.search(n) for n in names):
            continue
        hit = None
        if isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Add):
            v = node.value
            if isinstance(v, ast.BinOp) and isinstance(v.op, ast.Mult):
                hit = v
        if hit is None:
            hit = _has_inline_mult_add(node.value)
        if hit is None and isinstance(node.value, ast.Call):
            f = node.value.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "add"
                and node.value.args
            ):
                arg = node.value.args[0]
                if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Mult):
                    base = _subscript_base_name(f.value)
                    if base is not None and _SCORE_RE.search(base):
                        hit = arg
        if hit is not None:
            yield ctx.finding(
                "JX012", node,
                "inline multiply feeding the add on a score/carry path: "
                "whether LLVM contracts this into an FMA depends on how XLA "
                "fuses the surrounding program %s; bind the product to its "
                "own value (or materialize it as a program output) so every "
                "program shape performs the identical plain add" % _PR8_CITE,
                detail=ctx.detail_for(hit),
            )


# --------------------------------------------------------------------------
# JX013: lock discipline in the threaded serve/obs stack
# --------------------------------------------------------------------------
_LOCK_FACTORIES = {
    "Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore", "make_lock",
}
_THREADED_DIRS = ("serve", "obs")
_HOLDS_RE = re.compile(
    r"caller[s]? .{0,40}hold|holds? (the )?_?\w*lock|lock (is )?held", re.I
)


def _lock_attrs_of(cls: ast.ClassDef) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(cls):
        if not (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Attribute)
            and isinstance(node.targets[0].value, ast.Name)
            and node.targets[0].value.id == "self"
            and isinstance(node.value, ast.Call)
        ):
            continue
        if _last_attr(dotted_name(node.value.func)) in _LOCK_FACTORIES:
            out.add(node.targets[0].attr)
    return out


def _lock_order_of(ctx: FileContext, cls: ast.ClassDef) -> List[str]:
    """Declared acquisition order: a ``_LOCK_ORDER = ("_a", "_b")`` tuple at
    class or module level (outermost first)."""
    for scope in (cls, ctx.tree):
        for node in scope.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "_LOCK_ORDER"
            ):
                from .engine import _str_elems

                return _str_elems(node.value)
    return []


def _self_lock_attr(expr: ast.AST, lock_attrs: Set[str]) -> Optional[str]:
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
        and expr.attr in lock_attrs
    ):
        return expr.attr
    return None


@rule("JX013", "shared state mutated outside the owning lock")
def jx013_lock_discipline(ctx: FileContext, project: ProjectContext) -> Iterator[Finding]:
    """In the multi-threaded ``serve/`` and ``obs/`` packages, a class that
    owns a lock (``self._lock = threading.Lock()`` — or obs/sanitize.py's
    ``make_lock``) declares that its ``self._*`` attributes are shared
    state. Two violations:

      * rebinding / item-assigning such an attribute outside a
        ``with self._<lock>:`` block — a hot-swap, scrape or drain racing
        the mutation sees torn state. Methods documented "caller holds
        _lock" are exempt, and a deliberately lock-free site carries a
        trailing ``# unlocked: <why>`` comment (single-writer GIL-atomic
        rebinds, init-once);
      * acquiring a second ``self`` lock while holding another without a
        ``_LOCK_ORDER = ("_outer", "_inner")`` declaration at class/module
        level — undeclared nesting is how lock-order inversions (and the
        deadlocks the runtime sanitizer's lock mode hunts) get written.
    """
    if not any(seg in _THREADED_DIRS for seg in ctx.rel_path.split("/")[:-1]):
        return
    for cls in ast.walk(ctx.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        lock_attrs = _lock_attrs_of(cls)
        if not lock_attrs:
            continue
        order = _lock_order_of(ctx, cls)

        def enclosing_locks(node: ast.AST) -> List[str]:
            """Lock attrs held at ``node``, outermost first."""
            chain: List[str] = []
            cur = ctx.parent(node)
            while cur is not None and cur is not cls:
                if isinstance(cur, ast.With):
                    for item in cur.items:
                        attr = _self_lock_attr(item.context_expr, lock_attrs)
                        if attr is not None:
                            chain.append(attr)
                cur = ctx.parent(cur)
            chain.reverse()
            return chain

        for method in cls.body:
            if not isinstance(method, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if method.name in ("__init__", "__del__", "__new__"):
                continue
            doc = ast.get_docstring(method) or ""
            if _HOLDS_RE.search(doc):
                continue
            for node in ast.walk(method):
                # -- nested acquisition without a declared order ----------
                if isinstance(node, ast.With):
                    for item in node.items:
                        inner = _self_lock_attr(item.context_expr, lock_attrs)
                        if inner is None:
                            continue
                        held = [a for a in enclosing_locks(node) if a != inner]
                        for outer in held:
                            ok = (
                                outer in order
                                and inner in order
                                and order.index(outer) < order.index(inner)
                            )
                            if not ok and ctx.pragma(node, "unlocked") is None:
                                yield ctx.finding(
                                    "JX013", node,
                                    "acquires self.%s while holding self.%s "
                                    "with no _LOCK_ORDER declaring that "
                                    "nesting; an undeclared order is how "
                                    "inversion deadlocks get written — "
                                    "declare _LOCK_ORDER = (%r, %r) (and "
                                    "keep every site consistent) or drop "
                                    "the nesting" % (inner, outer, outer, inner),
                                    detail="nest=%s>%s" % (outer, inner),
                                )
                    continue
                # -- unguarded mutation of self._* ------------------------
                attr: Optional[str] = None
                if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    if isinstance(node, ast.AnnAssign) and node.value is None:
                        continue
                    for t in targets:
                        if isinstance(t, ast.Subscript):
                            t = t.value
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and t.attr.startswith("_")
                            and t.attr not in lock_attrs
                        ):
                            attr = t.attr
                            break
                elif isinstance(node, ast.Delete):
                    for t in node.targets:
                        if isinstance(t, ast.Subscript):
                            t = t.value
                        if (
                            isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and t.attr.startswith("_")
                        ):
                            attr = t.attr
                            break
                if attr is None:
                    continue
                if enclosing_locks(node):
                    continue
                if ctx.pragma(node, "unlocked") is not None:
                    continue
                yield ctx.finding(
                    "JX013", node,
                    "mutates shared attribute self.%s outside any "
                    "`with self.<lock>:` block in a lock-owning class; "
                    "guard it, document the method \"caller holds _lock\", "
                    "or justify in place with a trailing "
                    "`# unlocked: <why>`" % attr,
                    detail="attr=%s" % attr,
                )

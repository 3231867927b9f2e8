"""graftir: jaxpr/StableHLO-level program auditor — lint what XLA actually sees.

graftlint (tools/graftlint, JX001-13) polices *source* idioms and graftsan
(obs/sanitize.py) polices *runtime* behavior — but every exactness and perf
regression this repo has shipped or narrowly dodged lived in the layer
between: the traced program XLA compiles. The serial-learner FMA contraction
that moved model bytes by 1 ulp (PR 8/11), implicit per-iteration
host->device uploads (PR 10), psum grouping drift (PR 14) and recompile
churn are all *visible in the ClosedJaxpr and the lowered StableHLO* before
a single chip cycle is spent. This module traces the canonical jitted entry
points with abstract arguments over the run's real shape lattice
(ops/grow.bucket_sizes + the HistRoute shape classes) and runs a rule
engine over the IR:

  IR001  forbidden primitives in hot paths — host callbacks
         (debug/pure/io_callback), in-program transfers (device_put),
         infeed/outfeed: each is a host sync or upload inside compiled code.
  IR002  dtype discipline — no f64 anywhere (TPUs have none; x64 drift),
         score/carry accumulation stays f32, convert_element_type churn
         counted against a per-entry budget.
  IR003  large baked-in constants — a host (numpy) constvar over the
         threshold is re-uploaded per executable and re-folded per trace
         (recompile + HBM duplication hazard). Device-resident captures
         (the bins closure) are accounted but intentional.
  IR004  donation honored — declared donate_argnums must survive into the
         lowered module as input/output aliases (``tf.aliasing_output``);
         silently-dropped donation doubles peak HBM.
  IR005  collective audit — psum/all_gather axis names must be declared
         mesh axes for the entry, an expected-collective program must
         actually contain one, and the combine payload must match the
         ``HistogramSource.payload_bytes`` seam estimate.
  IR006  exactness fences — the materialized-output / per-row-select FMA
         pins on the score-carry adds (PR 8, _finish_step) must survive
         into the IR: a scatter-add carry update whose addend is neither a
         program output nor select-fed is one fusion pass from a 1-ulp
         model drift.

On top of the rules sits a per-entry-point, per-shape-class
**program-fingerprint contract** (irscan_contract.json, checked in like the
graftlint baseline): digests of the lowered modules plus their op-count
histograms. Unexplained program drift fails loudly with an op-level diff,
and a static trace-count budget per entry point is the compile-time twin of
obs/retrace's runtime gauge. Findings follow the graftlint baseline
workflow (irscan_baseline.txt — line-number-free keys, mandatory
justifications, exit 1 on new findings OR stale entries).

Run::

    python -m lightgbm_tpu.obs.irscan              # scan vs baseline+contract
    python -m lightgbm_tpu.obs.irscan --full       # the whole bucket lattice
    python -m lightgbm_tpu.obs.irscan --write-contract   # refresh fingerprints
    python -m lightgbm_tpu.obs.irscan --selfcheck  # seeded violations caught?

Wired as ``helpers/check.sh --ir`` (helpers/irscan_smoke.py). Docs: docs/StaticAnalysis.md §Program-level audit.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import sys
import warnings
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BASELINE = os.path.join(_HERE, "irscan_baseline.txt")
DEFAULT_CONTRACT = os.path.join(_HERE, "irscan_contract.json")

#: a host (numpy) constant baked into a program above this is IR003 —
#: re-folded on every trace and duplicated per executable
NP_CONST_LIMIT = 64 * 1024

#: convert_element_type eqns tolerated per program before IR002 flags churn
DEFAULT_CONVERT_BUDGET = 128

#: primitives that are a host sync / transfer inside compiled code (IR001).
#: ``device_put`` is handled separately: traced as a bare aliasing
#: annotation (devices=[None], CopySemantics.ALIAS) it is a no-op the real
#: tree legitimately contains; with a concrete destination/source or copy
#: semantics it is an in-program transfer and IR001 fires.
FORBIDDEN_PRIMS = frozenset({
    "debug_callback", "debug_print", "pure_callback", "io_callback",
    "callback",
    "infeed", "outfeed", "copy_to_host",
})


def _device_put_is_transfer(params: dict) -> bool:
    if any(d is not None for d in params.get("devices", ())):
        return True
    if any(s is not None for s in params.get("srcs", ())):
        return True
    return any(
        "ALIAS" not in str(cs) for cs in params.get("copy_semantics", ())
    )

#: cross-device collectives whose axis names IR005 validates
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "all_gather", "all_reduce", "reduce_scatter",
    "all_to_all", "ppermute", "pmax", "pmin",
})
#: collectives that ship one operand-sized payload per participant —
#: cross-checked against HistogramSource.payload_bytes (IR005)
PAYLOAD_PRIMS = frozenset({"psum", "psum2", "all_reduce"})
#: axis-name-bearing but payload-free primitives (still axis-validated)
AXIS_PRIMS = COLLECTIVE_PRIMS | frozenset({"axis_index"})

RULES: Dict[str, str] = {
    "IR001": "forbidden primitive in a hot-path program",
    "IR002": "dtype discipline: f64 / non-f32 carry / convert churn",
    "IR003": "large host constant baked into the program",
    "IR004": "declared donation dropped by lowering",
    "IR005": "collective axis/payload audit",
    "IR006": "FMA exactness fence stripped from the IR",
}


# ---------------------------------------------------------------------------
# findings + baseline (graftlint's workflow, program-scoped keys)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Finding:
    rule: str
    entry: str
    shape: str
    detail: str
    message: str

    @property
    def key(self) -> str:
        """Line-number-free content key: RULE:entry:shape:detail."""
        return "%s:%s:%s:%s" % (self.rule, self.entry, self.shape, self.detail)

    def format(self) -> str:
        return "%s %s[%s] %s — %s" % (
            self.rule, self.entry, self.shape, self.detail, self.message
        )


def load_baseline(path: str) -> Tuple[Counter, Dict[str, str]]:
    """-> (multiset of suppressed keys, key -> justification). Same file
    format as tools/graftlint/baseline.txt."""
    keys: Counter = Counter()
    notes: Dict[str, str] = {}
    if not os.path.exists(path):
        return keys, notes
    with open(path, "r", encoding="utf-8") as fh:
        for raw in fh:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if "  # " in line:
                key, note = line.split("  # ", 1)
                key = key.strip()
                notes[key] = note.strip()
            else:
                key = line
            keys[key] += 1
    return keys, notes


def compare_to_baseline(
    findings: Sequence[Finding], baseline: Counter
) -> Tuple[List[Finding], Counter]:
    """-> (unsuppressed findings, stale baseline keys)."""
    remaining = Counter(baseline)
    new: List[Finding] = []
    for f in findings:
        if remaining[f.key] > 0:
            remaining[f.key] -= 1
        else:
            new.append(f)
    stale = Counter({k: n for k, n in remaining.items() if n > 0})
    return new, stale


def write_baseline(
    path: str, findings: Sequence[Finding], notes: Optional[Dict[str, str]] = None
) -> None:
    notes = notes or {}
    entries: Counter = Counter(f.key for f in findings)
    lines = [
        "# graftir baseline — accepted IR findings, one per line:",
        "#   <RULE:entry:shape:detail>  # <one-line justification>",
        "# Regenerate with: python -m lightgbm_tpu.obs.irscan --write-baseline",
        "",
    ]
    for key in sorted(entries):
        lines.append("%s  # %s" % (key, notes.get(key, "TODO: justify or fix")))
        lines.extend([key] * (entries[key] - 1))
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("\n".join(lines) + "\n")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _sub_jaxprs(value) -> Iterable[Tuple[Any, list]]:
    """Yield (Jaxpr, consts) pairs reachable from an eqn param value."""
    from jax.extend import core as jex_core  # jax.core lost these in 0.9

    if isinstance(value, jex_core.Jaxpr):
        yield value, []
    elif isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr, list(value.consts)
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_jaxprs(closed) -> Iterable[Tuple[Any, list]]:
    """(jaxpr, consts) for the top program and every nested sub-program
    (scan/while/cond bodies, pjit calls, shard_map regions, ...)."""
    seen = []
    stack = [(closed.jaxpr, list(closed.consts))]
    while stack:
        jx, consts = stack.pop()
        if any(jx is s for s in seen):
            continue
        seen.append(jx)
        yield jx, consts
        for eqn in jx.eqns:
            for v in eqn.params.values():
                stack.extend(_sub_jaxprs(v))


def iter_eqns(closed) -> Iterable[Any]:
    for jx, _ in iter_jaxprs(closed):
        for eqn in jx.eqns:
            yield eqn


def _aval(v):
    return getattr(v, "aval", None)


def _aval_nbytes(aval) -> int:
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n * int(np.dtype(aval.dtype).itemsize)


# ---------------------------------------------------------------------------
# entry specs + per-program audit records
# ---------------------------------------------------------------------------
@dataclass
class EntrySpec:
    """One registered jitted entry point and its audit contract."""

    name: str
    #: [(shape_label, jit_fn, args, kwargs)] — abstract (ShapeDtypeStruct)
    #: traced operands; statics ride in args/kwargs as concrete values
    variants: List[Tuple[str, Any, tuple, dict]]
    hot: bool = True                 # IR001 engages
    donated_min: int = 0             # IR004: >= this many lowered aliases
    pin: str = "none"                # IR006: none | materialized | select
    axes: frozenset = frozenset()    # IR005: declared mesh axes
    expect_collective: bool = False  # IR005: program must contain one
    carry_out: Optional[int] = None  # IR002: this output must stay f32
    convert_budget: int = DEFAULT_CONVERT_BUDGET
    np_const_limit: int = NP_CONST_LIMIT
    x64: bool = False                # trace under enable_x64 (seeded tests)


@dataclass
class Audit:
    """The scan record for one (entry, shape) program."""

    entry: str
    shape: str
    findings: List[Finding] = field(default_factory=list)
    digest: str = ""
    ops: Dict[str, int] = field(default_factory=dict)
    convert_count: int = 0
    np_const_bytes: int = 0
    device_const_bytes: int = 0
    donation_aliases: int = 0
    collectives: List[str] = field(default_factory=list)


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------
def _rule_ir001(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    if not spec.hot:
        return
    seen = set()
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name == "device_put" and _device_put_is_transfer(eqn.params):
            name = "device_put[transfer]"
        if name in FORBIDDEN_PRIMS or name == "device_put[transfer]":
            if name in seen:
                continue
            seen.add(name)
            audit.findings.append(Finding(
                "IR001", spec.name, shape, "prim=%s" % name,
                "forbidden primitive %r in a hot-path program — a host "
                "callback/transfer inside compiled code serializes the "
                "dispatch pipeline (the IR-level form of JX001)" % name,
            ))


def _rule_ir002(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    f64_prims = set()
    converts = 0
    for eqn in iter_eqns(closed):
        if eqn.primitive.name == "convert_element_type":
            converts += 1
        for v in list(eqn.outvars) + list(eqn.invars):
            aval = _aval(v)
            dt = getattr(aval, "dtype", None)
            if dt is not None and np.dtype(dt) == np.float64:
                f64_prims.add(eqn.primitive.name)
    audit.convert_count = converts
    for prim in sorted(f64_prims):
        audit.findings.append(Finding(
            "IR002", spec.name, shape, "f64=%s" % prim,
            "float64 value flows through %r — TPUs have no f64 (silent "
            "downcast with x64 off, bandwidth/precision drift with it on; "
            "the IR-level form of JX006)" % prim,
        ))
    if spec.carry_out is not None:
        outvars = closed.jaxpr.outvars
        if spec.carry_out < len(outvars):
            dt = getattr(_aval(outvars[spec.carry_out]), "dtype", None)
            if dt is not None and np.dtype(dt) != np.float32:
                audit.findings.append(Finding(
                    "IR002", spec.name, shape,
                    "carry_dtype=%s" % np.dtype(dt).name,
                    "score/carry output %d accumulates in %s, not float32 — "
                    "the exactness contract pins f32 carries"
                    % (spec.carry_out, np.dtype(dt).name),
                ))
    if converts > spec.convert_budget:
        audit.findings.append(Finding(
            "IR002", spec.name, shape, "convert_churn=%d" % converts,
            "%d convert_element_type eqns exceed this entry's budget of %d "
            "— dtype churn costs bandwidth every dispatch"
            % (converts, spec.convert_budget),
        ))


def _rule_ir003(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    import jax

    np_bytes = dev_bytes = 0
    for _, consts in iter_jaxprs(closed):
        for c in consts:
            if isinstance(c, jax.Array):
                dev_bytes += int(getattr(c, "nbytes", 0))
            elif hasattr(c, "shape") and hasattr(c, "dtype"):
                # a host constant: np.ndarray, or the TypedNdArray jax 0.9
                # wraps closed-over numpy values in (which has no nbytes)
                nbytes = _aval_nbytes(c)
                np_bytes += nbytes
                if nbytes > spec.np_const_limit:
                    audit.findings.append(Finding(
                        "IR003", spec.name, shape,
                        "const_bytes=%d" % nbytes,
                        "host constant of %d bytes (%s%s) baked into the "
                        "program (> %d limit) — re-folded on every trace "
                        "and duplicated per executable; hoist to a "
                        "device-resident argument or module-level buffer"
                        % (nbytes, np.dtype(c.dtype).name,
                           list(c.shape), spec.np_const_limit),
                    ))
    audit.np_const_bytes = np_bytes
    audit.device_const_bytes = dev_bytes


def _rule_ir004(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    # an immediately-aliasable donation lowers to tf.aliasing_output; a
    # donation whose aliasing is decided by XLA's own pass (sharded
    # programs) survives as jax.buffer_donor — both honor the declaration,
    # a DROPPED donation leaves neither attribute
    aliases = len(re.findall(r"tf\.aliasing_output", hlo)) + len(
        re.findall(r"jax\.buffer_donor", hlo)
    )
    audit.donation_aliases = aliases
    if spec.donated_min and aliases < spec.donated_min:
        audit.findings.append(Finding(
            "IR004", spec.name, shape,
            "aliases=%d<%d" % (aliases, spec.donated_min),
            "declared donation was dropped by lowering: %d input/output "
            "aliases in the StableHLO module, >= %d expected — the donated "
            "buffer stays live across the call, doubling peak HBM (the "
            "runtime fate JX005 can only guess at)"
            % (aliases, spec.donated_min),
        ))


def _axis_names(params: dict) -> List[str]:
    names = []
    for key in ("axes", "axis_name"):
        v = params.get(key)
        if v is None:
            continue
        for item in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(item, str):
                names.append(item)
    return names


def _rule_ir005(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    from ..ops.histogram import HistogramSource

    bad_axes = set()
    payload_drift = []
    for eqn in iter_eqns(closed):
        name = eqn.primitive.name
        if name not in AXIS_PRIMS:
            continue
        if name in COLLECTIVE_PRIMS:
            audit.collectives.append(name)
        for ax in _axis_names(eqn.params):
            if ax not in spec.axes:
                bad_axes.add((name, ax))
        if name in PAYLOAD_PRIMS:
            for v in eqn.invars:
                aval = _aval(v)
                if aval is None or not hasattr(aval, "dtype"):
                    continue
                actual = _aval_nbytes(aval)
                est = HistogramSource.payload_bytes(
                    aval.shape, np.dtype(aval.dtype).itemsize
                )
                if est != actual:
                    payload_drift.append((name, actual, est))
    for name, ax in sorted(bad_axes):
        audit.findings.append(Finding(
            "IR005", spec.name, shape, "axis=%s" % ax,
            "collective %r runs over axis %r which is not a declared mesh "
            "axis for this entry (declared: %s) — a typo'd axis fails only "
            "at run time, on the hardware (the IR-level form of JX007)"
            % (name, ax, sorted(spec.axes) or "none"),
        ))
    for name, actual, est in payload_drift:
        audit.findings.append(Finding(
            "IR005", spec.name, shape, "payload=%d!=%d" % (actual, est),
            "%r combine payload is %d bytes but the "
            "HistogramSource.payload_bytes seam estimates %d — the "
            "observability layer's comms accounting has drifted from the "
            "program" % (name, actual, est),
        ))
    if spec.expect_collective and not audit.collectives:
        audit.findings.append(Finding(
            "IR005", spec.name, shape, "collective_missing",
            "entry is declared collective (sharded combine expected) but "
            "the traced program contains no cross-device collective — "
            "shard partials are never combined",
        ))


def _producer_map(jaxpr) -> Dict[Any, Any]:
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def _is_select_producer(eqn) -> bool:
    """The update operand was produced by a per-row select — directly, or
    through the jnp.where jit wrapper (`_where`; the primitive jax 0.9
    names ``jit``)."""
    if eqn.primitive.name == "select_n":
        return True
    if eqn.primitive.name == "jit":
        if "_where" in str(eqn.params.get("name", "")):
            return True
        sub = eqn.params.get("jaxpr")
        if sub is not None:
            return any(
                q.primitive.name == "select_n" for q in sub.jaxpr.eqns
            )
    return False


def _rule_ir006(spec: EntrySpec, shape: str, closed, hlo: str, audit: Audit):
    if spec.pin == "none":
        return
    scatter_adds = 0
    pinned = False
    if spec.pin == "materialized":
        # the per-iteration form: the addend is a PROGRAM OUTPUT (and the
        # scatter-add's update operand) — a materialized output cannot be
        # recomputed-and-contracted inside the add kernel (PR 8)
        top = closed.jaxpr
        outset = set(top.outvars)
        for eqn in top.eqns:
            if eqn.primitive.name == "scatter-add":
                scatter_adds += 1
                if len(eqn.invars) >= 3 and eqn.invars[2] in outset:
                    pinned = True
    else:  # select: the scan/shard_map form — update fed by a per-row select
        for jx, _ in iter_jaxprs(closed):
            produced = _producer_map(jx)
            for eqn in jx.eqns:
                if eqn.primitive.name != "scatter-add":
                    continue
                scatter_adds += 1
                if len(eqn.invars) < 3:
                    continue
                prod = produced.get(eqn.invars[2])
                if prod is not None and _is_select_producer(prod):
                    pinned = True
    if scatter_adds == 0:
        audit.findings.append(Finding(
            "IR006", spec.name, shape, "pin_site_missing",
            "entry declares an FMA-pinned score add (%s mode) but the "
            "program contains no scatter-add carry update — the pinned "
            "seam has been rewritten; re-audit the exactness fence"
            % spec.pin,
        ))
    elif not pinned:
        audit.findings.append(Finding(
            "IR006", spec.name, shape, "fma_pin_stripped",
            "score-carry scatter-add has no surviving FMA pin (%s mode "
            "expected): the addend is neither a materialized program "
            "output nor select-fed — one fusion pass from the 1-ulp model "
            "drift PR 8 measured (the IR-level proof JX012 cannot give)"
            % spec.pin,
        ))


_RULE_FNS = (
    _rule_ir001, _rule_ir002, _rule_ir003, _rule_ir004, _rule_ir005,
    _rule_ir006,
)


# ---------------------------------------------------------------------------
# tracing + fingerprints
# ---------------------------------------------------------------------------
_LOC_RE = re.compile(r"\s*loc\([^)]*\)")
_OP_RE = re.compile(r"\b(?:stablehlo|mhlo|chlo|func)\.[\w.]+")


def _normalize_hlo(text: str) -> str:
    """Strip location metadata so fingerprints track the program, not the
    source file layout that traced it."""
    lines = [
        _LOC_RE.sub("", ln) for ln in text.splitlines()
        if not ln.lstrip().startswith("#loc")
    ]
    return "\n".join(lines)


def op_histogram(hlo: str) -> Dict[str, int]:
    return dict(Counter(_OP_RE.findall(hlo)))


def audit_program(spec: EntrySpec, shape: str, fn, args, kwargs) -> Audit:
    """Trace one entry variant abstractly and run every IR rule."""
    import jax

    audit = Audit(entry=spec.name, shape=shape)
    ctx = (
        jax.enable_x64(True)
        if spec.x64 else contextlib.nullcontext()
    )
    with warnings.catch_warnings():
        # a dropped donation warns at lowering; IR004 is the loud version
        warnings.simplefilter("ignore")
        with ctx:
            traced = fn.trace(*args, **kwargs)
            closed = traced.jaxpr
            lowered = traced.lower() if hasattr(traced, "lower") else (
                fn.lower(*args, **kwargs)
            )
            hlo = _normalize_hlo(lowered.as_text())
    audit.digest = hashlib.sha256(hlo.encode("utf-8")).hexdigest()[:16]
    audit.ops = op_histogram(hlo)
    for rule_fn in _RULE_FNS:
        rule_fn(spec, shape, closed, hlo, audit)
    return audit


def audit_entry(spec: EntrySpec) -> List[Audit]:
    return [
        audit_program(spec, shape, fn, args, kwargs)
        for shape, fn, args, kwargs in spec.variants
    ]


# ---------------------------------------------------------------------------
# the fingerprint contract
# ---------------------------------------------------------------------------
def contract_env() -> Dict[str, Any]:
    import jax

    return {
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "devices": len(jax.devices()),
    }


def load_contract(path: str) -> Optional[Dict[str, Any]]:
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def write_contract(
    path: str, audits: Sequence[Audit], trace_counts: Dict[str, int]
) -> Dict[str, Any]:
    entries: Dict[str, Any] = {}
    for a in audits:
        ent = entries.setdefault(a.entry, {"trace_budget": 0, "shapes": {}})
        ent["shapes"][a.shape] = {"digest": a.digest, "ops": a.ops}
    for name, n in trace_counts.items():
        if name in entries:
            entries[name]["trace_budget"] = n
    doc = {"env": contract_env(), "entries": entries}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return doc


def _op_diff(old: Dict[str, int], new: Dict[str, int]) -> str:
    """Human op-level diff between two fingerprint op histograms."""
    parts = []
    for op in sorted(set(old) | set(new)):
        d = new.get(op, 0) - old.get(op, 0)
        if d:
            parts.append("%+d %s" % (d, op))
    return ", ".join(parts) if parts else "same op mix (order/shape change)"


def check_contract(
    contract: Optional[Dict[str, Any]],
    audits: Sequence[Audit],
    trace_counts: Dict[str, int],
) -> Tuple[List[str], Optional[str]]:
    """-> (problems, skip_reason). A missing contract or a foreign
    environment skips LOUDLY (the reason is printed) instead of comparing
    digests that can never match across jax versions/backends."""
    if contract is None:
        return [], "no contract file — run --write-contract to pin"
    env = contract_env()
    if contract.get("env") != env:
        return [], (
            "contract recorded for %s, this environment is %s — "
            "fingerprints not comparable; re-pin with --write-contract"
            % (contract.get("env"), env)
        )
    problems: List[str] = []
    entries = contract.get("entries", {})
    for a in audits:
        ent = entries.get(a.entry)
        if ent is None:
            problems.append(
                "unpinned entry point %r — program drift or a new entry; "
                "review and --write-contract" % a.entry
            )
            continue
        rec = ent.get("shapes", {}).get(a.shape)
        if rec is None:
            problems.append(
                "unpinned shape class %s[%s] — review and --write-contract"
                % (a.entry, a.shape)
            )
            continue
        if rec.get("digest") != a.digest:
            problems.append(
                "program drift at %s[%s]: digest %s -> %s; op diff: %s"
                % (a.entry, a.shape, rec.get("digest"), a.digest,
                   _op_diff(rec.get("ops", {}), a.ops))
            )
    for name, n in trace_counts.items():
        ent = entries.get(name)
        if ent is None:
            continue
        budget = int(ent.get("trace_budget", 0))
        if budget and n > budget:
            problems.append(
                "trace-count budget exceeded for %r: %d traces > budget %d "
                "— a shape/static-arg class multiplied (the compile-time "
                "twin of obs/retrace's runtime gauge)" % (name, n, budget)
            )
    return problems, None


# ---------------------------------------------------------------------------
# the real entry-point registry (the corpus)
# ---------------------------------------------------------------------------
ENV_ROWS = "LIGHTGBM_TPU_IRSCAN_ROWS"


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _sds_like(a):
    import jax

    return jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype))


@dataclass
class Corpus:
    """Tiny deterministic bootstrap models whose live jit seams the
    registry traces — the args are ABSTRACTED (ShapeDtypeStruct), so no
    program in the scan ever executes."""

    bst: Any
    g: Any
    bst_data: Optional[Any] = None
    g_data: Optional[Any] = None
    pk: Optional[Any] = None
    chunk: int = 3


def build_corpus(
    rows: int = 384, chunk: int = 3, include_data: bool = True,
    include_serve: bool = True,
) -> Corpus:
    import jax

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(7)
    X = rng.rand(rows, 8).astype(np.float32)
    y = (X[:, 0] + 0.25 * rng.rand(rows) > 0.6).astype(np.float32)
    params = {
        "objective": "binary", "num_leaves": 7, "max_bin": 31,
        "learning_rate": 0.1, "verbosity": -1, "min_data_in_leaf": 5,
        "device_chunk_size": chunk,
    }
    bst = lgb.train(params, lgb.Dataset(X, label=y), 2 * chunk + 1)
    g = bst._gbdt
    reason = g.device_chunk_fallback_reason()
    if reason is not None:
        raise RuntimeError(
            "irscan corpus cannot reach the chunked device path: %s" % reason
        )
    corpus = Corpus(bst=bst, g=g, chunk=chunk)
    if include_data and len(jax.devices()) >= 2:
        bst2 = lgb.train(
            dict(params, tree_learner="data", num_machines=2),
            lgb.Dataset(X, label=y), 2 * chunk + 1,
        )
        corpus.bst_data, corpus.g_data = bst2, bst2._gbdt
    if include_serve:
        corpus.pk = bst.to_packed()
    return corpus


def _lattice_buckets(full: bool) -> List[int]:
    from ..ops.grow import bucket_sizes

    n = int(os.environ.get(ENV_ROWS, "4096"))
    buckets = list(bucket_sizes(n))
    if full or len(buckets) <= 3:
        return buckets
    # quick scan: smallest, a middle class, largest — the full sweep rides
    # --full (check.sh --ir) and the slow-marked lattice test
    return [buckets[0], buckets[len(buckets) // 2], buckets[-1]]


def _serve_buckets(full: bool) -> List[int]:
    from ..serve.cache import DEFAULT_MIN_ROWS

    top = 11 if full else 8  # 2^11 = 2048 full ladder, 256 quick
    return [1 << b for b in range(DEFAULT_MIN_ROWS.bit_length() - 1, top)]


def _spec_serial_chunk(c: Corpus) -> EntrySpec:
    g = c.g
    fn = g._chunk_fn(c.chunk)
    fmasks = g._sample_feature_masks(c.chunk)
    args = (
        _sds_like(g.scores), _sds_like(g._bag_mask), _sds((), np.int32),
        fmasks, _sds((), np.float32), g._pin_all,
    )
    return EntrySpec(
        name="gbdt.train_chunk[serial]",
        variants=[("rows=%d" % g.num_data, fn, args, {})],
        donated_min=2, pin="select", carry_out=0,
    )


def _spec_data_chunk(c: Corpus) -> Optional[EntrySpec]:
    g = c.g_data
    if g is None:
        return None
    extra = g._sharded_chunk_args()  # places the sharded carries
    fn = g._chunk_fn(c.chunk)
    fmasks = g._sample_feature_masks(c.chunk)
    args = (
        _sds_like(g.scores), _sds_like(g._bag_mask), _sds((), np.int32),
        fmasks, _sds((), np.float32),
    ) + tuple(extra)
    return EntrySpec(
        name="gbdt.train_chunk[data]",
        variants=[("rows=%d" % g.num_data, fn, args, {})],
        donated_min=2, pin="select", carry_out=0,
        axes=frozenset({"data"}), expect_collective=True,
    )


def _spec_grow_tree(c: Corpus) -> EntrySpec:
    from ..ops.grow import grow_tree, spec_batch_slots
    from ..ops.histogram import route_rows_variant

    g = c.g
    cfg = g.config
    M = cfg.num_leaves
    F = g.feature_meta["num_bin"].shape[0]
    N = g.num_data
    slots = g._hist_pool_slots()
    rows = slots if slots is not None else M
    buf = _sds((rows, F, g.num_bins, 3), np.float32)
    sbuf = None
    donated = 1
    if spec_batch_slots(
        M, hist_mode=cfg.tpu_hist_mode,
        has_lazy_cegb=g.cegb_params.has_lazy,
        pooled=slots is not None and slots < M,
        cegb_on=g.cegb_params.enabled,
        route_rows_variant=route_rows_variant(
            g._hist_route, num_bins=g.num_group_bins or g.num_bins,
            hist_dtype=cfg.tpu_hist_dtype, n_rows=N,
        ),
    ):
        sbuf = _sds((M, F, g.num_bins, 3), np.float32)
        donated += 1
    kwargs = dict(
        num_leaves=M, max_depth=cfg.max_depth, num_bins=g.num_bins,
        num_group_bins=g.num_group_bins, params=g.split_params,
        chunk=cfg.tpu_hist_chunk, hist_dtype=cfg.tpu_hist_dtype,
        hist_mode=cfg.tpu_hist_mode, two_way=g._two_way,
        hist_route=g._hist_route, forced_splits=g._forced_splits,
        cegb=g.cegb_params, cegb_state=g._cegb_state, hist_buf=buf,
        bins_nf=g.bins_dev_nf, hist_pool_slots=slots, spec_buf=sbuf,
    )
    args = (
        g.bins_dev, _sds((N,), np.float32), _sds((N,), np.float32),
        _sds_like(g._bag_mask), g._sample_features(), g.feature_meta,
    )
    return EntrySpec(
        name="ops.grow_tree",
        variants=[("rows=%d" % N, grow_tree, args, kwargs)],
        donated_min=donated,
    )


def _spec_finish_step(c: Corpus) -> EntrySpec:
    import jax

    g = c.g
    _, step = g._finish_step(0)
    fn = jax.jit(step, donate_argnums=(0,))
    ta, _ = g._device_trees[-1]
    args = (
        _sds_like(g.scores), _sds_like(ta.leaf_value),
        _sds_like(ta.internal_value), _sds((g.num_data,), np.int32),
        _sds_like(g._bag_mask), _sds((), np.int32), _sds((), np.float32),
    )
    return EntrySpec(
        name="gbdt.finish_step",
        variants=[("rows=%d" % g.num_data, fn, args, {})],
        donated_min=1, pin="materialized", carry_out=0,
    )


def _spec_leaf_histograms(c: Corpus, full: bool) -> List[EntrySpec]:
    from ..ops import histogram as hist_mod

    g = c.g
    cfg = g.config
    B = g.num_group_bins or g.num_bins
    F = g.feature_meta["num_bin"].shape[0]
    bins_dtype = np.dtype(c.g.bins_dev.dtype)
    buckets = _lattice_buckets(full)
    default = hist_mod.default_impl()
    impls = {default, "xla"}  # the routed default + the exactness oracle
    if g._hist_route is not None:
        impls |= g._hist_route.effective_impls(
            default, B, 3, cfg.tpu_hist_dtype, buckets
        )
    # every routing contender this backend can serve at the corpus width is
    # pinned (ISSUE 17): a tune table written later can route to any of
    # them without first widening the contract, and an IR drift in a
    # not-currently-routed kernel still trips the scan
    impls |= {
        i for i in hist_mod.IMPLS if hist_mod.impl_supported(i, B)
    }
    specs = []
    for impl in sorted(impls):
        if not hist_mod.impl_supported(impl, B):
            continue
        variants = []
        for rb in buckets:
            kwargs = dict(
                num_bins=B, chunk=min(cfg.tpu_hist_chunk, rb), impl=impl,
                hist_dtype=cfg.tpu_hist_dtype,
            )
            variants.append((
                "rows=%d" % rb, hist_mod.leaf_histogram,
                (_sds((F, rb), bins_dtype), _sds((rb, 3), np.float32)),
                kwargs,
            ))
        specs.append(EntrySpec(
            name="ops.leaf_histogram[%s]" % impl, variants=variants,
            carry_out=0,
        ))
    return specs


def _spec_serve(c: Corpus, full: bool) -> List[EntrySpec]:
    from ..ops import predict as predict_mod

    pk = c.pk
    if pk is None:
        return []
    F = pk.num_features
    buckets = _serve_buckets(full)
    if not full:
        buckets = [buckets[0], buckets[-1]]
    leaves, values, binrows = [], [], []
    for r in buckets:
        codes = _sds((r, F), np.int32)
        isnan = _sds((r, F), np.bool_)
        label = "rows=%d" % r
        leaves.append((
            label, predict_mod.packed_predict_leaves,
            (codes, isnan, pk.packed), {},
        ))
        values.append((
            label, predict_mod.packed_predict_values,
            (codes, isnan, pk.packed),
            dict(num_class=pk.num_class, average_output=pk.average_output),
        ))
        binrows.append((
            label, predict_mod.packed_bin_rows,
            (_sds((r, F), np.float32), pk.bounds_dev, pk.is_cat_dev), {},
        ))
    return [
        EntrySpec(name="serve.packed_predict_leaves", variants=leaves),
        EntrySpec(name="serve.packed_predict_values", variants=values,
                  carry_out=0),
        EntrySpec(name="serve.packed_bin_rows", variants=binrows),
    ]


def build_registry(
    corpus: Corpus, full: bool = False,
    include: Optional[Sequence[str]] = None,
) -> Tuple[List[EntrySpec], List[str]]:
    """-> (entry specs, loudly-skipped entry names)."""
    skipped: List[str] = []
    specs: List[EntrySpec] = [
        _spec_serial_chunk(corpus),
        _spec_grow_tree(corpus),
        _spec_finish_step(corpus),
    ]
    data = _spec_data_chunk(corpus)
    if data is not None:
        specs.append(data)
    else:
        skipped.append(
            "gbdt.train_chunk[data] (needs >= 2 devices and a data-learner "
            "corpus)"
        )
    specs.extend(_spec_leaf_histograms(corpus, full))
    if corpus.pk is not None:
        specs.extend(_spec_serve(corpus, full))
    else:
        skipped.append("serve.packed_* (corpus built without a packed model)")
    if include:
        keep = [
            s for s in specs if any(tok in s.name for tok in include)
        ]
        skipped.extend(
            "%s (filtered by --entries)" % s.name
            for s in specs if s not in keep
        )
        specs = keep
    return specs, skipped


# ---------------------------------------------------------------------------
# seeded-violation self-check: one poisoned program per rule, proven caught
# ---------------------------------------------------------------------------
def seeded_specs() -> List[Tuple[str, EntrySpec]]:
    """[(rule expected to fire, poisoned EntrySpec)] — the golden 'bad
    fixtures' of the IR rule set (tests/test_irscan.py + the --ir smoke
    prove each is caught, and that its healthy twin in the real registry
    is clean)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    f32 = np.float32
    out: List[Tuple[str, EntrySpec]] = []

    def bad_callback(x):
        jax.debug.print("x={}", x)
        return x * 2
    out.append(("IR001", EntrySpec(
        name="seeded.ir001", variants=[
            ("rows=8", jax.jit(bad_callback), (_sds((8,), f32),), {}),
        ],
    )))

    def bad_f64(x):
        return (x.astype(jnp.float64) * 1.5).astype(jnp.float32)
    out.append(("IR002", EntrySpec(
        name="seeded.ir002", variants=[
            ("rows=8", jax.jit(bad_f64), (_sds((8,), f32),), {}),
        ],
        x64=True,
    )))

    big = np.arange(NP_CONST_LIMIT // 2, dtype=np.float32)  # 2x the limit

    def bad_const(x):
        return x + jnp.asarray(big)[: x.shape[0]]
    out.append(("IR003", EntrySpec(
        name="seeded.ir003", variants=[
            ("rows=8", jax.jit(bad_const), (_sds((8,), f32),), {}),
        ],
    )))

    # shape-changing output: XLA cannot alias it, donation silently drops
    dropped = jax.jit(lambda x: x[:2], donate_argnums=(0,))
    out.append(("IR004", EntrySpec(
        name="seeded.ir004", variants=[
            ("rows=8", dropped, (_sds((8,), f32),), {}),
        ],
        donated_min=1,
    )))

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    undeclared = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(),
    ))
    out.append(("IR005", EntrySpec(
        name="seeded.ir005", variants=[
            ("rows=8", undeclared, (_sds((8, 4), f32),), {}),
        ],
        axes=frozenset({"batch"}),  # the program's "data" is undeclared
        expect_collective=True,
    )))

    def stripped_pin(scores, leaf, lid):
        add = leaf[lid]  # no per-row select, add not returned: pin stripped
        return scores.at[0].add(add)
    out.append(("IR006", EntrySpec(
        name="seeded.ir006", variants=[
            ("rows=8", jax.jit(stripped_pin),
             (_sds((2, 8), f32), _sds((4,), f32), _sds((8,), np.int32)), {}),
        ],
        pin="select",
    )))

    def dropped_pin_output(scores, leaf, lid, pin):
        add = jnp.where(pin, leaf[lid], jnp.float32(0.0))
        return scores.at[0].add(add)  # pinned add NOT materialized as output
    out.append(("IR006", EntrySpec(
        name="seeded.ir006_materialized", variants=[
            ("rows=8", jax.jit(dropped_pin_output),
             (_sds((2, 8), f32), _sds((4,), f32), _sds((8,), np.int32),
              _sds((8,), np.bool_)), {}),
        ],
        pin="materialized",
    )))
    return out


def run_selfcheck() -> Dict[str, bool]:
    """rule id -> was its seeded violation caught (every value must be
    True). Entries seeded twice (IR006's two pin modes) must BOTH fire."""
    results: Dict[str, bool] = {}
    for rule, spec in seeded_specs():
        audits = audit_entry(spec)
        caught = any(f.rule == rule for a in audits for f in a.findings)
        results.setdefault(rule, True)
        results[rule] = results[rule] and caught
    return results


# ---------------------------------------------------------------------------
# scan driver + CLI
# ---------------------------------------------------------------------------
@dataclass
class ScanResult:
    audits: List[Audit]
    findings: List[Finding]
    trace_counts: Dict[str, int]
    skipped: List[str]


def run_scan(
    corpus: Optional[Corpus] = None, full: bool = False,
    include: Optional[Sequence[str]] = None,
) -> ScanResult:
    if corpus is None:
        corpus = build_corpus()
    specs, skipped = build_registry(corpus, full=full, include=include)
    audits: List[Audit] = []
    trace_counts: Dict[str, int] = {}
    for spec in specs:
        got = audit_entry(spec)
        audits.extend(got)
        trace_counts[spec.name] = len(got)
    findings = [f for a in audits for f in a.findings]
    return ScanResult(audits, findings, trace_counts, skipped)


def _list_rules() -> str:
    lines = []
    for rid in sorted(RULES):
        lines.append("%s — %s" % (rid, RULES[rid]))
    lines.append("")
    lines.append("Details: docs/StaticAnalysis.md §Program-level audit")
    return "\n".join(lines)


def _fmt_bytes(n: int) -> str:
    return "%.1fKiB" % (n / 1024.0) if n >= 1024 else "%dB" % n


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.irscan",
        description="jaxpr/StableHLO-level audit of the jitted entry points",
    )
    parser.add_argument("--full", action="store_true",
                        help="trace the whole bucket lattice / serve ladder")
    parser.add_argument("--baseline", default=DEFAULT_BASELINE)
    parser.add_argument("--contract", default=DEFAULT_CONTRACT)
    parser.add_argument("--no-baseline", action="store_true",
                        help="report every finding, ignoring the baseline")
    parser.add_argument("--write-baseline", action="store_true")
    parser.add_argument("--write-contract", action="store_true",
                        help="re-pin program fingerprints (implies --full)")
    parser.add_argument("--entries", action="append", metavar="SUBSTR",
                        help="audit only entry names containing SUBSTR")
    parser.add_argument("--selfcheck", action="store_true",
                        help="run the seeded-violation self-check and exit")
    parser.add_argument("--json", default=None, metavar="PATH",
                        help="write the scan record as JSON")
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        print(_list_rules())
        return 0

    # the sharded entry needs a multi-device mesh; on CPU hosts force the
    # same virtual 8-device platform the test mesh and multichip smoke use
    # (must happen before the backend initializes — a no-op afterwards)
    if os.environ.get("JAX_PLATFORMS", "cpu").startswith("cpu"):
        from ..utils.platform import force_cpu_devices

        force_cpu_devices(8)
    import jax  # noqa: F401  (backend is configured above)

    if args.selfcheck:
        results = run_selfcheck()
        for rule in sorted(results):
            print("%s seeded violation: %s"
                  % (rule, "caught" if results[rule] else "MISSED"))
        return 0 if all(results.values()) else 1

    full = args.full or args.write_contract
    env = contract_env()
    print("irscan: building the bootstrap corpus (platform=%s jax=%s "
          "devices=%d)" % (env["platform"], env["jax"], env["devices"]))
    result = run_scan(full=full, include=args.entries)
    for reason in result.skipped:
        print("irscan: SKIPPED %s" % reason)
    for a in result.audits:
        print(
            "  %-32s %-10s ops=%-4d convert=%-3d np-consts=%-8s "
            "dev-consts=%-9s aliases=%d digest=%s"
            % (a.entry, a.shape, sum(a.ops.values()), a.convert_count,
               _fmt_bytes(a.np_const_bytes),
               _fmt_bytes(a.device_const_bytes), a.donation_aliases,
               a.digest)
        )
    print("irscan: %d entry point(s), %d program variant(s) traced"
          % (len(result.trace_counts), len(result.audits)))

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump({
                "env": env,
                "audits": [vars(a) | {
                    "findings": [f.format() for f in a.findings],
                } for a in result.audits],
                "trace_counts": result.trace_counts,
                "skipped": result.skipped,
            }, fh, indent=1, default=str)
            fh.write("\n")

    rc = 0
    if args.write_contract:
        write_contract(args.contract, result.audits, result.trace_counts)
        print("irscan: wrote %d fingerprint(s) to %s"
              % (len(result.audits), args.contract))
    else:
        problems, skip = check_contract(
            load_contract(args.contract), result.audits, result.trace_counts
        )
        if skip is not None:
            print("irscan: contract check skipped — %s" % skip)
        elif problems:
            for p in problems:
                print("irscan: CONTRACT: %s" % p)
            rc = 1
        else:
            print("irscan: contract OK (%d fingerprint(s) match, trace "
                  "budgets honored)" % len(result.audits))

    if args.write_baseline:
        _, notes = load_baseline(args.baseline)
        write_baseline(args.baseline, result.findings, notes)
        print("irscan: wrote %d finding(s) to %s"
              % (len(result.findings), args.baseline))
        return rc
    if args.no_baseline:
        for f in result.findings:
            print(f.format())
        print("irscan: %d finding(s)" % len(result.findings))
        return 1 if (result.findings or rc) else 0

    baseline, _ = load_baseline(args.baseline)
    new, stale = compare_to_baseline(result.findings, baseline)
    for f in new:
        print(f.format())
    for key, n in sorted(stale.items()):
        print("stale baseline entry (finding no longer present x%d): %s"
              % (n, key))
    if new or stale:
        print("irscan: %d new finding(s), %d stale baseline entr%s"
              % (len(new), sum(stale.values()),
                 "y" if sum(stale.values()) == 1 else "ies"))
        return 1
    print("irscan: clean (%d finding(s) baselined, %d rules)"
          % (len(result.findings), len(RULES)))
    return rc


if __name__ == "__main__":
    sys.exit(main())

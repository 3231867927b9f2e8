"""Measured XLA cost analysis per executable + the roofline peak table.

The reference ships only coarse wall-clock utilities (``utils/log.h``/
TIMETAG); on a TPU-native stack the compiler itself knows what every
executable costs. This module harvests
``jit(fn).lower(avals).compile().cost_analysis()`` (flops, bytes accessed)
and ``.memory_analysis()`` (argument/output/temp bytes) for the core
executables — keyed by the SAME names the retrace watchdog counts
(``ops.grow_tree``, ``gbdt.train_chunk``, ``ops.packed_predict_values``,
``ops.packed_bin_rows``, ``ops.leaf_histogram``) — so one scrape answers
"what compiled, how big, how hot":

 * every harvested record publishes ``xla_cost_*`` gauges (labeled by
   executable) on the default metrics registry, next to the watchdog's
   per-name ``jit_traces`` compile counts;
 * ``run_report()`` carries the whole book as a ``cost_analysis`` section.

Harvesting is env-gated (``LIGHTGBM_TPU_COSTS=1``): ``lower().compile()``
is a SECOND XLA compile of the executable (the AOT path does not share the
jit dispatch cache), which the persistent compilation cache makes cheap on
re-runs but which plain training should not pay silently. Call sites
(models/gbdt.py, serve/packed.py) check :func:`enabled` and
dedupe per (name, arg-shape signature), so the steady-state overhead is a
dict lookup.

The chip peak table is an explicit ``device_kind -> (peak_flops, peak_bw,
vmem_bytes)`` map over the TPU families v4/v5e/v5p/v6e, with its source. A
device that is not in it is an error, never a default: a utilisation is
computed against a TPU's published peak or not at all.

Stdlib + jax-lazy: importing this module never touches a backend.
"""
from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Tuple

from ..utils import log
from . import registry as registry_mod
from . import sanitize as sanitize_mod

ENV_COSTS = "LIGHTGBM_TPU_COSTS"


def enabled() -> bool:
    """Read per call, not at import: a process may flip it."""
    return os.environ.get(ENV_COSTS, "") not in ("", "0")


# --------------------------------------------------------------------------
# per-device_kind peak table
# --------------------------------------------------------------------------

#: TPU family -> published per-chip peaks. The v5e row — the only chip this
#: repo has run on — is Google Cloud's "TPU v5e" documentation page: 197
#: TFLOP/s bf16, 819 GB/s HBM per chip. The other rows carry the public
#: system specs of the earlier table (v4 275 TFLOP/s bf16, 1228 GB/s; v5p
#: 459, 2765; v6e 918, 1640), not re-checked.
#: ``peak_flops`` is that bf16 dense-matmul figure. It is also the rate of
#: this repo's float32 histograms: the MXU accumulates in f32 at the bf16
#: rate, and XLA's default-precision f32 contraction feeds it bf16 operands
#: in one pass (chip_smoke.py measures the resulting 2^-8 operand rounding
#: against a float64 oracle), so no separate, halved "f32" peak applies.
#: ``peak_bw`` is HBM bytes/s. ``vmem_bytes`` is the scoped-VMEM limit
#: Mosaic enforces per kernel by default — measured for the v5e (its
#: "Scoped allocation ... limit 16.00M" refusals, PR 21 chip runs), carried
#: over unmeasured for the rest — the bound ops/hist_pallas.py budgets
#: against and graftlint JX011 statically enforces by reading THIS table
#: (the smallest vmem_bytes gates every kernel).
CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "v4": {"peak_flops": 275e12, "peak_bw": 1228e9,
           "vmem_bytes": 16 * 2 ** 20},
    "v5e": {"peak_flops": 197e12, "peak_bw": 819e9,
            "vmem_bytes": 16 * 2 ** 20},
    "v5p": {"peak_flops": 459e12, "peak_bw": 2765e9,
            "vmem_bytes": 16 * 2 ** 20},
    "v6e": {"peak_flops": 918e12, "peak_bw": 1640e9,
            "vmem_bytes": 32 * 2 ** 20},
}


def normalize_device_kind(device_kind: Optional[str]) -> Optional[str]:
    """Map a jax ``device.device_kind`` string onto a chip family name.

    Handles the spellings seen in the wild: "TPU v4", "TPU v5e",
    "TPU v5 lite" (what a v5e reports under jax 0.9 / libtpu 0.0.34) /
    "TPU v5litepod", "TPU v5p"/"TPU v5", "TPU v6e", "TPU v6 lite"/
    "Trillium", and "cpu" for cpu hosts (a name for tune-table keys, not a
    CHIP_PEAKS row). Returns None when unknown.
    """
    if not device_kind:
        return None
    k = device_kind.lower().replace("_", " ")
    if "cpu" in k:
        return "cpu"
    if "trillium" in k or "v6" in k:
        return "v6e"
    if "v5p" in k:
        return "v5p"
    if "v5" in k:  # v5e / v5 lite / v5litepod; bare "v5" maps to v5p
        if "lite" in k or "v5e" in k:
            return "v5e"
        return "v5p"
    if "v4" in k:
        return "v4"
    return None


def chip_peaks(
    device_kind: Optional[str], platform: Optional[str] = None
) -> Dict[str, object]:
    """The roofline peaks of a TPU: ``{peak_flops, peak_bw, vmem_bytes,
    chip}``, ``chip`` being the family label annotated with the raw
    device_kind. Raises for anything that is not a TPU family in
    :data:`CHIP_PEAKS` — a CPU, or a TPU generation this table has no
    published row for — so no caller can divide by a guessed peak.
    ``platform`` only makes the error say what was running.
    """
    fam = normalize_device_kind(device_kind)
    rec = CHIP_PEAKS.get(fam)
    if rec is None:
        raise log.LightGBMError(
            "no peak-table row for device_kind=%r (platform=%r): roofline "
            "and utilisation figures exist only for the TPU families in "
            "obs/costs.CHIP_PEAKS (%s); add the generation's published "
            "peaks there" % (device_kind, platform, ", ".join(CHIP_PEAKS))
        )
    return {
        "peak_flops": rec["peak_flops"],
        "peak_bw": rec["peak_bw"],
        "vmem_bytes": rec["vmem_bytes"],
        "chip": "%s (device_kind=%s)" % (fam, device_kind),
    }


def vmem_bytes(device_kind: Optional[str] = None) -> int:
    """Scoped-VMEM bytes a Pallas kernel's blocks must fit: this TPU's row,
    or — with no device_kind, i.e. off-chip (interpret mode, static lint) —
    the smallest row, so shapes sized anywhere lower everywhere. An
    unrecognised TPU device_kind raises like :func:`chip_peaks`."""
    if device_kind is None:
        return int(min(r["vmem_bytes"] for r in CHIP_PEAKS.values()))
    return int(chip_peaks(device_kind)["vmem_bytes"])


# --------------------------------------------------------------------------
# the harvest book
# --------------------------------------------------------------------------

def _to_aval(x):
    """jax arrays -> ShapeDtypeStructs so a harvest never needs the live
    (possibly donated-away) buffers; everything else passes through."""
    import jax

    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def sds_args(args: tuple, kwargs: dict):
    """Abstract (args, kwargs) for a later harvest call — snapshot BEFORE
    invoking a donating jit, while the buffers still have shapes."""
    import jax

    return jax.tree_util.tree_map(_to_aval, (tuple(args), dict(kwargs)))


def _normalize_cost(ca) -> Dict[str, float]:
    """compiled.cost_analysis() returns a dict on TPU and a 1-element list
    of dicts on CPU/GPU (jax<=0.4.x); flatten to the keys we publish."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out: Dict[str, float] = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    if "bytes accessedout{}" in ca:
        out["bytes_accessed_out"] = float(ca["bytes accessedout{}"])
    if "transcendentals" in ca:
        out["transcendentals"] = float(ca["transcendentals"])
    return out


class CostBook:
    """name -> harvested cost/memory record, deduped per argument-shape
    signature, published as labeled gauges on the default registry."""

    def __init__(self) -> None:
        self._records: Dict[str, Dict[str, object]] = {}
        self._seen: set = set()
        self._lock = sanitize_mod.make_lock("obs.costs")

    def harvest(self, name: str, jit_fn, args=(), kwargs=None,
                registry=None) -> Optional[Dict[str, object]]:
        """Lower+compile ``jit_fn`` at the (abstracted) call signature and
        record its cost analysis under ``name``. ``args``/``kwargs`` may be
        live arrays, ShapeDtypeStructs, or the pre-snapshotted pair from
        :func:`sds_args`. Returns the record, the cached one on a repeat
        signature, or None when the backend/compile declines — a failed
        harvest must never take training or serving down.
        """
        kwargs = kwargs or {}
        try:
            a_args, a_kwargs = sds_args(args, kwargs)
        except Exception as e:
            log.warn_once(
                "costs:%s" % name,
                "cost-analysis harvest for %r failed abstracting args: %r"
                % (name, e),
            )
            return None
        try:
            key = (name, _sig(a_args), _sig(tuple(sorted(a_kwargs.items()))))
        except Exception:
            key = None
        if key is not None:
            with self._lock:
                if key in self._seen:
                    return self._records.get(name)
        try:
            compiled = jit_fn.lower(*a_args, **a_kwargs).compile()
            rec: Dict[str, object] = dict(_normalize_cost(compiled.cost_analysis()))
            try:
                ma = compiled.memory_analysis()
                rec["argument_bytes"] = int(ma.argument_size_in_bytes)
                rec["output_bytes"] = int(ma.output_size_in_bytes)
                rec["temp_bytes"] = int(ma.temp_size_in_bytes)
                rec["alias_bytes"] = int(ma.alias_size_in_bytes)
            except Exception as e:
                # some backends ship cost analysis but no memory stats;
                # keep the flops record rather than dropping the harvest
                log.debug("costs: memory_analysis unavailable for %r: %r"
                          % (name, e))
        except Exception as e:
            log.warn_once(
                "costs:%s" % name,
                "cost-analysis harvest for %r failed: %s: %s"
                % (name, type(e).__name__, str(e)[:160]),
            )
            return None
        with self._lock:
            if key is not None:
                self._seen.add(key)
            self._records[name] = rec
        self._publish(name, rec, registry)
        return rec

    def _publish(self, name: str, rec: Dict[str, object], registry=None) -> None:
        reg = registry if registry is not None else registry_mod.REGISTRY
        gauges = {
            "flops": "xla_cost_flops",
            "bytes_accessed": "xla_cost_bytes_accessed",
            "argument_bytes": "xla_cost_argument_bytes",
            "output_bytes": "xla_cost_output_bytes",
            "temp_bytes": "xla_cost_temp_bytes",
        }
        for field, gname in gauges.items():
            v = rec.get(field)
            if v is not None:
                reg.gauge(gname).set(float(v), executable=name)

    def get(self, name: str) -> Optional[Dict[str, object]]:
        with self._lock:
            rec = self._records.get(name)
            return dict(rec) if rec is not None else None

    def report(self) -> Dict[str, Dict[str, object]]:
        """The whole book — run_report()'s ``cost_analysis`` section."""
        with self._lock:
            return {k: dict(v) for k, v in sorted(self._records.items())}

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._seen.clear()


def _sig(obj) -> str:
    """Hashable-ish signature of an abstracted arg tree (shapes/dtypes and
    static values rendered to a string; stable across processes)."""
    import jax

    parts = []

    def walk(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            parts.append("%s%s" % (x.dtype, tuple(x.shape)))
        else:
            parts.append(repr(x)[:80])

    jax.tree_util.tree_map(walk, obj)
    return "|".join(parts)


#: process-wide cost book; gbdt/serve/prof harvest into it when enabled()
COSTS = CostBook()

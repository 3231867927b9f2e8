"""Shape-aware histogram autotuner (docs/HistogramRouting.md, ISSUE 13).

``hist_build`` owns most of tree-growth time (PERF.md section 5's table by
scope) — yet until this module the kernel that served it was picked by
ONE import-time env default. The bucketed grower actually emits histogram
calls at a *distribution* of shapes (the {2^k} ∪ {3·2^(k-1)} bucket lattice,
ops/grow.py ``bucket_sizes``), and the winner measurably differs per shape:
on this CPU box the static default (scatter) loses at EVERY lattice shape —
8.7x to ``xla`` at 512x16, 1.3x to ``xla_radix`` at 65536x256 — and the r5
on-silicon notes found the same class of inversion for small buckets.

This module closes the loop, the same move the reference makes by keeping a
family of histogram256.cl variants and selecting by workload (PAPER.md
layer 4):

 * :func:`sweep` — micro-bench every supported impl
   (ops/histogram.IMPLS, gated by ``impl_supported``; the Pallas
   contenders size their own row chunk to the chip's ``vmem_bytes``) at
   the exact bucket-shape distribution the grower emits, recording
   per-shape medians and the winner.
 * a persisted JSON cache (``save_table`` / ``load_table``) published
   through resil/atomic — a reader sees the old table or the new table,
   never a torn one; a digest over the entries detects tampering and a
   schema stamp makes stale caches REFUSE loudly instead of mis-routing.
 * :func:`active_table` — the adoption seam ``GBDT._setup_train`` calls to
   FREEZE the route for a run (param ``hist_tune`` > env
   ``LIGHTGBM_TPU_HIST_TUNE`` > nothing).

The CLI::

    python -m lightgbm_tpu.obs.tune --out TUNE_HIST.json \
        --rows 1048576 --bins 15,63,255 --features 28

Exactness: this module only MEASURES and WRITES; routing consumes the table
through the frozen ``HistRoute`` (ops/histogram.py), so nothing here can
perturb a training run in flight.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from ..utils import log
from ..utils.log import LightGBMError

#: bump when the table layout changes: a loaded table with a different
#: schema is REFUSED (never reinterpreted) — mis-parsed routing would
#: silently send shapes to the wrong kernel
SCHEMA = 1

ENV_PATH = "LIGHTGBM_TPU_HIST_TUNE"


# ---------------------------------------------------------------------------
# table build / digest / persistence
# ---------------------------------------------------------------------------

def entries_digest(entries: Sequence[Dict]) -> str:
    """Content digest over the routing-relevant entry fields — the value
    the flight manifest stamps, and the tamper check
    ``load_table`` verifies."""
    import hashlib

    canon = sorted(
        (int(e["B"]), int(e["K"]), str(e["hist_dtype"]),
         int(e["rows_bucket"]), str(e["impl"]))
        for e in entries
    )
    return hashlib.sha256(repr(canon).encode("utf-8")).hexdigest()[:16]


def build_table(
    entries: Sequence[Dict],
    backend: Optional[str] = None,
    device_kind: Optional[str] = None,
    device_family: Optional[str] = None,
    sweep_meta: Optional[Dict] = None,
) -> Dict:
    """Assemble a schema-stamped, digest-sealed table dict from entries
    (each ``{B, K, hist_dtype, rows_bucket, impl[, times_ms]}``). Shared by
    :func:`sweep` and the tests' hand-built tables (e.g. the tune smoke's
    default-pinned table), so every table in existence carries a valid
    digest."""
    if backend is None or device_family is None:
        from ..ops import histogram as hist_mod

        if backend is None:
            backend = hist_mod._default_backend()
        if device_family is None:
            device_family = hist_mod.device_family() or backend
    if device_kind is None:
        try:
            import jax

            device_kind = jax.devices()[0].device_kind
        except Exception:
            device_kind = ""
    try:
        import jax

        jax_version = getattr(jax, "__version__", "")
    except Exception:
        jax_version = ""
    ents = [dict(e) for e in entries]
    return {
        "schema": SCHEMA,
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": backend,
        "device_kind": device_kind,
        "device_family": device_family,
        "jax": jax_version,
        "digest": entries_digest(ents),
        "entries": ents,
        "sweep": dict(sweep_meta or {}),
    }


def save_table(table: Dict, path: str) -> str:
    """Atomically publish ``table`` at ``path`` (resil/atomic: temp +
    fsync + rename — a SIGKILL mid-write leaves the previous complete
    table, never a prefix). Returns ``path``."""
    from ..resil.atomic import atomic_write_text

    return atomic_write_text(
        path, json.dumps(table, indent=1, sort_keys=True) + "\n"
    )


def load_table(path: str) -> Dict:
    """Load + validate a tune table. Raises :class:`LightGBMError` on a
    missing/torn file, a stale schema, or a digest mismatch — a cache this
    function cannot vouch for must never route kernels."""
    try:
        with open(path) as fh:
            table = json.load(fh)
    except (OSError, ValueError) as e:
        raise LightGBMError(
            "histogram tune cache %s is unreadable: %s" % (path, e)
        )
    if not isinstance(table, dict) or table.get("schema") != SCHEMA:
        raise LightGBMError(
            "histogram tune cache %s has schema %r but this build expects "
            "%d; refusing stale routing — regenerate it with "
            "`python -m lightgbm_tpu.obs.tune --out %s`"
            % (path, table.get("schema") if isinstance(table, dict) else None,
               SCHEMA, path)
        )
    entries = table.get("entries")
    if not isinstance(entries, list):
        raise LightGBMError(
            "histogram tune cache %s carries no entries list" % path
        )
    want = table.get("digest")
    got = entries_digest(entries)
    if want != got:
        raise LightGBMError(
            "histogram tune cache %s failed its digest check (%s != %s) — "
            "hand-edited or corrupted tables must not route kernels; "
            "regenerate it" % (path, want, got)
        )
    return table


def active_table(param: str = "") -> Tuple[Optional[Dict], str]:
    """The tune table a training run should freeze, or (None, "").

    ``param`` is the ``hist_tune`` config value: an explicit path (load
    failures RAISE — the user asked for this table), ``"off"`` (disable
    even the env var), or ``""`` (consult ``LIGHTGBM_TPU_HIST_TUNE``;
    ambient adoption, so failures warn once and fall back to static
    routing instead of killing the run)."""
    param = (param or "").strip()
    if param.lower() == "off":
        return None, ""
    explicit = bool(param)
    path = param or os.environ.get(ENV_PATH, "").strip()
    if not path or path.lower() == "off":
        return None, ""
    try:
        return load_table(path), path
    except LightGBMError:
        if explicit:
            raise
        log.warn_once(
            "hist-tune-env-load:%s" % path,
            "LIGHTGBM_TPU_HIST_TUNE=%s could not be loaded; continuing "
            "with static histogram routing" % path,
        )
        return None, ""


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def sweep_shapes(
    n_rows: int,
    bins_list: Sequence[int],
    num_features: int,
    k: int = 3,
    dtypes: Sequence[str] = ("float32",),
) -> List[Dict]:
    """The shape set a training at this (rows, bins) geometry will emit:
    one shape per (bucket-lattice row class, B, dtype). Row classes come
    from the grower's own lattice (ops/grow.py ``bucket_sizes``) folded
    through ``rows_bucket`` so each swept row count IS its route key."""
    from ..ops.grow import bucket_sizes
    from ..ops.histogram import rows_bucket

    rows = sorted({rows_bucket(s) for s in bucket_sizes(int(n_rows))})
    return [
        {"rows": r, "B": int(b), "K": int(k), "F": int(num_features),
         "hist_dtype": str(d)}
        for d in dtypes
        for b in bins_list
        for r in rows
    ]


def candidate_impls(num_bins: int, backend: Optional[str] = None) -> List[str]:
    """The impls worth racing at a shape on this backend: supported
    (ops/histogram.impl_supported — the router's own vocabulary)."""
    from ..ops import histogram as hist_mod

    b = backend if backend is not None else hist_mod._default_backend()
    return [
        impl
        for impl in hist_mod.IMPLS
        if hist_mod.impl_supported(impl, num_bins, b)
    ]


def _time_impl(impl, bins, values, num_bins, chunk, hist_dtype, repeats):
    """Median wall seconds of a fully-dispatched leaf_histogram call (one
    untimed warmup run absorbs the XLA/Mosaic compile)."""
    import jax

    from ..ops.histogram import leaf_histogram

    def run():
        return leaf_histogram(
            bins, values, num_bins, chunk=chunk, impl=impl,
            hist_dtype=hist_dtype,
        )

    jax.block_until_ready(run())  # compile
    times = []
    for _ in range(max(int(repeats), 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(run())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def sweep(
    shapes: Sequence[Dict],
    repeats: int = 3,
    chunk: int = 16384,
    seed: int = 0,
) -> Dict:
    """Race every candidate impl at every shape; returns the table dict
    (save with :func:`save_table`).

    Each entry records the winner AND the per-impl medians (``times_ms``)
    so downstream gates — the tune smoke's "no slower anywhere, strictly
    faster somewhere" assertion — can audit the
    decision without re-measuring."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import histogram as hist_mod

    backend = hist_mod._default_backend()
    rng = np.random.RandomState(seed)
    entries: List[Dict] = []
    skipped: List[str] = []
    for sh in shapes:
        rows, B, K, F = (int(sh["rows"]), int(sh["B"]), int(sh["K"]),
                         int(sh["F"]))
        dt = str(sh["hist_dtype"])
        impls = candidate_impls(B, backend)
        if not impls:
            skipped.append("B=%d rows=%d (no supported impl)" % (B, rows))
            continue
        bins = jnp.asarray(rng.randint(0, B, (F, rows)).astype(np.uint8))
        vals = jnp.asarray(rng.randn(rows, K).astype(np.float32))
        times = {}
        for impl in impls:
            try:
                times[impl] = _time_impl(
                    impl, bins, vals, B, chunk, dt, repeats
                )
            except Exception as e:  # a contender that fails to lower loses
                log.warn_once(
                    "hist-tune-sweep-fail:%s:%d:%d" % (impl, B, rows),
                    "tune sweep: impl=%s failed at B=%d rows=%d (%s); "
                    "excluded from this shape's race"
                    % (impl, B, rows, str(e)[:200]),
                )
        if not times:
            skipped.append("B=%d rows=%d (every impl failed)" % (B, rows))
            continue
        winner = min(times, key=times.get)
        entries.append({
            "B": B, "K": K, "hist_dtype": dt,
            "rows_bucket": hist_mod.rows_bucket(rows), "rows": rows, "F": F,
            "impl": winner,
            "times_ms": {k: round(v * 1e3, 4) for k, v in times.items()},
        })
        # release the shape's buffers before the next allocation
        del bins, vals
    meta = {"repeats": int(repeats), "chunk": int(chunk), "seed": int(seed),
            "n_shapes": len(shapes)}
    if skipped:
        # never a silent cap: a table that skipped shapes says so
        meta["skipped"] = skipped
    return build_table(entries, backend=backend, sweep_meta=meta)


# ---------------------------------------------------------------------------
# CLI: python -m lightgbm_tpu.obs.tune
# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.obs.tune",
        description="Measure histogram kernels at the grower's bucket-shape "
        "distribution and persist the routing table "
        "(docs/HistogramRouting.md).",
    )
    ap.add_argument("--out", required=True, help="table path (atomic write)")
    ap.add_argument("--rows", type=int, default=1048576,
                    help="training row count whose bucket lattice to sweep")
    ap.add_argument("--bins", default="15,63,255",
                    help="comma-separated histogram widths (B) to sweep — "
                    "use the widths trainings actually emit (num_bin <= "
                    "max_bin: 255 for max_bin=255), NOT round powers of "
                    "two; route keys match exactly")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--k", type=int, default=3,
                    help="value channels (grad, hess, count)")
    ap.add_argument("--dtypes", default="float32",
                    help="comma-separated hist_dtype list "
                    "(float32[,bfloat16])")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=16384)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    bins_list = [int(b) for b in args.bins.split(",") if b]
    dtypes = [d for d in args.dtypes.split(",") if d]
    shapes = sweep_shapes(
        args.rows, bins_list, args.features, k=args.k, dtypes=dtypes
    )
    t0 = time.perf_counter()
    table = sweep(shapes, repeats=args.repeats, chunk=args.chunk,
                  seed=args.seed)
    save_table(table, args.out)
    winners: Dict[str, str] = {}
    for e in table["entries"]:
        winners["B=%d,dt=%s,rows=%d" % (e["B"], e["hist_dtype"],
                                        e["rows_bucket"])] = e["impl"]
    # one-line JSON result
    print(json.dumps({
        "ok": bool(table["entries"]),
        "path": args.out,
        "digest": table["digest"],
        "backend": table["backend"],
        "device_family": table["device_family"],
        "entries": len(table["entries"]),
        "sweep_s": round(time.perf_counter() - t0, 1),
        "winners": winners,
    }), flush=True)
    return 0 if table["entries"] else 1


if __name__ == "__main__":
    raise SystemExit(main())

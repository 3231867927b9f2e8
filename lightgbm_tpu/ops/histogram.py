"""Per-leaf gradient/hessian histogram construction.

TPU-native replacement for the reference's histogram kernels — the CPU scatter-add
loops (DenseBin::ConstructHistogram, /root/reference/src/io/dense_bin.hpp:71-167) and
the OpenCL workgroup kernels (src/treelearner/ocl/histogram256.cl). TPUs have no fast
atomics, so the scatter-add becomes a chunked one-hot contraction that XLA maps onto
the MXU/VPU: for each row-chunk, ``onehot(bins) @ [grad*mask, hess*mask, mask]``
accumulated over chunks with ``lax.scan``.

The histogram layout is ``[num_features, num_bins, 3]`` float32 with channels
(sum_grad, sum_hess, count) — the dtype-native analogue of the reference's
20-byte HistogramBinEntry {double, double, int32} (bin.h:33-62). float32
accumulation follows the reference's GPU path, which demonstrates AUC parity with
single-precision accumulators (docs/GPU-Performance.rst:131-145).

``leaf_histogram`` dispatches at trace time, in precedence order:

  1. an explicit ``impl=`` argument (tests, chip_smoke.py's kernel phase);
  2. the ``LIGHTGBM_TPU_HIST_IMPL`` env escape hatch (frozen at import);
  3. a frozen per-run :class:`HistRoute` — the measured, shape-keyed tune
     table (obs/tune.py sweep, persisted via resil/atomic, frozen at
     ``GBDT._setup_train``; docs/HistogramRouting.md);
  4. the static backend default (:func:`default_impl`): the chunked one-hot
     contraction on TPU (faster than the pallas v1 kernel at the full-N
     shape in the one 2026-07-31 measurement, PERF.md §Before this round;
     on a TPU its default-precision MXU pass cuts grad/hess to bf16 — the
     2^-8 operand rounding chip_smoke.py measures — whatever ``hist_dtype``
     says), the chunked scatter-add on CPU.

The route is a pure function of the call shape and the frozen table, and it
rides the jit static args — so routing is deterministic for a training run
and every exactness contract (chunk=1-vs-K, segmented-vs-fused, sharded,
checkpoint resume) holds *within* a run by construction.
"""
from __future__ import annotations

import functools
import hashlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hist_pallas
from ..utils import log


class HistogramSource:
    """Partial-histogram accumulation seam (ROADMAP items 1 + 5).

    A histogram — or any reduction that is linear across row shards, like
    the root grad/hess/count sums — may arrive in PARTIALS: one per mesh
    shard today (the data-parallel learner), one per streamed row shard in
    the out-of-core engine. ``combine(partial)`` turns a shard's partial
    into the total; exactly one implementation exists per distribution
    mechanism, so every consumer (the ``leaf_histogram`` tail, the grower's
    post-bucket-switch collective, the root sums) spells accumulation the
    same way. Instances are value-hashable so they can ride jit statics.

    ``is_collective`` tells observability (obs/dist.py) whether a combine
    moves bytes across devices, and :meth:`payload_bytes` is the per-call
    collective payload estimate — the partial's own size, since psum ships
    (and receives) one operand-sized buffer per participant.
    """

    #: True when combine() lowers to a cross-device collective (psum)
    is_collective = False

    def combine(self, partial):
        raise NotImplementedError

    @staticmethod
    def payload_bytes(shape, dtype_itemsize: int = 4) -> int:
        """Estimated bytes one combine() call moves per participant: the
        partial's size (0 payload for non-collective sources, whose
        combine is the identity — callers should gate on is_collective).
        Cross-checked against live array nbytes in tests."""
        n = 1
        for d in shape:
            n *= int(d)
        return n * int(dtype_itemsize)


class LocalHistogramSource(HistogramSource):
    """Single-shard: the partial IS the total."""

    def combine(self, partial):
        return partial

    def __eq__(self, other):
        return type(other) is LocalHistogramSource

    def __hash__(self):
        return hash(LocalHistogramSource)


class MeshHistogramSource(HistogramSource):
    """Mesh-sharded partials: ONE psum over the named axis — the
    data-parallel learner's ReduceScatter of HistogramBinEntry
    (data_parallel_tree_learner.cpp:161) collapsed into an XLA collective
    over ICI."""

    is_collective = True

    def __init__(self, axis_name: str) -> None:
        self.axis_name = axis_name

    def combine(self, partial):
        return jax.lax.psum(partial, self.axis_name)

    def __eq__(self, other):
        return (
            type(other) is MeshHistogramSource
            and other.axis_name == self.axis_name
        )

    def __hash__(self):
        return hash((MeshHistogramSource, self.axis_name))


class StreamAccumHistogramSource(HistogramSource):
    """Streamed partials (ROADMAP item 5, the out-of-core engine): a host
    loop feeds ``add(partial)`` once per streamed row shard; ``total()``
    is the running sum. ``combine`` is the identity — a streamed shard's
    partial is combined by repeated addition, not by a collective — so a
    grower fed one shard at a time composes with the same seam the mesh
    path uses."""

    def __init__(self) -> None:
        self._acc = None

    def combine(self, partial):
        return partial

    def add(self, partial):
        self._acc = partial if self._acc is None else self._acc + partial
        return self._acc

    def total(self):
        return self._acc

    def reset(self) -> None:
        self._acc = None


_SOURCES = {None: LocalHistogramSource()}


def histogram_source(axis_name: Optional[str]) -> HistogramSource:
    """The process-wide HistogramSource for a mesh axis (None = local)."""
    src = _SOURCES.get(axis_name)
    if src is None:
        src = _SOURCES[axis_name] = MeshHistogramSource(axis_name)
    return src


def _combine(hist, axis_name):
    """Shared cross-shard combine tail of every leaf_histogram impl — the
    data-parallel ReduceScatter analogue lives in exactly one place
    (the HistogramSource seam above)."""
    return histogram_source(axis_name).combine(hist)


def _default_backend() -> str:
    """The process-default backend. A backend that fails to initialise
    raises here: it must never be mistaken for a CPU and routed as one."""
    return jax.default_backend()


# The full impl vocabulary leaf_histogram can route among. "pallas_packed4"
# is the nibble-packed (two 4-bit bins per byte) MXU kernel — promoted from
# measurement-only into the routed set for <=16-bin shapes (ISSUE 13).
# ISSUE 17 adds the wide-bin MXU family: "xla_onehot" (the one-hot-as-LHS
# pure-XLA contraction, CPU-measurable and the differential oracle for the
# Pallas twins), "pallas_onehot" (dense one-hot tile, B-tiled at 128), and
# "pallas_bitplane" (bit-plane-factored one-hots, the low-VMEM contender at
# B=255).
IMPLS = (
    "xla", "xla_onehot", "xla_radix", "scatter",
    "pallas", "pallas_onehot", "pallas_bitplane", "pallas_packed4",
)

# The impls that lower everywhere at any B: plain XLA programs with no
# kernel shape constraints. Everything else is a Pallas kernel whose bounds
# live in hist_pallas.KERNEL_CAPS — impl_supported() below is the union of
# the two tables and never special-cases an individual kernel name.
_XLA_IMPLS = frozenset(("xla", "xla_onehot", "xla_radix", "scatter"))

# Resolved ONCE at import so routing is deterministic per process: leaf_histogram
# is jitted with impl as a static arg, and an env var read at trace time would
# silently keep stale routing for already-compiled shapes if it changed later.
# Set LIGHTGBM_TPU_HIST_IMPL before importing lightgbm_tpu.
from ..utils.platform import env_choice

_ENV_IMPL = env_choice("LIGHTGBM_TPU_HIST_IMPL", IMPLS)


def default_impl(backend: Optional[str] = None) -> str:
    """The static routing default a shape falls to with no explicit impl,
    env override, or tune-table entry: the scatter-add on CPU (F*N adds vs
    the one-hot's 2*F*N*B flops), the MXU one-hot contraction elsewhere."""
    b = backend if backend is not None else _default_backend()
    return "scatter" if b == "cpu" else "xla"


def impl_supported(
    impl: str,
    num_bins: int,
    backend: Optional[str] = None,
    ignore_backend: bool = False,
) -> bool:
    """Can ``impl`` serve a ``num_bins``-wide histogram on ``backend``?

    The ONE supported() vocabulary the router, the tune sweep (obs/tune.py)
    and the table-load filter (:func:`resolve_route`) share, so a table can
    never route a shape to a kernel that cannot lower there. Pure-XLA impls
    lower everywhere; Pallas impls consult the hist_pallas.KERNEL_CAPS
    capability table — no per-kernel special cases here."""
    if impl in _XLA_IMPLS:
        return True
    if impl in hist_pallas.KERNEL_CAPS:
        return hist_pallas.kernel_supported(
            impl, num_bins, backend, ignore_backend
        )
    return False


def rows_bucket(n: int) -> int:
    """Shape-class row bucket: ``n`` rounded UP to the grower's bucket
    lattice family {2^k} ∪ {3·2^(k-1)} (ops/grow.py bucket_sizes). The
    bucketed grower only ever calls leaf_histogram at lattice sizes, so on
    those calls the bucket IS the call shape; full-N calls (root, masked
    mode) round up to the nearest class."""
    n = max(int(n), 1)
    k = (n - 1).bit_length()  # smallest k with 2^k >= n
    p = 1 << k
    t = 3 << (k - 2) if k >= 2 else p  # 3*2^(k-2) == 0.75 * 2^k
    return t if t >= n else p


class HistRoute:
    """Frozen shape-class -> impl routing table for ONE training run.

    Built once from a measured tune table (obs/tune.py) at
    ``GBDT._setup_train`` and threaded as a jit STATIC argument through
    ``grow_tree`` / ``make_bucket_kernels`` / ``leaf_histogram`` — the route
    is a pure function of (call shape, this frozen object), so a tune cache
    rewritten mid-process can never change an already-set-up run, and every
    compiled program's identity includes the table it routed under.

    ``entries`` maps ``(B, K, hist_dtype, rows_bucket)`` -> impl name;
    hashable/comparable by value so jit caches key correctly. ``digest`` is
    the content digest the flight manifest records (docs/HistogramRouting.md).
    """

    __slots__ = ("entries", "digest", "source", "_map")

    def __init__(
        self,
        entries,
        source: str = "",
    ) -> None:
        ent: Tuple = tuple(sorted(
            ((int(b), int(k), str(d), int(r)), str(impl))
            for (b, k, d, r), impl in entries
        ))
        self.entries = ent
        self._map = dict(ent)
        if len(self._map) != len(ent):
            # duplicate shape classes with CONFLICTING impls (e.g. two sweep
            # outputs merged by hand): routing would silently follow sort
            # order instead of a measurement, and two semantically-equal
            # tables could carry different digests — refuse loudly
            dupes = sorted(
                {k for k, v in ent if self._map[k] != v}
            )
            if dupes:
                from ..utils.log import LightGBMError

                raise LightGBMError(
                    "histogram route has conflicting impls for shape "
                    "class(es) %s — merge tables by re-sweeping, not by "
                    "concatenating entries" % (dupes,)
                )
            # exact duplicates: deduplicate so the digest is canonical
            ent = tuple(sorted(self._map.items()))
            self.entries = ent
        self.source = str(source)
        self.digest = hashlib.sha256(repr(ent).encode("utf-8")).hexdigest()[:16]

    def pick(
        self, rows: int, num_bins: int, k: int, hist_dtype: str
    ) -> Optional[str]:
        """Impl for this call shape, or None (-> the static default)."""
        return self._map.get(
            (int(num_bins), int(k), str(hist_dtype), rows_bucket(rows))
        )

    def rows_variant(self, default: str) -> bool:
        """Shape-blind conservative check: True when ANY entry routes away
        from ``default``. Callers that know the run's geometry should use
        :func:`route_effective_impls` / the shape-aware
        :func:`route_rows_variant` instead — an entry whose (B, K, dtype)
        class this run can never emit must not cost it spec mode."""
        return any(v != default for v in self._map.values())

    def effective_impls(
        self, default: str, num_bins: int, k: int, hist_dtype: str,
        row_buckets,
    ) -> set:
        """The set of impls the given row-bucket classes of ONE (B, K,
        dtype) group resolve to — classes without an entry fall back to
        ``default``."""
        return {
            self._map.get(
                (int(num_bins), int(k), str(hist_dtype), int(rb)), default
            )
            for rb in row_buckets
        }

    def __eq__(self, other) -> bool:
        return type(other) is HistRoute and other.entries == self.entries

    def __hash__(self) -> int:
        return hash((HistRoute, self.entries))

    def __repr__(self) -> str:
        return "HistRoute(%d entries, digest=%s%s)" % (
            len(self.entries), self.digest,
            ", source=%r" % self.source if self.source else "",
        )


def route_effective_impls(
    route: Optional[HistRoute],
    num_bins: int,
    hist_dtype: str,
    n_rows: int,
    k: int = 3,
) -> set:
    """The set of impls a run at this geometry actually resolves to: its
    reachable row-bucket classes (the grower's bucket lattice for
    ``n_rows``, ops/grow.py ``bucket_sizes``) looked up in the route's
    (``num_bins``, ``k``, ``hist_dtype``) group, defaulting per class.
    ``{default_impl()}`` when the route is absent or env-overridden."""
    if route is None or _ENV_IMPL:
        return {default_impl()}
    from .grow import bucket_sizes  # lazy: grow imports this module

    buckets = {rows_bucket(s) for s in bucket_sizes(int(n_rows))}
    return route.effective_impls(
        default_impl(), num_bins, k, hist_dtype, buckets
    )


def route_rows_variant(
    route: Optional[HistRoute],
    num_bins: Optional[int] = None,
    hist_dtype: Optional[str] = None,
    n_rows: Optional[int] = None,
    k: int = 3,
) -> bool:
    """Does ``route`` make the effective impl depend on the row bucket?

    The spec-mode gate (ops/grow.py ``spec_batch_slots``): the speculative
    grower histograms a candidate batch at the batch-max bucket size while
    the W=1 pass uses each segment's own bucket — a route whose impl choice
    VARIES across the run's reachable bucket classes would let the SAME
    logical segment take different impls in the two, so the speculative
    and the sequential grower could grow different trees. Such a route
    runs the sequential grower; a route that resolves every reachable
    class to ONE impl (the default, or uniformly any single kernel) is
    self-consistent and leaves spec mode on. With the run geometry
    (``num_bins``/``hist_dtype``/``n_rows``) the check is exact — entries
    for unreachable (B, dtype) groups cost nothing; without it,
    conservatively shape-blind. With LIGHTGBM_TPU_HIST_IMPL in force the
    route never engages (env precedence), so it cannot introduce
    variance."""
    if route is None or _ENV_IMPL:
        return False
    if num_bins is None or hist_dtype is None or n_rows is None:
        return route.rows_variant(default_impl())
    return len(
        route_effective_impls(route, num_bins, hist_dtype, n_rows, k)
    ) > 1


def resolve_route(
    table: Optional[dict], source: str = ""
) -> Optional[HistRoute]:
    """Tune-table dict (obs/tune.py schema) -> frozen :class:`HistRoute`.

    Filters to THIS process's backend + device family and drops entries
    whose impl cannot serve their shape here (``impl_supported``) — a table
    measured on a TPU never routes a CPU run and vice versa. Returns None
    when nothing survives (callers then use the static default)."""
    if not table or not table.get("entries"):
        return None
    backend = _default_backend()
    if table.get("backend") != backend:
        log.warn_once(
            "hist-tune-backend-mismatch",
            "histogram tune table %s was measured on backend=%r but this "
            "process runs %r; ignoring it (static default routing applies)"
            % (source or "<dict>", table.get("backend"), backend),
        )
        return None
    fam = device_family()
    tfam = table.get("device_family")
    if tfam and fam and tfam != fam:
        log.warn_once(
            "hist-tune-device-mismatch",
            "histogram tune table %s was measured on device family %r but "
            "this process runs %r; ignoring it"
            % (source or "<dict>", tfam, fam),
        )
        return None
    if tfam and fam is None and tfam != backend:
        # this chip's family is UNRECOGNIZED (normalize_device_kind knows
        # no name for it) while the table names a concrete family from
        # another generation — adopting stale winners silently would
        # violate the "v5e never routes v6e" contract. A table measured on
        # an equally-unrecognized chip records its backend as the family
        # (build_table fallback) and still matches above.
        log.warn_once(
            "hist-tune-unknown-device",
            "histogram tune table %s was measured on device family %r but "
            "this chip's family is unrecognized; ignoring it (re-sweep on "
            "this chip to adopt measured routing)" % (source or "<dict>",
                                                      tfam),
        )
        return None
    ents = []
    for e in table["entries"]:
        impl = str(e.get("impl", ""))
        b = int(e["B"])
        if impl not in IMPLS or not impl_supported(impl, b, backend):
            log.warn_once(
                "hist-tune-unsupported:%s:%d" % (impl, b),
                "histogram tune entry (B=%d impl=%r) is not supported on "
                "this backend/shape; dropping it from the route" % (b, impl),
            )
            continue
        ents.append(
            ((b, int(e["K"]), str(e["hist_dtype"]), int(e["rows_bucket"])),
             impl)
        )
    if not ents:
        return None
    return HistRoute(ents, source=source)


def device_family() -> Optional[str]:
    """This process's normalized chip family (obs/costs.py's ONE device-kind
    vocabulary) — the tune table's device key, so a cache written on v5e is
    never adopted on v6e."""
    from ..obs.costs import normalize_device_kind

    return normalize_device_kind(jax.devices()[0].device_kind)


def _note_impl_fallback(requested: str, num_bins: int) -> None:
    """A forced impl (explicit, env, or a tune entry) that cannot serve this
    shape falls back to the XLA one-hot — loudly, once per (impl, B), and
    counted so bench artifacts surface how often routing degraded."""
    log.warn_once(
        "hist-impl-fallback:%s:%d" % (requested, num_bins),
        "impl=%r requested (explicitly, via LIGHTGBM_TPU_HIST_IMPL, or a "
        "tune-table entry) but that kernel does not support num_bins=%d; "
        "falling back to the XLA one-hot implementation"
        % (requested, num_bins),
    )
    from ..obs.registry import REGISTRY

    REGISTRY.counter(
        "hist_impl_fallback_total",
        "leaf_histogram impl requests that fell back to the XLA one-hot",
    ).inc(requested=requested)


def _pick_chunk(num_features: int, num_bins: int, requested: int, n: int) -> int:
    """Bound the transient one-hot tensor to ~64MB of f32, and never exceed
    the row count itself: N is padded UP to a chunk multiple, so a chunk
    larger than N would multiply the work of every small-bucket pass (the
    majority of per-split histograms in bucketed mode) by chunk/N."""
    budget = 64 * 1024 * 1024 // 4
    c = budget // max(num_features * num_bins, 1)
    n_ceil = -(-n // 256) * 256
    c = max(256, min(int(c), requested, n_ceil))
    # round down to a multiple of 256 for clean tiling
    return max(256, (c // 256) * 256)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_bins", "chunk", "axis_name", "impl", "hist_dtype", "feature_sharded",
        "route", "interpret",
    ),
)
def leaf_histogram(
    bins: jax.Array,
    values: jax.Array,
    num_bins: int,
    chunk: int = 4096,
    axis_name: Optional[str] = None,
    impl: str = "auto",
    hist_dtype: str = "float32",
    feature_sharded: bool = False,
    route: Optional[HistRoute] = None,
    interpret: bool = False,
) -> jax.Array:
    """Histogram of per-row values over binned features.

    Args:
      bins: ``[F, N]`` integer bin matrix (uint8/int32). N must be a multiple of
        the chunk size actually used (pad rows with value-0 masked entries).
      values: ``[N, K]`` float32 per-row accumulands; K is typically 3 for
        (grad*mask, hess*mask, mask). Rows outside the leaf must already be
        zeroed via the mask.
      num_bins: histogram width B (padded max over features).
      axis_name: if set, psum the result over that mesh axis (the data-parallel
        ReduceScatter path of data_parallel_tree_learner.cpp:161 collapsed into
        one XLA collective).
      impl: "auto" (env override -> frozen ``route`` -> the backend default,
        see the module banner), "pallas", "pallas_onehot" (dense one-hot
        tile, B <= 256), "pallas_bitplane" (bit-plane-factored one-hots,
        B <= 256), "pallas_packed4" (nibble-packed MXU kernel, B <= 16),
        "scatter", "xla" (the one-hot contraction — also the differential
        oracle for the others), "xla_onehot" (the one-hot-as-LHS
        contraction, the pure-XLA twin of pallas_onehot), or "xla_radix"
        (the radix factorization in plain XLA).
      hist_dtype: MXU operand dtype for the pallas kernels and the XLA
        one-hot/radix contractions — "float32" (exact) or "bfloat16"
        (rounds grad/hess operands; the one-hot side and the count channel
        are exact 0/1 values, and accumulation stays f32 via
        preferred_element_type — the reference GPU path's single-precision
        trade, docs/GPU-Performance.rst:131-145).
      route: frozen per-run :class:`HistRoute` (the measured tune table);
        consulted only for ``impl="auto"`` with no env override, keyed on
        this call's actual (rows, B, K, dtype) shape class at trace time.
      interpret: run the pallas kernels in interpret mode (differential
        tests off-TPU; never set on the training path).

    Returns:
      ``[F, B, K]`` float32 histogram.
    """
    if impl == "auto" and _ENV_IMPL:
        impl = _ENV_IMPL
    if impl == "auto" and route is not None:
        picked = route.pick(
            bins.shape[1], num_bins, values.shape[1], hist_dtype
        )
        if picked is not None:
            impl = picked
    if impl in hist_pallas.KERNEL_CAPS and not impl_supported(
        impl, num_bins, ignore_backend=True
    ):
        # A forced pallas impl must still satisfy the kernel's shape
        # constraints (num_bins bound from the VMEM block rules / nibble
        # width / bin-tile caps) or it would mis-lower instead of falling
        # back. One generic gate over the capability table — every Pallas
        # impl gets the warn_once + fallback-counter path.
        _note_impl_fallback(impl, num_bins)
        impl = "xla"
    if impl == "pallas":
        hist = hist_pallas.histogram_pallas(
            bins, values, num_bins, chunk=max(chunk, 512),
            dtype_name=hist_dtype, interpret=interpret,
        )
        return _combine(hist, axis_name)
    if impl == "pallas_onehot":
        hist = hist_pallas.histogram_pallas_onehot(
            bins, values, num_bins, chunk=max(chunk, 512),
            dtype_name=hist_dtype, interpret=interpret,
        )
        return _combine(hist, axis_name)
    if impl == "pallas_bitplane":
        hist = hist_pallas.histogram_pallas_bitplane(
            bins, values, num_bins, chunk=max(chunk, 512),
            dtype_name=hist_dtype, interpret=interpret,
        )
        return _combine(hist, axis_name)
    if impl == "pallas_packed4":
        # nibble packing happens inside the jit: [F, N] u8 + [N, K] f32 ->
        # ([F, N/2] u8, [N/2, 2K] f32) is a cheap vectorized relayout that
        # halves the bin-matrix HBM stream the kernel reads
        bins_p, vals_p = hist_pallas.pack4(bins, values)
        hist = hist_pallas.histogram_pallas_packed4(
            bins_p, vals_p, num_bins, chunk=max(chunk // 2, 512),
            dtype_name=hist_dtype, interpret=interpret,
        )
        return _combine(hist, axis_name)
    if impl == "auto" and _default_backend() == "tpu":
        # The STATIC fallback for shapes with no tune entry: the one-hot
        # contraction measured fastest at the full-N 1Mx28x255 pass on
        # v5e-1 (16.8 ms vs pallas v1's 34.8 ms, 2026-07-31 — PERF.md
        # §Before this round). Shapes a tune sweep has measured route
        # through the frozen HistRoute above instead (obs/tune.py,
        # docs/HistogramRouting.md).
        impl = "xla"
    if impl == "scatter" or (impl == "auto" and _default_backend() == "cpu"):
        # CPU: a scatter-add is the dense_bin.hpp:71 loop XLA can actually run
        # well — F*N adds instead of the one-hot contraction's 2*F*N*B flops
        # (B× waste). TPU keeps the MXU paths: scatter lowers poorly there.
        F, N = bins.shape
        K = values.shape[1]
        if not feature_sharded:
            # One scatter per feature via lax.scan: a flat [F*N, K] scatter
            # forces XLA to materialize the broadcast update tensor (F copies
            # of values — 33MB at the 100k bench shape), while the per-feature
            # form scatters the shared [N, K] values into an L2-resident
            # [B, K] accumulator (2-9x faster measured at N=16k..100k).
            def body(carry, b_f):
                return carry, jnp.zeros((num_bins, K), jnp.float32).at[
                    b_f.astype(jnp.int32)
                ].add(values)

            _, hist = jax.lax.scan(body, 0, bins)
        else:
            # Feature-sharded bins (the GSPMD feature-parallel learner): a
            # scan over the feature axis would force an all-gather of the bin
            # matrix, so chunk over rows instead and keep features vectorized
            # — each shard scatters only its own features.
            C = (64 * 1024 * 1024 // 4) // max(F * (K + 1), 1)
            C = max(256, min((C // 256) * 256, N))
            if N % C != 0:
                pad = (-N) % C
                bins = jnp.pad(bins, ((0, 0), (0, pad)))
                values = jnp.pad(values, ((0, pad), (0, 0)))
                N += pad
            n_chunks = N // C
            offs = (jnp.arange(F, dtype=jnp.int32) * num_bins)[:, None]
            bins_c = bins.reshape(F, n_chunks, C).transpose(1, 0, 2)  # [n, F, C]
            vals_c = values.reshape(n_chunks, C, K)

            def body(acc, inputs):
                b, v = inputs  # [F, C], [C, K]
                idx = (b.astype(jnp.int32) + offs).reshape(-1)
                upd = jnp.broadcast_to(v[None], (F, C, K)).reshape(F * C, K)
                return acc.at[idx].add(upd), None

            init = jnp.zeros((F * num_bins, K), jnp.float32)
            hist, _ = jax.lax.scan(body, init, (bins_c, vals_c))
            hist = hist.reshape(F, num_bins, K)
        return _combine(hist, axis_name)
    F, N = bins.shape
    K = values.shape[1]
    B = num_bins
    op_dtype = jnp.bfloat16 if hist_dtype == "bfloat16" else jnp.float32

    if impl == "xla_radix":
        # The Pallas kernel's radix factorization (hist_pallas.py module
        # banner) expressed in plain XLA for the routing bake-off: the
        # [F, C, B] one-hot operand shrinks to [F, C, LO*K] (x) [F, C, HI],
        # an ~8x better MXU row fill and ~5x less one-hot build work, with
        # XLA free to fuse/layout. Same default-precision behavior as the
        # plain one-hot contraction below (bf16 operand rounding on TPU).
        LO = 8
        HI = -(-B // LO)
        # chunk sized for THIS path's transients ([F, C, LO*K+HI], not the
        # one-hot's [F, C, B]) — the B-based budget would undersize C ~4x
        # and handicap the very contender this branch exists to race
        C = _pick_chunk(F, LO * K + HI, chunk, N)
        if N % C != 0:
            pad = (-N) % C
            bins = jnp.pad(bins, ((0, 0), (0, pad)))
            values = jnp.pad(values, ((0, pad), (0, 0)))
            N += pad
        n_chunks = N // C
        bins_c = bins.reshape(F, n_chunks, C).transpose(1, 0, 2)  # [n, F, C]
        vals_c = values.reshape(n_chunks, C, K)  # [n, C, K]
        lo_iota = jnp.arange(LO, dtype=jnp.int32)
        hi_iota = jnp.arange(HI, dtype=jnp.int32)

        def body_rx(acc, inputs):
            b, v = inputs  # [F, C], [C, K]
            bi = b.astype(jnp.int32)
            hi = bi // LO
            lo = bi - hi * LO
            oh_lo = (lo[:, :, None] == lo_iota[None, None, :]).astype(op_dtype)
            lhs = (oh_lo[:, :, :, None] * v.astype(op_dtype)[None, :, None, :]).reshape(
                F, C, LO * K
            )
            oh_hi = (hi[:, :, None] == hi_iota[None, None, :]).astype(op_dtype)
            part = jax.lax.dot_general(
                lhs, oh_hi,
                dimension_numbers=(((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [F, LO*K, HI]
            return acc + part, None

        init = jnp.zeros((F, LO * K, HI), jnp.float32)
        out, _ = jax.lax.scan(body_rx, init, (bins_c, vals_c))
        # out[f, lo*K + k, hi] -> hist[f, hi*LO + lo, k]
        hist = (
            out.reshape(F, LO, K, HI)
            .transpose(0, 3, 1, 2)
            .reshape(F, HI * LO, K)[:, :B, :]
        )
        return _combine(hist, axis_name)

    if impl == "xla_onehot":
        # The one-hot-as-LHS formulation (ISSUE 17): hist[f] =
        # onehot(bins_f) @ values — [B, C] one-hot tiles contracted against
        # the shared [C, K] stat matrix, scanned feature-by-feature (and
        # chunk-by-chunk within a feature). The transposed twin of the
        # batched [F, C, B] contraction below: one 2-D MXU matmul per
        # (feature, chunk) with the one-hot as the streamed operand, the
        # same dataflow the pallas_onehot kernel tiles in VMEM — this branch
        # is its CPU-measurable differential oracle.
        C = _pick_chunk(1, B, chunk, N)
        if N % C != 0:
            pad = (-N) % C
            bins = jnp.pad(bins, ((0, 0), (0, pad)))
            values = jnp.pad(values, ((0, pad), (0, 0)))
            N += pad
        n_chunks = N // C
        bins_c = bins.reshape(F, n_chunks, C)  # [F, n, C]
        vals_c = values.reshape(n_chunks, C, K)  # [n, C, K]
        iota = jnp.arange(B, dtype=jnp.int32)

        def body_oh(carry, b_f):  # b_f: [n, C]
            def chunk_oh(acc, inputs):
                b, v = inputs  # [C], [C, K]
                oh = (iota[:, None] == b.astype(jnp.int32)[None, :]).astype(
                    op_dtype
                )  # [B, C]
                return acc + jax.lax.dot_general(
                    oh, v.astype(op_dtype),
                    dimension_numbers=(((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ), None

            h, _ = jax.lax.scan(
                chunk_oh, jnp.zeros((B, K), jnp.float32), (b_f, vals_c)
            )
            return carry, h

        _, hist = jax.lax.scan(body_oh, 0, bins_c)  # [F, B, K]
        return _combine(hist, axis_name)

    C = _pick_chunk(F, B, chunk, N)
    if N % C != 0:
        pad = (-N) % C
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, pad), (0, 0)))
        N += pad
    n_chunks = N // C

    bins_c = bins.reshape(F, n_chunks, C).transpose(1, 0, 2)  # [n, F, C]
    vals_c = values.reshape(n_chunks, C, K)  # [n, C, K]

    def body(acc, inputs):
        b, v = inputs  # [F, C], [C, K]
        return acc + onehot_chunk_partial(b, v, B, op_dtype), None

    init = jnp.zeros((F, B, K), dtype=jnp.float32)
    hist, _ = jax.lax.scan(body, init, (bins_c, vals_c))
    return _combine(hist, axis_name)


def onehot_chunk_partial(b, v, num_bins, op_dtype=jnp.float32):
    """One chunk's one-hot contraction: [F, C] bins x [C, K] values ->
    [F, B, K] partial histogram, f32-accumulated on the MXU.

    THE shared accumulation body of the XLA one-hot impl above and the
    spec-mode flat batched histogram (ops/grow.py segment_histogram_flat):
    the flat path's bitwise-equality-with-sequential guarantee requires the
    two to be byte-identical, so there is exactly one copy."""
    iota = jnp.arange(num_bins, dtype=jnp.int32)
    onehot = (b.astype(jnp.int32)[:, :, None] == iota[None, None, :]).astype(op_dtype)
    return jax.lax.dot_general(
        onehot,
        v.astype(op_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def leaf_values(
    grad: jax.Array, hess: jax.Array, mask: jax.Array
) -> jax.Array:
    """Stack (grad, hess, 1) * mask into the [N, 3] accumuland matrix."""
    m = mask.astype(jnp.float32)
    return jnp.stack([grad * m, hess * m, m], axis=1)


def histogram_reference(bins: np.ndarray, values: np.ndarray, num_bins: int) -> np.ndarray:
    """Numpy oracle for tests (mirrors dense_bin.hpp:71-167 accumulation order-free)."""
    F, N = bins.shape
    K = values.shape[1]
    out = np.zeros((F, num_bins, K), dtype=np.float64)
    for f in range(F):
        for k in range(K):
            np.add.at(out[f, :, k], bins[f].astype(np.int64), values[:, k])
    return out.astype(np.float32)

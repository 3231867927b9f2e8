"""Pallas TPU histogram kernel: VMEM-resident gradient/hessian accumulators.

The TPU-native replacement for the reference's histogram engines — the CPU
scatter-add loops (/root/reference/src/io/dense_bin.hpp:71-167) and the OpenCL
workgroup kernels (/root/reference/src/treelearner/ocl/histogram256.cl:350-363).
TPUs have no fast atomics and no per-lane scatter, so the scatter-add is
reformulated as a matmul the MXU can run, with the accumulator block resident
in VMEM across the row-chunk grid (the analogue of the OpenCL kernel's
workgroup-local shared-memory sub-histograms).

Why not the plain one-hot contraction (ops/histogram.py)? It contracts K=3
channels against a B-wide one-hot, three useful rows of an MXU pass. This
kernel uses a *radix factorization*:

    bin = hi * LO + lo          (LO = 8, HI = ceil(B / 8))

    hist[f, hi*LO + lo, k] = sum_i 1[hi_i = hi] * v[i, k] * 1[lo_i = lo]

The routed kernel (``_kernel_fb``, feature-batched) feeds that to the MXU
with the rows on the lanes of both operands:

      oh_hi [HI, C]       bf16: 1 where row i's high digit is hi
      vlo   [P*K*LO, C]   bf16: row (p, k, lo) = piece p of v[i, k] where
                                row i's low digit is lo, else 0
      OUT   [HI, P*K*LO]  = oh_hi . vlo^T over the rows, accumulated in f32;
                          the wrapper adds the P pieces and reshapes to
                          [B, K].

Exactness comes from the split of the values, not from a matmul precision:
a float32 operand (``dtype=float32``) is cut once a grid step into P = 3
bfloat16 pieces that sum back to it bit for bit (``split_bf16``: 24
significand bits = 8 + 8 + 8), the one-hots are exact 0/1 in bfloat16, so
ONE single-pass bf16 contraction forms the very products a
``Precision.HIGHEST`` float32 dot would (piece x 0/1), and they are
accumulated in float32. ``dtype=bfloat16`` is the one-piece case of the same
body: it rounds the grad/hess operand to bf16 before the MXU (accumulation
stays f32) — the single-precision-accumulator trade the reference's GPU path
makes and validates for AUC parity
(/root/reference/docs/GPU-Performance.rst:131-145).

Why the rows sit on the lanes and the one-hot is the streamed operand (one
v5e, 2000 features x 255 bins; PERF.md §6, PR 26): a ``[C, HI]`` one-hot
with the rows on the sublanes uses 32 of 128 lanes and needs a
lane-to-sublane relayout of every row's high digit; building it, not the
MXU's passes, bound a ``Precision.HIGHEST`` body at 0.77 ns a row and
feature (a single pass over the same operands won 8%). Lane-dense operands
read 0.117 ns with the values streamed and 0.066 with the one-hot streamed.

Grid: (F/FB, N/C). The output block index map pins each feature batch's
accumulator to the same VMEM block across all row chunks, so partial
histograms never round-trip through HBM (pallas revisiting semantics).
Inputs stream: bins [FB, C] u8 and the shared values [K, C] f32 per step;
a step works through its block ``SUB`` = 512 rows at a time, one dot a
feature and sub-block, so a segment's float32 additions are the same at
every block size. The rows may be several segments laid end to end
(``histogram_pallas_slots``, the speculative grower's batch): a per-chunk
slot table, a scalar-prefetch operand, picks the accumulator block of each
chunk, and the one-segment pass is the case of one slot.
The per-feature-grid v1 kernel (``histogram_pallas_v1``) keeps the older
orientation and ``Precision.HIGHEST``; it is a differential oracle only.

ISSUE 17 adds two wide-bin siblings, both feature-batched like the v2 radix
kernel and registered as first-class routing contenders:

- ``histogram_pallas_onehot``: the dense formulation, B-tiled — grid
  (F/FB, B/BT, N/C) with BT=128, one [C, 128] one-hot slab per bin tile, so
  the MXU runs full-lane-width passes at any B up to 256.
- ``histogram_pallas_bitplane``: bin = hi*lob + lo with power-of-two factor
  widths from ``bitplane_split`` (16x16 at B=255); each one-hot factor is
  the AND-product of log2(width) bit-plane equality masks, keeping VMEM
  intermediates narrow where the dense 256-wide one-hot tile is marginal.

``KERNEL_CAPS`` is the single capability table gating all four kernels.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LO = 8  # low-radix width: one sublane tile of the values operand
LO_BITS = LO.bit_length() - 1  # bin = (hi << LO_BITS) | lo

# Scoped-VMEM budget for one grid step: Mosaic's limit (16MiB by default on
# a v5e, obs/costs.CHIP_PEAKS vmem_bytes) less this margin for its own stack.
_VMEM_MARGIN = 6 * 1024 * 1024


def _vmem_budget() -> int:
    """Per-grid-step scoped-VMEM budget: this chip's ``vmem_bytes`` on a
    TPU, the smallest row of the table anywhere else (interpret mode — so a
    chunking chosen off-chip also lowers on every chip), less the margin."""
    from ..obs import costs as costs_mod

    kind = (
        jax.devices()[0].device_kind
        if jax.default_backend() == "tpu" else None
    )
    return costs_mod.vmem_bytes(kind) - _VMEM_MARGIN


# Scoped-VMEM bytes one row-chunk column costs each routed kernel, as Mosaic
# itself reported them on a v5e (libtpu 0.0.34, PR 21 chip runs): the size in
# its "Scoped allocation with size X and limit 16.00M" refusal over the chunk
# it was given. They replace hand-written footprint models that were 1.7x-6x
# low — those counted array elements, while VMEM holds (8,128) tiles: a
# [C, 16] one-hot costs 128 lanes per row and bf16 pads to 16 sublanes (bf16
# needs MORE than f32 here) — so every kernel was refused at the trainer's
# tpu_hist_chunk=16384. One figure serves both operand dtypes, the larger.
_BYTES_PER_COL = {
    # the lane-dense body of PR 26, read the same way on the chip (libtpu
    # 0.0.34): f32 46.07M / 200192 = 241, bf16 27.53M / 200192 = 144; the
    # compiler run for a described v5e with no chip says the same (f32
    # 23.10M / 99840 = 243). Since PR 34 the body works through its block
    # 512 rows at a time and holds no [*, C] intermediate, so the figure is
    # an upper bound: kept, the cap (42,496 rows) binds nothing the grower
    # asks for
    "pallas": 244,
    # bf16 17.68M / 12288 = 1509; f32 compiles at 6144, which this keeps
    "pallas_onehot": 1640,
    # f32, 16x16 split: 57.52M / 12800 = 4712 (and 18.82M / 4096); bf16 not
    # measured, allowed the 1.25x the other kernels show
    "pallas_bitplane": 5900,
    # never refused: held to the 2048 packed columns proven to compile
    "pallas_packed4": 5120,
}


def _max_chunk_for(impl: str) -> int:
    """Largest row chunk (a multiple of 512) kernel ``impl`` may take."""
    c = _vmem_budget() // _BYTES_PER_COL[impl]
    return max(512, (c // 512) * 512)


def _max_chunk(hi_n: int, k_n: int, dtype) -> int:
    """Chunk cap of the per-feature-grid v1 kernel (the differential oracle,
    not routed): a footprint model that reproduced the one allocation
    Mosaic reported for it (est. 1007 against 1068 B/row, 2026-07-31)."""
    d = jnp.dtype(dtype).itemsize
    per_row = (
        1 + 2 * (1 + 4 * k_n)  # double-buffered bins [1,C] u8 + vt [K,C] f32
        + 8  # hi/lo int32 vectors
        + d * (hi_n + hi_n * k_n + LO + k_n)  # oh_hi, lhs, oh_lo, vt cast
    )
    if d == 4:
        # Precision.HIGHEST decomposes each f32 operand into bf16 hi/lo
        # shadows: two bf16 copies of lhs and of oh_lo
        per_row += 2 * 2 * (hi_n * k_n + LO)
    c = _vmem_budget() // per_row
    return max(512, (c // 512) * 512)


FB = 8  # features per grid step in the feature-batched kernel (sublane-aligned
# i8 block: Mosaic cannot load a single dynamic u8 row, but an [8, C] block
# starting at a multiple of 8 is provably aligned)


def _hi_for(num_bins: int) -> int:
    hi = -(-num_bins // LO)
    if hi * 3 > 128:
        raise ValueError("num_bins %d too large for radix kernel" % num_bins)
    return hi


def _kernel(bins_ref, vt_ref, out_ref, *, hi_n: int, dtype):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    b = bins_ref[0, 0, :].astype(jnp.int32)  # [C]
    # rounding vt to the operand dtype BEFORE the one-hot product equals
    # rounding the product (one-hot entries are exact 0/1) and keeps the
    # [HI*K, C] intermediate in the narrow dtype — half the VMEM for bf16
    vt = vt_ref[:].astype(dtype)  # [K, C]
    k_n, C = vt.shape

    hi = b // LO
    lo = b - hi * LO

    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (hi_n, C), 0)
    oh_hi = (hi[None, :] == hi_iota).astype(dtype)  # [HI, C]
    # LHS row (h, k) = onehot_hi[h, i] * values[k, i]
    lhs = (oh_hi[:, None, :] * vt[None, :, :]).reshape(hi_n * k_n, C)

    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (C, LO), 1)
    oh_lo = (lo[:, None] == lo_iota).astype(dtype)  # [C, LO]

    out_ref[0] += jax.lax.dot_general(
        lhs,
        oh_lo,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        # f32 operands need the 3-pass bf16 decomposition on the MXU; the
        # default single pass silently rounds to bf16 precision
        precision=(
            jax.lax.Precision.HIGHEST
            if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT
        ),
    )


def split_bf16(v: jax.Array, pieces: int):
    """``pieces`` bfloat16-representable float32 arrays that sum to ``v``.

    One piece is ``bf16(v)``: the rounding the caller asked for with
    ``dtype=bfloat16``. Three pieces are exact: ``p0 = bf16(v)``, ``p1 =
    bf16(v - p0)``, ``p2 = v - p0 - p1`` with the subtractions in float32.
    Each remainder is exact in float32, and the third fits bf16's 8
    significand bits (24 = 8 + 8 + 8), so ``p0 + p1 + p2 == v`` bit for bit
    wherever the smallest piece is a normal number: ``|v|`` from 1e-30 up to
    bf16's largest finite value."""
    out, r = [], v
    for i in range(pieces):
        p = r.astype(jnp.bfloat16).astype(jnp.float32)
        out.append(p)
        if i + 1 < pieces:
            r = r - p
    return out


def _pieces_for(dtype) -> int:
    """MXU passes' worth of bf16 pieces that carry an operand of ``dtype``."""
    return 3 if jnp.dtype(dtype) == jnp.float32 else 1


SUB = 512  # rows of one partial sum: the unit every pass adds its rows in
_UNROLL = 4  # sub-blocks to an iteration of the body's loop


def _kernel_fb(slot_ref, live_ref, bins_ref, vt_ref, out_ref, *, hi_n: int,
               pieces: int):
    """Feature-batched kernel body: one grid step consumes an [FB, C] bins
    block + ONE [K, C] values block, ``SUB`` rows at a time, and unrolls the
    FB features in VMEM.

    The values are split once into ``pieces`` bf16 pieces (3 for float32
    operands, exact: :func:`split_bf16`; 1 for bfloat16); one single-pass
    bf16 ``dot_general`` a feature contracts oh_hi [HI, SUB] with vlo
    [P*K*LO, SUB] over their shared last axis (the transposed-RHS form;
    operands as the module docstring lays them out) into out[hi, (p, k,
    lo)], float32. The wrapper adds the pieces.

    Chunk ``c`` belongs to slot ``slot_ref[c]`` (the out block's index map
    reads the same table); a slot's chunks are consecutive and the chunk
    axis is the inner one, so the block is zeroed at a slot's first chunk
    and stays in VMEM to its last. Chunks from ``live_ref[0]`` on are the
    lattice's round-up: no work, and their index maps repeat the last live
    chunk's blocks, so nothing is fetched for them either.

    **A segment's sums do not depend on the chunk.** Each dot runs over
    ``SUB`` rows at a segment-relative offset (a slot starts at a chunk
    boundary, a multiple of ``SUB``) and its result is added to the block
    in row order, so the float32 additions of one segment are the same
    ones at every block size, alone or among other slots; a sub-block of
    zero pad rows adds an exact +0."""
    c = pl.program_id(1)
    first = (c == 0) | (slot_ref[c] != slot_ref[jnp.maximum(c - 1, 0)])
    live = c < live_ref[0]

    @pl.when(live & first)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LO, SUB), 0)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (hi_n, SUB), 0)

    def sub_block(i):
        rows = pl.ds(pl.multiple_of(i * SUB, SUB), SUB)
        pk = jnp.concatenate(split_bf16(vt_ref[:, rows], pieces), axis=0)
        m_n = pk.shape[0]  # P*K
        b_all = bins_ref[:, rows].astype(jnp.int32)  # [FB, SUB]
        hi_all = b_all >> LO_BITS
        lo_all = b_all & (LO - 1)
        for j in range(FB):  # static unroll: register slices, no dynamic u8 rows
            lo_hit = lo_all[j][None, :] == lo_iota  # [LO, SUB]
            # built in float32 ([P*K, LO, SUB] -> [P*K*LO, SUB] moves
            # nothing: LO rows are one sublane tile) and cast once: the
            # pieces are bf16 values already
            vlo = (
                jnp.where(lo_hit[None, :, :], pk[:, None, :], 0.0)
                .reshape(m_n * LO, SUB)
                .astype(jnp.bfloat16)
            )
            oh_hi = (hi_all[j][None, :] == hi_iota).astype(jnp.bfloat16)
            out_ref[j] += jax.lax.dot_general(
                oh_hi, vlo,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(live)
    def _chunk():
        # _UNROLL sub-blocks to a loop iteration, the rest after the loop
        # (Mosaic unrolls a fori_loop wholly or not at all). On one v5e the
        # root's 200K x 2000 reads 38.8 ms one by one, 27.5 four at a time,
        # 25.6 eight at a time (the whole-chunk dots they replace: 26.0);
        # four, because the grower traces and lowers a kernel a switch
        # branch and what is unrolled is lowered again each time (PERF.md
        # section 6, PR 34)
        n_sub = bins_ref.shape[1] // SUB
        unroll = min(_UNROLL, n_sub)

        def run(first, n):
            def one(i, carry):
                sub_block(first + i)
                return carry

            if n:
                jax.lax.fori_loop(0, n, one, 0, unroll=True)

        def group(g, carry):
            run(g * unroll, unroll)
            return carry

        if n_sub // unroll > 1:
            jax.lax.fori_loop(0, n_sub // unroll, group, 0)
        else:
            run(0, unroll)
        run(n_sub - n_sub % unroll, n_sub % unroll)


def _fb_call(bins, values, slot_of_chunk, n_live, num_slots, num_bins, C,
             dtype_name, interpret):
    """The one ``pallas_call`` of the routed kernel: ``bins`` [F, n*C] and
    ``values`` [n*C, K] cut into n chunks of C rows, chunk c summed into
    slot ``slot_of_chunk[c]`` while c < ``n_live``. -> the kernel's raw
    output, which ``_fb_relayout`` turns into [W, F, B, K]; a slot that owns
    no live chunk is never written (the caller knows which those are)."""
    F, L = bins.shape
    K = values.shape[1]
    B = num_bins
    HI = _hi_for(B)
    P = _pieces_for(dtype_name)
    W = num_slots
    n_chunks = L // C
    Fp = -(-F // FB) * FB
    if Fp != F:
        # padded feature rows histogram the padded bins (all zero) against
        # real values; ``_fb_relayout`` slices their rows off
        bins = jnp.pad(bins, ((0, Fp - F), (0, 0)))

    def src(c, live_ref):
        return jnp.minimum(c, jnp.maximum(live_ref[0] - 1, 0))

    return pl.pallas_call(
        functools.partial(_kernel_fb, hi_n=HI, pieces=P),
        name="hist_pallas_fb",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Fp // FB, n_chunks),
            in_specs=[
                pl.BlockSpec(
                    (FB, C), lambda f8, c, slot, live: (f8, src(c, live)),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (K, C), lambda f8, c, slot, live: (0, src(c, live)),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, FB, HI, P * K * LO),
                lambda f8, c, slot, live: (slot[src(c, live)], f8, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((W, Fp, HI, P * K * LO), jnp.float32),
        interpret=interpret,
    )(
        slot_of_chunk.astype(jnp.int32),
        jnp.reshape(n_live, (1,)).astype(jnp.int32),
        bins, values.T,
    )


def _fb_relayout(out, F, num_bins, dtype_name):
    """``_fb_call``'s output -> [W, F, B, K] histograms."""
    W, Fp, HI = out.shape[:3]
    P = _pieces_for(dtype_name)
    K = out.shape[3] // (P * LO)
    # out[w, f, hi, (p*K + k)*LO + lo] -> hist[w, f, hi*LO + lo, k]; the
    # pieces are added largest first, in an order no fusion can change
    parts = out.reshape(W, Fp, HI, P, K, LO)
    total = parts[:, :, :, 0]
    for p in range(1, P):
        total = total + parts[:, :, :, p]
    hist = total.transpose(0, 1, 2, 4, 3).reshape(W, Fp, HI * LO, K)
    return hist[:, :F, :num_bins, :]


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "dtype_name", "interpret")
)
def _histogram_pallas_fb(
    bins: jax.Array,  # [F, N]
    values: jax.Array,  # [N, K]
    num_bins: int,
    chunk: int = 8192,
    dtype_name: str = "float32",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram via the feature-batched radix MXU kernel:
    the one-slot case of :func:`histogram_pallas_slots`' call."""
    N = bins.shape[1]
    # equal chunks under the cap: the grower's lattice sizes (2^k, 3*2^k)
    # then pad by nothing, where a fixed C pads 8192 rows to 12288
    n_chunks = -(-N // min(max(chunk, SUB), _max_chunk_for("pallas")))
    C = -(-N // (n_chunks * SUB)) * SUB
    if N != n_chunks * C:
        pad = n_chunks * C - N
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, pad), (0, 0)))
    out = _fb_call(
        bins, values, jnp.zeros((n_chunks,), jnp.int32), jnp.int32(n_chunks),
        1, num_bins, C, dtype_name, interpret,
    )
    return _fb_relayout(out, bins.shape[0], num_bins, dtype_name)[0]


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "chunk", "dtype_name", "interpret"),
)
def histogram_pallas_slots(
    bins: jax.Array,  # [F, L]: W segments end to end
    values: jax.Array,  # [L, K]; zeros on every pad row
    ends: jax.Array,  # [W] int32: each segment's end, a multiple of chunk
    num_bins: int,
    chunk: int,
    dtype_name: str = "float32",
    interpret: bool = False,
) -> jax.Array:
    """[W, F, B, K] f32 histograms of W segments in ONE pass of the
    feature-batched kernel over their concatenation.

    Segment w holds rows ``[ends[w-1], ends[w])`` of the flat operands,
    padded with zero values to a whole number of ``chunk`` rows (a
    multiple of ``SUB`` under the kernel's cap, ``L`` a multiple of it);
    a segment may be empty. The pass costs the chunks up to ``ends[-1]``:
    the rest of ``L`` (a caller's static round-up) is neither fetched nor
    summed. Each segment's histogram is bit for bit what
    :func:`histogram_pallas` gives for it alone, whatever ``chunk`` and
    whatever lies beside it (``_kernel_fb``). The speculative grower's
    batch: its kernel and the relayout of its output carry the grower's
    phases (obs/trace.SCOPES)."""
    W = ends.shape[0]
    L = bins.shape[1]
    if chunk % SUB or chunk > _max_chunk_for("pallas") or L % chunk:
        raise ValueError(
            "chunk %d must be a multiple of %d under the kernel's cap that "
            "divides the %d flat rows" % (chunk, SUB, L)
        )
    with jax.named_scope("hist_build/kernel"):
        ends = ends.astype(jnp.int32)
        starts = jnp.arange(L // chunk, dtype=jnp.int32) * chunk
        slot_of_chunk = jnp.minimum(
            jnp.searchsorted(ends, starts, side="right").astype(jnp.int32),
            W - 1,
        )
        out = _fb_call(
            bins, values, slot_of_chunk, ends[-1] // chunk, W, num_bins,
            chunk, dtype_name, interpret,
        )
    with jax.named_scope("hist_build/relayout"):
        hist = _fb_relayout(out, bins.shape[0], num_bins, dtype_name)
        # a slot with no chunk was never written: whatever the buffer held
        owns_rows = jnp.diff(ends, prepend=0) > 0
        return jnp.where(owns_rows[:, None, None, None], hist, 0.0)


def histogram_pallas(
    bins: jax.Array,  # [F, N] uint8/int32
    values: jax.Array,  # [N, K] f32 (mask pre-applied; out-of-leaf rows are 0)
    num_bins: int,
    chunk: int = 2048,
    dtype_name: str = "bfloat16",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram via the radix-packed MXU kernel.

    Dispatches to the feature-batched kernel (the on-silicon winner); the
    per-feature-grid v1 below remains as its differential oracle
    (tests/test_hist_pallas.py)."""
    return _histogram_pallas_fb(
        bins, values, num_bins, chunk=max(chunk, 4096),
        dtype_name=dtype_name, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "dtype_name", "interpret")
)
def histogram_pallas_v1(
    bins: jax.Array,  # [F, N] uint8/int32
    values: jax.Array,  # [N, K] f32 (mask pre-applied; out-of-leaf rows are 0)
    num_bins: int,
    chunk: int = 2048,
    dtype_name: str = "bfloat16",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram via the per-feature-grid radix kernel (v1)."""
    F, N = bins.shape
    K = values.shape[1]
    B = num_bins
    HI = _hi_for(B)
    dtype = jnp.dtype(dtype_name)

    # Mosaic block rule: the last two block dims must each be divisible by
    # (8, 128) or equal the full array dim. C is therefore forced to a
    # multiple of 512, and bins gets a singleton middle axis so its block's
    # last-two dims are (1, C) against array dims (1, N) — the feature axis
    # becomes a leading grid axis, which has no tiling constraint.
    C = min(max(chunk, 512), max(512, N), _max_chunk(HI, K, dtype))
    C = max(512, (C // 512) * 512)
    if N % C != 0:
        pad = (-N) % C
        # zero values contribute nothing; padded rows land in bin 0 with v=0
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, pad), (0, 0)))
        N += pad
    n_chunks = N // C

    vt = values.T  # [K, N] — lane axis on rows for clean (8,128) tiling
    bins3 = bins.reshape(F, 1, N)

    kernel = functools.partial(_kernel, hi_n=HI, dtype=dtype)
    out = pl.pallas_call(
        kernel,
        name="hist_pallas_v1",
        grid=(F, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, C), lambda f, c: (f, 0, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((K, C), lambda f, c: (0, c), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, HI * K, LO), lambda f, c: (f, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((F, HI * K, LO), jnp.float32),
        interpret=interpret,
    )(bins3, vt)

    # [F, HI*K, LO] -> [F, HI, K, LO] -> [F, HI, LO, K] -> [F, HI*LO, K] -> [F, B, K]
    hist = out.reshape(F, HI, K, LO).transpose(0, 1, 3, 2).reshape(F, HI * LO, K)
    return hist[:, :B, :]


def _kernel_p4(bins_ref, vt_ref, out_ref, *, num_bins: int, dtype):
    """Nibble-packed kernel body (measurement for the 4-bit-bin question,
    dense_nbits_bin.hpp:42): each u8 carries TWO rows' bins (even | odd<<4),
    halving the bin-matrix HBM stream; the values block carries the two
    rows' channels stacked ([2K, C2]). B <= 16 needs no radix split — one
    one-hot dot per half: [K, C2] @ [C2, B]."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vt = vt_ref[:].astype(dtype)  # [2K, C2]
    k2, C2 = vt.shape
    k_n = k2 // 2
    b_all = bins_ref[:, :].astype(jnp.int32)  # [FB, C2]
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (C2, num_bins), 1)
    prec = (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    for j in range(FB):
        b_even = b_all[j] & 15
        b_odd = b_all[j] >> 4
        oh_e = (b_even[:, None] == b_iota).astype(dtype)  # [C2, B]
        oh_o = (b_odd[:, None] == b_iota).astype(dtype)
        out_ref[j] += jax.lax.dot_general(
            vt[:k_n], oh_e, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        ) + jax.lax.dot_general(
            vt[k_n:], oh_o, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec,
        )


def pack4(bins, values):
    """Pack [F, N] u8 bins (all < 16) + [N, K] values into the nibble layout
    histogram_pallas_packed4 consumes: ([F, N/2] u8, [N/2, 2K] f32)."""
    F, N = bins.shape
    if N % 2:
        bins = jnp.pad(bins, ((0, 0), (0, 1)))
        values = jnp.pad(values, ((0, 1), (0, 0)))
        N += 1
    even = bins[:, 0::2].astype(jnp.uint8)
    odd = bins[:, 1::2].astype(jnp.uint8)
    packed = even | (odd << 4)
    K = values.shape[1]
    v2 = jnp.concatenate([values[0::2], values[1::2]], axis=1)  # [N/2, 2K]
    return packed, v2


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "dtype_name", "interpret")
)
def histogram_pallas_packed4(
    bins_packed: jax.Array,  # [F, N2] u8: two 4-bit bins per byte
    values_packed: jax.Array,  # [N2, 2K] f32 (even rows' K ++ odd rows' K)
    num_bins: int,
    chunk: int = 8192,
    dtype_name: str = "float32",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram from nibble-packed bins (B <= 16)."""
    if num_bins > 16:
        raise ValueError("packed4 kernel requires num_bins <= 16")
    F, N2 = bins_packed.shape
    K2 = values_packed.shape[1]
    K = K2 // 2
    dtype = jnp.dtype(dtype_name)
    C = min(max(chunk, 512), max(512, N2), _max_chunk_for("pallas_packed4"))
    C = max(512, (C // 512) * 512)
    if N2 % C != 0:
        pad = (-N2) % C
        bins_packed = jnp.pad(bins_packed, ((0, 0), (0, pad)))
        values_packed = jnp.pad(values_packed, ((0, pad), (0, 0)))
        N2 += pad
    n_chunks = N2 // C
    Fp = -(-F // FB) * FB
    if Fp != F:
        bins_packed = jnp.pad(bins_packed, ((0, Fp - F), (0, 0)))

    vt = values_packed.T  # [2K, N2]
    kernel = functools.partial(_kernel_p4, num_bins=num_bins, dtype=dtype)
    out = pl.pallas_call(
        kernel,
        name="hist_pallas_packed4",
        grid=(Fp // FB, n_chunks),
        in_specs=[
            pl.BlockSpec((FB, C), lambda f8, c: (f8, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((K2, C), lambda f8, c: (0, c), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (FB, K, num_bins), lambda f8, c: (f8, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Fp, K, num_bins), jnp.float32),
        interpret=interpret,
    )(bins_packed, vt)
    return out[:F].transpose(0, 2, 1)  # [F, B, K]


BT = 128  # bin-tile width for the dense one-hot kernel: one MXU lane tile


def _kernel_onehot(bins_ref, vt_ref, out_ref, *, bt: int, dtype):
    """Dense one-hot tile kernel body (ISSUE 17): grid (F/FB, B/BT, N/C).
    Each step builds the [C, BT] one-hot slab for ONE bin tile in VMEM and
    contracts it against the shared [K, C] stat block — the direct MXU
    transcription of hist[f] = onehot(bins_f) @ values, B-tiled so the
    one-hot never exceeds one 128-lane tile regardless of B. The output
    block revisits across the row-chunk axis (innermost grid dim) so each
    (feature-batch, bin-tile) accumulator stays VMEM-resident."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vt = vt_ref[:].astype(dtype)  # [K, C]
    k_n, C = vt.shape
    b_all = bins_ref[:, :].astype(jnp.int32)  # [FB, C]
    # global bin ids covered by this tile: tile_start + [0, bt)
    iota = (
        jax.lax.broadcasted_iota(jnp.int32, (C, bt), 1)
        + pl.program_id(1) * bt
    )
    prec = (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    for j in range(FB):  # static unroll: register slices, no dynamic u8 rows
        oh = (b_all[j][:, None] == iota).astype(dtype)  # [C, BT]
        out_ref[j] += jax.lax.dot_general(
            vt, oh,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec,
        )


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "dtype_name", "interpret")
)
def histogram_pallas_onehot(
    bins: jax.Array,  # [F, N] uint8/int32
    values: jax.Array,  # [N, K] f32 (mask pre-applied; out-of-leaf rows are 0)
    num_bins: int,
    chunk: int = 8192,
    dtype_name: str = "float32",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram via the dense one-hot-tile MXU kernel."""
    F, N = bins.shape
    K = values.shape[1]
    B = num_bins
    Bp = -(-B // BT) * BT
    dtype = jnp.dtype(dtype_name)

    C = min(max(chunk, 512), max(512, N), _max_chunk_for("pallas_onehot"))
    C = max(512, (C // 512) * 512)
    if N % C != 0:
        pad = (-N) % C
        # zero values contribute nothing; padded rows land in bin 0 with v=0
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, pad), (0, 0)))
        N += pad
    n_chunks = N // C
    Fp = -(-F // FB) * FB
    if Fp != F:
        bins = jnp.pad(bins, ((0, Fp - F), (0, 0)))

    vt = values.T  # [K, N]
    kernel = functools.partial(_kernel_onehot, bt=BT, dtype=dtype)
    out = pl.pallas_call(
        kernel,
        name="hist_pallas_onehot",
        grid=(Fp // FB, Bp // BT, n_chunks),
        in_specs=[
            pl.BlockSpec(
                (FB, C), lambda f8, b, c: (f8, c), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (K, C), lambda f8, b, c: (0, c), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (FB, K, BT), lambda f8, b, c: (f8, 0, b), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Fp, K, Bp), jnp.float32),
        interpret=interpret,
    )(bins, vt)
    return out[:F].transpose(0, 2, 1)[:, :B, :]  # [F, B, K]


def bitplane_split(num_bins: int):
    """(lob, hib): power-of-two factor widths for the bit-plane kernel.

    ``bin = hi * lob + lo`` where lo is the low ``log2(lob)`` bits of the
    index and hi the remaining high bits — an even split of
    ``ceil(log2(B))`` planes, so B=255 factors 16x16 and B=63 factors 8x8.
    ``lob * hib >= num_bins`` always holds (out-of-range slots stay zero and
    are sliced off)."""
    p = max((num_bins - 1).bit_length(), 2)
    lob = 1 << (p // 2)
    hib = 1 << (p - p // 2)
    return lob, hib


def _kernel_bitplane(bins_ref, vt_ref, out_ref, *, lob: int, hib: int, dtype):
    """Bit-plane kernel body (ISSUE 17): the u8 bin index is decomposed into
    bit planes and each one-hot factor is built as the 0/1 AND-product of
    one equality mask per plane — ``log2(B)`` vector compares total, never a
    full-B-wide compare, so the widest VMEM intermediate is the [lob*K, C]
    LHS (48 rows at B=255/K=3) instead of a dense 256-wide one-hot slab.
    The matmul shape matches the radix kernel: lhs = onehot_lo (x) values,
    rhs = onehot_hi, OUT [lob*K, hib] accumulated f32 per feature."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    vt = vt_ref[:].astype(dtype)  # [K, C]
    k_n, C = vt.shape
    b_all = bins_ref[:, :].astype(jnp.int32)  # [FB, C]
    lo_bits = lob.bit_length() - 1
    hi_bits = hib.bit_length() - 1
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (lob, C), 0)
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (C, hib), 1)
    prec = (
        jax.lax.Precision.HIGHEST
        if dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    for j in range(FB):  # static unroll: register slices, no dynamic u8 rows
        b = b_all[j]
        oh_lo = ((lo_iota & 1) == (b & 1)[None, :]).astype(dtype)
        for p in range(1, lo_bits):
            oh_lo = oh_lo * (
                ((lo_iota >> p) & 1) == ((b >> p) & 1)[None, :]
            ).astype(dtype)
        oh_hi = ((hi_iota & 1) == ((b >> lo_bits) & 1)[:, None]).astype(dtype)
        for p in range(1, hi_bits):
            oh_hi = oh_hi * (
                ((hi_iota >> p) & 1) == ((b >> (lo_bits + p)) & 1)[:, None]
            ).astype(dtype)
        lhs = (oh_lo[:, None, :] * vt[None, :, :]).reshape(lob * k_n, C)
        out_ref[j] += jax.lax.dot_general(
            lhs, oh_hi,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec,
        )


@functools.partial(
    jax.jit, static_argnames=("num_bins", "chunk", "dtype_name", "interpret")
)
def histogram_pallas_bitplane(
    bins: jax.Array,  # [F, N] uint8/int32
    values: jax.Array,  # [N, K] f32 (mask pre-applied; out-of-leaf rows are 0)
    num_bins: int,
    chunk: int = 8192,
    dtype_name: str = "float32",
    interpret: bool = False,
) -> jax.Array:
    """[F, B, K] f32 histogram via the bit-plane-factored MXU kernel."""
    F, N = bins.shape
    K = values.shape[1]
    B = num_bins
    lob, hib = bitplane_split(B)
    dtype = jnp.dtype(dtype_name)

    C = min(max(chunk, 512), max(512, N), _max_chunk_for("pallas_bitplane"))
    C = max(512, (C // 512) * 512)
    if N % C != 0:
        pad = (-N) % C
        bins = jnp.pad(bins, ((0, 0), (0, pad)))
        values = jnp.pad(values, ((0, pad), (0, 0)))
        N += pad
    n_chunks = N // C
    Fp = -(-F // FB) * FB
    if Fp != F:
        bins = jnp.pad(bins, ((0, Fp - F), (0, 0)))

    vt = values.T  # [K, N]
    kernel = functools.partial(_kernel_bitplane, lob=lob, hib=hib, dtype=dtype)
    out = pl.pallas_call(
        kernel,
        name="hist_pallas_bitplane",
        grid=(Fp // FB, n_chunks),
        in_specs=[
            pl.BlockSpec((FB, C), lambda f8, c: (f8, c), memory_space=pltpu.VMEM),
            pl.BlockSpec((K, C), lambda f8, c: (0, c), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (FB, lob * K, hib), lambda f8, c: (f8, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((Fp, lob * K, hib), jnp.float32),
        interpret=interpret,
    )(bins, vt)

    # out[f, lo*K + k, hi] -> hist[f, hi*lob + lo, k]
    hist = (
        out.reshape(Fp, lob, K, hib)
        .transpose(0, 3, 1, 2)
        .reshape(Fp, hib * lob, K)
    )
    return hist[:F, :B, :]


# ---------------------------------------------------------------------------
# Capability table (ISSUE 17 satellite): the ONE place that says which bin
# widths each Pallas kernel serves. histogram.impl_supported() consults this
# instead of special-casing impl names, the leaf_histogram unsupported-B
# fallback (warn_once + hist_impl_fallback_total counter) covers every impl
# listed here, and obs/tune's candidate filter inherits both for free.
KERNEL_CAPS = {
    # radix kernel: ceil(B/LO) * 3 LHS rows must fit the 128-row MXU pass
    "pallas": lambda b: -(-b // LO) * 3 <= 128,
    # nibble-packed: two 4-bit bins per byte (dense_nbits_bin.hpp question)
    "pallas_packed4": lambda b: b <= 16,
    # dense one-hot tile: B-tiled at BT=128; capped at the 256-bin family
    "pallas_onehot": lambda b: 2 <= b <= 256,
    # bit-plane factorization: power-of-two factor widths up to 16x16
    "pallas_bitplane": lambda b: 2 <= b <= 256,
}


def kernel_supported(
    impl: str,
    num_bins: int,
    backend: Optional[str] = None,
    ignore_backend: bool = False,
) -> bool:
    """True when Pallas kernel ``impl`` can serve this shape on this backend.

    Pure shape+backend predicate over :data:`KERNEL_CAPS` — the
    ``LIGHTGBM_TPU_HIST_IMPL`` escape hatch acts only in the routing layer
    (``histogram._ENV_IMPL``, frozen at import), never here, so differential
    tests that force a Pallas impl really exercise the kernel.
    ``ignore_backend`` checks only the shape constraints — the gate for a
    forced Pallas impl, which may legitimately target interpret mode
    off-TPU. Unknown impls are unsupported."""
    cap = KERNEL_CAPS.get(impl)
    if cap is None or not cap(num_bins):
        return False
    if ignore_backend:
        return True
    if backend is None:
        backend = jax.default_backend()
    return backend == "tpu"


def supported(
    num_bins: int, backend: Optional[str] = None, ignore_backend: bool = False
) -> bool:
    """:func:`kernel_supported` delegate for the radix kernel (kept for the
    original call sites and tests)."""
    return kernel_supported("pallas", num_bins, backend, ignore_backend)


def supported_packed4(
    num_bins: int, backend: Optional[str] = None, ignore_backend: bool = False
) -> bool:
    """:func:`kernel_supported` delegate for the nibble-packed kernel."""
    return kernel_supported("pallas_packed4", num_bins, backend, ignore_backend)

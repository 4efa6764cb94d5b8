"""Pallas TPU kernels: fused mixed-precision OTA data plane.

Two entry points share the (K, block) streaming grid and numerics:
``ota_fused_2d`` consumes f32 rows and quantizes in-pass (below);
``ota_packed_2d`` consumes pre-quantized bit-packed wire rows
(``core/packing.PackedRow``, DESIGN.md §6) and only unpacks + dequantizes
— for a 4-bit cohort its HBM read is 1/8 of the f32 matrix.

One pass over the flat ``(K, M)`` client-update matrix does the whole
per-round hot loop that ``core/ota.py`` used to run as three materialized
stages per client (quantize -> dequantize -> weighted add):

    for each (K, BLOCK_COLS) tile:
        u_k   = dither(seed, client, position)            (computed, not read)
        q_k   = clip(floor(x_k / s_k) + (u_k < frac), -qmax_k, qmax_k)
        dq_k  = q_k * s_k                (or x_k when qmax_k == 0: fp32 client)
        acc   = sum_k w_k * dq_k         (VPU K-step FMA)
        out  += acc;  sumsq += |acc|^2   (running scalar for the AWGN power)

Per-client scalars — quant scale ``s_k``, symmetric range ``qmax_k``, and
FedAvg/channel weight ``w_k`` — ride along as (K, 1) blocks resident for
every grid step; the parameter axis streams through VMEM, so HBM traffic
is one read of x plus one write of the aggregate. The kernel is
bits-agnostic: precision enters only through the qmax/scale arrays, so
one compiled program serves every precision mix.

Stochastic-rounding dither: a counter-based positional hash
(``sr_dither``: murmur3 finalizer over seed/client/position) generated
*inside* the kernel. The dither needs avalanche, not cryptographic
strength — on CPU a threefry draw of the same (K, M) uniforms costs ~3x
the entire fused math, and as a kernel input it would double the HBM read
traffic. Being a pure function of (seed, client, position), the jnp
oracle (``ref.ota_fused_ref``) and the per-tree reference
(``core/ota.ota_aggregate_pertree``) reproduce the exact same numbers.

The receiver AWGN rides the epilogue in ``core/ota.py`` rather than this
kernel: its std is defined by the *global* aggregate norm (SNR relative to
the received signal), which only exists after the reduction. The kernel
therefore emits the blockwise sum-of-squares as a second (1, 1) output —
accumulated across the sequential TPU grid — so the O(M) noise axpy is the
only work left outside the single O(K*M) pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_COLS = 2048
LANES = 128
INT4_GROUP = 2 * LANES  # symbols per planar int4 wire group (``_unpack_tile``)

_GOLDEN = 0x9E3779B9  # Weyl increment decorrelating client rows


def sr_dither(seed, rows, pos) -> jnp.ndarray:
    """Positional uniform dither u in [0, 1) for stochastic rounding.

    murmur3 finalizer (SplitMix-style counter hash) of
    ``pos ^ (seed + GOLDEN * row)`` — ~6 elementwise int ops per element.
    seed/rows/pos: uint32 arrays (broadcastable). 24-bit mantissa-exact
    output, strictly below 1 so integer inputs never round away.
    """
    seed = seed.astype(jnp.uint32)
    rows = rows.astype(jnp.uint32)
    pos = pos.astype(jnp.uint32)
    h = pos ^ (seed + jnp.uint32(_GOLDEN) * rows)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    # via int32: exact below 2^24, and Mosaic has no uint32 -> f32 cast
    u24 = (h >> jnp.uint32(8)).astype(jnp.int32)
    return u24.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _fused_kernel(seed_ref, scale_ref, qmax_ref, w_ref, x_ref, o_ref, ss_ref):
    i = pl.program_id(0)
    K, B = x_ref.shape
    x = x_ref[...].astype(jnp.float32)          # (K, B)
    scale = scale_ref[...].astype(jnp.float32)  # (K, 1)
    qmax = qmax_ref[...].astype(jnp.float32)    # (K, 1); 0 => passthrough
    w = w_ref[...].astype(jnp.float32)          # (K, 1)

    rows = jax.lax.broadcasted_iota(jnp.uint32, (K, B), 0)
    pos = (
        jax.lax.broadcasted_iota(jnp.uint32, (K, B), 1)
        + i.astype(jnp.uint32) * jnp.uint32(B)
    )
    u = sr_dither(seed_ref[0, 0], rows, pos)

    scaled = x / scale
    floor = jnp.floor(scaled)
    q = floor + (u < (scaled - floor)).astype(jnp.float32)
    q = jnp.clip(q, -qmax, qmax)
    dq = jnp.where(qmax > 0, q * scale, x)
    acc = jnp.sum(dq * w, axis=0, keepdims=True)  # (1, B)
    o_ref[...] = acc.reshape(o_ref.shape)

    @pl.when(i == 0)
    def _init():
        ss_ref[...] = jnp.zeros(ss_ref.shape, jnp.float32)

    # (1, 1) vector accumulate: Mosaic cannot store a scalar to VMEM
    ss_ref[...] += jnp.sum(acc * acc, axis=1, keepdims=True)


def nibbles(p: jnp.ndarray):
    """uint8 bytes -> (low, high) nibbles as sign-extended int32 symbols.

    Widened to int32 before any bit op: the TPU vector unit has no 8-bit
    arithmetic. Shared by the in-kernel unpack and the host-side
    ``kernels.ops.unpack_int4_rows``, so both run the same ops.
    """
    x = p.astype(jnp.int32)
    lo = x & 0x0F
    hi = (x >> 4) & 0x0F
    return jnp.where(lo >= 8, lo - 16, lo), jnp.where(hi >= 8, hi - 16, hi)


def _unpack_tile(p: jnp.ndarray) -> jnp.ndarray:
    """(K, N) uint8 wire tile -> (K, 2N) int32 symbols, N % LANES == 0.

    The in-kernel half of the planar int4 wire format
    (``kernels.ops.pack_int4_rows``): each 128-byte lane group holds a
    256-symbol group, symbol j in the low nibble of byte j and symbol
    128 + j in its high nibble. Unpacking is then a concatenation of
    lane-aligned slices — no interleaving reshape, which Mosaic cannot
    lower.
    """
    lo, hi = nibbles(p)
    parts = []
    for g in range(p.shape[1] // LANES):
        sl = slice(g * LANES, (g + 1) * LANES)
        parts += [lo[:, sl], hi[:, sl]]
    return jnp.concatenate(parts, axis=1)


def repeat_lanes(s, first, count, width):
    """Columns ``first`` .. ``first + count - 1`` of ``s`` (R, L), each
    repeated ``width`` times along lanes -> (R, count * width).

    What ``jnp.repeat`` of a lane slice gives, built from one-hot lane
    sums (one nonzero term each: exact) and broadcasts, which Mosaic
    lowers where it refuses repeat's reshape and a dynamic lane slice.
    ``first`` may be traced.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    cols = [
        jnp.sum(jnp.where(lane == first + b, s, 0.0), axis=1, keepdims=True)
        for b in range(count)
    ]
    return jnp.concatenate([jnp.broadcast_to(c, (s.shape[0], width)) for c in cols], 1)


def _tile_scale_cols(scale_ref, i, K, B, qblock, mode):
    """Per-column dequant scales for grid step ``i``'s (K, B) symbol tile.

    ``mode`` (from ``_packed_specs``):

    - ``"row"``: one per-update scale; the (K, 1) column broadcasts.
    - ``"lanes"`` (qblock a multiple of 128 dividing B — incl. the 256
      default): scale_ref is the (K, 128) block of the scale matrix
      covering 128 quantization blocks, streamed by its BlockSpec and
      shared by ``128 // (B // qblock)`` consecutive grid steps, so
      VMEM stays O(K * B) for any M. This step's B // qblock columns
      are repeated over their qblock lanes (``repeat_lanes``).
    - ``"gather"`` (any other qblock; interpret mode only, see
      ``_packed_specs``): the whole (K, n_blocks) matrix is resident and
      indexed per column. Positions past the last block clip to it;
      padding symbols are exact zeros, so the value there never shows.
    """
    scales = scale_ref[...].astype(jnp.float32)
    if mode == "row":
        return scales
    if mode == "lanes":
        bpt = B // qblock
        return repeat_lanes(scales, (i % (LANES // bpt)) * bpt, bpt, qblock)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1).reshape(B) + i * B
    return jnp.take(scales, pos // qblock, axis=1, mode="clip")


def _row_coeff(w_ref, g_ref):
    """Per-row combining coefficient: w_k, or w_k * g_k under fading.

    ``g_ref`` is the (K, 1) effective channel gain column (DESIGN.md
    §12: truncated-inversion receive gain in [0, 1]; 0 = truncated
    client) — present only in the gain-aware call variants. The gain
    multiplies the weight BEFORE the symbol math, so the gained kernel
    runs exactly the ungained ops on a pre-scaled weight column: with
    ``g_ref`` absent the coefficient is untouched (bitwise the legacy
    path), and a unit gain multiplies by 1.0, which is exact in f32.
    """
    w = w_ref[...].astype(jnp.float32)
    if g_ref is not None:
        w = w * g_ref[...].astype(jnp.float32)
    return w


def _dq_superpose_kernel(scale_ref, w_ref, *refs, qblock=0, mode="row", gained=False):
    """Dequantize pre-quantized rows and superpose: acc = sum_k w_k s_k q_k
    (times the per-row channel gain g_k in the gain-aware variant).

    q_ref: (K, B) int8/int16/f32 tile — client-side quantized symbols (or
    f32 passthrough rows with scale 1). The stochastic rounding already
    happened at the client (core.quant.quantize_row_sr), so unlike
    ``_fused_kernel`` there is no dither here — just the receiver-side
    dequant+reduction over the packed wire format. scale_ref: this
    tile's slice of the blockwise scale matrix (``_tile_scale_cols``;
    n_blocks = 1: per-update). With ``gained`` an extra (K, 1) gain
    column rides between w_ref and the symbol tile — the same
    shape trick as the blockwise scale matrix, resident every grid step.
    """
    g_ref, (q_ref, o_ref) = (refs[0], refs[1:]) if gained else (None, refs)
    i = pl.program_id(0)
    K, B = q_ref.shape
    scale = _tile_scale_cols(scale_ref, i, K, B, qblock, mode)
    dq = q_ref[...].astype(jnp.float32) * scale
    o_ref[...] = jnp.sum(dq * _row_coeff(w_ref, g_ref), axis=0).reshape(o_ref.shape)


def _dq_superpose_int4_kernel(
    scale_ref, w_ref, *refs, qblock=0, mode="row", gained=False
):
    """int4 variant: unpack two symbols per byte in-VMEM, then dequant+sum.

    p_ref: (K, B//2) uint8 tile of planar packed nibbles
    (``_unpack_tile``); the HBM read for a 4-bit cohort is 1/8 of the
    f32 path. Block ids index *symbol*
    positions (two per packed byte), so the scale expansion happens
    after the in-VMEM unpack.
    """
    g_ref, (p_ref, o_ref) = (refs[0], refs[1:]) if gained else (None, refs)
    i = pl.program_id(0)
    q = _unpack_tile(p_ref[...])
    K, B = q.shape
    scale = _tile_scale_cols(scale_ref, i, K, B, qblock, mode)
    dq = q.astype(jnp.float32) * scale
    o_ref[...] = jnp.sum(dq * _row_coeff(w_ref, g_ref), axis=0).reshape(o_ref.shape)


def _fold_superpose_kernel(
    scale_ref, w_ref, *refs, qblock=0, mode="row", gained=False
):
    """Streaming fold: out = acc + sum_k w_k s_k q_k (DESIGN.md §11).

    The persistent-accumulator variant of ``_dq_superpose_kernel``: the
    running (M,) superposition streams through VMEM alongside the
    micro-batch's symbol tiles, and each grid step writes the folded
    tile. Per-column math is identical to the barrier kernel plus one
    elementwise add, so fold(zeros, batch) == superpose(batch) and
    fold(fold(state, b0), b1) is exactly the left-associated group sum
    the synchronous path computes (core/ota._fold_groups). The
    gain-aware variant folds with w_k * g_k row coefficients
    (``_row_coeff``) — a wave of all-truncated rows (every g_k = 0)
    adds exact zeros and leaves the accumulator value unchanged.
    """
    g_ref, (q_ref, acc_ref, o_ref) = (refs[0], refs[1:]) if gained else (None, refs)
    i = pl.program_id(0)
    K, B = q_ref.shape
    scale = _tile_scale_cols(scale_ref, i, K, B, qblock, mode)
    dq = q_ref[...].astype(jnp.float32) * scale
    part = jnp.sum(dq * _row_coeff(w_ref, g_ref), axis=0)
    o_ref[...] = acc_ref[...] + part.reshape(o_ref.shape)


def _fold_superpose_int4_kernel(
    scale_ref, w_ref, *refs, qblock=0, mode="row", gained=False
):
    """int4 fold variant: in-VMEM nibble unpack, then fold into acc."""
    g_ref, (p_ref, acc_ref, o_ref) = (refs[0], refs[1:]) if gained else (None, refs)
    i = pl.program_id(0)
    q = _unpack_tile(p_ref[...])
    K, B = q.shape
    scale = _tile_scale_cols(scale_ref, i, K, B, qblock, mode)
    dq = q.astype(jnp.float32) * scale
    part = jnp.sum(dq * _row_coeff(w_ref, g_ref), axis=0)
    o_ref[...] = acc_ref[...] + part.reshape(o_ref.shape)


def _packed_specs(q, scale, *, qblock, packed4, interpret):
    """Shared scaffolding of the packed superpose/fold calls.

    Returns (M, grid, mode, scales, smat, col, tile): the logical symbol
    count, the grid, the scale mode of ``_tile_scale_cols``, the
    normalized (and, in ``"lanes"`` mode, padded) scale matrix, and the
    BlockSpecs for (scale matrix, per-client column, symbol tile).

    Scale streaming (``"lanes"``): the TPU block rule wants a block's
    minor dim to be a multiple of 128 or the whole axis, so the scale
    matrix streams in (K, 128) blocks, each covering 128 quantization
    blocks. It is padded with 1.0 to a whole number of such blocks
    (padding symbols are exact zeros, so that scale never shows). Any
    other blockwise qblock keeps the whole matrix resident for an
    in-kernel gather, which only interpret mode runs: on TPU it is a
    ValueError naming the value.
    """
    K, cols = q.shape
    bc = BLOCK_COLS // 2 if packed4 else BLOCK_COLS
    assert cols % bc == 0, (cols, bc)
    M = 2 * cols if packed4 else cols
    scales = jnp.asarray(scale, jnp.float32)
    if scales.ndim == 1:
        scales = scales.reshape(K, 1)
    n_blocks = scales.shape[1]
    grid = (cols // bc,)
    col = pl.BlockSpec((K, 1), lambda i: (0, 0))
    tile = pl.BlockSpec((K, bc), lambda i: (0, i))
    if qblock <= 0 or n_blocks == 1:
        return M, grid, "row", scales, col, col, tile
    if qblock % LANES == 0 and BLOCK_COLS % qblock == 0:
        tps = LANES // (BLOCK_COLS // qblock)  # grid steps per scale block
        need = -(-grid[0] // tps) * LANES
        scales = jnp.pad(scales, ((0, 0), (0, need - n_blocks)), constant_values=1.0)
        smat = pl.BlockSpec((K, LANES), lambda i: (0, i // tps))
        return M, grid, "lanes", scales, smat, col, tile
    if not interpret:
        raise ValueError(
            f"qblock={qblock}: the TPU data plane needs a blockwise scale "
            f"size that is a multiple of {LANES} and divides {BLOCK_COLS}"
        )
    smat = pl.BlockSpec((K, n_blocks), lambda i: (0, 0))
    return M, grid, "gather", scales, smat, col, tile


def ota_packed_2d(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains=None,
    qblock: int = 0,
    packed4: bool = False,
    interpret: bool = False,
):
    """Dequant + weighted superpose of quantized client rows.

    q: (K, M) int8/int16/f32 symbols, or (K, M//2) uint8 when ``packed4``
    (planar int4 nibbles; logical M = 2 * q.shape[1]). scale: (K,) or
    (K, 1) per-update scales, or the (K, n_blocks) blockwise scale
    matrix with ``qblock`` symbols per block (``core/quant.
    quantize_row_sr`` with block = qblock; last block ragged). w: (K,).
    ``gains``: optional (K,) per-row effective channel gain (DESIGN.md
    §12) — the fading/power-control receive gain multiplying each row's
    combining weight in-pass; None (the default) is the unit channel
    and runs the exact legacy program (no extra kernel input). Returns
    the (M,) f32 partial aggregate for this storage group; the caller
    combines groups and computes the AWGN power on the total (see
    core/ota.py).
    """
    K = q.shape[0]
    M, grid, mode, scales, smat, col, tile = _packed_specs(
        q, scale, qblock=qblock, packed4=packed4, interpret=interpret
    )
    body = _dq_superpose_int4_kernel if packed4 else _dq_superpose_kernel
    gained = gains is not None
    in_specs = [smat, col] + ([col] if gained else []) + [tile]
    operands = [scales, w.reshape(K, 1).astype(jnp.float32)]
    if gained:
        operands.append(jnp.asarray(gains).reshape(K, 1).astype(jnp.float32))
    operands.append(q)
    return pl.pallas_call(
        functools.partial(body, qblock=qblock, mode=mode, gained=gained),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BLOCK_COLS,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((M,), jnp.float32),
        interpret=interpret,
    )(*operands)


def ota_fold_2d(
    acc: jnp.ndarray,
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains=None,
    qblock: int = 0,
    packed4: bool = False,
    interpret: bool = False,
):
    """Fold one packed micro-batch into a persistent (M,) accumulator.

    Same contract as ``ota_packed_2d`` plus ``acc``: the running
    superposition state ((M,) f32, M the logical symbol count). Returns
    acc + the micro-batch's partial aggregate — the streaming-round
    primitive (DESIGN.md §11): arrivals fold in batch by batch instead
    of one (K, M) barrier, and HBM traffic per fold is one read of the
    batch symbols + one read/write of the accumulator. ``gains``: the
    optional per-row channel gain column as in ``ota_packed_2d``.
    Oracle: ``ref.ota_fold_ref`` (within ``ref.ota_fold_bound``).
    """
    K = q.shape[0]
    M, grid, mode, scales, smat, col, tile = _packed_specs(
        q, scale, qblock=qblock, packed4=packed4, interpret=interpret
    )
    assert acc.shape == (M,), (acc.shape, M)
    body = _fold_superpose_int4_kernel if packed4 else _fold_superpose_kernel
    gained = gains is not None
    acc_spec = pl.BlockSpec((BLOCK_COLS,), lambda i: (i,))
    in_specs = [smat, col] + ([col] if gained else []) + [tile, acc_spec]
    operands = [scales, w.reshape(K, 1).astype(jnp.float32)]
    if gained:
        operands.append(jnp.asarray(gains).reshape(K, 1).astype(jnp.float32))
    operands.extend([q, acc.astype(jnp.float32)])
    return pl.pallas_call(
        functools.partial(body, qblock=qblock, mode=mode, gained=gained),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BLOCK_COLS,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((M,), jnp.float32),
        interpret=interpret,
    )(*operands)


def ota_fused_2d(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    qmax: jnp.ndarray,
    w: jnp.ndarray,
    seed: jnp.ndarray,
    *,
    interpret: bool = False,
):
    """x: (K, M) with M % BLOCK_COLS == 0; scale/qmax/w: (K,); seed: ().

    Returns (acc (M,) f32, sumsq (1, 1) f32) — the pre-noise aggregate and
    its squared norm.
    """
    K, M = x.shape
    assert M % BLOCK_COLS == 0, M
    grid = (M // BLOCK_COLS,)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    col = pl.BlockSpec((K, 1), lambda i: (0, 0))
    tile = pl.BlockSpec((K, BLOCK_COLS), lambda i: (0, i))
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[scalar, col, col, col, tile],
        out_specs=[
            pl.BlockSpec((BLOCK_COLS,), lambda i: (i,)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M,), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(
        seed.reshape(1, 1).astype(jnp.uint32),
        scale.reshape(K, 1).astype(jnp.float32),
        qmax.reshape(K, 1).astype(jnp.float32),
        w.reshape(K, 1).astype(jnp.float32),
        x,
    )

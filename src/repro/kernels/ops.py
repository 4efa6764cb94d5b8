"""jit'd public wrappers around the Pallas kernels.

Handles shape normalisation (flatten/pad to tile multiples), scale
computation, and backend selection: on CPU (this container) the kernels
execute in ``interpret=True`` mode — the kernel *body* runs exactly as it
would on TPU, which is what the allclose tests validate.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quant import qrange
from repro.kernels import ota_aggregate as _ota
from repro.kernels import ota_fused as _otaf
from repro.kernels import qmatmul as _qmm
from repro.kernels import quantize as _q


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def interpret_mode() -> bool:
    """True off-TPU: the Pallas data-plane kernels then run under the
    interpreter (a correctness tool for tests), never compiled."""
    return jax.devices()[0].platform != "tpu"


def kernel_path(use_kernel: bool) -> str:
    """Path label a data-plane call reports: "kernel" (compiled on the
    TPU), "interpret" (the kernel body under the interpreter) or "ref"
    (the XLA-compiled jnp reference)."""
    if not use_kernel:
        return "ref"
    return "interpret" if interpret_mode() else "kernel"


def _pad_to(x: jnp.ndarray, m: int, axis: int = 0) -> Tuple[jnp.ndarray, int]:
    n = x.shape[axis]
    pad = (-n) % m
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, pad


@functools.partial(jax.jit, static_argnames=("bits", "stochastic"))
def fake_quant(
    x: jnp.ndarray,
    bits: int,
    *,
    stochastic: bool = False,
    key: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """Per-tensor fake-quant of an arbitrary-shape tensor via the kernel."""
    interpret = _on_cpu()
    qmax = float(qrange(bits))
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-12) / qmax
    flat = x.reshape(-1)
    cols = _q.LANES
    rows_block = _q.BLOCK_ROWS
    flat, pad = _pad_to(flat, cols * rows_block)
    x2 = flat.reshape(-1, cols)
    noise = None
    if stochastic:
        noise = jax.random.uniform(key, x2.shape, jnp.float32)
    out = _q.fake_quant_2d(x2, scale, bits, noise, interpret=interpret)
    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape).astype(x.dtype)


@jax.jit
def ota_aggregate(
    x: jnp.ndarray, w: jnp.ndarray, noise: jnp.ndarray, noise_std: jnp.ndarray
) -> jnp.ndarray:
    """Superpose K flat client streams. x: (K, M); w: (K,); noise: (M,)."""
    interpret = _on_cpu()
    M = x.shape[1]
    xp, pad = _pad_to(x, _ota.BLOCK_COLS, axis=1)
    np_, _ = _pad_to(noise, _ota.BLOCK_COLS)
    out = _ota.ota_aggregate_2d(xp, w, np_, jnp.asarray(noise_std), interpret=interpret)
    return out[:M]


@jax.jit
def ota_quantize_superpose(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    qmax: jnp.ndarray,
    w: jnp.ndarray,
    seed: jnp.ndarray,
):
    """Fused per-client stochastic quantize -> dequant -> weighted superpose.

    x: (K, M); scale/qmax/w: (K,) (qmax == 0 => fp32 passthrough row);
    seed: () uint32 driving the in-kernel positional rounding dither.
    Returns (acc (M,) f32, sumsq () f32). One streaming pass on TPU; the
    jnp oracle with identical semantics is ``ref.ota_fused_ref``.

    Interpret mode everywhere but TPU: the kernel's cross-grid-step
    sumsq accumulation relies on TPU sequential-grid semantics and would
    race under a parallel (GPU) grid.
    """
    interpret = interpret_mode()
    M = x.shape[1]
    xp, _ = _pad_to(x, _otaf.BLOCK_COLS, axis=1)
    acc, ss = _otaf.ota_fused_2d(
        xp, scale, qmax, w, jnp.asarray(seed), interpret=interpret
    )
    return acc[:M], ss.reshape(())


@functools.partial(jax.jit, static_argnames=("qblock", "packed4"))
def ota_dequant_superpose(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains=None,
    qblock: int = 0,
    packed4: bool = False,
):
    """Receiver half of the packed uplink: dequant + weighted superpose.

    q: (K, M) int8/int16/f32 pre-quantized client symbols, or (K, M//2)
    uint8 planar int4 nibbles when ``packed4`` (``pack_int4_rows``).
    scale: (K,) per-update scales or the (K, n_blocks) blockwise scale
    matrix (``qblock`` symbols per scale; 0 = per-update). w: (K,).
    ``gains``: optional (K,) per-row effective channel gain (fading +
    truncated channel inversion, ``core/channel.py``, DESIGN.md §12) —
    each row's combining coefficient becomes w_k * g_k inside the pass;
    None is the unit channel and runs the exact legacy program.
    Returns the (M,) f32 partial aggregate for this storage group. The
    stochastic quantization happened client-side
    (``core.quant.quantize_row_sr``); this pass never materialises the
    f32 (K, M) matrix — the unpack runs inside the kernel tile. Oracle:
    ``ref.ota_packed_ref``. Interpret mode off-TPU (CPU correctness tool;
    the jnp oracle is the CPU perf path, as with ota_quantize_superpose).
    """
    interpret = interpret_mode()
    bc = _otaf.BLOCK_COLS // 2 if packed4 else _otaf.BLOCK_COLS
    M = 2 * q.shape[1] if packed4 else q.shape[1]
    qp, _ = _pad_to(q, bc, axis=1)
    out = _otaf.ota_packed_2d(
        qp, scale, w, gains=gains, qblock=qblock, packed4=packed4, interpret=interpret
    )
    return out[:M]


@functools.partial(jax.jit, static_argnames=("k", "use_kernel"))
def topk_cosine(
    qm: jnp.ndarray,
    recs: jnp.ndarray,
    scales: Optional[jnp.ndarray],
    n: jnp.ndarray,
    *,
    k: int,
    use_kernel: bool = True,
):
    """Batched cosine top-k over an arena record slab.

    qm: (Q, D) f32 unit-norm query batch; recs: (Np, D) f32 or int8
    capacity slab with Np % topk_similarity.TILE_N == 0; scales:
    (Np, D // qblock) f32 scale grid (int8 recs) or None; n: () traced
    live record count — the jit cache keys on (Q-pad, Np, D, k, storage
    class), never on n, so arena appends don't recompile. k is static,
    <= topk_similarity.TOPK_LANES.

    Returns (scores (Q, k) f32, idx (Q, k) int32) under the engine's tie
    contract (descending score, ties by ascending index). With
    ``use_kernel`` the Pallas kernel runs (interpret mode off-TPU);
    otherwise the jnp oracle ``ref.topk_similarity_ref`` (bit-equal in
    interpret mode) — the CPU perf path, as with the OTA kernels.
    """
    from repro.kernels import ref as _ref
    from repro.kernels import topk_similarity as _tk

    Q, D = qm.shape
    assert 0 < k <= _tk.TOPK_LANES, k
    Qp = -(-Q // 8) * 8  # f32 sublane multiple
    qp = jnp.pad(qm, ((0, Qp - Q), (0, 0))) if Qp != Q else qm
    if use_kernel:
        interpret = interpret_mode()
        s, i = _tk.topk_similarity_2d(qp, recs, scales, n, k=k, interpret=interpret)
    else:
        s, i = _ref.topk_similarity_ref(qp, recs, scales, n)
    return s[:Q, :k], i[:Q, :k]


@functools.partial(jax.jit, static_argnames=("k", "use_kernel", "mesh"))
def topk_cosine_sharded(
    qm: jnp.ndarray,
    recs: jnp.ndarray,
    scales: Optional[jnp.ndarray],
    n: jnp.ndarray,
    *,
    k: int,
    mesh,
    use_kernel: bool = False,
):
    """Mesh-sharded ``topk_cosine``: the record slab rows place across
    the ``data`` axis of ``mesh`` (DESIGN.md §15).

    recs: (Np, D) capacity slab with Np divisible by
    shards * topk_similarity.TILE_N (the engine pads with the arena's
    own zero-row/unit-scale convention); scales row-shard alongside.
    Every shard runs the identical tile loop on its row block with its
    local live count — shard boundaries are TILE_N-aligned, so each
    per-tile dot is literally one of the unsharded path's dots and the
    per-record scores are bit-equal. The merge then re-sorts the
    per-shard candidate lanes with ``lax.top_k``: within a shard the
    lanes are already (desc score, asc index)-ordered and shards
    concatenate in ascending index-range order, so positional ties
    resolve exactly per the engine tie contract (descending score,
    ties by ascending global index) and the result is bit-identical to
    ``topk_cosine`` — scores and indices. k <= TOPK_LANES guarantees
    any global top-k member survives its shard's lane budget.
    """
    from repro.kernels import ref as _ref
    from repro.kernels import topk_similarity as _tk

    P = jax.sharding.PartitionSpec
    n_shards = mesh.shape["data"]
    Np = recs.shape[0]
    assert Np % (n_shards * _tk.TILE_N) == 0, (Np, n_shards)
    rows = Np // n_shards
    Q, D = qm.shape
    assert 0 < k <= _tk.TOPK_LANES, k
    Qp = -(-Q // 8) * 8  # f32 sublane multiple
    qp = jnp.pad(qm, ((0, Qp - Q), (0, 0))) if Qp != Q else qm
    interpret = interpret_mode()

    def _local_topk(qloc, rloc, sloc, nloc):
        lo = jax.lax.axis_index("data") * rows
        n_local = jnp.clip(nloc - lo, 0, rows)
        if use_kernel:
            s, i = _tk.topk_similarity_2d(
                qloc, rloc, sloc, n_local, k=k, interpret=interpret
            )
        else:
            s, i = _ref.topk_similarity_ref(qloc, rloc, sloc, n_local)
        return s[None], (i + lo)[None]

    if scales is None:
        body = lambda q_, r_, n_: _local_topk(q_, r_, None, n_)
        in_specs = (P(), P("data"), P())
        args = (qp, recs, n)
    else:
        body = _local_topk
        in_specs = (P(), P("data"), P("data"), P())
        args = (qp, recs, scales, n)
    # check_vma=False: pallas_call has no varying-manual-axes rule
    s, i = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )(*args)
    # (shards, Qp, LANES) candidates -> flatten the shard axis in index
    # order: every tied set is then positionally ascending-index, and
    # lax.top_k keeps earliest positions among ties — the same merge
    # mechanism (and hence the same tie contract) as the unsharded
    # running merge.
    cand_s = jnp.swapaxes(s, 0, 1).reshape(Qp, n_shards * _tk.TOPK_LANES)
    cand_i = jnp.swapaxes(i, 0, 1).reshape(Qp, n_shards * _tk.TOPK_LANES)
    v, a = jax.lax.top_k(cand_s, k)
    return v[:Q], jnp.take_along_axis(cand_i, a, axis=1)[:Q]


@functools.partial(jax.jit, static_argnames=("qblock", "packed4"))
def ota_fold_packed(
    acc: jnp.ndarray,
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains=None,
    qblock: int = 0,
    packed4: bool = False,
):
    """Fold one packed micro-batch into the persistent superposition state.

    The streaming-round primitive (DESIGN.md §11): acc is the running
    (M,) f32 accumulator (start from zeros or a prior
    ``ota_dequant_superpose`` partial), q/scale/w one micro-batch of
    same-storage-class client rows exactly as in
    ``ota_dequant_superpose`` — including the optional (K,) per-row
    channel ``gains`` (DESIGN.md §12; None = unit channel, the exact
    legacy program). Returns acc + the batch's weighted dequantized
    superposition, so a round becomes
    fold(fold(fold(state, batch0), batch1), ...) instead of one (K, M)
    barrier. Oracle: ``ref.ota_fold_ref`` (within ``ref.ota_fold_bound``;
    the jnp path is the CPU perf path, as with the other OTA kernels).
    """
    interpret = interpret_mode()
    bc = _otaf.BLOCK_COLS // 2 if packed4 else _otaf.BLOCK_COLS
    M = 2 * q.shape[1] if packed4 else q.shape[1]
    qp, _ = _pad_to(q, bc, axis=1)
    Mp = 2 * qp.shape[1] if packed4 else qp.shape[1]
    accp, _ = _pad_to(acc, Mp)
    out = _otaf.ota_fold_2d(
        accp,
        qp,
        scale,
        w,
        gains=gains,
        qblock=qblock,
        packed4=packed4,
        interpret=interpret,
    )
    return out[:M]


@jax.jit
def qmatmul(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """x (M, K) @ dequant(w_q (K, N) int8; per-channel scale (N,))."""
    interpret = _on_cpu()
    M, K = x.shape
    _, N = w_q.shape
    xp, pm = _pad_to(x, _qmm.BM, axis=0)
    xp, pk = _pad_to(xp, _qmm.BK, axis=1)
    wp, _ = _pad_to(w_q, _qmm.BK, axis=0)
    wp, pn = _pad_to(wp, _qmm.BN, axis=1)
    sp, _ = _pad_to(scale, _qmm.BN)
    out = _qmm.qmatmul(xp, wp, sp, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_mha(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal: bool = True
) -> jnp.ndarray:
    """Multi-head flash attention. q: (B, S, H, D); k/v: (B, S, KV, D).

    GQA handled by repeating KV heads to H (zero-copy broadcast reshape);
    sequences padded to the kernel tile size.
    """
    from repro.kernels import flash_attention as _fa

    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    pad_q = (-Sq) % _fa.BQ
    pad_k = (-Sk) % _fa.BK
    qf = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    # Padding: padded query rows are sliced off below; padded KEY rows sit
    # at positions >= Sk, which causal masking (q_pos >= k_pos) hides from
    # every real query row — so causal=True handles padding for free.
    # (Non-causal callers must pass tile-aligned Sk.)
    qf = qf.swapaxes(1, 2).reshape(B * H, Sq + pad_q, D)
    kf = kf.swapaxes(1, 2).reshape(B * H, Sk + pad_k, D)
    vf = vf.swapaxes(1, 2).reshape(B * H, Sk + pad_k, D)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, interpret=_on_cpu())
    out = out.reshape(B, H, Sq + pad_q, D).swapaxes(1, 2)
    return out[:, :Sq]


def quantize_weights(w: jnp.ndarray, bits: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-output-channel symmetric int8 quantization for qmatmul."""
    qmax = qrange(bits)
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.maximum(amax, 1e-12) / qmax
    q = jnp.clip(jnp.round(w / scale[None, :]), -qmax, qmax).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


# ---------------------------------------------------------------------------
# int4: pack two nibbles per int8 byte; the same qmatmul kernel consumes the
# unpacked representation (TPU int4 matmul via int8 lanes)
# ---------------------------------------------------------------------------


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """q: int8 values in [-8, 7], even first-dim -> (K//2, N) packed bytes."""
    K = q.shape[0]
    assert K % 2 == 0, "pack_int4 needs an even K dim"
    lo = (q[0::2].astype(jnp.uint8)) & 0x0F
    hi = (q[1::2].astype(jnp.uint8)) & 0x0F
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of pack_int4 -> int8 in [-8, 7], shape (2*Kp, N)."""
    lo = (packed & 0x0F).astype(jnp.int8)
    hi = ((packed >> 4) & 0x0F).astype(jnp.int8)
    # sign-extend the 4-bit two's complement values
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    Kp, N = packed.shape
    out = jnp.zeros((2 * Kp, N), jnp.int8)
    out = out.at[0::2].set(lo)
    out = out.at[1::2].set(hi)
    return out


def pack_int4_rows(q: jnp.ndarray) -> jnp.ndarray:
    """Planar int4 pack: (..., M) int values in [-8, 7] -> (..., P) uint8,
    P = 128 * ceil(M / 256).

    The uplink wire variant of ``pack_int4`` (which pairs adjacent *rows*
    for the weight layout): a client's flat update row stays a row, at
    half the bytes. Symbols go in groups of ``ota_fused.INT4_GROUP`` =
    256: in group g, byte j holds symbol 256 g + j in its low nibble and
    symbol 256 g + 128 + j in its high nibble, so the kernel unpacks a
    tile by concatenating lane-aligned slices (``ota_fused._unpack_tile``).
    A row that is not a whole number of groups is zero-padded;
    ``unpack_int4_rows`` takes the logical length to trim it back.
    """
    group = _otaf.INT4_GROUP
    pad = (-q.shape[-1]) % group
    if pad:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
    g = q.reshape(*q.shape[:-1], -1, 2, group // 2).astype(jnp.uint8) & 0x0F
    out = g[..., 0, :] | (g[..., 1, :] << 4)
    return out.reshape(*q.shape[:-1], -1).astype(jnp.uint8)


def unpack_int4_rows(packed: jnp.ndarray, n: Optional[int] = None) -> jnp.ndarray:
    """Inverse of ``pack_int4_rows``: (..., P) uint8 -> (..., n) int32.

    ``n`` trims the zero padding of a row that is not a whole number of
    groups (defaults to 2P). Same nibble math as the in-kernel unpack
    (``ota_fused.nibbles``).
    """
    half = _otaf.INT4_GROUP // 2
    lo, hi = _otaf.nibbles(packed.reshape(*packed.shape[:-1], -1, half))
    out = jnp.concatenate([lo, hi], axis=-1)
    out = out.reshape(*packed.shape[:-1], 2 * packed.shape[-1])
    return out if n is None else out[..., :n]


def quantize_weights_int4(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-channel symmetric int4: returns (packed (K//2, N) uint8, scale)."""
    q, scale = quantize_weights(w, bits=4)
    return pack_int4(q), scale


@jax.jit
def qmatmul_int4(
    x: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray
) -> jnp.ndarray:
    """x (M, K) @ dequant(int4-packed weights (K//2, N))."""
    return qmatmul(x, unpack_int4(w_packed), scale)

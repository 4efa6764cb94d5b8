"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

The quantization semantics are shared with ``repro.core.quant`` — these
re-exports *are* the reference the kernels are tested against.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quant import qrange


def fake_quant_ref(
    x: jnp.ndarray, scale: jnp.ndarray, bits: int, noise: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Fake-quantize with a precomputed per-tensor scale.

    noise: optional uniform [0,1) array (stochastic rounding); None = RTN.
    """
    qmax = float(qrange(bits))
    scaled = x.astype(jnp.float32) / scale
    if noise is None:
        q = jnp.round(scaled)
    else:
        floor = jnp.floor(scaled)
        q = floor + (noise < (scaled - floor)).astype(jnp.float32)
    q = jnp.clip(q, -qmax, qmax)
    return q * scale


def ota_fused_ref(
    x: jnp.ndarray,
    scale: jnp.ndarray,
    qmax: jnp.ndarray,
    w: jnp.ndarray,
    seed: jnp.ndarray,
):
    """Oracle for the fused OTA data-plane kernel (see ota_fused.py).

    x: (K, M); scale/qmax/w: (K,); seed: () uint32 for the positional
    stochastic-rounding dither. qmax == 0 marks an unquantized (fp32,
    bits >= 32) client. Returns (acc (M,), sumsq () f32): the
    stochastic-quantize -> dequantize -> weighted superposition of the K
    client streams, plus the aggregate's squared norm (the AWGN power
    calibration input).
    """
    from repro.kernels.ota_fused import sr_dither

    K, M = x.shape
    x = x.astype(jnp.float32)
    scale = scale.reshape(-1, 1).astype(jnp.float32)
    qmax = qmax.reshape(-1, 1).astype(jnp.float32)
    w = w.reshape(-1, 1).astype(jnp.float32)
    u = sr_dither(
        jnp.asarray(seed),
        jax.lax.broadcasted_iota(jnp.uint32, (K, M), 0),
        jax.lax.broadcasted_iota(jnp.uint32, (K, M), 1),
    )
    scaled = x / scale
    floor = jnp.floor(scaled)
    q = floor + (u < (scaled - floor)).astype(jnp.float32)
    q = jnp.clip(q, -qmax, qmax)
    dq = jnp.where(qmax > 0, q * scale, x)
    acc = jnp.sum(dq * w, axis=0)
    return acc, jnp.sum(acc * acc)


def ota_packed_ref(
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains: Optional[jnp.ndarray] = None,
    qblock: int = 0,
    packed4: bool = False,
) -> jnp.ndarray:
    """Oracle for the packed-uplink dequant+superpose kernel
    (``ota_fused.ota_packed_2d``).

    q: (K, M) int8/int16/f32 symbols, or (K, M//2) uint8 planar int4
    nibbles when ``packed4``. scale: (K,)/(K, 1) per-update scales, or
    the (K, n_blocks) blockwise scale matrix — symbol position p
    dequantizes with block p // qblock (``qblock`` = 0 or n_blocks = 1:
    one scale per update, the PR-2 format). w: (K,). ``gains``: optional
    (K,) effective channel gain per row (DESIGN.md §12) — the combining
    coefficient becomes w_k * g_k, multiplied out BEFORE the symbol
    math exactly as the kernel's ``_row_coeff`` does (None skips the
    multiply entirely: the legacy program). Returns the (M,) f32
    partial aggregate sum_k w_k [* g_k] * scale_k[block] * q_k. Every
    term is the kernel's own product; the two differ only in the order
    of the K-row sum, within ``ota_fold_bound``.
    """
    if packed4:
        from repro.kernels.ops import unpack_int4_rows

        q = unpack_int4_rows(q)
    K, M = q.shape
    scales = jnp.asarray(scale, jnp.float32)
    if scales.ndim == 1:
        scales = scales.reshape(K, 1)
    if qblock > 0 and scales.shape[1] > 1:
        bid = jnp.arange(M, dtype=jnp.int32) // qblock
        scale_cols = jnp.take(scales, bid, axis=1, mode="clip")
    else:
        scale_cols = scales  # (K, 1) broadcast
    dq = q.astype(jnp.float32) * scale_cols
    wcol = w.reshape(-1, 1).astype(jnp.float32)
    if gains is not None:
        wcol = wcol * jnp.asarray(gains).reshape(-1, 1).astype(jnp.float32)
    return jnp.sum(dq * wcol, axis=0)


def ota_fold_ref(
    acc: jnp.ndarray,
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains: Optional[jnp.ndarray] = None,
    qblock: int = 0,
    packed4: bool = False,
) -> jnp.ndarray:
    """Oracle for the streaming fold kernel (``ota_fused.ota_fold_2d``).

    acc: the running (M,) f32 superposition state; remaining args as in
    ``ota_packed_ref`` (incl. the optional per-row channel ``gains``).
    Returns acc + sum_k w_k [* g_k] * scale_k[block] * q_k — the
    per-column math of the barrier oracle plus one elementwise add, so
    fold(zeros, batch) equals ``ota_packed_ref(batch)`` (the
    persistent-accumulator contract, DESIGN.md §11) and the kernel
    agrees within ``ota_fold_bound``. A wave whose gains are all zero
    adds exact zeros: the accumulator value is unchanged.
    """
    return acc.astype(jnp.float32) + ota_packed_ref(
        q, scale, w, gains=gains, qblock=qblock, packed4=packed4
    )


def ota_fold_bound(
    acc: Optional[jnp.ndarray],
    q: jnp.ndarray,
    scale: jnp.ndarray,
    w: jnp.ndarray,
    *,
    gains: Optional[jnp.ndarray] = None,
    qblock: int = 0,
    packed4: bool = False,
) -> jnp.ndarray:
    """Per-element bound on |kernel - oracle| for the packed superpose
    (``acc`` None) or fold, where the two differ only in summation order.

    Every term w_k [g_k] s_k q_k is formed by the same multiplies on both
    sides; what differs is the order of the f32 sum over the K rows (plus
    the accumulator), which XLA and Mosaic each choose freely. Any order
    of an n-term f32 sum is within (n - 1) u sum|x_i| of the exact sum
    (u = 2^-24, Higham, "Accuracy and Stability of Numerical
    Algorithms", §4.2), so two orders differ by at most twice that. The
    bound takes n + 2 terms at eps = 2u — room for one fused
    multiply-add per term — plus n times the smallest normal f32 for
    flushed subnormals.
    """
    if packed4:
        from repro.kernels.ops import unpack_int4_rows

        q = unpack_int4_rows(q)
    absq = jnp.abs(q.astype(jnp.float32))
    absg = None if gains is None else jnp.abs(jnp.asarray(gains, jnp.float32))
    abss = jnp.abs(jnp.asarray(scale, jnp.float32))
    mag = ota_packed_ref(absq, abss, jnp.abs(w), gains=absg, qblock=qblock)
    n = q.shape[0]
    if acc is not None:
        mag = mag + jnp.abs(acc.astype(jnp.float32))
        n += 1
    info = jnp.finfo(jnp.float32)
    return (n + 2) * float(info.eps) * mag + n * float(info.tiny)


def ota_aggregate_ref(
    x: jnp.ndarray, w: jnp.ndarray, noise: jnp.ndarray, noise_std: jnp.ndarray
) -> jnp.ndarray:
    """Superpose K client streams: sum_k w_k x_k + noise_std * noise.

    x: (K, M) f32; w: (K,) f32; noise: (M,) f32.
    """
    return (
        jnp.einsum("k,km->m", w.astype(jnp.float32), x.astype(jnp.float32))
        + noise_std * noise
    )


def topk_similarity_ref(
    qm: jnp.ndarray, recs: jnp.ndarray, scales: Optional[jnp.ndarray], n: jnp.ndarray
):
    """Oracle for the fused similarity/top-k kernel
    (``topk_similarity.topk_similarity_2d``) — the identical tile loop
    (dot -> live-count mask -> running ``lax.top_k`` merge) unrolled in
    jnp, so kernel and oracle are bit-equal in interpret mode and share
    the tie contract (descending score, ties by ascending record index).

    qm: (Qp, D) f32; recs: (Np, D) f32 or int8 (Np % TILE_N == 0);
    scales: (Np, D // qblock) f32 for int8 recs, None for f32; n: ()
    live count. Returns (scores (Qp, TOPK_LANES), idx (Qp, TOPK_LANES)).
    """
    from repro.kernels.topk_similarity import TILE_N, TOPK_LANES

    Qp, D = qm.shape
    Np = recs.shape[0]
    assert Np % TILE_N == 0, (Np, TILE_N)
    n = jnp.asarray(n, jnp.int32)
    scores = jnp.full((Qp, TOPK_LANES), -jnp.inf, jnp.float32)
    idx = jnp.zeros((Qp, TOPK_LANES), jnp.int32)
    for i in range(Np // TILE_N):
        rec = recs[i * TILE_N : (i + 1) * TILE_N]
        if scales is not None:
            qblock = D // scales.shape[1]
            rec = rec.astype(jnp.float32) * jnp.repeat(
                scales[i * TILE_N : (i + 1) * TILE_N].astype(jnp.float32),
                qblock,
                axis=1,
            )
        s = jnp.dot(qm, rec.T, preferred_element_type=jnp.float32)
        pos = jax.lax.broadcasted_iota(jnp.int32, (Qp, TILE_N), 1) + i * TILE_N
        s = jnp.where(pos < n, s, -jnp.inf)
        cand_s = jnp.concatenate([scores, s], axis=1)
        cand_i = jnp.concatenate([idx, pos], axis=1)
        v, a = jax.lax.top_k(cand_s, TOPK_LANES)
        scores = v
        idx = jnp.take_along_axis(cand_i, a, axis=1)
    return scores, idx


def qmatmul_ref(x: jnp.ndarray, w_q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """x (M, K) f32/bf16 @ dequant(w_q (K, N) int8, scale (N,)) -> (M, N) f32."""
    w = w_q.astype(jnp.float32) * scale.astype(jnp.float32)[None, :]
    return x.astype(jnp.float32) @ w


def flash_attention_ref(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True
) -> jnp.ndarray:
    """Naive softmax attention. q: (BH, Sq, D); k/v: (BH, Sk, D)."""
    D = q.shape[-1]
    s = (
        jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
        * D**-0.5
    )
    if causal:
        Sq, Sk = q.shape[1], k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)

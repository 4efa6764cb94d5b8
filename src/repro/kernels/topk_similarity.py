"""Pallas TPU kernel: fused batched similarity + running top-k retrieval.

The RAG control plane's hot query (DESIGN.md §10): score a (Q, D) batch
of unit-norm query embeddings against the arena's (N, D) record slab and
return each query's k best records — without ever materialising the
(Q, N) score matrix. One sequential pass over (TILE_N, D) record tiles:

    for each record tile i:
        s      = q @ tile.T                  (MXU; cosine via unit norms)
        s     |= -inf past the live count    (arena capacity padding)
        topk   = k x select-max([topk | s])  (running (Q, KP) merge)

The running top-k (scores + record indices) lives in the two output refs,
revisited every grid step — the same sequential-grid accumulation pattern
as ``ota_fused``'s sum-of-squares. int8 arena tiles (the blockwise
storage class of ``retrieval/arena.py``) are dequantized in-pass from
their (TILE_N, D/qblock) scale-grid slice, so the HBM read of an int8
store is ~1/3.8 of the f32 slab.

Tie contract: descending score, equal scores by ascending record index.
The merge keeps the lower candidate position on ties, as ``lax.top_k``
does, and every merge concatenates the running list (all indices from
earlier tiles, already tie-ordered) before the current tile (ascending
positions), so the invariant holds inductively and the result is
exactly the top-k a stable brute-force scan produces. The jnp oracle
(``ref.topk_similarity_ref``) replays the same tile loop with
``lax.top_k``, so kernel and oracle agree bit for bit in interpret mode;
on the chip the MXU dot may round differently from XLA's.

The live record count ``n`` is a *traced* scalar: the arena hands the
kernel its zero-padded capacity slab, so the jit cache keys on
(Q-pad, capacity, D, k, storage class) and appends never recompile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ota_fused import repeat_lanes

TILE_N = 256  # records per grid step; arena capacity is a multiple
TOPK_LANES = 128  # running top-k width (one lane tile); k <= TOPK_LANES


def _merge_topk(score_ref, idx_ref, s, pos, i, k):
    """Fold one tile's (Q, T) scores into the running (Q, KP) top-k.

    k rounds of select-max over the candidates [running | tile]: take
    the largest live score, the lowest candidate position holding it,
    and retire that position. Lowest position among ties is exactly what
    ``lax.top_k`` keeps (Mosaic has no lowering for it), so the running
    lanes match the oracle's first k lanes; lanes past k stay -inf.
    """

    @pl.when(i == 0)
    def _init():
        score_ref[...] = jnp.full(score_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    cand_s = jnp.concatenate([score_ref[...], s], axis=1)
    cand_i = jnp.concatenate([idx_ref[...], pos], axis=1)
    Q, W = cand_s.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, score_ref.shape, 1)

    def select(r, carry):
        taken, out_s, out_i = carry
        live = jnp.where(taken > 0, -jnp.inf, cand_s)
        best = jnp.max(live, axis=1, keepdims=True)
        first = jnp.min(
            jnp.where((taken == 0) & (live == best), lane, W), axis=1, keepdims=True
        )
        hit = lane == first
        got = jnp.sum(jnp.where(hit, cand_i, 0), axis=1, keepdims=True)
        out_s = jnp.where(out_lane == r, best, out_s)
        out_i = jnp.where(out_lane == r, got, out_i)
        return jnp.where(hit, 1, taken), out_s, out_i

    init = (
        jnp.zeros((Q, W), jnp.int32),
        jnp.full(score_ref.shape, -jnp.inf, jnp.float32),
        jnp.zeros(idx_ref.shape, jnp.int32),
    )
    _, out_s, out_i = jax.lax.fori_loop(0, k, select, init)
    score_ref[...] = out_s
    idx_ref[...] = out_i


def _tile_scores(q, rec, i, n):
    s = jnp.dot(q, rec.T, preferred_element_type=jnp.float32)
    Qp, T = s.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (Qp, T), 1) + i * T
    return jnp.where(pos < n, s, -jnp.inf), pos


def _topk_f32_kernel(n_ref, q_ref, r_ref, score_ref, idx_ref, *, k):
    i = pl.program_id(0)
    s, pos = _tile_scores(q_ref[...], r_ref[...], i, n_ref[0, 0])
    _merge_topk(score_ref, idx_ref, s, pos, i, k)


def _topk_int8_kernel(n_ref, q_ref, r_ref, s_ref, score_ref, idx_ref, *, qblock, k):
    """int8 variant: dequantize the record tile in-VMEM from its blockwise
    scale slice (``qblock`` dims per scale, the arena storage class)."""
    i = pl.program_id(0)
    scales = s_ref[...].astype(jnp.float32)
    rec = r_ref[...].astype(jnp.float32) * repeat_lanes(
        scales, 0, scales.shape[1], qblock
    )
    s, pos = _tile_scores(q_ref[...], rec, i, n_ref[0, 0])
    _merge_topk(score_ref, idx_ref, s, pos, i, k)


def topk_similarity_2d(
    qm, recs, scales, n, *, k: int = TOPK_LANES, interpret: bool = False
):
    """qm: (Qp, D) f32 queries; recs: (Np, D) f32 or int8 record slab with
    Np % TILE_N == 0 (the arena capacity buffer, zero-padded); scales:
    (Np, D // qblock) f32 scale grid for int8 recs, None for f32; n: ()
    live record count (positions >= n score -inf).

    Returns (scores (Qp, TOPK_LANES) f32, idx (Qp, TOPK_LANES) int32),
    each row sorted by the tie contract. The first k <= TOPK_LANES
    lanes are the top-k; lanes past k (and past n) are -inf.
    """
    Qp, D = qm.shape
    Np = recs.shape[0]
    assert Np % TILE_N == 0, (Np, TILE_N)
    assert 0 < k <= TOPK_LANES, k
    grid = (Np // TILE_N,)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    qspec = pl.BlockSpec((Qp, D), lambda i: (0, 0))
    rspec = pl.BlockSpec((TILE_N, D), lambda i: (i, 0))
    out_specs = [
        pl.BlockSpec((Qp, TOPK_LANES), lambda i: (0, 0)),
        pl.BlockSpec((Qp, TOPK_LANES), lambda i: (0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Qp, TOPK_LANES), jnp.float32),
        jax.ShapeDtypeStruct((Qp, TOPK_LANES), jnp.int32),
    ]
    n2d = jnp.asarray(n, jnp.int32).reshape(1, 1)
    if recs.dtype == jnp.int8:
        nb = scales.shape[1]
        assert D % nb == 0, (D, nb)
        sspec = pl.BlockSpec((TILE_N, nb), lambda i: (i, 0))
        return pl.pallas_call(
            functools.partial(_topk_int8_kernel, qblock=D // nb, k=k),
            grid=grid,
            in_specs=[scalar, qspec, rspec, sspec],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(n2d, qm, recs, scales)
    return pl.pallas_call(
        functools.partial(_topk_f32_kernel, k=k),
        grid=grid,
        in_specs=[scalar, qspec, rspec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(n2d, qm, recs)

"""Batched top-k retrieval over an ``ArenaStore``.

One selection contract everywhere (DESIGN.md §10): descending score,
equal scores by ascending record index. Three implementations share it:

- ``brute_force_topk`` — the O(N log N) stable-argsort specification the
  equivalence tests anchor on;
- the numpy engine path — one GEMM over the live slab plus
  ``stable_topk`` (argpartition + tie repair), the CPU perf path. The
  GEMM is the *same* ``queries @ slab.T`` call the brute force makes, so
  on f32 stores the engine's top-k equals the brute-force results
  exactly, scores included;
- the Pallas kernel / jnp-oracle path (``kernels.ops.topk_cosine``) —
  the TPU path, streamed over record tiles with a running in-kernel
  top-k, bit-equal to its oracle in interpret mode.

As with the OTA data plane, the kernel runs by default only on TPU
(interpret-mode Pallas is a correctness tool); off-TPU the engine uses
the numpy path unless ``use_kernel`` forces otherwise. The device path
keeps at most ``TOPK_LANES`` candidates, so a larger k there is a
ValueError, never a silent switch to the host.

Mesh sharding (DESIGN.md §15): construct the engine with ``mesh`` (a
``data``-axis device mesh) to place the slab rows across devices — the
per-shard fused top-k plus the exact lane merge is bit-identical to the
unsharded jax path, scores and indices. ``n_shards`` instead shards on
the host (per-shard GEMM + ``merge_candidates``), bounding peak f32
bytes at ~1/n_shards under the same tie contract.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from repro import obs
from repro.retrieval.arena import ArenaStore

# int8 stores dequantize in row chunks of this size on the numpy path so
# a large arena never materialises its full f32 slab
CHUNK_ROWS = 1 << 15


@functools.lru_cache(maxsize=None)
def _default_use_kernel() -> bool:
    """Kernel path on TPU only, as in core/ota.py. Memoized: the first
    ``jax.devices()`` call initializes the backend (~0.1s) and must not
    recur per query."""
    import jax

    return jax.devices()[0].platform == "tpu"


def stable_topk(scores: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact (Q, k) top-k of a (Q, N) score matrix under the tie contract.

    A partition's boundary splits tied scores arbitrarily, so only the
    kth-largest *value* is taken from ``np.partition`` (cheaper than
    argpartition: no index payload to permute); candidates are then
    re-gathered from that threshold upward and stable-sorted by
    (-score, index) — duplicates always resolve to the lowest record
    indices, matching ``brute_force_topk`` and the kernel's running
    ``lax.top_k`` merge.
    """
    q, n = scores.shape
    k = min(k, n)
    if k == n:
        order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    else:
        thresh = np.partition(scores, n - k, axis=1)[:, n - k]
        order = np.empty((q, k), np.int64)
        for r in range(q):
            row = scores[r]
            cand = np.nonzero(row >= thresh[r])[0]
            order[r] = cand[np.lexsort((cand, -row[cand]))][:k]
    return np.take_along_axis(scores, order, axis=1), order.astype(np.int32)


def brute_force_topk(
    vectors: np.ndarray, queries: np.ndarray, k: int
) -> Tuple[np.ndarray, np.ndarray]:
    """The specification: full scores, full stable argsort, slice k."""
    scores = queries @ vectors.T
    k = min(k, vectors.shape[0])
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(scores, order, axis=1), order.astype(np.int32)


def merge_candidates(cand_s, cand_i, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Exact k-way merge of per-chunk top-k candidate lists under the
    tie contract: any global top-k member is top-k within its chunk, so
    re-sorting the concatenated candidates by (-score, ascending global
    index) — ``np.lexsort``'s last-key-primary order — reproduces the
    global selection exactly. Shared by the int8 chunked path and the
    host-sharded path (DESIGN.md §15)."""
    s_all = np.concatenate(cand_s, axis=1)
    i_all = np.concatenate(cand_i, axis=1)
    q = s_all.shape[0]
    k = min(k, s_all.shape[1])
    scores = np.empty((q, k), np.float32)
    idx = np.empty((q, k), np.int32)
    for r in range(q):
        order = np.lexsort((i_all[r], -s_all[r]))[:k]
        scores[r] = s_all[r, order]
        idx[r] = i_all[r, order]
    return scores, idx


def normalize_rows(mat: np.ndarray) -> np.ndarray:
    """Unit-normalize rows; all-zero rows stay zero (the zero-norm query
    guard — downstream similarity filters drop their sim-0 hits)."""
    mat = np.asarray(mat, np.float32)
    norms = np.linalg.norm(mat, axis=1, keepdims=True)
    return np.where(norms > 0, mat / np.maximum(norms, 1e-30), mat)


class RetrievalEngine:
    """Batched cosine top-k queries against one arena."""

    def __init__(
        self,
        store: ArenaStore,
        *,
        use_kernel: Optional[bool] = None,
        mesh=None,
        n_shards: int = 0,
    ):
        self.store = store
        self.use_kernel = use_kernel
        # mesh-sharded data plane (DESIGN.md §15): with ``mesh`` (a
        # ``data``-axis device mesh, launch.mesh.make_data_mesh) the
        # slab rows place across devices and queries run the sharded
        # fused top-k — bit-identical to the unsharded jax path.
        # ``n_shards`` > 1 instead shards on the host: per-shard GEMM +
        # exact merge (the int8 chunked machinery over shard bounds) —
        # ~1/n_shards peak f32 bytes, same tie contract.
        self.mesh = mesh
        self.n_shards = int(n_shards)
        # device copies of the arena slab for the kernel path, keyed on
        # (buffer identity, live count): appends (new n) and grows (new
        # buffer) invalidate; repeated queries between appends reuse the
        # upload instead of re-transferring the whole capacity slab
        self._dev_cache = None

    def topk(self, queries: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(Q, D) query batch -> (scores (Q, k'), idx (Q, k')) with
        k' = min(k, len(store)); empty stores return zero-width arrays."""
        queries = np.ascontiguousarray(np.asarray(queries, np.float32))
        if queries.ndim != 2 or queries.shape[1] != self.store.dim:
            raise ValueError(f"expected (Q, {self.store.dim}), got {queries.shape}")
        q = queries.shape[0]
        n = len(self.store)
        k = min(k, n)
        if n == 0 or k <= 0 or q == 0:
            return np.zeros((q, 0), np.float32), np.zeros((q, 0), np.int32)
        with obs.span("retrieval.query", q=q, k=k, rows=n):
            obs.metrics.inc("retrieval.query_rows", q * n)
            use_kernel = self.use_kernel
            if use_kernel is None:
                use_kernel = _default_use_kernel()
            from repro.kernels.ops import kernel_path
            from repro.kernels.topk_similarity import TOPK_LANES

            device = use_kernel or self.mesh is not None
            path = kernel_path(use_kernel) if device else "numpy"
            obs.metrics.inc("retrieval.queries", q, path=path)

            if device and k > TOPK_LANES:
                raise ValueError(
                    f"k={k}: the device top-k keeps at most {TOPK_LANES} lanes"
                )
            if self.mesh is not None:
                return self._topk_jax_sharded(queries, k, use_kernel)
            if use_kernel:
                return self._topk_jax(queries, k)
            if self.n_shards > 1:
                return self._topk_numpy_sharded(queries, k)
            return self._topk_numpy(queries, k)

    def _topk_numpy(self, queries, k):
        store = self.store
        n = len(store)
        if store.storage == "f32":
            scores = queries @ store.vectors().T
            return stable_topk(scores, k)
        # int8: per-chunk candidates, then one stable merge — any global
        # top-k member is top-k within its chunk, so the merge is exact
        cand_s, cand_i = [], []
        for lo in range(0, n, CHUNK_ROWS):
            hi = min(lo + CHUNK_ROWS, n)
            s, i = stable_topk(queries @ store.dequantize_rows(lo, hi).T, k)
            cand_s.append(s)
            cand_i.append(i + lo)
        return merge_candidates(cand_s, cand_i, k)

    def _topk_numpy_sharded(self, queries, k):
        """Host-sharded numpy path: per-shard GEMM + top-k over the
        arena's shard bounds, then the exact merge. The selection obeys
        the tie contract against the per-shard GEMM scores; note BLAS
        may pick different microkernels per GEMM shape, so last-ulp
        score agreement with the single-GEMM path is not guaranteed —
        the bitwise-locked multi-device lane is the jax path
        (``_topk_jax_sharded``), see DESIGN.md §15."""
        store, n = self.store, len(self.store)
        cand_s, cand_i = [], []
        with obs.span("shard_merge", shards=self.n_shards, k=k):
            for lo, hi in store.shard_bounds(self.n_shards):
                hi = min(hi, n)
                if hi <= lo:
                    continue
                s, i = stable_topk(queries @ store.dequantize_rows(lo, hi).T, k)
                cand_s.append(s)
                cand_i.append(i + lo)
            return merge_candidates(cand_s, cand_i, k)

    def _topk_jax_sharded(self, queries, k, use_kernel: bool):
        """Mesh-sharded fused top-k (DESIGN.md §15): slab rows place
        across the mesh's ``data`` axis, each shard runs the fused tile
        loop locally, and the lane merge reproduces the unsharded
        selection bit-identically (``kernels.ops.topk_cosine_sharded``).
        The slab is padded to shards * shard_rows with the arena's own
        zero-row/unit-scale convention before upload."""
        import jax.numpy as jnp

        from repro.kernels.ops import topk_cosine_sharded

        store = self.store
        n_shards = self.mesh.shape["data"]
        data, scales = store.raw()
        pad = n_shards * store.shard_rows(n_shards) - data.shape[0]
        cache = self._dev_cache
        if cache is None or cache[0] is not data or cache[1] != len(store):
            dd, ss = data, scales
            if pad:
                dd = np.concatenate(
                    [dd, np.zeros((pad, dd.shape[1]), dd.dtype)]
                )
                if ss is not None:
                    ss = np.concatenate(
                        [ss, np.ones((pad, ss.shape[1]), np.float32)]
                    )
            cache = (
                data,
                len(store),
                jnp.asarray(dd),
                None if ss is None else jnp.asarray(ss),
            )
            self._dev_cache = cache
        with obs.span("shard_merge", shards=n_shards, k=k):
            s, i = topk_cosine_sharded(
                jnp.asarray(queries),
                cache[2],
                cache[3],
                jnp.int32(len(store)),
                k=k,
                mesh=self.mesh,
                use_kernel=use_kernel,
            )
            return np.asarray(s), np.asarray(i)

    def _topk_jax(self, queries, k):
        import jax.numpy as jnp

        from repro.kernels.ops import topk_cosine

        data, scales = self.store.raw()
        cache = self._dev_cache
        if cache is None or cache[0] is not data or cache[1] != len(self.store):
            cache = (
                data,
                len(self.store),
                jnp.asarray(data),
                None if scales is None else jnp.asarray(scales),
            )
            self._dev_cache = cache
        dev_data, dev_scales = cache[2], cache[3]
        s, i = topk_cosine(
            jnp.asarray(queries),
            dev_data,
            dev_scales,
            jnp.int32(len(self.store)),
            k=k,
        )
        return np.asarray(s), np.asarray(i)

"""Retrieval subsystem (DESIGN.md §10): arena growth, the int8 blockwise
storage class, batched top-k == brute force exactly on f32 stores, the
Pallas kernel == jnp oracle bitwise on ragged record counts, tie/edge
semantics, ckpt round-trips, and the cohort-batched planner parity."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.profiling import RAGPlanner, make_fleet, make_users, plan_round
from repro.core.profiling.ragdb import (
    ContextQuantFeedbackDB,
    HardwareQuantPerfDB,
    VectorStore,
    embed_batch,
    embed_features,
)
from repro.core.profiling.users import satisfaction_score, true_performance
from repro.kernels.ops import topk_cosine
from repro.kernels.topk_similarity import TILE_N, TOPK_LANES
from repro.retrieval import (
    ArenaStore,
    RetrievalEngine,
    brute_force_topk,
    normalize_rows,
    stable_topk,
)


def _unit_rows(n, d=256, seed=0):
    rng = np.random.RandomState(seed)
    return normalize_rows(rng.randn(n, d).astype(np.float32))


# ---------------------------------------------------------------------------
# arena storage
# ---------------------------------------------------------------------------


def test_arena_growth_preserves_vectors():
    vecs = _unit_rows(3000, d=64, seed=1)
    st = ArenaStore(64)
    st.add_batch(vecs[:100])
    for v in vecs[100:200]:
        st.add(v)
    st.add_batch(vecs[200:])
    assert len(st) == 3000
    assert st.capacity % TILE_N == 0 and st.capacity >= 3000
    np.testing.assert_array_equal(st.vectors(), vecs)
    # capacity padding stays exact zeros (the kernel feeds on the raw slab)
    data, _ = st.raw()
    assert not np.any(data[3000:])


def test_arena_int8_blockwise_roundtrip_error_bounded():
    vecs = _unit_rows(300, d=256, seed=2)
    st = ArenaStore(256, storage="int8", qblock=64)
    st.add_batch(vecs)
    deq = st.vectors()
    # RTN on the symmetric amax/127 grid: error <= scale/2 per element
    amax = np.abs(vecs.reshape(300, 4, 64)).max(axis=2)
    bound = np.repeat(np.maximum(amax, 1e-12) / 127.0, 64, axis=1) / 2
    assert np.all(np.abs(deq - vecs) <= bound + 1e-7)
    assert st.nbytes < 0.3 * vecs.nbytes


# ---------------------------------------------------------------------------
# batched top-k == brute force, kernel == oracle
# ---------------------------------------------------------------------------


def test_engine_equals_brute_force_exactly_f32():
    vecs = _unit_rows(1500, seed=3)
    st = ArenaStore(256)
    st.add_batch(vecs)
    queries = _unit_rows(9, seed=4)
    s_eng, i_eng = RetrievalEngine(st, use_kernel=False).topk(queries, 20)
    s_bf, i_bf = brute_force_topk(st.vectors(), queries, 20)
    np.testing.assert_array_equal(i_eng, i_bf)
    np.testing.assert_array_equal(s_eng, s_bf)  # scores too, bit-for-bit


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_kernel_bit_equal_to_oracle_ragged_n(storage):
    """N = 777 is not a multiple of the 256-record tile: the capacity
    slab is padded and the live-count mask must hide the tail."""
    vecs = _unit_rows(777, seed=5)
    st = ArenaStore(256, storage=storage)
    st.add_batch(vecs)
    queries = jnp.asarray(_unit_rows(5, seed=6))
    data, scales = st.raw()
    data = jnp.asarray(data)
    scales = None if scales is None else jnp.asarray(scales)
    n = jnp.int32(len(st))
    s_k, i_k = topk_cosine(queries, data, scales, n, k=33, use_kernel=True)
    s_o, i_o = topk_cosine(queries, data, scales, n, k=33, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_o))
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_o))
    # and the kernel's selection matches the numpy engine's
    s_np, i_np = RetrievalEngine(st, use_kernel=False).topk(np.asarray(queries), 33)
    np.testing.assert_array_equal(np.asarray(i_k), i_np)
    np.testing.assert_allclose(np.asarray(s_k), s_np, rtol=1e-5, atol=1e-6)


def test_kernel_path_through_engine_matches_numpy_path():
    vecs = _unit_rows(600, seed=7)
    st = ArenaStore(256)
    st.add_batch(vecs)
    queries = _unit_rows(3, seed=8)
    s_k, i_k = RetrievalEngine(st, use_kernel=True).topk(queries, 10)
    s_n, i_n = RetrievalEngine(st, use_kernel=False).topk(queries, 10)
    np.testing.assert_array_equal(i_k, i_n)
    np.testing.assert_allclose(s_k, s_n, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# tie and edge semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_tied_scores_resolve_to_lowest_indices(use_kernel):
    """Duplicate records score identically; the contract returns them in
    ascending record-index order — in every engine path."""
    v = _unit_rows(2, seed=9)
    st = ArenaStore(256)
    st.add_batch(np.stack([v[0]] * 10 + [v[1]] * 3))
    scores, idx = RetrievalEngine(st, use_kernel=use_kernel).topk(v[:1], 12)
    np.testing.assert_array_equal(idx[0], np.arange(12))
    assert np.all(scores[0, :10] == scores[0, 0])


def test_empty_store_and_k_greater_than_n():
    st = ArenaStore(256)
    queries = _unit_rows(4, seed=10)
    scores, idx = RetrievalEngine(st, use_kernel=False).topk(queries, 8)
    assert scores.shape == (4, 0) and idx.shape == (4, 0)
    st.add_batch(_unit_rows(5, seed=11))
    scores, idx = RetrievalEngine(st, use_kernel=False).topk(queries, 50)
    assert scores.shape == (4, 5)  # k clamps to n
    s_bf, i_bf = brute_force_topk(st.vectors(), queries, 50)
    np.testing.assert_array_equal(idx, i_bf)


def test_stable_topk_full_width_matches_argsort():
    rng = np.random.RandomState(12)
    scores = rng.randn(3, 40).astype(np.float32)
    scores[:, 7] = scores[:, 21]  # plant exact ties
    s_a, i_a = stable_topk(scores, 40)
    order = np.argsort(-scores, axis=1, kind="stable")
    np.testing.assert_array_equal(i_a, order)
    s_b, i_b = stable_topk(scores, 11)
    np.testing.assert_array_equal(i_b, order[:, :11])
    np.testing.assert_array_equal(s_b, s_a[:, :11])


def test_zero_norm_query_guard():
    legacy = VectorStore()
    db = ContextQuantFeedbackDB()
    for store in (legacy, db):
        store.add({"loc_bedroom": 1.0}, {"bits": 8, "satisfaction": 0.5, "perf": {}})
    assert legacy.query({}) == []
    assert db.query({}) == []
    assert db.estimate_satisfaction({}, 8) is None
    # zero rows inside a batch: sim-0 hits, filtered by the estimators
    hits = db.query_batch(np.zeros((1, 256), np.float32), 4)
    assert all(s == 0.0 for s, _ in hits[0])


# ---------------------------------------------------------------------------
# int8 retrieval quality
# ---------------------------------------------------------------------------


def test_int8_recall_close_to_f32():
    vecs = _unit_rows(2000, seed=13)
    st32 = ArenaStore(256)
    st8 = ArenaStore(256, storage="int8")
    st32.add_batch(vecs)
    st8.add_batch(vecs)
    queries = normalize_rows(vecs[:32] + 0.05 * _unit_rows(32, seed=14))
    _, i32 = RetrievalEngine(st32, use_kernel=False).topk(queries, 10)
    _, i8 = RetrievalEngine(st8, use_kernel=False).topk(queries, 10)
    overlap = np.mean([len(set(a) & set(b)) / 10 for a, b in zip(i32, i8)])
    assert overlap >= 0.8, overlap
    assert st8.nbytes <= 0.3 * st32.nbytes


# ---------------------------------------------------------------------------
# arena DBs vs the legacy oracle, persistence
# ---------------------------------------------------------------------------


def test_arena_db_matches_legacy_oracle():
    rng = np.random.RandomState(15)
    legacy = VectorStore()
    db = HardwareQuantPerfDB()
    feats = []
    for i in range(200):
        f = {f"k{rng.randint(6)}": float(rng.uniform(0.1, 2.0))}
        feats.append(f)
        payload = {"bits": int(rng.choice([4, 8, 16])), "perf": {"x": float(i)}}
        legacy.add(f, payload)
        db.add(f, payload)
    for f in feats[:20]:
        a = legacy.query(f, k=9)
        b = db.query(f, k=9)
        xa = [rec.payload["perf"]["x"] for _, rec in a]
        xb = [rec.payload["perf"]["x"] for _, rec in b]
        assert xa == xb
        np.testing.assert_allclose(
            [s for s, _ in a], [s for s, _ in b], rtol=1e-5, atol=1e-6
        )


def _make_db(storage):
    db = ContextQuantFeedbackDB()
    if storage != "f32":
        db.arena = ArenaStore(256, storage=storage)
        db.engine = RetrievalEngine(db.arena, use_kernel=False)
    return db


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_store_save_restore_and_append_only_writeback(tmp_path, storage):
    db = _make_db(storage)
    for i in range(40):
        db.add_feedback({"loc_bedroom": 1.0, f"u{i}": 0.3}, 8, i / 40.0, {})
    path = str(tmp_path / f"cqf_{storage}.ckpt")
    db.save(path)
    fresh = _make_db(storage)
    fresh.restore(path)
    assert len(fresh) == len(db) == 40
    q = {"loc_bedroom": 1.0}
    got = [(s, rec.payload["satisfaction"]) for s, rec in fresh.query(q, 6)]
    want = [(s, rec.payload["satisfaction"]) for s, rec in db.query(q, 6)]
    assert got == want
    # feedback writeback after restore is append-only and queryable
    fresh.add_feedback({"loc_kitchen": 1.0}, 4, 0.9, {})
    assert len(fresh) == 41
    top = fresh.query({"loc_kitchen": 1.0}, 1)
    assert top[0][1].payload["bits"] == 4


# ---------------------------------------------------------------------------
# cohort-batched planning
# ---------------------------------------------------------------------------


def test_plan_cohort_matches_per_client_plan():
    users = make_users(20, seed=21)
    fleet = make_fleet(20, seed=21)
    a = RAGPlanner(seed=21)
    b = RAGPlanner(seed=21)
    for _ in range(3):
        da = plan_round(a.plan(users, fleet))
        db = plan_round(b.plan_cohort(users, fleet))
        assert [d.bits for d in da] == [d.bits for d in db]
        for d, u, s in zip(da, users, fleet):
            sat = satisfaction_score(u, s, d.bits)
            perf = true_performance(u, s, d.bits)
            a.observe_feedback(u, s, d.bits, sat, perf)
            b.observe_feedback(u, s, d.bits, sat, perf)
    assert len(a.cqf_db) == len(b.cqf_db) > 0


def test_plan_cohort_empty_cohort_and_subclass_override():
    from repro.core.profiling.planner import PlanDecision

    assert RAGPlanner(seed=0).plan_cohort([], []) == []

    class FloorBitsPlanner(RAGPlanner):
        def plan(self, users, specs, **kw):
            return [
                PlanDecision(u.user_id, min(s.supported_bits), 0.0, [])
                for u, s in zip(users, specs)
            ]

    users = make_users(5, seed=30)
    fleet = make_fleet(5, seed=30)
    # a customized per-client pipeline must not be bypassed by the
    # batched entry point the FL server calls
    got = FloorBitsPlanner(seed=30).plan_cohort(users, fleet)
    assert [d.bits for d in got] == [min(s.supported_bits) for s in fleet]


def test_query_batch_equals_serial_queries():
    db = ContextQuantFeedbackDB()
    rng = np.random.RandomState(22)
    for i in range(120):
        db.add_feedback(
            {f"f{rng.randint(8)}": float(rng.uniform(0.2, 1.5))},
            int(rng.choice([4, 8, 16])),
            float(rng.uniform()),
            {},
        )
    feats = [{f"f{i % 8}": 1.0} for i in range(10)]
    batched = db.query_batch(embed_batch(feats), k=12)
    for f, hits in zip(feats, batched):
        serial = db.query(f, k=12)
        assert [id(rec) for _, rec in serial] == [id(rec) for _, rec in hits]


def test_embed_batch_matches_embed_features():
    feats = [{"a": 1.0}, {"b": 0.5, "c": 0.2}, {}]
    mat = embed_batch(feats)
    assert mat.shape == (3, 256)
    for row, f in zip(mat, feats):
        np.testing.assert_array_equal(row, embed_features(f))


def test_topk_lanes_bound_enforced():
    st = ArenaStore(256)
    st.add_batch(_unit_rows(300, seed=23))
    queries = _unit_rows(2, seed=24)
    # k beyond the kernel's running top-k width is refused on the device
    # path (never a silent drop to the host); the numpy path serves it
    with pytest.raises(ValueError, match=f"k={TOPK_LANES + 50}"):
        RetrievalEngine(st, use_kernel=True).topk(queries, TOPK_LANES + 50)
    scores, idx = RetrievalEngine(st, use_kernel=False).topk(queries, TOPK_LANES + 50)
    assert scores.shape == (2, TOPK_LANES + 50)
    s_bf, i_bf = brute_force_topk(st.vectors(), queries, TOPK_LANES + 50)
    np.testing.assert_array_equal(idx, i_bf)

"""Mesh-sharded data planes == single-host oracles, *bitwise*
(DESIGN.md §15), on a real forced-8-device mesh.

Every equivalence test runs in a forced-multi-device subprocess
(tests/_multidevice.py) and asserts byte identity (``tobytes``), not
allclose: the sharded OTA fold places the symbol axis across shards and
combines by concatenation, and the sharded retrieval top-k re-merges
per-shard lanes under the engine tie contract — both are bit-identical
to their unsharded paths by construction, which is exactly what these
tests pin. Host-side helpers (shard bounds, chunk alignment, the numpy
host-sharded engine) are tested in-process.
"""

import numpy as np

from _multidevice import run_multidevice


def _header(**params) -> str:
    return "".join(f"{k} = {v!r}\n" for k, v in params.items())


# --- OTA: sharded fold vs ota_aggregate_packed -------------------------

_OTA_BODY = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ota, packing, wire
from repro.launch.mesh import make_data_mesh

assert len(jax.devices()) == 8, jax.devices()
rng = np.random.RandomState(SEED)
tree = {"a": jnp.zeros((3000,), jnp.float32),
        "b": jnp.zeros((17, 5), jnp.float32)}
layout = packing.make_layout(tree)
key = jax.random.key(3)
sr = ota.derive_sr_seed(key)
rows = []
for j, b in enumerate(BITS):
    full = np.zeros(layout.padded_size, np.float32)
    full[: layout.size] = rng.randn(layout.size).astype(np.float32)
    rows.append(wire.encode_row(jnp.asarray(full), b, sr, j, block=BLOCK))
w = (rng.rand(len(rows)) + 0.5).astype(np.float32)
g = None if GAINS is None else jnp.asarray(GAINS, jnp.float32)
cfg = ota.OTAConfig()
ref, _ = ota.ota_aggregate_packed(
    key, rows, [r.bits for r in rows], w, layout, cfg, gains=g,
    use_kernel=USE_KERNEL)
for D in D_LIST:
    sh, info = ota.ota_aggregate_packed(
        key, rows, [r.bits for r in rows], w, layout, cfg, gains=g,
        use_kernel=USE_KERNEL, mesh=make_data_mesh(D))
    for a, b_ in zip(jax.tree.leaves(ref), jax.tree.leaves(sh)):
        assert np.asarray(a).tobytes() == np.asarray(b_).tobytes(), D
print("ok")
"""


def _ota_case(
    *, seed=0, bits, block=64, gains=None, d_list=(2, 4, 8), use_kernel=False
):
    run_multidevice(
        _header(SEED=seed, BITS=list(bits), BLOCK=block,
                GAINS=None if gains is None else list(gains),
                D_LIST=list(d_list), USE_KERNEL=use_kernel)
        + _OTA_BODY
    )


def test_ota_sharded_int8_blockwise_bitwise():
    _ota_case(bits=[8] * 8)


def test_ota_sharded_int4_blockwise_bitwise():
    _ota_case(bits=[4] * 6, seed=1)


def test_ota_sharded_int16_blockwise_bitwise():
    _ota_case(bits=[16] * 5, seed=2)


def test_ota_sharded_f32_passthrough_bitwise():
    _ota_case(bits=[32] * 4, block=0, seed=3)


def test_ota_sharded_mixed_storage_bitwise():
    # all four storage classes in one cohort: four fold groups
    _ota_case(bits=[4, 8, 16, 32, 8, 4, 16, 32], seed=4)


def test_ota_sharded_per_update_scale_bitwise():
    # qblock = 0: one scale per update (the PR-2 wire format)
    _ota_case(bits=[8, 8, 4, 16], block=0, seed=5)


def test_ota_sharded_gains_bitwise():
    # fading-channel gains ride inside the fold; one truncated (0) row
    _ota_case(bits=[8] * 6, gains=[0.9, 0.0, 1.1, 0.7, 1.0, 0.85], seed=6)


def test_ota_sharded_ragged_cohort_bitwise():
    # K = 7 rows on 8 shards, and K = 3 < shard count: K is never
    # divided by the symbol-axis placement, so ragged cohorts are free
    _ota_case(bits=[8] * 7, seed=7)
    _ota_case(bits=[4, 8, 32], seed=8)


def test_ota_one_shard_mesh_byte_identical():
    # D = 1: the mesh path with a single shard == the non-mesh path
    _ota_case(bits=[8, 4, 32, 16], d_list=(1,), seed=9)


def test_ota_sharded_kernel_path_bitwise():
    # interpret-mode Pallas kernel inside shard_map (check_vma=False is
    # load-bearing: pallas_call has no varying-manual-axes rule)
    _ota_case(bits=[4, 8, 16, 8], d_list=(4,), seed=10, use_kernel=True)


def test_ota_accumulator_multiwave_staleness_bitwise():
    run_multidevice("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ota, packing, wire
        from repro.launch.mesh import make_data_mesh

        rng = np.random.RandomState(11)
        tree = {"a": jnp.zeros((2500,), jnp.float32)}
        layout = packing.make_layout(tree)
        key = jax.random.key(5)
        sr = ota.derive_sr_seed(key)
        rows = []
        for j, b in enumerate([8, 8, 4, 4, 16, 32]):
            full = np.zeros(layout.padded_size, np.float32)
            full[: layout.size] = rng.randn(layout.size).astype(np.float32)
            rows.append(wire.encode_row(jnp.asarray(full), b, sr, j, block=64))
        w = (rng.rand(6) + 0.5).astype(np.float32)
        stale = [0.9, 0.8, 0.7]

        def run(mesh):
            acc = ota.OtaAccumulator(layout, ota.OTAConfig(), mesh=mesh)
            acc.fold(rows[:3], w[:3])
            acc.fold(rows[3:], w[3:], staleness=stale)
            y, _ = acc.finalize(key)
            return y

        ref = run(None)
        for D in (2, 8):
            sh = run(make_data_mesh(D))
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(sh)):
                assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), D
        print("ok")
    """)


def test_fl_server_mesh_knob_round_bitwise():
    # end to end: FLConfig.mesh_data_shards=4 vs 0 — identical params.
    # TWO rounds on purpose: round 2's uplink rows are built from the
    # gathered (device-0-committed) round-1 broadcast, the placement
    # that once crashed the jitted shard_map (explicit _place fix).
    run_multidevice("""
        import numpy as np, jax
        from repro.configs.base import FLConfig
        from repro.fl import FLServer

        assert len(jax.devices()) == 8

        def run(shards):
            cfg = FLConfig(n_clients=6, clients_per_round=3, n_rounds=2,
                           local_steps=1, local_batch=2, lr=1e-3,
                           planner="unified", seed=0,
                           mesh_data_shards=shards)
            srv = FLServer(cfg, shard_size=6)
            srv.run_round(0)
            srv.run_round(1)
            return srv

        a, b = run(0), run(4)
        assert a.mesh is None and b.mesh is not None
        for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes()
        print("ok")
    """)


# --- retrieval: sharded arena top-k vs the unsharded engine ------------

_RET_BODY = """
import numpy as np, jax, jax.numpy as jnp
from repro.retrieval.arena import ArenaStore
from repro.retrieval.engine import (
    RetrievalEngine, brute_force_topk, normalize_rows)
from repro.kernels.ops import topk_cosine
from repro.launch.mesh import make_data_mesh

assert len(jax.devices()) == 8, jax.devices()
rng = np.random.RandomState(SEED)
if GRID:
    base = rng.randint(-3, 4, size=(N // 16, 64)).astype(np.float32)
    vecs = np.concatenate([base] * 16)  # heavy score ties, exact dots
    qm = rng.randint(-3, 4, size=(4, 64)).astype(np.float32)
else:
    vecs = normalize_rows(rng.randn(N, 64))
    qm = normalize_rows(rng.randn(5, 64))
store = ArenaStore(64, storage=STORAGE)
store.add_batch(vecs)

# single-host anchor: the unsharded fused-path oracle on the raw slab
data, scales = store.raw()
s0, i0 = topk_cosine(
    jnp.asarray(qm), jnp.asarray(data),
    None if scales is None else jnp.asarray(scales),
    jnp.int32(len(store)), k=K_SEL, use_kernel=False)
s0, i0 = np.asarray(s0), np.asarray(i0)
for D in D_LIST:
    eng = RetrievalEngine(store, use_kernel=False, mesh=make_data_mesh(D))
    s1, i1 = eng.topk(qm, K_SEL)
    assert s0.tobytes() == s1.tobytes(), D
    assert i0.tobytes() == i1.tobytes(), D
if GRID:  # integer grid: every path's dots are exact -> equals the spec
    sb, ib = brute_force_topk(store.vectors(), qm, K_SEL)
    assert sb.tobytes() == s0.tobytes() and ib.tobytes() == i0.tobytes()
print("ok")
"""


def _ret_case(*, seed=0, n, k, storage="f32", grid=False, d_list=(2, 4, 8)):
    run_multidevice(
        _header(SEED=seed, N=n, K_SEL=k, STORAGE=storage, GRID=grid,
                D_LIST=list(d_list))
        + _RET_BODY
    )


def test_retrieval_sharded_f32_ragged_n_bitwise():
    # n = 1000 live rows: not a multiple of the shard size, pad tiles
    # masked to -inf on the last live shard and empty trailing shards
    _ret_case(n=1000, k=16)


def test_retrieval_sharded_tied_scores_exact():
    # duplicated integer-grid rows: ties across shard boundaries must
    # resolve to ascending global index — and match brute force exactly
    _ret_case(n=640, k=20, grid=True, seed=1)


def test_retrieval_sharded_k_larger_than_shard_live():
    # k = 100 exceeds any single shard's live rows (300 over 8 shards)
    _ret_case(n=300, k=100, seed=2)


def test_retrieval_sharded_int8_bitwise():
    _ret_case(n=2000, k=32, storage="int8", seed=3)


def test_retrieval_one_shard_mesh_byte_identical():
    _ret_case(n=512, k=8, d_list=(1,), seed=4)


# --- host-side helpers: no mesh needed, run in-process -----------------


def test_arena_shard_bounds_tile_aligned_cover_capacity():
    from repro.kernels.topk_similarity import TILE_N
    from repro.retrieval.arena import ArenaStore

    store = ArenaStore(64, capacity=1024)
    for n_shards in (1, 2, 4, 8):
        bounds = store.shard_bounds(n_shards)
        assert len(bounds) == n_shards
        assert bounds[0][0] == 0 and bounds[-1][1] == store.capacity
        for (lo, hi), (lo2, _) in zip(bounds, bounds[1:]):
            assert hi == lo2  # contiguous
        for lo, hi in bounds:
            assert lo % TILE_N == 0 and lo <= hi
        rows = store.shard_rows(n_shards)
        assert rows % TILE_N == 0
        assert rows * n_shards >= store.capacity


def test_arena_shard_nbytes_reduction():
    from repro.retrieval.arena import ArenaStore

    for storage in ("f32", "int8"):
        store = ArenaStore(64, storage=storage, capacity=16384)
        full = store.shard_nbytes(1)
        assert full >= store.nbytes or len(store) == 0
        assert full / store.shard_nbytes(4) == 4.0
        assert full / store.shard_nbytes(8) == 8.0


def test_ota_shard_chunk_alignment():
    from repro.core.ota import _shard_chunk

    assert _shard_chunk(4096, 8, (("int8", 64),)) == 512
    # mixed qblocks align to the lcm so every block stays whole
    assert _shard_chunk(4096, 8, (("int8", 64), ("int16", 96))) == 576
    # int4 nibble pairs force even chunks even without blockwise scales
    assert _shard_chunk(101, 8, (("int4", 0),)) % 2 == 0
    for m, d, qb in [(3328, 8, 64), (1000, 4, 128), (17, 8, 0)]:
        kinds = (("int8", qb),)
        mc = _shard_chunk(m, d, kinds)
        assert mc * d >= m
        assert mc % 2 == 0
        if qb:
            assert mc % qb == 0


def test_numpy_sharded_engine_matches_brute_force():
    from repro.retrieval.arena import ArenaStore
    from repro.retrieval.engine import RetrievalEngine, brute_force_topk

    # f32 integer-grid fixture: every GEMM's dots are exact, so the
    # host-sharded per-shard GEMMs equal the single-GEMM brute force
    # bit for bit. (int8 dequantized slabs are NOT integer-grid — the
    # BLAS last-ulp caveat in _topk_numpy_sharded's docstring — so the
    # bitwise int8 coverage lives in the jax mesh lane above.)
    rng = np.random.RandomState(7)
    base = rng.randint(-3, 4, size=(40, 64)).astype(np.float32)
    vecs = np.concatenate([base] * 16)  # exact f32 dots + heavy ties
    qm = rng.randint(-3, 4, size=(4, 64)).astype(np.float32)
    store = ArenaStore(64)
    store.add_batch(vecs)
    sb, ib = brute_force_topk(store.vectors(), qm, 20)
    for n_shards in (2, 3, 8):
        eng = RetrievalEngine(store, n_shards=n_shards)
        s1, i1 = eng.topk(qm, 20)
        np.testing.assert_array_equal(sb, s1)
        np.testing.assert_array_equal(ib, i1)


def test_merge_candidates_tie_contract():
    from repro.retrieval.engine import merge_candidates

    # two chunks, overlapping tied scores: lowest global index wins
    s_a = np.array([[3.0, 1.0]], np.float32)
    i_a = np.array([[0, 5]], np.int32)
    s_b = np.array([[3.0, 2.0]], np.float32)
    i_b = np.array([[7, 9]], np.int32)
    s, i = merge_candidates([s_a, s_b], [i_a, i_b], 3)
    np.testing.assert_array_equal(s, [[3.0, 3.0, 2.0]])
    np.testing.assert_array_equal(i, [[0, 7, 9]])

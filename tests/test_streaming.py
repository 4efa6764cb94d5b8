"""Streaming aggregation (DESIGN.md §11): the fold kernel/oracle pair,
the persistent ``OtaAccumulator``, the ``plan_stream`` round planner,
the ``LatencyModel`` arrival simulation, and the ``StreamingFLServer``
round loop — including its equivalence oracle: no deadline + identical
arrival set => bit-identical to the synchronous ``FLServer``."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core import ota, packing
from repro.core.profiling.hardware import make_fleet
from repro.fl import FLServer, LatencyModel, StreamingFLServer, plan_stream
from repro.kernels import ops as kops
from repro.kernels import ref as kref

M = 4096
K = 5


def _rows(bits_list, block=0, seed=0):
    """Packed cohort rows (one flat leaf, quantized at the edge)."""
    rng = np.random.RandomState(seed)
    tree = {"w": jnp.zeros((M,), jnp.float32)}
    layout = packing.make_layout(tree)
    key = jax.random.key(seed + 5)
    sr = ota.derive_sr_seed(key)
    rows = []
    for i, b in enumerate(bits_list):
        up = {"w": jnp.asarray(rng.randn(M).astype(np.float32) * 0.01)}
        rows.append(ota.quantize_uplink(packing.pack(up, layout), b, sr, i,
                                        block=block))
    return rows, layout, key


# ---------------------------------------------------------------------------
# fold kernel == oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits,block", [
    (4, 0), (4, packing.QUANT_BLOCK), (8, packing.QUANT_BLOCK),
    (16, 0), (32, 0),
])
def test_fold_kernel_matches_oracle(bits, block):
    rows, layout, _ = _rows([bits] * K, block=block)
    kinds, datas, scales, _ = ota._group_rows(rows)
    assert len(kinds) == 1
    (kind, qblock), data, scale = kinds[0], datas[0], scales[0]
    rng = np.random.RandomState(7)
    acc = jnp.asarray(rng.randn(layout.padded_size).astype(np.float32))
    w = jnp.asarray(rng.rand(K).astype(np.float32))
    packed4 = kind == "int4"
    got = kops.ota_fold_packed(acc, data, scale, w, qblock=qblock,
                               packed4=packed4)
    want = kref.ota_fold_ref(acc, data, scale, w, qblock=qblock,
                             packed4=packed4)
    # kernel and oracle form the same products and differ only in the
    # order of the K-row f32 sum: bounded by ref.ota_fold_bound
    bound = kref.ota_fold_bound(acc, data, scale, w, qblock=qblock,
                                packed4=packed4)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert (err <= np.asarray(bound)).all(), float(err.max())


def test_fold_zero_acc_equals_barrier():
    rows, layout, _ = _rows([8] * K, block=packing.QUANT_BLOCK)
    kinds, datas, scales, _ = ota._group_rows(rows)
    (kind, qblock), data, scale = kinds[0], datas[0], scales[0]
    w = jnp.linspace(0.1, 1.0, K, dtype=jnp.float32)
    zeros = jnp.zeros((layout.padded_size,), jnp.float32)
    fold = kops.ota_fold_packed(zeros, data, scale, w, qblock=qblock)
    barrier = kops.ota_dequant_superpose(data, scale, w, qblock=qblock)
    np.testing.assert_array_equal(np.asarray(fold), np.asarray(barrier))


# ---------------------------------------------------------------------------
# staleness discount
# ---------------------------------------------------------------------------


def test_staleness_weights():
    w = np.asarray(ota.staleness_weights([0.0, 1.0, 2.0, 5.0], 2.0,
                                         gamma=0.5))
    assert w[0] == 1.0                       # at the trigger: full weight
    np.testing.assert_allclose(w[1], 0.5 ** 0.5, rtol=1e-6)
    np.testing.assert_allclose(w[2], 0.5)    # end of grace: gamma
    np.testing.assert_allclose(w[3], 0.5)    # clipped, never below gamma
    assert np.all(np.diff(w) <= 0)


# ---------------------------------------------------------------------------
# OtaAccumulator
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_accumulator_bit_equal_to_one_shot(use_kernel):
    """One-batch fold in cohort order == ota_aggregate_packed, bitwise."""
    rows, layout, key = _rows([4, 8, 8, 16, 32], block=packing.QUANT_BLOCK)
    weights = [1.0 + (i % 3) for i in range(K)]
    cfg = ota.OTAConfig(snr_db=20.0)
    ref, ref_info = ota.ota_aggregate_packed(key, rows, None, weights,
                                             layout, cfg,
                                             use_kernel=use_kernel)
    _, _, w = ota.round_channel(key, jnp.asarray(weights, jnp.float32),
                                cfg=cfg)
    acc = ota.OtaAccumulator(layout, cfg, use_kernel=use_kernel)
    got, info = acc.fold(rows, w).finalize(key)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert info["n_folded"] == K
    assert info["uplink_bytes"] == ref_info["uplink_bytes"]


def test_accumulator_two_wave_fold_and_reset():
    rows, layout, key = _rows([4, 8, 8, 16, 32], block=packing.QUANT_BLOCK)
    cfg = ota.OTAConfig(snr_db=20.0)
    _, _, w = ota.round_channel(key, jnp.ones((K,), jnp.float32), cfg=cfg)
    acc = ota.OtaAccumulator(layout, cfg)
    acc.fold(rows[:3], w[:3])
    acc.fold(rows[3:], w[3:], staleness=[0.7, 0.5])
    assert acc.n_folded == K
    assert acc.wire_bytes == sum(r.wire_nbytes for r in rows)
    agg, info = acc.finalize(key)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(agg))
    assert info["n_folded"] == K
    acc.reset()
    assert acc.n_folded == 0
    np.testing.assert_array_equal(np.asarray(acc.accumulator), 0.0)


# ---------------------------------------------------------------------------
# plan_stream
# ---------------------------------------------------------------------------


def test_plan_stream_all_on_time():
    p = plan_stream([3.0, 1.0, 2.0], fill=3)
    assert p.on_time == (0, 1, 2) and not p.late and not p.lost
    assert p.t_trigger == 3.0 and p.t_close == 3.0
    assert p.counted == (0, 1, 2)


def test_plan_stream_fill_triggers_early():
    p = plan_stream([1.0, 2.0, 10.0, 3.0], fill=2)
    assert p.t_trigger == 2.0
    assert p.on_time == (0, 1) and p.lost == (2, 3)


def test_plan_stream_deadline_fires_with_partial_cohort():
    p = plan_stream([1.0, 2.0, 10.0, 20.0], fill=4, deadline=5.0)
    assert p.t_trigger == 5.0
    assert p.on_time == (0, 1) and p.lost == (2, 3) and not p.late
    assert p.t_close == 5.0


def test_plan_stream_grace_window_and_staleness():
    p = plan_stream([1.0, 2.0, 3.0, 4.0, 9.0], fill=2, grace=2.0,
                    gamma=0.5)
    assert p.t_trigger == 2.0
    assert p.on_time == (0, 1) and p.late == (2, 3) and p.lost == (4,)
    np.testing.assert_allclose(p.staleness, [0.5 ** 0.5, 0.5], rtol=1e-6)
    assert p.t_close == 4.0  # the last counted late arrival ends the round


def test_plan_stream_unreachable_fill_degenerates_to_barrier():
    # fill target above the finite arrivals, no deadline: the plan falls
    # back to the synchronous barrier at the last finite arrival
    p = plan_stream([1.0, 5.0, math.inf], fill=3)
    assert p.t_trigger == 5.0
    assert p.on_time == (0, 1) and p.lost == (2,)


def test_plan_stream_everyone_dropped():
    p = plan_stream([math.inf, math.inf], fill=2, deadline=4.0)
    assert not p.on_time and not p.late and p.lost == (0, 1)
    assert p.t_trigger == 4.0 and p.counted == ()


# ---------------------------------------------------------------------------
# LatencyModel
# ---------------------------------------------------------------------------


def test_latency_model_deterministic_and_tailed():
    lat = LatencyModel.with_tail(5.0)
    np.testing.assert_allclose(lat.p95_over_p50(), 5.0, rtol=1e-3)
    spec = make_fleet(1, seed=0)[0]
    rng_a, rng_b = np.random.RandomState(3), np.random.RandomState(3)
    a = [lat.sample(spec, rng_a, uplink_bytes=1 << 16) for _ in range(2)]
    b = [lat.sample(spec, rng_b, uplink_bytes=1 << 16) for _ in range(2)]
    assert a == b and a[0] != a[1]  # seeded replay, fresh draws


def test_latency_model_low_battery_doubles_dropout():
    lat = LatencyModel(drop_prob=0.4)
    spec = make_fleet(1, seed=0)[0]
    normal = dataclasses.replace(spec, power_state="normal")
    low = dataclasses.replace(spec, power_state="low_battery")
    n = 4000
    rng = np.random.RandomState(0)
    d_norm = sum(lat.dropped(normal, rng) for _ in range(n)) / n
    d_low = sum(lat.dropped(low, rng) for _ in range(n)) / n
    assert 0.35 < d_norm < 0.45 and 0.75 < d_low < 0.85
    assert not LatencyModel().dropped(normal, rng)  # drop_prob=0: never


# ---------------------------------------------------------------------------
# StreamingFLServer
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(n_clients=6, clients_per_round=3, n_rounds=2, local_steps=1,
                local_batch=2, lr=1e-3, planner="unified", seed=0)
    base.update(kw)
    return FLConfig(**base)


def test_streaming_matches_sync_bitwise():
    """No deadline, full fill, no latency dropouts: the buffered engine
    and the synchronous barrier run the same float ops in the same order
    => bit-identical global parameters (the §11 equivalence oracle)."""
    sync = FLServer(_cfg(), shard_size=4)
    stream = StreamingFLServer(_cfg(), shard_size=4)
    for r in range(2):
        la = sync.run_round(r)
        lb = stream.run_round(r)
        assert lb.n_late == 0 and lb.n_lost == 0
        assert la.train_loss == lb.train_loss
    for a, b in zip(jax.tree.leaves(sync.params),
                    jax.tree.leaves(stream.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_everyone_lost_skips_aggregation():
    srv = StreamingFLServer(_cfg(), shard_size=4,
                            latency=LatencyModel(drop_prob=1.0))
    before = jax.tree.leaves(srv.params)[0].copy()
    log = srv.run_round(0)
    assert log.n_participating == 0 and log.n_lost == 3
    assert np.isnan(log.train_loss)
    np.testing.assert_array_equal(before, jax.tree.leaves(srv.params)[0])


def test_streaming_deadline_fires_with_partial_cohort():
    """A deadline between the first and last arrival aggregates a strict
    subset of the cohort and still moves the model."""
    lat = LatencyModel.with_tail(3.0)
    probe = StreamingFLServer(_cfg(), shard_size=4, latency=lat)
    probe.run_round(0)
    times = sorted(probe.last_times)  # same seed => same arrival draws
    assert len(times) == 3 and all(map(math.isfinite, times))
    deadline = (times[0] + times[2]) / 2
    srv = StreamingFLServer(_cfg(), shard_size=4, latency=lat,
                            deadline_s=deadline, grace_s=0.0)
    before = jax.tree.leaves(srv.params)[0].copy()
    log = srv.run_round(0)
    assert 1 <= log.n_on_time < 3 and log.n_lost >= 1 and log.n_late == 0
    assert log.n_on_time + log.n_lost == 3
    assert log.sim_seconds == deadline
    assert np.isfinite(log.train_loss)
    assert not np.array_equal(before, jax.tree.leaves(srv.params)[0])

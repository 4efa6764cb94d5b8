"""Physical OTA channel model (DESIGN.md §12): property tests pinning the
channel math bit-for-bit.

Four contracts, the last three asserted with exact (``==``) float
equality:

- kernel vs oracle: the gain-aware Pallas pass (``ota_packed_2d`` /
  ``ota_fold_2d`` with ``gains=``) matches the jnp oracles for every
  storage class, including truncated (zero-gain) rows, within the f32
  summation-order bound ``ref.ota_fold_bound``;
- ``gains=None`` regression: the unit channel is bitwise identical to
  the pre-channel aggregation, in barrier and streaming modes;
- truncation == exclusion: zero-gain rows contribute exactly nothing —
  the aggregate equals dropping those rows before aggregation;
- stream separation: the channel fading draw, the legacy channel/dither/
  noise splits, and the numpy round streams are pairwise distinct (the
  seed-reuse hazard fix in ``fl/server.round_rng``).

Runs under real hypothesis when installed, else the deterministic
fallback sampler (tests/_hypothesis_fallback.py) — tier-1 needs no
extra wheels.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # bare container: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.core import channel as chan
from repro.core import ota, packing
from repro.fl.server import round_drift_rng, round_rng
from repro.kernels import ota_fused as kf
from repro.kernels import ref as kref

M = 4096
K = 5

STORAGE = [(4, 0), (4, packing.QUANT_BLOCK), (8, 0),
           (8, packing.QUANT_BLOCK), (16, 0), (16, packing.QUANT_BLOCK),
           (32, 0)]


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


def _group(rows):
    kinds, datas, scales, _ = ota._group_rows(rows)
    assert len(kinds) == 1
    (kind, qblock), data, scale = kinds[0], datas[0], scales[0]
    return data, scale, qblock, kind == "int4"


def _assert_within(got, want, bound):
    """Kernel vs oracle: the same products summed over the K rows in
    different orders — |got - want| stays within ``ref.ota_fold_bound``
    (the f32 summation-order bound), elementwise."""
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert (err <= np.asarray(bound)).all(), float(err.max())


def _gains(rng, k, zero_first=True):
    g = rng.rand(k).astype(np.float32)
    if zero_first:
        g[0] = 0.0  # always exercise a truncated row
    return jnp.asarray(g)


# ---------------------------------------------------------------------------
# kernel == oracle with gains (property: random gains, every storage class)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=5)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(STORAGE))
def test_gain_superpose_kernel_matches_oracle(seed, storage):
    bits, block = storage
    rows, _, _ = _rows([bits] * K, block=block, seed=seed % 997)
    data, scale, qblock, packed4 = _group(rows)
    rng = np.random.RandomState(seed % 2 ** 31)
    w = jnp.asarray(rng.rand(K).astype(np.float32))
    g = _gains(rng, K)
    got = kf.ota_packed_2d(data, scale, w, gains=g, qblock=qblock,
                           packed4=packed4, interpret=True)
    want = kref.ota_packed_ref(data, scale, w, gains=g, qblock=qblock,
                               packed4=packed4)
    bound = kref.ota_fold_bound(None, data, scale, w, gains=g,
                                qblock=qblock, packed4=packed4)
    _assert_within(got, want, bound)


@settings(deadline=None, max_examples=5)
@given(st.integers(0, 2 ** 31 - 1), st.sampled_from(STORAGE))
def test_gain_fold_kernel_matches_oracle(seed, storage):
    bits, block = storage
    rows, layout, _ = _rows([bits] * K, block=block, seed=seed % 997)
    data, scale, qblock, packed4 = _group(rows)
    rng = np.random.RandomState(seed % 2 ** 31)
    acc = jnp.asarray(rng.randn(layout.padded_size).astype(np.float32))
    w = jnp.asarray(rng.rand(K).astype(np.float32))
    g = _gains(rng, K)
    got = kf.ota_fold_2d(acc, data, scale, w, gains=g, qblock=qblock,
                         packed4=packed4, interpret=True)
    want = kref.ota_fold_ref(acc, data, scale, w, gains=g, qblock=qblock,
                             packed4=packed4)
    bound = kref.ota_fold_bound(acc, data, scale, w, gains=g,
                                qblock=qblock, packed4=packed4)
    _assert_within(got, want, bound)


def test_unit_gains_bitwise_identical_superpose():
    """gains=ones must be bit-identical to the legacy gains=None program
    — kernel and oracle — for every storage class."""
    for bits, block in STORAGE:
        rows, _, _ = _rows([bits] * K, block=block)
        data, scale, qblock, packed4 = _group(rows)
        w = jnp.linspace(0.1, 0.3, K, dtype=jnp.float32)
        ones = jnp.ones((K,), jnp.float32)
        for fn, kw in ((kf.ota_packed_2d, dict(interpret=True)),
                       (kref.ota_packed_ref, {})):
            a = fn(data, scale, w, qblock=qblock, packed4=packed4, **kw)
            b = fn(data, scale, w, gains=ones, qblock=qblock,
                   packed4=packed4, **kw)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unit_gains_bitwise_identical_fold():
    for bits, block in STORAGE:
        rows, layout, _ = _rows([bits] * K, block=block)
        data, scale, qblock, packed4 = _group(rows)
        rng = np.random.RandomState(3)
        acc = jnp.asarray(rng.randn(layout.padded_size).astype(np.float32))
        w = jnp.linspace(0.1, 0.3, K, dtype=jnp.float32)
        ones = jnp.ones((K,), jnp.float32)
        for fn, kw in ((kf.ota_fold_2d, dict(interpret=True)),
                       (kref.ota_fold_ref, {})):
            a = fn(acc, data, scale, w, qblock=qblock, packed4=packed4, **kw)
            b = fn(acc, data, scale, w, gains=ones, qblock=qblock,
                   packed4=packed4, **kw)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# gains=None regression oracle: the PR-5 aggregation, composed by hand
# ---------------------------------------------------------------------------


def test_gains_none_matches_pr5_composition():
    """``ota_aggregate_packed`` without gains must equal the manual
    round_channel -> grouped oracle folds -> AWGN epilogue composition —
    the pre-channel data plane, pinned bitwise."""
    rows, layout, key = _rows([4, 8, 8, 16, 32], block=packing.QUANT_BLOCK)
    weights = jnp.asarray([1.0, 2.0, 1.5, 1.0, 0.5], jnp.float32)
    cfg = ota.OTAConfig(snr_db=17.0)
    kinds, datas, scales, perm = ota._group_rows(rows)
    _, _, w = ota.round_channel(key, weights, cfg=cfg)
    acc = ota._fold_groups(None, kinds, datas, scales, w[perm],
                           use_kernel=False)
    y, _ = ota._awgn_epilogue(key, acc, cfg=cfg, n_valid=layout.size)
    want = packing.unpack(y, layout, cast=False)
    got, _ = ota.ota_aggregate_packed(key, rows, [4, 8, 8, 16, 32],
                                      weights, layout, cfg,
                                      use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(want["w"]))


def test_accumulator_unit_gains_identical():
    """Streaming mode: folding with unit gains == folding without, bit
    for bit, across mixed storage classes."""
    rows, layout, key = _rows([4, 8, 16, 32, 4])
    w = jnp.asarray([0.2, 0.3, 0.1, 0.25, 0.15], jnp.float32)
    a0 = ota.OtaAccumulator(layout, use_kernel=False)
    a1 = ota.OtaAccumulator(layout, use_kernel=False)
    a0.fold(rows, w)
    a1.fold(rows, w, gains=jnp.ones((K,), jnp.float32))
    np.testing.assert_array_equal(np.asarray(a0.accumulator),
                                  np.asarray(a1.accumulator))


# ---------------------------------------------------------------------------
# truncation == exclusion (zero-gain rows contribute exactly nothing)
# ---------------------------------------------------------------------------


def _truncated_equals_dropped(use_kernel):
    rows, layout, key = _rows([4, 8, 8, 16, 32])
    bits = [4, 8, 8, 16, 32]
    g = jnp.asarray([0.0, 0.8, 0.0, 1.0, 0.5], jnp.float32)
    cfg = ota.OTAConfig(snr_db=20.0)
    full, info = ota.ota_aggregate_packed(key, rows, bits, [1.0] * K,
                                          layout, cfg, gains=g,
                                          use_kernel=use_kernel)
    keep = [i for i in range(K) if float(g[i]) > 0]
    sub, _ = ota.ota_aggregate_packed(
        key, [rows[i] for i in keep], [bits[i] for i in keep],
        [1.0] * len(keep), layout, cfg, gains=g[jnp.asarray(keep)],
        use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(full["w"]), np.asarray(sub["w"]))
    assert info["n_participating"] == 3
    assert info["n_truncated"] == 2
    assert info["participation"] == [False, True, False, True, True]


def test_truncated_rows_equal_dropped_rows_oracle():
    _truncated_equals_dropped(use_kernel=False)


def test_truncated_rows_equal_dropped_rows_kernel():
    _truncated_equals_dropped(use_kernel=True)


def test_single_surviving_client():
    """One non-truncated row: the aggregate is that client's update alone
    (weight renormalises to 1), bit-equal to aggregating just it."""
    rows, layout, key = _rows([8, 4, 16])
    g = jnp.asarray([0.0, 0.7, 0.0], jnp.float32)
    cfg = ota.OTAConfig(snr_db=25.0)
    full, info = ota.ota_aggregate_packed(key, rows, [8, 4, 16],
                                          [3.0, 2.0, 1.0], layout, cfg,
                                          gains=g, use_kernel=False)
    solo, _ = ota.ota_aggregate_packed(key, [rows[1]], [4], [1.0], layout,
                                       cfg, gains=g[1:2], use_kernel=False)
    np.testing.assert_array_equal(np.asarray(full["w"]), np.asarray(solo["w"]))
    assert info["n_participating"] == 1


def test_all_truncated_aggregate_is_zero_update():
    """Every row truncated: weights renormalise to all-zero (the 1e-12
    guard, no NaN) and the aggregate is the pure-zero update."""
    rows, layout, key = _rows([8, 8, 8])
    agg, info = ota.ota_aggregate_packed(
        key, rows, [8, 8, 8], [1.0, 1.0, 1.0], layout,
        ota.OTAConfig(snr_db=20.0), gains=jnp.zeros((3,), jnp.float32),
        use_kernel=False)
    arr = np.asarray(agg["w"])
    assert np.all(np.isfinite(arr))
    np.testing.assert_array_equal(arr, np.zeros_like(arr))
    assert info["n_participating"] == 0
    assert info["n_truncated"] == 3


def test_all_truncated_wave_leaves_accumulator_bit_unchanged():
    """Streaming fold of a wave whose rows are all truncated adds exact
    zeros: the accumulator value is bitwise what it was."""
    rows, layout, _ = _rows([4, 8, 16, 32, 8])
    acc = ota.OtaAccumulator(layout, use_kernel=False)
    acc.fold(rows[:2], [0.6, 0.4], gains=jnp.asarray([1.0, 0.5]))
    before = np.asarray(acc.accumulator).copy()
    acc.fold(rows[2:], [0.3, 0.3, 0.4], gains=jnp.zeros((3,), jnp.float32))
    np.testing.assert_array_equal(before, np.asarray(acc.accumulator))
    assert acc.n_folded == 5  # the wave still counts as folded traffic


# ---------------------------------------------------------------------------
# ChannelModel: truncation rule, power budget, misalignment
# ---------------------------------------------------------------------------


def test_channel_model_deterministic():
    cm = chan.ChannelModel()
    key = jax.random.key(9)
    s1, s2 = cm.sample(key, 32), cm.sample(key, 32)
    np.testing.assert_array_equal(np.asarray(s1.habs), np.asarray(s2.habs))
    np.testing.assert_array_equal(np.asarray(s1.gains), np.asarray(s2.gains))


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.01, 1.0),
       st.floats(0.5, 100.0))
def test_truncation_rule_and_gain_range(seed, threshold, budget):
    cfg = chan.ChannelConfig(fade_threshold=threshold, power_budget=budget)
    st_ = chan.ChannelModel(cfg).sample(jax.random.key(seed % 2 ** 31), 48)
    h = np.asarray(st_.habs)
    g = np.asarray(st_.gains)
    tx = np.asarray(st_.tx_amp)
    # truncate exactly when |h|^2 < threshold; gains in [0, 1]
    np.testing.assert_array_equal(g == 0.0, h ** 2 < threshold)
    assert np.all((g >= 0.0) & (g <= 1.0))
    # power budget respected with a float32 ulp of slack
    assert np.all(tx ** 2 <= budget * (1 + 1e-6))


def test_perfect_inversion_when_budget_unconstrained():
    """With a huge power budget every surviving client fully inverts:
    gain exactly 1.0 (h * (rho/h) / rho), no misalignment."""
    habs = jnp.asarray([0.4, 1.0, 2.5], jnp.float32)
    st_ = chan.state_from_habs(
        habs, cfg=chan.ChannelConfig(fade_threshold=0.01,
                                     power_budget=1e9))
    np.testing.assert_array_equal(np.asarray(st_.gains), np.ones(3))
    np.testing.assert_array_equal(np.asarray(st_.misalignment), np.zeros(3))


def test_threshold_boundary_client_participates():
    """|h|^2 exactly at the truncation threshold participates (>=)."""
    cfg = chan.ChannelConfig(fade_threshold=0.25, power_budget=100.0)
    st_ = chan.state_from_habs(jnp.asarray([0.5, 0.49999]), cfg=cfg)
    g = np.asarray(st_.gains)
    assert g[0] > 0.0  # 0.5^2 == 0.25: exactly at threshold, survives
    assert g[1] == 0.0  # just below: truncated


def test_power_budget_exactly_at_inversion_threshold():
    """A client whose full inversion needs exactly the budget amplitude
    (rho/|h| == sqrt(P)) transmits at the cap and aligns perfectly:
    gain exactly 1.0 — the cap binds but does not yet misalign."""
    budget = 16.0  # sqrt(P) = 4
    habs = jnp.asarray([0.25, 0.125], jnp.float32)  # rho/h = 4 and 8
    cfg = chan.ChannelConfig(fade_threshold=1e-4, rho=1.0,
                             power_budget=budget)
    st_ = chan.state_from_habs(habs, cfg=cfg)
    g = np.asarray(st_.gains)
    tx = np.asarray(st_.tx_amp)
    assert tx[0] == 4.0 and g[0] == 1.0  # exactly at the cap: aligned
    assert tx[1] == 4.0 and 0.0 < g[1] < 1.0  # beyond it: misaligned
    assert np.asarray(st_.misalignment)[1] > 0.0


def test_combine_weights_excludes_truncated_and_guards_zero():
    w = chan.combine_weights(jnp.asarray([1.0, 2.0, 3.0]),
                             jnp.asarray([0.0, 0.5, 1.0]))
    w = np.asarray(w)
    assert w[0] == 0.0
    np.testing.assert_allclose(w[1] + w[2], 1.0, rtol=1e-6)
    # all truncated: zeros, not NaN
    w0 = np.asarray(chan.combine_weights(jnp.ones(3), jnp.zeros(3)))
    np.testing.assert_array_equal(w0, np.zeros(3))


# ---------------------------------------------------------------------------
# stream separation (the seed-reuse hazard)
# ---------------------------------------------------------------------------


def test_channel_stream_disjoint_from_legacy_draws():
    """The channel fading key must draw differently from the round key
    itself and from every split(key, 3) child (legacy channel coin-flip,
    SR dither, AWGN) — enabling fading can't shift any legacy stream."""
    key = jax.random.key(123)
    ck = chan.derive_channel_key(key)
    others = list(jax.random.split(key, 3)) + [key]
    a = np.asarray(jax.random.bits(ck, (8,), jnp.uint32))
    for other in others:
        b = np.asarray(jax.random.bits(other, (8,), jnp.uint32))
        assert not np.array_equal(a, b)


def test_round_rng_salts_separate_at_seed_zero():
    """The old ``seed * salt + rnd`` collapsed every salt onto one
    stream at seed=0 (the FLConfig default): dropout and latency draws
    were identical. The mixed streams must now differ pairwise."""
    for rnd in (0, 1, 7):
        drop = round_rng(0, rnd).rand(6)
        lat = round_rng(0, rnd, salt=4099).rand(6)
        bench = round_rng(0, rnd, salt=6151).rand(6)
        assert not np.array_equal(drop, lat)
        assert not np.array_equal(drop, bench)
        assert not np.array_equal(lat, bench)


def test_round_streams_deterministic_and_round_varying():
    a = round_rng(3, 5).rand(4)
    np.testing.assert_array_equal(a, round_rng(3, 5).rand(4))
    assert not np.array_equal(a, round_rng(3, 6).rand(4))
    d = round_drift_rng(0, 2).random()
    assert d == round_drift_rng(0, 2).random()
    assert round_drift_rng(0, 2).random() != round_drift_rng(0, 3).random()


# ---------------------------------------------------------------------------
# FL loop wiring (barrier + streaming under fading)
# ---------------------------------------------------------------------------


def _fl_cfg(**kw):
    from repro.configs.base import FLConfig

    base = dict(n_clients=3, clients_per_round=2, n_rounds=1, local_steps=1,
                local_batch=2, lr=1e-3, planner="unified", seed=0,
                channel_model="fading", fade_threshold=0.3,
                tx_power_budget=4.0)
    base.update(kw)
    return FLConfig(**base)


def test_fading_round_runs_and_records_channel_features():
    from repro.fl import FLServer

    srv = FLServer(_fl_cfg(), shard_size=4)
    log = srv.run_round(0)
    assert math.isfinite(log.train_loss) or log.n_participating == 0
    recorded = [s for s in srv.fleet if s.channel_snr_db is not None]
    assert recorded  # radio state landed on the cohort's DeviceSpecs
    feats = recorded[0].features()
    assert "channel_snr_db" in feats and "truncation_rate" in feats


def test_client_uplink_metadata_echoes_channel_state():
    from repro.fl import FLServer

    srv = FLServer(_fl_cfg(), shard_size=4)
    _, m = srv.clients[0].local_update(
        srv.params, 8, local_steps=1, local_batch=2, lr=1e-3,
        layout=srv.layout, sr_seed=ota.derive_sr_seed(jax.random.key(0)),
        channel_gain=0.8125, channel_habs=1.5)
    assert m["channel_gain"] == 0.8125
    assert m["channel_habs"] == 1.5


def test_all_truncated_round_degenerates_like_all_dropped():
    """An impossible fade threshold truncates the whole cohort: the round
    skips aggregation exactly like the everyone-dropped round (NaN loss,
    params untouched)."""
    from repro.fl import FLServer

    srv = FLServer(_fl_cfg(fade_threshold=1e9), shard_size=4)
    before = np.asarray(jax.tree.leaves(srv.params)[0]).copy()
    log = srv.run_round(0)
    assert log.n_participating == 0
    assert math.isnan(log.train_loss)
    np.testing.assert_array_equal(
        before, np.asarray(jax.tree.leaves(srv.params)[0]))


def test_streaming_equals_barrier_under_fading():
    """No-deadline streaming round under fading == barrier round, bit
    for bit (same channel realisation, same gains in the fused pass)."""
    from repro.fl import FLServer, StreamingFLServer

    s1 = FLServer(_fl_cfg(seed=2), shard_size=4)
    s2 = StreamingFLServer(_fl_cfg(seed=2), shard_size=4)
    s1.run_round(0)
    s2.run_round(0)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

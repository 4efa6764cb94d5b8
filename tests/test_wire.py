"""Packed uplink wire format (DESIGN.md §6): row-major int4 round-trips,
edge quantization, packed-rows aggregation equivalence, and kernel/oracle
bit-equality for the dequant+superpose pass."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ota, packing, quant
from repro.kernels import ops, ref
from repro.kernels.ops import pack_int4_rows, unpack_int4_rows


# ---------------------------------------------------------------------------
# row-major int4 pack/unpack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 2, 7, 8, 63, 64, 4097])
def test_pack_int4_rows_roundtrip_odd_even(m):
    rng = np.random.RandomState(m)
    q = jnp.asarray(rng.randint(-8, 8, size=(m,)), jnp.int8)
    p = pack_int4_rows(q)
    # whole 256-symbol groups, two symbols per byte
    assert p.dtype == jnp.uint8 and p.shape == (-(-m // 256) * 128,)
    assert jnp.array_equal(unpack_int4_rows(p, m), q)
    assert not np.asarray(unpack_int4_rows(p)[m:]).any()  # zero pad


def test_pack_int4_rows_2d_and_half_bytes():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randint(-8, 8, size=(5, 512)), jnp.int8)
    p = pack_int4_rows(q)
    assert p.nbytes == q.nbytes // 2
    assert jnp.array_equal(unpack_int4_rows(p), q)


def test_pack_int4_rows_is_row_major():
    # symbols of one row share bytes (never two rows, unlike pack_int4's
    # weight layout): in each 256-symbol group, byte j holds symbol j
    # (low nibble) and symbol 128 + j (high nibble) — the planar layout
    # the in-kernel unpack concatenates without an interleaving reshape
    q = np.zeros((2, 512), np.int8)
    q[0, [0, 128, 1, 129, 256, 384]] = [1, -2, 3, -4, 5, -6]
    p = np.asarray(pack_int4_rows(jnp.asarray(q)))
    assert p.shape == (2, 256)
    assert p[0, 0] == (1 | ((-2 & 0xF) << 4))
    assert p[0, 1] == (3 | ((-4 & 0xF) << 4))
    assert p[0, 128] == (5 | ((-6 & 0xF) << 4))
    assert not p[1].any() and not np.delete(p[0], [0, 1, 128]).any()


# ---------------------------------------------------------------------------
# client-side uplink quantization
# ---------------------------------------------------------------------------


def test_quantize_row_sr_storage_classes():
    row = jnp.asarray(np.random.RandomState(1).randn(256), jnp.float32)
    seed = jnp.uint32(7)
    for bits, dtype in [(4, jnp.int8), (8, jnp.int8), (16, jnp.int16)]:
        q, scale = quant.quantize_row_sr(row, bits, seed, 0)
        assert q.dtype == dtype
        assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) <= quant.qrange(bits)
        assert float(scale) > 0
    q32, s32 = quant.quantize_row_sr(row, 32, seed, 0)
    assert q32.dtype == jnp.float32 and float(s32) == 1.0
    np.testing.assert_array_equal(np.asarray(q32), np.asarray(row))


def test_quantize_uplink_padding_stays_zero():
    tree = {"w": jnp.asarray(np.random.RandomState(2).randn(100), jnp.float32)}
    lay = packing.make_layout(tree)
    flat = packing.pack(tree, lay)
    for bits in (4, 8, 16):
        r = ota.quantize_uplink(flat, bits, jnp.uint32(3), 1)
        q = unpack_int4_rows(r.data) if r.kind == "int4" else r.data
        assert int(jnp.abs(q[lay.size :].astype(jnp.int32)).max()) == 0


def test_wire_bytes_4bit_cohort_under_one_seventh():
    """Acceptance: a 4-bit cohort's uplink <= 1/7 the f32 bytes."""
    tree = {"w": jnp.asarray(np.random.RandomState(3).randn(5000), jnp.float32)}
    lay = packing.make_layout(tree)
    flat = packing.pack(tree, lay)
    K = 4
    rows = [ota.quantize_uplink(flat, 4, jnp.uint32(9), i) for i in range(K)]
    wire = sum(r.wire_nbytes for r in rows)
    f32 = 4 * lay.padded_size * K
    assert wire <= f32 / 7, (wire, f32)
    assert packing.row_wire_bytes(4, lay.padded_size) == rows[0].wire_nbytes


# ---------------------------------------------------------------------------
# packed-rows aggregation: equivalence + bit-equality
# ---------------------------------------------------------------------------


def _mixed_updates(n, seed=7):
    rng = np.random.RandomState(seed)
    return [
        {
            "w": jnp.asarray(rng.randn(40, 13).astype(np.float32)),
            "b": [
                jnp.asarray(rng.randn(77).astype(np.float32)),
                jnp.asarray(rng.randn(3, 5, 2).astype(np.float32)),
            ],
        }
        for _ in range(n)
    ]


def _rows_of(ups, bits, lay, key):
    sr = ota.derive_sr_seed(key)
    return [
        ota.quantize_uplink(packing.pack(u, lay), b, sr, i)
        for i, (u, b) in enumerate(zip(ups, bits))
    ]


def test_packed_rows_match_pertree_oracle():
    """Edge-quantized packed rows == the per-tree loop == the f32 matrix
    path, for the same round key (shared dither stream)."""
    ups = _mixed_updates(6)
    bits = [4, 8, 16, 32, 8, 4]
    weights = [1.0, 2.0, 0.5, 1.0, 3.0, 1.5]
    lay = packing.make_layout(ups[0])
    for snr in (80.0, 15.0):
        cfg = ota.OTAConfig(snr_db=snr)
        key = jax.random.key(123)
        rows = _rows_of(ups, bits, lay, key)
        packed, info_p = ota.ota_aggregate_packed(key, rows, bits, weights, lay, cfg)
        tree, info_t = ota.ota_aggregate_pertree(key, ups, bits, weights, cfg)
        flat, _ = ota.ota_aggregate(key, ups, bits, weights, cfg)
        assert jax.tree.structure(packed) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(tree)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )
        for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(flat)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )
        assert info_p["participation"] == info_t["participation"]
        assert abs(info_p["noise_std"] - info_t["noise_std"]) < 1e-6


def test_packed_rows_via_ota_aggregate_entrypoint():
    ups = _mixed_updates(4, seed=19)
    bits = [8, 8, 4, 16]
    weights = [1.0, 0.5, 2.0, 1.0]
    lay = packing.make_layout(ups[0])
    key = jax.random.key(77)
    rows = _rows_of(ups, bits, lay, key)
    a, _ = ota.ota_aggregate(key, rows, bits, weights, layout=lay)
    b, _ = ota.ota_aggregate_packed(key, rows, bits, weights, lay)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_packed_kernel_bit_equal_to_oracle_mixed_4_8():
    """interpret-mode dequant+superpose kernel == jnp oracle, bitwise, on
    a mixed 4/8-bit cohort (the acceptance contract)."""
    ups = _mixed_updates(5, seed=11)
    bits = [4, 8, 4, 8, 4]
    weights = [1.0, 2.0, 0.5, 1.0, 1.5]
    lay = packing.make_layout(ups[0])
    key = jax.random.key(9)
    rows = _rows_of(ups, bits, lay, key)
    cfg = ota.OTAConfig(snr_db=30.0)
    a_ker, _ = ota.ota_aggregate_packed(
        key, rows, bits, weights, lay, cfg, use_kernel=True
    )
    a_jnp, _ = ota.ota_aggregate_packed(
        key, rows, bits, weights, lay, cfg, use_kernel=False
    )
    for a, b in zip(jax.tree.leaves(a_ker), jax.tree.leaves(a_jnp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dequant_superpose_kernel_matches_ref_direct():
    """ops.ota_dequant_superpose == ref.ota_packed_ref on raw arrays, for
    every storage class incl. the packed-int4 in-kernel unpack."""
    rng = np.random.RandomState(4)
    K, M = 3, 5000
    w = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.01, 0.2, K), jnp.float32)
    def within(got, want, bound):
        # same products, K-row f32 sum in another order (ota_fold_bound)
        err = np.abs(np.asarray(got) - np.asarray(want))
        assert (err <= np.asarray(bound)).all(), float(err.max())

    for dtype, hi in [(jnp.int8, 127), (jnp.int16, 32767)]:
        q = jnp.asarray(rng.randint(-hi, hi + 1, size=(K, M)), dtype)
        got = ops.ota_dequant_superpose(q, scale, w)
        want = ref.ota_packed_ref(q, scale, w)
        within(got, want, ref.ota_fold_bound(None, q, scale, w))
    q4 = jnp.asarray(rng.randint(-8, 8, size=(K, M)), jnp.int8)
    p4 = pack_int4_rows(q4)
    got = ops.ota_dequant_superpose(p4, scale, w, packed4=True)
    want = ref.ota_packed_ref(p4, scale, w, packed4=True)
    within(got, want, ref.ota_fold_bound(None, p4, scale, w, packed4=True))
    # and both dequantize to the unpacked truth (the planar pack pads
    # the row to whole 256-symbol groups; the pad symbols are zeros)
    truth = ref.ota_packed_ref(q4, scale, w)
    within(got[:M], truth, ref.ota_fold_bound(None, q4, scale, w))
    np.testing.assert_array_equal(np.asarray(got[M:]), 0.0)


def test_degenerate_and_midrange_bits_match_flat_path():
    """bits <= 1 (empty grid) passes through without NaN, and 17..31-bit
    clients quantize on the wire (int32) exactly like the flat path —
    the same-key equivalence contract holds across odd precisions."""
    ups = _mixed_updates(4, seed=23)
    bits = [1, 20, 8, 4]
    weights = [1.0, 2.0, 1.0, 0.5]
    lay = packing.make_layout(ups[0])
    key = jax.random.key(31)
    rows = _rows_of(ups, bits, lay, key)
    assert rows[0].kind == "float32" and rows[1].kind == "int32"
    packed, _ = ota.ota_aggregate_packed(key, rows, bits, weights, lay)
    flat, _ = ota.ota_aggregate(key, ups, bits, weights)
    for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(flat)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_fl_round_uplink_is_packed():
    """The FL server's uplink is PackedRows: bytes logged and well under
    the f32 volume for sub-f32 cohorts."""
    from repro.configs.base import FLConfig
    from repro.fl import FLServer

    cfg = FLConfig(
        n_clients=3,
        clients_per_round=2,
        n_rounds=1,
        local_steps=1,
        local_batch=2,
        lr=1e-3,
        planner="unified",
        seed=3,
    )
    srv = FLServer(cfg, shard_size=4)
    srv.run(1)
    f32 = 4 * srv.layout.padded_size * 2
    assert 0 < srv.last_uplink_bytes < f32

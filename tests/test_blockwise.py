"""Blockwise uplink scales (DESIGN.md §6): ragged last block, the
n_blocks=1 degenerate case vs the per-row wire format, all-zero blocks,
and mixed bit/block cohorts through the fused aggregation pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ota, packing, quant
from repro.kernels import ops, ref
from repro.kernels.ota_fused import sr_dither


def _row(m, seed=0, outlier=True):
    rng = np.random.RandomState(seed)
    row = jnp.asarray(rng.randn(m).astype(np.float32) * 0.01)
    if outlier:
        row = row.at[m // 3].set(40.0)  # one heavy leaf-ish outlier
    return row


def _expand_scales(scale, block, m):
    """Per-block scales -> per-position scales (ragged tail trimmed)."""
    return jnp.repeat(jnp.atleast_1d(scale), block)[:m]


def _reference_symbols(row, bits, sr_seed, row_index, scale_cols):
    """Hand-rolled stochastic quantization given per-position scales.

    Uses the scales the implementation returned: exact scale recompute
    across separate XLA compilations differs in the last ulp (constant
    division folding), so — as everywhere else in this suite — the
    bit-equality contract is over shared scale tensors, not recomputed
    ones.
    """
    qmax = float(quant.qrange(bits))
    pos = jnp.arange(row.shape[0], dtype=jnp.uint32)
    u = sr_dither(jnp.uint32(sr_seed), jnp.uint32(row_index), pos)
    scaled = row / scale_cols
    floor = jnp.floor(scaled)
    q = floor + (u < (scaled - floor)).astype(jnp.float32)
    return jnp.clip(q, -qmax, qmax)


# ---------------------------------------------------------------------------
# quantize_row_sr blockwise semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,block", [(2048, 256), (2048, 768), (4096, 384)])
def test_blockwise_matches_reference_incl_ragged(m, block):
    """Blockwise symbols and scales match the spec, including block sizes
    that do not divide M (ragged last block)."""
    row = _row(m)
    q, scale = quant.quantize_row_sr(row, 4, jnp.uint32(5), 2, block=block)
    n_blocks = -(-m // block)
    assert scale.shape == (n_blocks,)
    padded = jnp.pad(row, (0, n_blocks * block - m))
    amax = jnp.max(jnp.abs(padded.reshape(n_blocks, block)), axis=1)
    np.testing.assert_allclose(
        np.asarray(scale),
        np.asarray(jnp.maximum(amax, 1e-12) / quant.qrange(4)),
        rtol=1e-6,
    )
    q_ref = _reference_symbols(row, 4, 5, 2, _expand_scales(scale, block, m))
    np.testing.assert_array_equal(np.asarray(q.astype(jnp.float32)), np.asarray(q_ref))


def test_ragged_last_block_dequantizes_with_its_own_scale():
    """Symbols past the last full block use the ragged block's scale."""
    m, block = 2048, 768  # 3 blocks: 768 + 768 + 512 (ragged)
    row = _row(m, seed=3)
    r = ota.quantize_uplink(row, 8, jnp.uint32(9), 0, block=block)
    assert r.n_scales == 3 and r.qblock == block
    scale_cols = _expand_scales(r.scale, block, m)
    dq = ota.dequantize_uplink(r)
    want = np.asarray(r.data).astype(np.float32) * np.asarray(scale_cols)
    np.testing.assert_array_equal(np.asarray(dq), want)


def test_blockwise_cuts_outlier_mse():
    """The motivating property: one outlier no longer wrecks the whole
    row's int4 grid."""
    row = _row(4096, seed=7)
    sr = jnp.uint32(11)
    per = ota.quantize_uplink(row, 4, sr, 0)
    blk = ota.quantize_uplink(row, 4, sr, 0, block=256)
    e_per = float(jnp.mean((ota.dequantize_uplink(per) - row) ** 2))
    e_blk = float(jnp.mean((ota.dequantize_uplink(blk) - row) ** 2))
    assert e_blk < e_per


# ---------------------------------------------------------------------------
# n_blocks == 1 degenerate case == the PR-2 per-row wire format
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [0, 2048, 4096])
def test_nblocks1_reproduces_per_row_bitwise(block):
    """block = 0 and block >= M both collapse to the per-row format:
    identical symbols, () scalar scale, qblock 0 — old rows still parse."""
    row = _row(2048, seed=1)
    base = ota.quantize_uplink(row, 4, jnp.uint32(3), 1)
    r = ota.quantize_uplink(row, 4, jnp.uint32(3), 1, block=block)
    assert r.qblock == 0 and jnp.asarray(r.scale).shape == ()
    assert float(r.scale) == float(base.scale)
    np.testing.assert_array_equal(np.asarray(r.data), np.asarray(base.data))


def test_nblocks1_aggregate_equals_pr2_path_exactly():
    """A block >= M cohort aggregates bit-identically to the per-row path
    (and the (K, 1) kernel branch is the PR-2 code path)."""
    m = 2048
    tree = {"w": _row(m, seed=2)}
    lay = packing.make_layout(tree)
    flat = packing.pack(tree, lay)
    key = jax.random.key(17)
    sr = ota.derive_sr_seed(key)
    bits = [4, 8, 4]
    weights = [1.0, 2.0, 0.5]
    rows_a = [ota.quantize_uplink(flat, b, sr, i) for i, b in enumerate(bits)]
    rows_b = [
        ota.quantize_uplink(flat, b, sr, i, block=lay.padded_size)
        for i, b in enumerate(bits)
    ]
    agg_a, _ = ota.ota_aggregate_packed(key, rows_a, bits, weights, lay)
    agg_b, _ = ota.ota_aggregate_packed(key, rows_b, bits, weights, lay)
    for x, y in zip(jax.tree.leaves(agg_a), jax.tree.leaves(agg_b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# all-zero blocks
# ---------------------------------------------------------------------------


def test_all_zero_blocks_stay_exact_zero():
    """A block of exact zeros quantizes to integer 0 and dequantizes to
    exact 0.0 (its amax-floor scale never divides by zero) — the property
    the padded-norm AWGN calibration relies on."""
    m, block = 1024, 256
    row = jnp.zeros((m,), jnp.float32)
    row = row.at[:block].set(_row(block, seed=4, outlier=False))
    for bits in (4, 8, 16):
        r = ota.quantize_uplink(row, bits, jnp.uint32(21), 0, block=block)
        scales = np.asarray(jnp.atleast_1d(r.scale))
        assert np.isfinite(scales).all() and (scales > 0).all()
        dq = np.asarray(ota.dequantize_uplink(r))
        assert (dq[block:] == 0.0).all()
        assert np.abs(dq[:block]).max() > 0


# ---------------------------------------------------------------------------
# mixed 4/8-bit cohorts with different block sizes in one round
# ---------------------------------------------------------------------------


def _mixed_round(m=2048, seed=5):
    tree = {"w": _row(m, seed=seed)}
    lay = packing.make_layout(tree)
    flat = packing.pack(tree, lay)
    key = jax.random.key(29)
    sr = ota.derive_sr_seed(key)
    bits = [4, 8, 4, 8, 32]
    blocks = [256, 0, 128, 256, 256]
    rows = [
        ota.quantize_uplink(flat, b, sr, i, block=bl)
        for i, (b, bl) in enumerate(zip(bits, blocks))
    ]
    weights = [1.0, 2.0, 0.5, 1.5, 1.0]
    return lay, key, bits, rows, weights


def test_mixed_block_sizes_group_separately():
    """Same storage class at different block sizes cannot share a stacked
    scale matrix — grouping must key on (kind, qblock)."""
    _, _, _, rows, _ = _mixed_round()
    kinds, datas, scales, perm = ota._group_rows(rows)
    assert ("int4", 128) in kinds and ("int4", 256) in kinds
    assert ("int8", 0) in kinds and ("int8", 256) in kinds
    for (kind, qblock), s in zip(kinds, scales):
        assert s.ndim == 2
        if qblock == 0:
            assert s.shape[1] == 1
    assert sorted(np.asarray(perm).tolist()) == list(range(len(rows)))


def test_mixed_block_cohort_kernel_bit_equal_to_oracle():
    """The acceptance contract on the mixed bits x blocks round: the
    interpret-mode Pallas kernel == the jnp oracle, bitwise."""
    lay, key, bits, rows, weights = _mixed_round()
    a_ker, _ = ota.ota_aggregate_packed(key, rows, bits, weights, lay, use_kernel=True)
    a_jnp, info = ota.ota_aggregate_packed(
        key, rows, bits, weights, lay, use_kernel=False
    )
    for x, y in zip(jax.tree.leaves(a_ker), jax.tree.leaves(a_jnp)):
        assert np.isfinite(np.asarray(y)).all()
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert info["uplink_bytes"] == sum(r.wire_nbytes for r in rows)


def test_unaligned_block_size_kernel_bit_equal_to_oracle():
    """Block sizes that do not divide the kernel tile width (768 vs
    BLOCK_COLS = 2048) take the resident-matrix gather path instead of
    the streamed aligned slices — still bit-equal to the oracle."""
    m = 4096
    tree = {"w": _row(m, seed=9)}
    lay = packing.make_layout(tree)
    flat = packing.pack(tree, lay)
    key = jax.random.key(41)
    sr = ota.derive_sr_seed(key)
    bits = [4, 8]
    rows = [ota.quantize_uplink(flat, b, sr, i, block=768) for i, b in enumerate(bits)]
    assert rows[0].qblock == 768
    a_ker, _ = ota.ota_aggregate_packed(
        key, rows, bits, [1.0, 2.0], lay, use_kernel=True
    )
    a_jnp, _ = ota.ota_aggregate_packed(
        key, rows, bits, [1.0, 2.0], lay, use_kernel=False
    )
    for x, y in zip(jax.tree.leaves(a_ker), jax.tree.leaves(a_jnp)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_mixed_block_cohort_matches_manual_superposition():
    """The grouped blockwise pass equals the naive per-row dequant +
    weighted sum + shared AWGN epilogue."""
    lay, key, bits, rows, weights = _mixed_round()
    agg, info = ota.ota_aggregate_packed(key, rows, bits, weights, lay)
    cfg = ota.OTAConfig()
    _, _, w = ota._round_channel(key, jnp.asarray(weights, jnp.float32), cfg=cfg)
    acc = sum(w[i] * ota.dequantize_uplink(r) for i, r in enumerate(rows))
    y, noise_std = ota._awgn_epilogue(key, acc, cfg=cfg, n_valid=lay.size)
    want = packing.unpack(y, lay, cast=False)
    for x, v in zip(jax.tree.leaves(agg), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(v), rtol=1e-5, atol=1e-6)
    assert abs(info["noise_std"] - float(noise_std)) < 1e-8


# ---------------------------------------------------------------------------
# wire-byte accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits,block", [(4, 256), (8, 256), (8, 768), (16, 0)])
def test_row_wire_bytes_counts_scale_vector(bits, block):
    m = 2048
    row = _row(m, seed=6)
    r = ota.quantize_uplink(row, bits, jnp.uint32(13), 0, block=block)
    assert r.wire_nbytes == packing.row_wire_bytes(bits, m, block=block)
    per_row = packing.row_wire_bytes(bits, m)
    extra = 4 * (packing.n_scale_blocks(block, m) - 1)
    assert r.wire_nbytes == per_row + extra


def test_dequant_superpose_accepts_blockwise_scale_matrix():
    """Direct kernel/oracle call with a (K, n_blocks) scale matrix."""
    rng = np.random.RandomState(8)
    K, m, qblock = 3, 4096, 512
    n_blocks = m // qblock
    w = jnp.asarray(rng.uniform(0, 1, K), jnp.float32)
    scales = jnp.asarray(rng.uniform(0.01, 0.2, (K, n_blocks)), jnp.float32)
    q = jnp.asarray(rng.randint(-127, 128, size=(K, m)), jnp.int8)
    got = ops.ota_dequant_superpose(q, scales, w, qblock=qblock)
    want = ref.ota_packed_ref(q, scales, w, qblock=qblock)
    # same products, K-row sum in another order: the f32 summation bound
    bound = ref.ota_fold_bound(None, q, scales, w, qblock=qblock)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert (err <= np.asarray(bound)).all(), float(err.max())
    # and the gather agrees with an explicit per-column expansion
    expand = jnp.repeat(scales, qblock, axis=1)
    manual = jnp.sum(q.astype(jnp.float32) * expand * w.reshape(-1, 1), axis=0)
    err = np.abs(np.asarray(got) - np.asarray(manual))
    assert (err <= np.asarray(bound)).all(), float(err.max())

"""Mixed-precision Over-the-Air aggregation (the MP-OTA-FL data plane).

Physical model (paper refs [1], [2]):

- Block-fading Rayleigh channel per client per round: h_i ~ CN(0, 1).
- Truncated channel inversion power control: clients with |h_i|^2 below a
  threshold are excluded for the round (deep fade); the rest pre-scale by
  alpha_i / h_i so their analog signals superpose to the FedAvg-weighted sum.
- Mixed-precision modulation: each client transmits its *quantized* update
  on a shared symmetric analog grid; a client at b bits occupies every
  2^(B_max - b)-th constellation point, so coarser clients ride the same
  OTA symbols at no extra channel uses — this is how the scheme "covers the
  quantization overheads".
- The server receives  sum_i alpha_i * dq(update_i)  + AWGN scaled by the
  receive SNR and the number of participating clients' aligned power.

Data plane (flat pipeline, DESIGN.md §5)
----------------------------------------

The per-round hot path is one flat, batched, jitted program:

1. **Pack** every client's update pytree into a padded flat f32 row via
   ``core.packing`` (the FL server derives the layout once at init and
   passes it down; the pytree entry point below derives it per call),
   giving the ``(K, M)`` client-update matrix — the OTA superposition is a
   reduction over its K axis, so cohort size never changes program shape
   beyond K. In the end-to-end FL loop the row additionally goes out as a
   *quantized, bit-packed* wire row (``quantize_uplink`` ->
   ``packing.PackedRow``, DESIGN.md §6): 4-bit clients ship two symbols
   per byte, so the simulator's uplink traffic matches the air interface
   instead of being 8x f32-inflated.
2. **Fuse** the per-round quantize/superpose into ONE pass over
   (K, block) tiles (``kernels/ota_fused.py`` on TPU; jnp oracles in
   ``kernels/ref.py`` on CPU, where interpret-mode Pallas is a
   correctness tool, not a perf path). Two in-pass variants share the
   dither stream and grid semantics: f32 rows run stochastic quantize ->
   dequantize -> weighted superposition (``ota_fused_2d``); packed rows
   arrive pre-quantized and run unpack -> dequant -> superposition per
   storage class (``ota_packed_2d``). The in-pass (f32) quantizer uses
   a single per-update quant scale — one analog constellation per
   client per round, the faithful physical choice. Packed wire rows may
   additionally carry *blockwise* scales (``quantize_uplink`` with
   ``block`` > 0, DESIGN.md §6): one f32 per ``block`` symbols, indexed
   in-pass via a (K, n_blocks) scale matrix, so heterogeneous-magnitude
   updates don't let one outlier leaf inflate the whole row's int grid.
   The kernel is bits-agnostic (precision enters as (K,) or
   (K, n_blocks) scale arrays plus (K,) qmax), so one compiled program
   serves every precision mix and the jit cache keys only on
   (K, M, n_blocks).
3. **AWGN epilogue**: the noise std is calibrated to the *global*
   aggregate norm (receive SNR), which only exists after the reduction,
   so the O(M) noise axpy rides the same jitted program right after the
   single O(K*M) pass (the kernel emits the running squared norm).
4. **Unpack** the aggregate back to the update pytree (kept f32 for the
   server optimizer).

``ota_aggregate_pertree`` keeps the legacy per-client/per-leaf Python
loop with identical semantics and PRNG stream — it is the reference
oracle the flat path is equivalence-tested against (tests/test_ota.py),
not a production path.

TPU mapping (DESIGN.md §4): superposition is a reduction. In the
distributed runtime the per-client updates live sharded across the mesh's
``data`` axis and the superposition lowers to a ``psum``/reduce-scatter;
in the single-host FL simulator it is the fused kernel above. The noise
is injected post-reduction at the calibrated receive SNR, exactly where
the channel adds it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from collections.abc import Mapping

from repro import obs
from repro.core import channel as chan
from repro.core import packing, quant, wire
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.ota_fused import INT4_GROUP

Pytree = Any


@dataclasses.dataclass(frozen=True)
class OTAConfig:
    snr_db: float = 20.0
    fade_threshold: float = 0.1  # |h|^2 truncation threshold
    max_bits: int = 32


def sample_channel(
    key, n_clients: int, fade_threshold: float = 0.1
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rayleigh fading gains. Returns (|h| (n,), participation mask (n,))."""
    kr, ki = jax.random.split(key)
    hr = jax.random.normal(kr, (n_clients,)) * jnp.sqrt(0.5)
    hi = jax.random.normal(ki, (n_clients,)) * jnp.sqrt(0.5)
    h2 = hr**2 + hi**2
    return jnp.sqrt(h2), h2 >= fade_threshold


def _use_kernel_default() -> bool:
    """Pallas kernel on TPU; fused jnp reference everywhere else.

    TPU only, not any accelerator: the kernel's sequential-grid
    sum-of-squares accumulation is a TPU pattern (GPU grids run blocks in
    parallel). On CPU, interpret-mode Pallas runs the kernel body per grid
    step under the interpreter — orders of magnitude slower than the
    XLA-fused jnp formulation with the same numerics. Tests that check
    the kernel on CPU pass ``use_kernel=True`` explicitly.
    """
    return jax.devices()[0].platform == "tpu"


def _client_grid(bits: jnp.ndarray, amax: jnp.ndarray):
    """Per-client analog grid: (scale, qmax) arrays from (bits, amax).

    qmax == 0 marks an unquantized (bits >= 32) client; its scale is 1 and
    the data plane passes its symbols through untouched.
    """
    bits = jnp.asarray(bits, jnp.int32)
    qmax = jnp.where(bits < 32, jnp.exp2((bits - 1).astype(jnp.float32)) - 1.0, 0.0)
    scale = jnp.where(qmax > 0, jnp.maximum(amax, 1e-12) / jnp.maximum(qmax, 1.0), 1.0)
    return scale, qmax


def derive_sr_seed(key) -> jnp.ndarray:
    """The round's stochastic-rounding seed, as ``ota_aggregate_flat``
    derives it internally from the round key.

    Clients quantizing at the edge (``quantize_uplink``) need this seed
    *before* the aggregation call; deriving it from the same key split
    keeps the packed path bit-identical to in-aggregate quantization (and
    to ``ota_aggregate_pertree``) for the same round key.
    """
    _, k_quant, _ = jax.random.split(key, 3)
    return jax.random.bits(k_quant, (), jnp.uint32)


def derive_dl_seed(key) -> jnp.ndarray:
    """The round's *downlink* dither seed (DESIGN.md §13).

    The server stochastic-quantizes the global param delta exactly once
    per round with this seed (``wire.encode_row`` at row 0) before
    broadcasting; decoding is deterministic, so every client reconstructs
    bit-identical params from the one encoded row. Derived from the same
    quantization key split as ``derive_sr_seed`` but folded with a
    downlink tag, so the two legs' dither streams are disjoint — a
    client's uplink symbols and the broadcast it just received never
    share rounding draws.
    """
    _, k_quant, _ = jax.random.split(key, 3)
    return jax.random.bits(jax.random.fold_in(k_quant, 0xD0_4B17), (), jnp.uint32)


def quantize_uplink(
    row: jnp.ndarray,
    bits: int,
    sr_seed: jnp.ndarray,
    row_index: int,
    *,
    block: int = 0,
) -> packing.PackedRow:
    """Modulate one client's flat packed row onto the wire (DESIGN.md §6).

    Thin alias for ``wire.encode_row`` — the symmetric codec facade both
    legs share (DESIGN.md §13) — kept for the established uplink call
    sites and tests. ``row_index`` = the client's row in this round's
    cohort (reporting clients only), dithering off ``derive_sr_seed``'s
    stream; ``block`` > 0 ships blockwise scales (``packing.QUANT_BLOCK``
    is the FL default). The server dequantizes inside the fused
    aggregation pass — the f32 row never crosses the uplink.
    """
    return wire.encode_row(row, bits, sr_seed, row_index, block=block)


def dequantize_uplink(row: packing.PackedRow, n: Optional[int] = None) -> jnp.ndarray:
    """Reconstruct the f32 row a ``PackedRow`` encodes (q * scale[block]).

    Thin alias for ``wire.decode_row``. The uplink data plane never does
    this on the host — dequantization lives inside the fused pass — but
    the quantization-*error* measurements
    (``benchmarks/bench_aggregation.py``) and the blockwise edge tests
    need the reconstruction standalone. ``n`` trims to the logical
    (unpadded) length.
    """
    return wire.decode_row(row, n)


@functools.partial(jax.jit, static_argnames=("cfg", "n_valid", "use_kernel"))
def ota_aggregate_flat(
    key,
    X: jnp.ndarray,
    bits: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    cfg: OTAConfig,
    n_valid: int,
    use_kernel: bool = False,
):
    """One-shot OTA aggregation of the flat (K, M) client-update matrix.

    X rows are zero-padded packed updates (``core.packing``); ``n_valid``
    is the real (unpadded) parameter count. bits/weights are (K,) arrays —
    traced, not static, so the jit cache keys on (K, M, n_valid, cfg)
    only. Returns (y (n_valid,) f32, habs, participate, noise_std).
    """
    K = X.shape[0]
    X = X.astype(jnp.float32)
    k_chan, k_quant, k_noise = jax.random.split(key, 3)
    habs, participate = sample_channel(k_chan, K, cfg.fade_threshold)

    w = jnp.asarray(weights, jnp.float32) * participate
    w = w / jnp.maximum(jnp.sum(w), 1e-12)

    scale, qmax = _client_grid(bits, jnp.max(jnp.abs(X), axis=1))
    sr_seed = jax.random.bits(k_quant, (), jnp.uint32)

    if use_kernel:
        acc, sumsq = kops.ota_quantize_superpose(X, scale, qmax, w, sr_seed)
    else:
        acc, sumsq = kref.ota_fused_ref(X, scale, qmax, w, sr_seed)

    # receiver AWGN: noise std chosen so that per-element
    # SNR = ||aggregate|| / ||noise|| matches cfg.snr_db. (Padding
    # contributes exact zeros to both acc and sumsq.)
    noise_std = jnp.sqrt(sumsq / n_valid * 10 ** (-cfg.snr_db / 10))
    y = acc[:n_valid] + noise_std * jax.random.normal(k_noise, (n_valid,))
    return y, habs, participate, noise_std


@functools.partial(jax.jit, static_argnames=("cfg",))
def round_channel(key, weights, *, cfg: OTAConfig):
    """Channel draw + FedAvg weight renormalisation (cache keys on K).

    Returns (habs, participate, w) with ``w`` the participation-masked,
    renormalised combining weights in the order of ``weights``. Public
    because the streaming round loop (``fl/server.py``, DESIGN.md §11)
    draws the channel itself at trigger time and hands the final weights
    to ``OtaAccumulator.fold`` — same key split as the one-shot paths,
    so a no-deadline streaming round reproduces their draws exactly.
    """
    k_chan, _, _ = jax.random.split(key, 3)
    habs, participate = sample_channel(k_chan, weights.shape[0], cfg.fade_threshold)
    w = jnp.asarray(weights, jnp.float32) * participate
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    return habs, participate, w


_round_channel = round_channel  # internal alias (pre-§11 name)


@functools.partial(jax.jit, static_argnames=("cfg", "n_valid"))
def _awgn_epilogue(key, acc, *, cfg: OTAConfig, n_valid: int):
    """Receiver AWGN on the combined aggregate (cache keys on (M, n_valid)).

    Identical to ota_aggregate_flat's epilogue: padding is exact zeros in
    every storage class, so the padded sumsq equals the n_valid one.
    """
    _, _, k_noise = jax.random.split(key, 3)
    sumsq = jnp.sum(acc * acc)
    noise_std = jnp.sqrt(sumsq / n_valid * 10 ** (-cfg.snr_db / 10))
    y = acc[:n_valid] + noise_std * jax.random.normal(k_noise, (n_valid,))
    return y, noise_std


_packed_ref_jit = jax.jit(kref.ota_packed_ref, static_argnames=("qblock", "packed4"))
_fold_ref_jit = jax.jit(kref.ota_fold_ref, static_argnames=("qblock", "packed4"))


def _shard_chunk(M: int, n_shards: int, kinds) -> int:
    """Per-shard column-chunk width for the mesh-sharded fold
    (DESIGN.md §15): ceil(M / n_shards) rounded up so every blockwise
    scale group (qblock columns) and every planar int4 group
    (``INT4_GROUP`` symbols) stays whole inside one shard's chunk — each
    shard's local block-id gather and nibble unpack are then literally
    the unsharded ones."""
    align = 2
    for kind, qblock in kinds:
        if kind == "int4":
            align = math.lcm(align, INT4_GROUP)
        if qblock > 0:
            align = math.lcm(align, int(qblock))
    mc = -(-M // n_shards)
    return -(-mc // align) * align


def _pad_cols(x, width: int, value=0):
    pad = width - x.shape[1]
    if pad <= 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad)), constant_values=value)


@functools.lru_cache(maxsize=None)
def _sharded_group_program(
    mesh,
    kind: str,
    qblock: int,
    scale_sharded: bool,
    has_acc: bool,
    has_gains: bool,
    use_kernel: bool,
):
    """Build (and cache) the jitted shard_map fold for ONE storage group.

    One executable per group, exactly like the unsharded path's
    ``_packed_ref_jit`` / ``_fold_ref_jit`` calls — this boundary is
    load-bearing for bitwise equality: compiling several group folds
    into one program lets XLA fuse one group's reduction into the next
    group's ``acc + ...`` add (reassociating the float sum, ~1 ulp per
    element, and ``optimization_barrier`` does not stop the rewrite).
    With one group per program the per-shard float program is the
    single-host one verbatim on a column chunk, and
    ``out_specs=P("data")`` makes the cross-shard combine a pure
    concatenation — zero cross-shard float ops (DESIGN.md §15). The
    running state flows between group programs still sharded, so chains
    of groups pay no intermediate gathers. Keyed per group (storage
    class, scale placement, acc/gains presence, backend), so varying
    cohorts reuse compiled programs across rounds exactly like the
    unsharded pieces."""
    P = jax.sharding.PartitionSpec
    packed4 = kind == "int4"

    def body(*ops):
        it = iter(ops)
        acc = next(it) if has_acc else None
        data, scale, wseg = next(it), next(it), next(it)
        gains = next(it) if has_gains else None
        if acc is None:
            fn = kops.ota_dequant_superpose if use_kernel else _packed_ref_jit
            return fn(data, scale, wseg, gains=gains, qblock=qblock, packed4=packed4)
        fn = kops.ota_fold_packed if use_kernel else _fold_ref_jit
        return fn(acc, data, scale, wseg, gains=gains, qblock=qblock, packed4=packed4)

    in_specs = [P("data")] if has_acc else []
    in_specs += [
        P(None, "data"),
        P(None, "data") if scale_sharded else P(None, None),
        P(),
    ]
    if has_gains:
        in_specs.append(P())
    # check_vma=False: pallas_call has no varying-manual-axes rule, so
    # the kernel path would otherwise refuse to trace under shard_map
    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P("data"),
            check_vma=False,
        )
    )


def _fold_groups_sharded(
    acc, kinds, datas, scales, wg, *, gains=None, mesh, use_kernel: bool
):
    """Mesh-sharded ``_fold_groups``: the fold's SYMBOL (column) axis is
    placed across the mesh's ``data`` axis (DESIGN.md §15).

    Each output element of the fold is an independent per-column sum
    over the K rows, so splitting columns never reassociates any float
    sum — every shard runs the identical fused group fold on its chunk
    and the combine is concatenation, making the sharded aggregate
    bit-identical to the single-host oracle by construction. (Splitting
    the K axis instead — per-shard partial superpositions psum'd across
    shards — reassociates the K-sum and is NOT bitwise; see §15.)
    Column chunks are padded to a qblock/nibble-aligned width with
    zero symbols and unit scales, exactly the layout's own padding
    convention, and trimmed after the gather. Per-shard resident symbol
    bytes and fold work drop ~1/n_shards."""
    n_shards = mesh.shape["data"]
    M = 0 if acc is None else acc.shape[0]
    for (kind, _), data in zip(kinds, datas):
        M = max(M, data.shape[1] * (2 if kind == "int4" else 1))
    mc = _shard_chunk(M, n_shards, kinds)
    Mp = mc * n_shards

    def _place(x, *spec):
        # Every operand gets an explicit mesh placement: uplink rows can
        # arrive committed to device 0 (client encode runs on the gathered
        # broadcast params), which a jitted shard_map rejects as a device
        # mismatch. A layout move only — zero float ops.
        return jax.device_put(
            x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec))
        )

    path = kops.kernel_path(use_kernel)
    with obs.span("shard_fold", shards=n_shards, groups=len(kinds), chunk=mc):
        running = acc
        if running is not None:
            # re-shard the (gathered, device-0-committed) running state
            # back onto the mesh
            running = _place(jnp.pad(running, (0, Mp - running.shape[0])), "data")
        off = 0
        for (kind, qblock), data, scale in zip(kinds, datas, scales):
            kg = scale.shape[0]
            obs.metrics.inc("ota.rows", kg, kind=kind, path=path)
            wseg = wg[off : off + kg]
            gseg = None if gains is None else gains[off : off + kg]
            off += kg
            width = Mp // 2 if kind == "int4" else Mp
            sharded = qblock > 0 and scale.shape[1] > 1
            fn = _sharded_group_program(
                mesh,
                kind,
                qblock,
                sharded,
                running is not None,
                gains is not None,
                use_kernel,
            )
            ops_in = [] if running is None else [running]
            ops_in += [
                _place(_pad_cols(data, width), None, "data"),
                _place(_pad_cols(scale, Mp // qblock, value=1.0), None, "data")
                if sharded
                else _place(scale, None, None),
                _place(wseg),
            ]
            if gseg is not None:
                ops_in.append(_place(gseg))
            running = fn(*ops_in)
        # Gather to ONE device before anything downstream consumes the
        # accumulator: jitted consumers (the AWGN epilogue's sumsq in
        # particular) would otherwise compile *distributed* reductions
        # over the still-sharded array — a different summation tree than
        # the single-host oracle, hence not bitwise. The gather itself
        # is a pure concatenation (zero float ops).
        out = jax.device_put(running, jax.devices()[0])
    return out[:M] if Mp != M else out


def _fold_groups(
    acc, kinds, datas, scales, wg, *, gains=None, mesh=None, use_kernel: bool
):
    """Fold grouped micro-batches into the running superposition ``acc``.

    kinds/datas/scales as produced by ``_group_rows``; ``wg`` the final
    combining weights in group order; ``gains`` the optional per-row
    effective channel gains (DESIGN.md §12), also in group order — when
    None the legacy (gain-free) kernel programs run, byte-identical to
    the pre-channel path. ``acc`` = None starts a fresh accumulator: the
    first group's partial *is* the state (no add with a zeros vector),
    every later group folds in via the fold kernel / oracle
    (``kernels.ota_fold_packed`` / ``ref.ota_fold_ref``) — the exact
    left-associated group sum the pre-§11 barrier loop computed, so the
    synchronous path and a single-batch streaming fold are bit-identical
    by construction.

    Telemetry (DESIGN.md §14): the whole fold runs under one ``fold``
    span, and each storage group bumps the row counter
    ``ota.rows{kind=...,path=...}`` — storage class and data-plane path
    (``kernels.ops.kernel_path``: kernel / interpret / ref); the
    observation side only, the folded values are untouched either way.

    ``mesh``: optional 1-D device mesh with a ``data`` axis
    (``launch.mesh.make_data_mesh``) — routes to the column-sharded
    fold (``_fold_groups_sharded``, span ``shard_fold``), bit-identical
    to this path by construction (DESIGN.md §15).
    """
    if mesh is not None:
        return _fold_groups_sharded(
            acc, kinds, datas, scales, wg, gains=gains, mesh=mesh,
            use_kernel=use_kernel,
        )
    path = kops.kernel_path(use_kernel)
    with obs.span("fold", groups=len(kinds)):
        off = 0
        for (kind, qblock), data, scale in zip(kinds, datas, scales):
            kg = scale.shape[0]
            obs.metrics.inc("ota.rows", kg, kind=kind, path=path)
            wseg = jax.lax.slice_in_dim(wg, off, off + kg)
            gseg = (
                None if gains is None else jax.lax.slice_in_dim(gains, off, off + kg)
            )
            off += kg
            packed4 = kind == "int4"
            if acc is None:
                fn = kops.ota_dequant_superpose if use_kernel else _packed_ref_jit
                acc = fn(data, scale, wseg, gains=gseg, qblock=qblock, packed4=packed4)
            else:
                fn = kops.ota_fold_packed if use_kernel else _fold_ref_jit
                acc = fn(
                    acc, data, scale, wseg, gains=gseg, qblock=qblock, packed4=packed4
                )
    return acc


def _aggregate_rows_flat(
    key,
    datas,
    scales,
    perm,
    weights,
    *,
    kinds: Tuple[Tuple[str, int], ...],
    cfg: OTAConfig,
    gains=None,
    n_valid: int,
    mesh=None,
    use_kernel: bool = False,
):
    """Aggregate packed uplink rows grouped by wire storage class.

    datas/scales: per-group stacked (Kg, ...) symbol matrices and
    (Kg, n_blocks) quant-scale matrices, ordered per ``kinds`` — a tuple
    of (storage class, qblock) group keys (qblock = 0: per-update
    scales); ``perm`` maps group order back to the cohort's original row
    order (weights/channel stay in cohort order). One fused
    dequant->superpose fold per storage group (``_fold_groups`` — the
    same persistent-accumulator primitive the streaming engine uses,
    DESIGN.md §11), then the shared AWGN epilogue on the combined
    aggregate — same channel, weight renormalisation, and noise-draw
    semantics as ``ota_aggregate_flat``.

    Deliberately NOT one jitted program: the group composition (which
    kinds, how many rows each) changes round to round with the planner's
    bit decisions and dropouts, and a composition-keyed jit would retrace
    per distinct mix. Instead the pieces are jitted on small key spaces —
    channel on K, each group fold on (Kg, kind, qblock), epilogue on
    (M, n_valid) — so a varying cohort reuses compiled code across
    rounds.

    ``gains``: optional (K,) effective channel gains in cohort order
    (``core.channel``, DESIGN.md §12). When given, the physical channel
    REPLACES the legacy coin-flip draw: participation is ``gains > 0``
    (truncated channel inversion), weights renormalise over the
    surviving set (``channel.combine_weights`` — same guard as
    ``round_channel``), and the per-row gain rides inside the fused
    pass. The AWGN epilogue and dither stream are untouched either way.
    """
    if gains is None:
        habs, participate, w = round_channel(key, weights, cfg=cfg)
        gg = None
    else:
        gains = jnp.asarray(gains, jnp.float32)
        participate = gains > 0
        habs = None
        w = chan.combine_weights(weights, gains)
        gg = gains[perm]  # group-order view of the per-row gains
    wg = w[perm]  # group-order view of the cohort weights
    acc = _fold_groups(
        None, kinds, datas, scales, wg, gains=gg, mesh=mesh, use_kernel=use_kernel
    )
    with obs.span("finalize"):
        y, noise_std = _awgn_epilogue(key, acc, cfg=cfg, n_valid=n_valid)
    return y, habs, participate, noise_std


def _group_rows(rows: Sequence[packing.PackedRow]):
    """Stable-sort rows by (storage class, qblock) -> groups.

    Returns (kinds, datas, scales, perm) where kinds is a tuple of
    (kind, qblock) keys. Rows sharing a storage class but quantized with
    different block sizes (a mixed-planner round) land in separate
    groups — their (Kg, n_blocks) scale matrices have different widths,
    and each group's fused pass gets its own static qblock.
    """

    def _key(i):
        return (packing.KIND_RANK[rows[i].kind], rows[i].qblock)

    order = sorted(range(len(rows)), key=_key)
    kinds, datas, scales, perm = [], [], [], []
    i = 0
    while i < len(order):
        kind, qblock = rows[order[i]].kind, rows[order[i]].qblock
        grp = [j for j in order[i:] if _key(j) == _key(order[i])]
        kinds.append((kind, qblock))
        datas.append(jnp.stack([rows[j].data for j in grp]))
        scales.append(
            jnp.stack([jnp.atleast_1d(jnp.asarray(rows[j].scale)) for j in grp])
        )
        perm.extend(grp)
        i += len(grp)
    return tuple(kinds), tuple(datas), tuple(scales), jnp.asarray(perm, jnp.int32)


def staleness_weights(delays, grace: float, *, gamma: float = 0.5) -> jnp.ndarray:
    """Staleness discount for rows arriving ``delays`` seconds after the
    round's aggregation trigger (DESIGN.md §11).

    Exponential in the normalised lag: gamma ** (delay / grace), so a row
    landing right at the trigger keeps weight ~1 and one landing at the
    end of the grace window keeps ``gamma``. Clipped to [gamma, 1] —
    rows past the grace window should not be folded at all (the round
    plan drops them), so the discount never decays below the end-of-
    window value.
    """
    d = jnp.asarray(delays, jnp.float32)
    g = jnp.float32(max(float(grace), 1e-9))
    return jnp.clip(jnp.float32(gamma) ** (d / g), min(gamma, 1.0), 1.0)


class OtaAccumulator:
    """Persistent superposition accumulator for streaming rounds
    (DESIGN.md §11).

    Owns the running (padded_size,) pre-noise aggregate the buffered
    round loop folds arrivals into: ``fold`` takes one micro-batch of
    ``packing.PackedRow`` uplinks with their *final* combining weights
    (participation-masked and renormalised — see ``round_channel`` — and
    optionally staleness-discounted), groups it by (storage class,
    qblock) exactly like the one-shot path, and folds each group through
    the fused fold kernel / oracle. ``finalize`` runs the shared AWGN
    epilogue (the aggregate's norm state — the noise-power calibration
    input — is derived from the persistent accumulator itself, the same
    jitted program the barrier path uses) and unpacks to the update
    pytree.

    Equivalence contract: folding the whole arrival set as ONE batch, in
    cohort order, with ``round_channel``-normalised weights and the same
    round key, is bit-identical to ``ota_aggregate_packed`` — the
    synchronous path *is* ``_fold_groups`` now, so the no-deadline
    streaming round and the barrier round run the same float ops in the
    same order. Multi-batch folds (the async path: late arrivals folding
    in after the trigger) left-associate batch partials instead, which
    is the documented semantic difference, not a bug.
    """

    def __init__(
        self,
        layout: packing.Layout,
        cfg: OTAConfig = OTAConfig(),
        *,
        mesh=None,
        use_kernel: Optional[bool] = None,
    ):
        self.layout = layout
        self.cfg = cfg
        # optional data-axis mesh: every fold shards its symbol axis
        # (DESIGN.md §15), bit-identical to the single-host fold
        self.mesh = mesh
        self.use_kernel = _use_kernel_default() if use_kernel is None else use_kernel
        self.reset()

    def reset(self) -> None:
        """Clear the running state (fresh round)."""
        self._acc = None
        self.n_folded = 0
        self.wire_bytes = 0

    @property
    def accumulator(self) -> jnp.ndarray:
        """The running (padded_size,) pre-noise aggregate (zeros before
        any fold)."""
        if self._acc is None:
            return jnp.zeros((self.layout.padded_size,), jnp.float32)
        return self._acc

    def fold(
        self, rows: Sequence[packing.PackedRow], weights, *, staleness=None, gains=None
    ) -> "OtaAccumulator":
        """Fold one micro-batch of packed uplink rows into the state.

        weights: final per-row combining weights (already channel-masked
        and renormalised by the caller); ``staleness``: optional per-row
        discount multipliers (``staleness_weights``) for late arrivals;
        ``gains``: optional per-row effective channel gains
        (``core.channel``, DESIGN.md §12) riding inside the fused fold —
        None is byte-identical to the pre-channel fold, and a wave of
        all-truncated rows (all gains 0) adds exact zeros, leaving the
        accumulator value bit-unchanged. Rows are grouped by (storage
        class, qblock) and each group runs one fused fold pass — no
        (K, M) f32 matrix ever materialises. Returns self for chaining:
        fold(fold(state, b0), b1)...
        """
        if len(rows) == 0:
            return self
        w = jnp.asarray(weights, jnp.float32)
        if staleness is not None:
            for s in staleness:  # late-arrival discount distribution (§14)
                obs.metrics.observe("stream.staleness_discount", float(s))
            w = w * jnp.asarray(staleness, jnp.float32)
        kinds, datas, scales, perm = _group_rows(rows)
        g = None if gains is None else jnp.asarray(gains, jnp.float32)[perm]
        self._acc = _fold_groups(
            self._acc,
            kinds,
            datas,
            scales,
            w[perm],
            gains=g,
            mesh=self.mesh,
            use_kernel=self.use_kernel,
        )
        self.n_folded += len(rows)
        self.wire_bytes += int(sum(r.wire_nbytes for r in rows))
        return self

    def finalize(self, key) -> Tuple[Pytree, "AggregateInfo"]:
        """AWGN epilogue on the accumulated superposition.

        Same key-split, noise draw, and norm calibration as the one-shot
        paths (``_awgn_epilogue``). Returns (update pytree with f32
        leaves, ``AggregateInfo``); the accumulator stays intact — call
        ``reset`` to start the next round.
        """
        assert self._acc is not None, "finalize() before any fold()"
        with obs.span("finalize"):
            y, noise_std = _awgn_epilogue(
                key, self._acc, cfg=self.cfg, n_valid=self.layout.size
            )
        info = AggregateInfo(
            noise_std=float(noise_std),
            n_folded=self.n_folded,
            uplink_bytes=self.wire_bytes,
            uplink_bytes_f32=4 * self.layout.padded_size * self.n_folded,
        )
        info.publish()
        return packing.unpack(y, self.layout, cast=False), info


@dataclasses.dataclass
class AggregateInfo(Mapping):
    """Typed per-aggregation report (PR 8; previously an untyped dict).

    One class serves every aggregation entry point — the one-shot paths
    (``ota_aggregate_packed`` / ``ota_aggregate_flat`` callers), the
    streaming ``OtaAccumulator.finalize``, and the per-tree oracle —
    with fields a given path doesn't produce left ``None``. It
    implements the ``Mapping`` protocol over its *present* (non-None)
    fields, so the established ``info["uplink_bytes"]`` /
    ``"n_truncated" in info`` call sites and tests keep working
    unchanged; new code should prefer the attributes.
    """

    noise_std: float
    n_participating: Optional[int] = None
    participation: Optional[list] = None
    channel_abs: Optional[list] = None  # legacy coin-flip channel |h| draws
    channel_gains: Optional[list] = None  # physical-channel effective gains
    n_truncated: Optional[int] = None
    n_folded: Optional[int] = None  # streaming accumulator rows folded
    uplink_bytes: Optional[int] = None
    uplink_bytes_f32: Optional[int] = None
    downlink_bytes: Optional[int] = None  # filled by the FL round loop

    def _present(self) -> Dict[str, Any]:
        return {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if getattr(self, f.name) is not None
        }

    def __getitem__(self, key: str) -> Any:
        return self._present()[key]

    def __iter__(self):
        return iter(self._present())

    def __len__(self) -> int:
        return len(self._present())

    def publish(self, registry=None) -> None:
        """Push this aggregation's numbers into the metrics registry
        (DESIGN.md §14) — the ``obs.metrics`` side of the report.

        Counters accumulate across rounds (``ota.uplink_bytes``,
        ``ota.rows_truncated``, ``ota.aggregations``); gauges carry the
        latest round (``ota.noise_std``, ``ota.truncation_rate``,
        ``ota.mean_misalignment``). The truncation rate covers both
        channel paths: the physical model's truncated-inversion count
        (``n_truncated``) and the legacy coin-flip's non-participating
        fraction come out of the same participation vector.
        """
        m = registry or obs.metrics.REGISTRY
        m.inc("ota.aggregations")
        m.set_gauge("ota.noise_std", self.noise_std)
        if self.uplink_bytes is not None:
            m.inc("ota.uplink_bytes", self.uplink_bytes)
        if self.n_folded is not None:
            m.inc("ota.rows_folded", self.n_folded)
        if self.n_participating is not None:
            m.set_gauge("ota.n_participating", self.n_participating)
        if self.participation:
            k = len(self.participation)
            n_trunc = (
                self.n_truncated
                if self.n_truncated is not None
                else k - sum(bool(p) for p in self.participation)
            )
            m.set_gauge("ota.truncation_rate", n_trunc / k)
            if n_trunc:
                m.inc("ota.rows_truncated", n_trunc)
        if self.channel_gains:
            alive = [g for g in self.channel_gains if g > 0]
            if alive:
                miss = sum(1.0 - g for g in alive) / len(alive)
                m.set_gauge("ota.mean_misalignment", miss)


def _info(habs, participate, noise_std, **kw) -> AggregateInfo:
    participate = jax.device_get(participate)
    return AggregateInfo(
        noise_std=float(noise_std),
        n_participating=int(participate.sum()),
        participation=[bool(p) for p in participate],
        channel_abs=[float(h) for h in jax.device_get(habs)],
        **kw,
    )


def ota_aggregate_packed(
    key,
    X,
    bits: Sequence[int],
    weights: Sequence[float],
    layout: packing.Layout,
    cfg: OTAConfig = OTAConfig(),
    *,
    gains=None,
    mesh=None,
    use_kernel: Optional[bool] = None,
) -> Tuple[Pytree, "AggregateInfo"]:
    """Aggregate pre-packed client rows; unpack the result per ``layout``.

    The entry point for callers that already hold flat updates (the FL
    server packs each client's delta exactly once, at the client). ``X``
    is either the legacy (K, M) f32 matrix — quantization then happens
    inside the fused pass — or a sequence of ``packing.PackedRow``
    produced by ``quantize_uplink`` with this round's ``derive_sr_seed``;
    then the rows arrive already quantized+bit-packed and the pass only
    dequantizes (DESIGN.md §5-§6). Same round key => identical aggregate
    either way (same dither stream, channel, and noise draws).

    ``gains``: optional (K,) effective channel gains from the physical
    channel model (``core.channel``, DESIGN.md §12) — packed rows only.
    When given it replaces the legacy participation coin-flip:
    truncated rows (gain 0) are excluded from the weight normaliser and
    contribute exact zeros, surviving rows superpose scaled by their
    misalignment gain inside the fused pass. ``gains=None`` is bitwise
    identical to the pre-channel aggregation for the same round key.

    ``mesh``: optional ``data``-axis device mesh
    (``launch.mesh.make_data_mesh``) — packed rows only. The fold's
    symbol axis shards across the mesh and the aggregate stays
    bit-identical to the single-host path (DESIGN.md §15); the AWGN
    epilogue runs unsharded on the gathered accumulator, so channel,
    weights, and noise draws are untouched.
    """
    if use_kernel is None:
        use_kernel = _use_kernel_default()
    if packing.is_packed_rows(X):
        rows: Sequence[packing.PackedRow] = X
        if bits is not None:
            assert [int(b) for b in bits] == [r.bits for r in rows], (
                "bits arg disagrees with PackedRow.bits"
            )
        kinds, datas, scales, perm = _group_rows(rows)
        y, habs, participate, noise_std = _aggregate_rows_flat(
            key,
            datas,
            scales,
            perm,
            jnp.asarray(weights, jnp.float32),
            kinds=kinds,
            cfg=cfg,
            gains=gains,
            n_valid=layout.size,
            mesh=mesh,
            use_kernel=use_kernel,
        )
        wire_kw = dict(
            uplink_bytes=wire.wire_bytes(rows),
            uplink_bytes_f32=4 * layout.padded_size * len(rows),
        )
        if gains is None:
            info = _info(habs, participate, noise_std, **wire_kw)
        else:
            participate = jax.device_get(participate)
            info = AggregateInfo(
                noise_std=float(noise_std),
                n_participating=int(participate.sum()),
                participation=[bool(p) for p in participate],
                n_truncated=int((~participate).sum()),
                channel_gains=[float(g) for g in jax.device_get(gains)],
                **wire_kw,
            )
    else:
        assert gains is None, (
            "gains= is a packed-uplink feature (PackedRow cohorts only)"
        )
        assert mesh is None, (
            "mesh= is a packed-uplink feature (PackedRow cohorts only)"
        )
        y, habs, participate, noise_std = ota_aggregate_flat(
            key,
            X,
            jnp.asarray(bits, jnp.int32),
            jnp.asarray(weights, jnp.float32),
            cfg=cfg,
            n_valid=layout.size,
            use_kernel=use_kernel,
        )
        info = _info(habs, participate, noise_std)
    info.publish()
    agg = packing.unpack(y, layout, cast=False)
    return agg, info


def ota_aggregate(
    key,
    updates: Sequence[Pytree],
    bits: Sequence[int],
    weights: Sequence[float],
    cfg: OTAConfig = OTAConfig(),
    *,
    layout: Optional[packing.Layout] = None,
    use_kernel: Optional[bool] = None,
) -> Tuple[Pytree, "AggregateInfo"]:
    """Aggregate client update pytrees over the simulated OTA channel.

    updates: per-client pytrees (same structure). bits: per-client precision.
    weights: FedAvg weights (sum need not be 1; renormalised over the
    participating set after fade truncation).

    Packs once into the (K, M) matrix and runs the fused flat pipeline
    (module docstring). Returns (aggregated update pytree with f32 leaves,
    info dict with participation/noise stats).

    ``updates`` may also be a sequence of ``packing.PackedRow`` (already
    quantized+bit-packed uplinks, see ``quantize_uplink``); then
    ``layout`` is required — there is no pytree to derive it from.
    """
    if packing.is_packed_rows(updates):
        assert layout is not None, "packed rows need an explicit layout"
        return ota_aggregate_packed(
            key, updates, bits, weights, layout, cfg, use_kernel=use_kernel
        )
    if layout is None:
        layout = packing.make_layout(updates[0])
    X = packing.pack_batch(updates, layout)
    return ota_aggregate_packed(
        key, X, bits, weights, layout, cfg, use_kernel=use_kernel
    )


def ota_aggregate_pertree(
    key,
    updates: Sequence[Pytree],
    bits: Sequence[int],
    weights: Sequence[float],
    cfg: OTAConfig = OTAConfig(),
) -> Tuple[Pytree, "AggregateInfo"]:
    """Reference oracle: the legacy per-client/per-leaf Python loop.

    Semantically identical to the flat path — same stochastic-rounding
    dither (the positional hash of ``kernels.ota_fused.sr_dither``
    evaluated over the flat layout and sliced per leaf), same receiver
    noise draw, same shared per-update analog grid — but dispatched as
    O(clients x leaves) unjitted ops. Kept for equivalence tests and as
    the readable specification of the data plane; production goes through
    ``ota_aggregate``.
    """
    n = len(updates)
    layout = packing.make_layout(updates[0])
    k_chan, k_quant, k_noise = jax.random.split(key, 3)
    habs, participate = sample_channel(k_chan, n, cfg.fade_threshold)

    w = jnp.asarray(weights, jnp.float32) * participate
    w = w / jnp.maximum(jnp.sum(w), 1e-12)

    from repro.kernels.ota_fused import sr_dither

    sr_seed = jax.random.bits(k_quant, (), jnp.uint32)
    positions = jnp.arange(layout.padded_size, dtype=jnp.uint32)
    leaves0, treedef = jax.tree.flatten(updates[0])
    agg_leaves = [jnp.zeros_like(l, jnp.float32) for l in leaves0]
    for i in range(n):
        leaves_i = jax.tree.leaves(updates[i])
        b = int(bits[i])
        if b >= 32:
            dq_leaves = [l.astype(jnp.float32) for l in leaves_i]
        else:
            qmax = float(quant.qrange(b))
            amax = jnp.max(
                jnp.stack([jnp.max(jnp.abs(l.astype(jnp.float32))) for l in leaves_i])
            )
            scale = jnp.maximum(amax, 1e-12) / qmax
            u_full = sr_dither(sr_seed, jnp.uint32(i), positions)
            dq_leaves = []
            for leaf, off, size, shape in zip(
                leaves_i, layout.offsets, layout.sizes, layout.shapes
            ):
                u = jax.lax.slice_in_dim(u_full, off, off + size).reshape(shape)
                scaled = leaf.astype(jnp.float32) / scale
                floor = jnp.floor(scaled)
                q = floor + (u < (scaled - floor)).astype(jnp.float32)
                q = jnp.clip(q, -qmax, qmax)
                dq_leaves.append(q * scale)
        wi = w[i]
        agg_leaves = [a + wi * l for a, l in zip(agg_leaves, dq_leaves)]

    total_elems = layout.size
    agg_norm2 = sum(jnp.sum(l**2) for l in agg_leaves)
    noise_std = jnp.sqrt(agg_norm2 / total_elems * 10 ** (-cfg.snr_db / 10))
    n_full = jax.random.normal(k_noise, (total_elems,))
    noisy = [
        a + noise_std * jax.lax.slice_in_dim(n_full, off, off + size).reshape(a.shape)
        for a, off, size in zip(agg_leaves, layout.offsets, layout.sizes)
    ]
    return jax.tree.unflatten(treedef, noisy), _info(habs, participate, noise_std)


def channel_uses(
    bits: Sequence[int], n_params: int, cfg: OTAConfig = OTAConfig()
) -> int:
    """OTA channel uses for one aggregation round.

    Mixed-precision modulation shares symbols across precisions: the round
    costs n_params symbols at the *max* participating precision's
    constellation — clients at lower b simply use coarser points. (This is
    the "quantization overhead covered by OTA" property: cost does NOT sum
    over clients.)
    """
    return n_params


def digital_uplink_bits(bits: Sequence[int], n_params: int) -> int:
    """Baseline comparison: digital per-client uplink cost (sums over clients)."""
    return int(sum(int(b) * n_params for b in bits))

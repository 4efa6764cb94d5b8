"""Symmetric wire codec: ONE encode/decode facade for both legs
(DESIGN.md §6, §13).

Until PR 8 the quantize/pack/dequant pipeline was spelled per leg: the
uplink went through ``ota.quantize_uplink`` (clients) and the fused
in-pass dequant (server), while the downlink broadcast shipped raw f32
and had no codec at all. This module is the single seam both legs now
route through:

- ``encode_rows`` / ``encode_row``: stochastic-quantize a flat f32 row
  at ``bits`` with a shared positional dither stream
  (``core.quant.quantize_row_sr``) and bit-pack the symbols into a
  ``packing.PackedRow`` — int4 two symbols per byte, int8/int16/int32
  above, f32 passthrough for ``bits`` >= 32 (byte-identical to an
  uncoded transfer, the equivalence oracle). ``block`` > 0 ships
  blockwise scales (one f32 per ``block`` symbols).
- ``decode_rows`` / ``decode_row``: reconstruct the f32 row
  (q * scale[block]) — the same math the fused aggregation pass
  (``kernels/ota_fused.ota_packed_2d`` / ``kernels/ref.ota_packed_ref``)
  applies in-tile, so a host-side decode and the in-kernel dequant agree
  bit-for-bit on the same ``PackedRow``.

Leg mapping:

- **Uplink**: clients encode their update row with the round's uplink
  dither seed (``ota.derive_sr_seed``; ``first_row`` = the client's row
  in the cohort) and the server never decodes on the host — rows feed
  the fused dequant+superpose pass directly. ``decode_rows`` is the
  measurement/oracle path (quantization-error reports, tests).
- **Downlink** (DESIGN.md §13): the server encodes the round's global
  param delta ONCE with the downlink dither seed (``ota.derive_dl_seed``,
  a stream disjoint from the uplink's), broadcasts the single
  ``PackedRow``, and every client decodes it — decoding is
  deterministic given the row, so the whole fleet reconstructs
  bit-identical params.

Encoding is deterministic given (row, bits, seed, row index, block), and
decoding is a pure function of the encoded row — any two decoders of one
encoded row agree bit-for-bit.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp

from repro.core import packing, quant
from repro.kernels import ops as kops


def encode_row(
    row: jnp.ndarray,
    bits: int,
    seed: jnp.ndarray,
    row_index: int,
    *,
    block: int = 0,
) -> packing.PackedRow:
    """Encode one flat f32 row into its wire form at ``bits``.

    ``seed``/``row_index`` select the positional dither stream
    (``quant.quantize_row_sr``): uplink rows use the round's
    ``ota.derive_sr_seed`` with their cohort row index, the downlink
    broadcast uses ``ota.derive_dl_seed`` with row 0. ``block`` > 0
    quantizes with blockwise scales (one f32 per ``block`` symbols,
    +4 bytes/block on the wire); 0 is the per-row scalar scale.
    ``bits`` >= 32 (and <= 1, the empty symmetric grid) is the f32
    passthrough: ``data`` is the row itself, byte-identical to an
    uncoded transfer.
    """
    q, scale = quant.quantize_row_sr(row, bits, seed, row_index, block=block)
    if packing.wire_kind(bits) == "int4":
        q = kops.pack_int4_rows(q)
    qblock = block if int(jnp.asarray(scale).size) > 1 else 0
    return packing.PackedRow(data=q, scale=scale, bits=int(bits), qblock=qblock)


def decode_row(row: packing.PackedRow, n: Optional[int] = None) -> jnp.ndarray:
    """Reconstruct the f32 row a ``PackedRow`` encodes (q * scale[block]).

    Deterministic: every decoder of the same row produces bit-identical
    output — the property the compressed downlink's fleet-wide param
    consistency rests on. ``n`` trims to the logical (unpadded) length.
    """
    if row.kind == "float32":
        out = jnp.asarray(row.data, jnp.float32)
        return out if n is None else out[:n]
    q = row.data
    if row.kind == "int4":
        q = kops.unpack_int4_rows(q)
    q = q.astype(jnp.float32)
    scales = jnp.atleast_1d(jnp.asarray(row.scale, jnp.float32))
    if row.qblock > 0 and scales.shape[0] > 1:
        bid = jnp.arange(q.shape[0], dtype=jnp.int32) // row.qblock
        out = q * jnp.take(scales, bid, mode="clip")
    else:
        out = q * scales[0]
    return out if n is None else out[:n]


def decode_broadcast(
    row: packing.PackedRow,
    base: Optional[jnp.ndarray] = None,
    n: Optional[int] = None,
) -> jnp.ndarray:
    """Client-side downlink reconstruction (DESIGN.md §13).

    An f32 passthrough broadcast carries the ABSOLUTE params vector —
    the decode IS the params, bit-identical to the legacy uncompressed
    broadcast (``a + fl(b - a) != b`` in floats, so passthrough never
    routes through a delta). A quantized broadcast carries the round's
    global delta against ``base`` (the fleet's current replica), and the
    reconstruction is ``base + decode(row)``. Every client holds the
    same ``base`` and decoding is deterministic, so the whole fleet —
    and the server, which adopts the same reconstruction — lands on
    bit-identical params.
    """
    decoded = decode_row(row, n)
    if row.kind == "float32":
        return decoded
    assert base is not None, "quantized broadcast needs the current replica"
    return jnp.asarray(base, jnp.float32)[: decoded.shape[0]] + decoded


def encode_rows(
    rows: Sequence[jnp.ndarray],
    bits: Sequence[int],
    seed: jnp.ndarray,
    *,
    block: int = 0,
    first_row: int = 0,
) -> List[packing.PackedRow]:
    """Encode a batch of flat rows; row ``j`` dithers as row
    ``first_row + j`` of ``seed``'s stream."""
    assert len(rows) == len(bits), (len(rows), len(bits))
    return [
        encode_row(r, int(b), seed, first_row + j, block=block)
        for j, (r, b) in enumerate(zip(rows, bits))
    ]


def decode_rows(
    rows: Sequence[packing.PackedRow], n: Optional[int] = None
) -> List[jnp.ndarray]:
    """Decode a batch of wire rows back to f32 (see ``decode_row``)."""
    return [decode_row(r, n) for r in rows]


def wire_bytes(rows: Sequence[packing.PackedRow]) -> int:
    """Total bytes the encoded rows occupy on the wire."""
    return int(sum(r.wire_nbytes for r in rows))

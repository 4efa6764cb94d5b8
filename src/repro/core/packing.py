"""Pytree <-> flat-vector packing with a static, hashable ``Layout``.

The MP-OTA-FL data plane works on a flat ``(K, M)`` client-update matrix:
every client's update pytree is raveled into one padded f32 vector so the
whole round — quantize, superpose, noise — is a single device program
instead of an O(clients x leaves) dispatch storm. The same layout is the
natural wire/storage format for checkpointing and serving weight pushes,
so it lives in ``core`` rather than next to the OTA kernels.

A ``Layout`` is derived once per tree structure (``make_layout``) and is
fully static: treedef, per-leaf shapes/dtypes/offsets, and the padded
total length (rounded up to a lane-block multiple so packed vectors drop
straight into the Pallas kernels without re-padding). ``Layout`` is
hashable, so jitted functions can take it as a static argument and the
jit cache keys on the layout identity.

The flat vector is f32: every leaf round-trips through float32, so
integer leaves are exact only up to the 24-bit mantissa (|v| <= 2^24).
Fine for update/weight trees (the data plane) and f32/bf16 params;
trees carrying large integer state (step counters, RNG keys) need a
side channel.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ota_fused import INT4_GROUP

Pytree = Any

# Matches kernels.ota_fused.BLOCK_COLS: packed vectors tile evenly into the
# fused aggregation kernel's (K, block) grid with no second padding pass.
DEFAULT_BLOCK = 2048

# Default *quantization* block for blockwise uplink scales (DESIGN.md §6):
# symbols per scale on the wire. Distinct from DEFAULT_BLOCK (the lane-pad
# granularity of the flat layout). 256 symbols/scale costs +4 bytes per
# 256 symbols — for int4 that is 1/64 of the symbol bytes — while capping
# how far one outlier leaf can inflate the shared integer grid.
QUANT_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Layout:
    """Static description of a pytree's flat packing.

    offsets[i] is leaf i's start in the flat vector; ``size`` is the real
    (unpadded) element count and ``padded_size`` the lane-aligned length.
    Frozen + all-hashable fields => usable as a jit static argument.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]
    size: int
    padded_size: int
    block: int

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    @property
    def padding(self) -> int:
        return self.padded_size - self.size


# --------------------------------------------------------------------------
# Packed uplink wire format (DESIGN.md §6)
# --------------------------------------------------------------------------

# storage class per planned precision: the smallest wire dtype that holds
# the symmetric integer symbols. int4 is two symbols per byte
# (kernels.ops.pack_int4_rows). bits <= 1 has an empty symmetric grid
# (qmax = 2^(b-1) - 1 = 0) and rides through unquantized, exactly like
# the fused f32 path's qmax == 0 passthrough; bits >= 32 is unquantized
# by definition. 17..31 bits quantize like every other level — int32
# symbols save no bytes over f32 but keep the packed/flat equivalence.
def wire_kind(bits: int) -> str:
    """"int4"|"int8"|"int16"|"int32"|"float32" for a b-bit uplink row."""
    if bits <= 1 or bits >= 32:
        return "float32"
    if bits <= 4:
        return "int4"
    if bits <= 8:
        return "int8"
    if bits <= 16:
        return "int16"
    return "int32"


# public: core/ota groups cohort rows by this ordering (densest first)
KIND_RANK = {"int4": 0, "int8": 1, "int16": 2, "int32": 3, "float32": 4}


def n_scale_blocks(block: int, padded_size: int) -> int:
    """Scales a blockwise row ships: ceil(M / block); 1 when per-row."""
    if block <= 0 or block >= padded_size:
        return 1
    return -(-padded_size // block)


def row_wire_bytes(bits: int, padded_size: int, block: int = 0) -> int:
    """Bytes one client's packed row occupies on the wire.

    Quantized rows carry their symbols plus one f32 scale per
    quantization block — ``block`` = 0 (per-row, the PR-2 format) ships
    exactly one; blockwise ships ceil(padded_size / block), i.e.
    +4 bytes per ``block`` symbols. The f32 passthrough row is just the
    symbols.
    """
    kind = wire_kind(bits)
    if kind == "float32":
        return 4 * padded_size
    nscales = n_scale_blocks(block, padded_size)
    if kind == "int4":  # two symbols per byte, in whole 256-symbol groups
        return -(-padded_size // INT4_GROUP) * INT4_GROUP // 2 + 4 * nscales
    per = {"int8": 1, "int16": 2, "int32": 4}[kind]
    return per * padded_size + 4 * nscales


@dataclasses.dataclass(frozen=True)
class PackedRow:
    """One client's uplink in wire form: quantized symbols + analog grid.

    data: (padded_size//2,) uint8 for a 4-bit client (two symbols per
    byte in planar 256-symbol groups, ``kernels.ops.pack_int4_rows``),
    (padded_size,) int8/int16/int32 for 5..8 / 9..16 / 17..31 bits,
    or the (padded_size,) f32 row
    for an unquantized client (bits >= 32, or <= 1 where the symmetric
    grid is empty). scale is the f32 analog grid step: the () per-update
    scalar of the PR-2 format (the ``qblock`` = 0 degenerate case — old
    rows parse unchanged), or an (n_blocks,) vector of per-block scales
    where symbol position p belongs to block p // qblock (last block
    ragged over the zero-pad region). 1 for f32 rows. bits is the
    planned precision. Dequantization (q * scale[block]) happens inside
    the fused aggregation pass (``kernels/ota_fused.ota_packed_2d`` /
    ``kernels/ref.ota_packed_ref``) — the f32 row never exists between
    client and server.
    """

    data: jnp.ndarray
    scale: jnp.ndarray
    bits: int
    qblock: int = 0  # symbols per scale block; 0 = one per-update scale

    @property
    def kind(self) -> str:
        return wire_kind(self.bits)

    @property
    def n_scales(self) -> int:
        """Scale entries on the wire (1 for the per-row format)."""
        return max(int(jnp.asarray(self.scale).size), 1)

    @property
    def wire_nbytes(self) -> int:
        n = int(self.data.size) * jnp.dtype(self.data.dtype).itemsize
        return n if self.kind == "float32" else n + 4 * self.n_scales


def is_packed_rows(x: Any) -> bool:
    """True when ``x`` is a sequence of ``PackedRow`` (vs a (K, M) matrix)."""
    return (
        isinstance(x, (list, tuple))
        and len(x) > 0
        and all(isinstance(r, PackedRow) for r in x)
    )


def make_layout(tree: Pytree, block: int = DEFAULT_BLOCK) -> Layout:
    """Derive the static flat layout of ``tree`` (leaf order = treedef order)."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes, dtypes, sizes, offsets = [], [], [], []
    off = 0
    for leaf in leaves:
        leaf = jnp.asarray(leaf)
        shapes.append(tuple(int(d) for d in leaf.shape))
        dtypes.append(jnp.dtype(leaf.dtype).name)
        n = int(leaf.size)
        sizes.append(n)
        offsets.append(off)
        off += n
    padded = -(-max(off, 1) // block) * block
    return Layout(
        treedef=treedef,
        shapes=tuple(shapes),
        dtypes=tuple(dtypes),
        sizes=tuple(sizes),
        offsets=tuple(offsets),
        size=off,
        padded_size=padded,
        block=block,
    )


@functools.partial(jax.jit, static_argnames=("layout",))
def pack(tree: Pytree, layout: Layout) -> jnp.ndarray:
    """Ravel + concat + zero-pad ``tree`` into a ``(padded_size,)`` f32 vector."""
    leaves = jax.tree.leaves(tree)
    assert len(leaves) == layout.n_leaves, (len(leaves), layout.n_leaves)
    flat = [jnp.asarray(l).astype(jnp.float32).reshape(-1) for l in leaves]
    if layout.padding:  # padded_size >= block, so an empty tree is all pad
        flat.append(jnp.zeros((layout.padding,), jnp.float32))
    return jnp.concatenate(flat)


@functools.partial(jax.jit, static_argnames=("layout", "cast"))
def unpack(flat: jnp.ndarray, layout: Layout, *, cast: bool = True) -> Pytree:
    """Inverse of ``pack``. ``cast=False`` keeps every leaf f32 (the OTA
    aggregation path hands f32 aggregates to the server optimizer)."""
    leaves = []
    for shape, dtype, off, size in zip(
        layout.shapes, layout.dtypes, layout.offsets, layout.sizes
    ):
        leaf = jax.lax.slice_in_dim(flat, off, off + size).reshape(shape)
        leaves.append(leaf.astype(dtype) if cast else leaf)
    return jax.tree.unflatten(layout.treedef, leaves)


def pack_batch(trees: Sequence[Pytree], layout: Layout) -> jnp.ndarray:
    """Stack K packed client updates into the ``(K, padded_size)`` matrix."""
    return jnp.stack([pack(t, layout) for t in trees])

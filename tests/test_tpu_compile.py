"""Compile the main-path Pallas kernels for a TPU v5e chip that is
described, not attached (no chip needed).

Interpret mode runs a kernel body under XLA:CPU and accepts things the
TPU compiler (Mosaic) refuses: blocks that break the (8, 128) tiling
rule, casts and reshapes it has no lowering for, ops such as
``lax.top_k``. These tests lower and compile each data-plane kernel at
the widths of the FL round — K = 20 cohort rows over the deepspeech2
layout (4,134,912 symbols) — and the retrieval kernel over a 4096-record
arena slab, for every storage class. A compile error here is the error
the chip would raise.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ota_fused as kf
from repro.kernels import topk_similarity as tk

K = 20  # FLConfig().clients_per_round
M = 4_134_912  # deepspeech2 layout padded_size (4,133,952 params)
QBLOCK = 256  # FLConfig().quant_block
STORAGE = {
    "int4": jnp.uint8,
    "int8": jnp.int8,
    "int16": jnp.int16,
    "f32": jnp.float32,
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def _compiles_to_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fold", [False, True], ids=["superpose", "fold"])
@pytest.mark.parametrize("gained", [False, True], ids=["unit", "gains"])
@pytest.mark.parametrize("qblock", [QBLOCK, 0], ids=["qb256", "per_row"])
@pytest.mark.parametrize("kind", list(STORAGE))
def test_ota_kernel_compiles_for_v5e(shape, kind, qblock, gained, fold):
    packed4 = kind == "int4"
    q = shape((K, M // 2 if packed4 else M), STORAGE[kind])
    n_blocks = -(-M // qblock) if qblock else 1
    args = [q, shape((K, n_blocks), jnp.float32), shape((K,), jnp.float32)]
    if gained:
        args.append(shape((K,), jnp.float32))
    kw = dict(qblock=qblock, packed4=packed4)
    if fold:
        args.insert(0, shape((M,), jnp.float32))

        def fn(acc, q, s, w, g=None):
            return kf.ota_fold_2d(acc, q, s, w, gains=g, **kw)
    else:

        def fn(q, s, w, g=None):
            return kf.ota_packed_2d(q, s, w, gains=g, **kw)

    _compiles_to_kernel(fn, *args)


@pytest.mark.parametrize("k", [32, tk.TOPK_LANES])
@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_topk_kernel_compiles_for_v5e(shape, storage, k):
    D, qblock = 256, 64  # ragdb.EMBED_DIM, ArenaStore int8 default
    qm = shape((8, D), jnp.float32)
    n = shape((), jnp.int32)
    if storage == "int8":
        recs = shape((4096, D), jnp.int8)
        scales = shape((4096, D // qblock), jnp.float32)
        _compiles_to_kernel(
            lambda a, r, s, c: tk.topk_similarity_2d(a, r, s, c, k=k),
            qm, recs, scales, n,
        )
    else:
        recs = shape((4096, D), jnp.float32)
        _compiles_to_kernel(
            lambda a, r, c: tk.topk_similarity_2d(a, r, None, c, k=k), qm, recs, n
        )


def test_unaligned_qblock_is_refused_off_interpret():
    # a blockwise scale size the TPU scale streaming cannot tile is a
    # ValueError naming it, not a silent fallback
    q = jax.ShapeDtypeStruct((4, 4096), jnp.int8)
    s = jax.ShapeDtypeStruct((4, 6), jnp.float32)
    w = jax.ShapeDtypeStruct((4,), jnp.float32)
    with pytest.raises(ValueError, match="qblock=768"):
        jax.jit(lambda q, s, w: kf.ota_packed_2d(q, s, w, qblock=768)).lower(q, s, w)

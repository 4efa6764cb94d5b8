"""Mesh-sharded data planes: equivalence bars + weak-scaling sweep
(DESIGN.md §15).

Both data planes shard over the ``data`` axis of a 1-D device mesh
(``launch.mesh.make_data_mesh``): the OTA fold places its SYMBOL
(column) axis across shards — per-column sums never reassociate, the
cross-shard combine is pure concatenation — and the retrieval engine
row-shards the arena slab, runs the fused top-k per shard, and re-merges
lanes under the engine tie contract. Sharded == single-host *bitwise*
is the whole point, so this bench asserts it rather than timing around
it.

``--smoke`` is the CI mode (scripts/tier1.sh + the multidevice CI
lane), asserting the PR's acceptance bars on a forced-multi-device CPU
mesh:

- **bit-equality**: ``ota_aggregate_packed(..., mesh=4 shards)`` equals
  the unsharded aggregate byte-for-byte on a mixed-precision cohort
  (one-shot AND two-wave streaming accumulator), and the mesh
  retrieval engine equals the unsharded fused top-k byte-for-byte on
  f32 and int8 arenas;
- **per-shard residency**: at 4 shards each device holds <= 1/2 of the
  single-host resident bytes — the retrieval slab slice
  (``ArenaStore.shard_nbytes``) and the OTA column chunk
  (``core.ota._shard_chunk``) both shrink >= 2x.

The default mode prints a weak-scaling table over 1/2/4/8 shards:
fold / query wall time and the per-shard resident fraction.

Usage: python benchmarks/bench_mesh.py [--smoke] [--json-stdout]
Runnable standalone (self-locates ``src/``, forces 8 host devices
before the first jax import) or via benchmarks/run.py --json, which
re-execs this file in a child interpreter when jax is already
initialised single-device on the CPU. On an accelerator it runs in
process on the real devices, and fails with a message when there are
fewer than 4.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

try:
    import repro  # noqa: F401  (importability probe)
except ImportError:  # standalone invocation: put <repo>/src on sys.path
    sys.path.insert(
        0, str(pathlib.Path(__file__).resolve().parent.parent / "src"))

# a data mesh needs real (forced) host devices, and XLA_FLAGS only takes
# effect before the first jax import — so claim the devices at module
# import when jax isn't loaded yet (standalone runs); anything importing
# us with jax already up single-device goes through _respawn() instead
DEVICE_COUNT = 8
if "jax" not in sys.modules and "host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={DEVICE_COUNT}"
    ).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ota, packing
from repro.kernels import ops as kops
from repro.launch.mesh import make_data_mesh
from repro.retrieval.arena import ArenaStore
from repro.retrieval.engine import RetrievalEngine

K_DEFAULT = 8
M_DEFAULT = 1 << 14
N_RECORDS = 3072
DIM = 64
SHARD_SWEEP = (1, 2, 4, 8)


def _packed_cohort(K: int, M: int, seed: int = 0):
    """Synthetic mixed-precision packed cohort + layout + round key."""
    rng = np.random.RandomState(seed)
    tree = {"w": jnp.asarray(rng.randn(M).astype(np.float32) * 0.01)}
    layout = packing.make_layout(tree)
    bits = [(4, 8, 8, 16, 32)[i % 5] for i in range(K)]
    weights = [1.0 + (i % 3) for i in range(K)]
    key = jax.random.key(seed + 11)
    sr = ota.derive_sr_seed(key)
    rows = []
    for i, b in enumerate(bits):
        up = {"w": jnp.asarray(rng.randn(M).astype(np.float32) * 0.01)}
        rows.append(ota.quantize_uplink(packing.pack(up, layout), b, sr, i,
                                        block=packing.QUANT_BLOCK))
    return rows, weights, layout, key


def _leaves_bytes_equal(a, b) -> bool:
    return all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _arena(storage: str, seed: int = 3) -> ArenaStore:
    rng = np.random.RandomState(seed)
    store = ArenaStore(DIM, storage=storage)
    store.add_batch(rng.randn(N_RECORDS, DIM).astype(np.float32))
    return store


def _queries(seed: int = 4) -> np.ndarray:
    return np.random.RandomState(seed).randn(8, DIM).astype(np.float32)


# ---------------------------------------------------------------------------
# smoke bars
# ---------------------------------------------------------------------------


def check_ota_bit_equality(n_shards: int = 4) -> None:
    """Sharded OTA fold == single-host aggregate, byte for byte —
    one-shot barrier mode and the two-wave streaming accumulator."""
    rows, weights, layout, key = _packed_cohort(K_DEFAULT, M_DEFAULT)
    cfg = ota.OTAConfig(snr_db=20.0, fade_threshold=0.0)
    mesh = make_data_mesh(n_shards)
    ref, _ = ota.ota_aggregate_packed(key, rows, None, weights, layout, cfg,
                                      use_kernel=False)
    sh, _ = ota.ota_aggregate_packed(key, rows, None, weights, layout, cfg,
                                     use_kernel=False, mesh=mesh)
    assert _leaves_bytes_equal(ref, sh), "one-shot sharded fold not bitwise"
    # streaming: two waves through the persistent accumulator
    _, _, w = ota.round_channel(
        key, jnp.asarray(weights, jnp.float32), cfg=cfg)

    def stream(mesh_):
        acc = ota.OtaAccumulator(layout, cfg, use_kernel=False, mesh=mesh_)
        acc.fold(rows[:3], w[:3])
        acc.fold(rows[3:], w[3:])
        return acc.finalize(key)[0]

    assert _leaves_bytes_equal(stream(None), stream(mesh)), \
        "streaming sharded fold not bitwise"


def check_retrieval_bit_equality(n_shards: int = 4, k: int = 32) -> None:
    """Mesh retrieval engine == unsharded fused top-k, byte for byte,
    f32 and int8 arenas."""
    qm = _queries()
    for storage in ("f32", "int8"):
        store = _arena(storage)
        data, scales = store.raw()
        s0, i0 = kops.topk_cosine(
            jnp.asarray(qm), jnp.asarray(data),
            None if scales is None else jnp.asarray(scales),
            jnp.int32(len(store)), k=k, use_kernel=False)
        eng = RetrievalEngine(store, use_kernel=False,
                              mesh=make_data_mesh(n_shards))
        s1, i1 = eng.topk(qm, k)
        assert np.asarray(s0).tobytes() == s1.tobytes(), storage
        assert np.asarray(i0).tobytes() == i1.tobytes(), storage


def check_shard_residency(n_shards: int = 4) -> tuple:
    """Per-shard resident bytes shrink >= 2x at 4 shards, both planes.
    Returns (retrieval bytes ratio, ota column-chunk ratio)."""
    store = _arena("int8")
    bytes_ratio = store.shard_nbytes(1) / store.shard_nbytes(n_shards)
    kinds = (("int4", packing.QUANT_BLOCK), ("int8", packing.QUANT_BLOCK),
             ("int16", packing.QUANT_BLOCK), ("float32", 0))
    chunk_ratio = M_DEFAULT / ota._shard_chunk(M_DEFAULT, n_shards, kinds)
    assert bytes_ratio >= 2.0, bytes_ratio
    assert chunk_ratio >= 2.0, chunk_ratio
    return bytes_ratio, chunk_ratio


# ---------------------------------------------------------------------------
# weak-scaling sweep
# ---------------------------------------------------------------------------


def _time_ms(fn, reps: int = 3) -> float:
    fn()  # warm the caches (trace + compile)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def sweep() -> list:
    """Per shard count: fold/query wall ms + resident fraction."""
    rows, weights, layout, key = _packed_cohort(K_DEFAULT, M_DEFAULT)
    cfg = ota.OTAConfig(snr_db=20.0, fade_threshold=0.0)
    store = _arena("int8")
    qm = _queries()
    out = []
    for d in SHARD_SWEEP:
        if d > len(jax.devices()):
            continue
        mesh = None if d == 1 else make_data_mesh(d)
        fold_ms = _time_ms(lambda: ota.ota_aggregate_packed(
            key, rows, None, weights, layout, cfg, use_kernel=False,
            mesh=mesh))
        eng = RetrievalEngine(store, use_kernel=False, mesh=mesh) \
            if mesh is not None else RetrievalEngine(store, use_kernel=True)
        query_ms = _time_ms(lambda: eng.topk(qm, 32))
        kinds = (("int8", packing.QUANT_BLOCK),)
        out.append({
            "shards": d,
            "fold_ms": fold_ms,
            "query_ms": query_ms,
            "ota_resident_frac":
                ota._shard_chunk(M_DEFAULT, d, kinds) / M_DEFAULT,
            "slab_resident_frac":
                store.shard_nbytes(d) / store.shard_nbytes(1),
        })
    return out


# ---------------------------------------------------------------------------
# entrypoints
# ---------------------------------------------------------------------------


def _needs_respawn() -> bool:
    """True when this process has too few devices for a 4-shard mesh and
    a child with forced host devices can supply them — on the CPU only.
    On an accelerator this process holds the chip and a child could not
    take it, so too few devices there is an error."""
    devs = jax.devices()
    if len(devs) >= 4:
        return False
    if devs[0].platform != "cpu":
        raise SystemExit(
            f"bench_mesh needs 4 devices; {devs[0].platform} shows {len(devs)}")
    return True


def _respawn(args: list) -> subprocess.CompletedProcess:
    """Re-exec this file in a child interpreter with forced host devices
    (jax in this process is already initialised on too few CPU ones)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={DEVICE_COUNT}"
    return subprocess.run([sys.executable, __file__, *args],
                          capture_output=True, text=True, env=env)


def smoke() -> int:
    """CI mode: bit-equality + residency acceptance bars."""
    if _needs_respawn():
        out = _respawn(["--smoke"])
        sys.stdout.write(out.stdout)
        sys.stderr.write(out.stderr)
        return out.returncode
    check_ota_bit_equality()
    check_retrieval_bit_equality()
    bytes_ratio, chunk_ratio = check_shard_residency()
    print(f"smoke OK: 4-shard mesh OTA fold + retrieval top-k bit-equal "
          f"to single-host (one-shot + streaming, f32 + int8); per-shard "
          f"residency: slab 1/{bytes_ratio:.0f}, ota chunk "
          f"1/{chunk_ratio:.0f}")
    return 0


def json_report() -> dict:
    """Machine-readable smoke-scale numbers (benchmarks/run.py --json).

    When the hosting process already initialised jax single-device (the
    run.py case), computes in a re-exec'd child and parses its stdout.
    """
    if _needs_respawn():
        out = _respawn(["--json-stdout"])
        if out.returncode != 0:
            raise RuntimeError(f"bench_mesh child failed:\n{out.stderr}")
        return json.loads(out.stdout)
    check_ota_bit_equality()
    check_retrieval_bit_equality()
    bytes_ratio, chunk_ratio = check_shard_residency()
    return {
        "devices": len(jax.devices()),
        "K": K_DEFAULT, "M": M_DEFAULT,
        "n_records": N_RECORDS, "dim": DIM,
        "bit_equal": True,
        "slab_bytes_ratio_4": bytes_ratio,
        "ota_chunk_ratio_4": chunk_ratio,
        "sweep": sweep(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: bit-equality + residency asserts")
    ap.add_argument("--json-stdout", action="store_true",
                    help="print the json_report payload to stdout")
    args = ap.parse_args()

    if args.smoke:
        raise SystemExit(smoke())
    if args.json_stdout:
        print(json.dumps(json_report(), indent=2, sort_keys=True))
        return

    check_ota_bit_equality()
    print("4-shard mesh == single-host: bit-equal (OTA + retrieval)")
    print(f"{'shards':>6} {'fold_ms':>9} {'query_ms':>9} "
          f"{'ota_frac':>9} {'slab_frac':>10}")
    for row in sweep():
        print(f"{row['shards']:>6} {row['fold_ms']:>9.2f} "
              f"{row['query_ms']:>9.2f} {row['ota_resident_frac']:>9.3f} "
              f"{row['slab_resident_frac']:>10.3f}")


if __name__ == "__main__":
    main()

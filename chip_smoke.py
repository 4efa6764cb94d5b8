"""Run the FL round end to end on a TPU and check what it computes.

From the repository root, on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the mesh-sharded path on four chips

One chip: ``FLServer(FLConfig())`` — the paper defaults: 100 clients, 20
per round, 4 local steps of batch 8, the RAG planner, blockwise uplink
scales every 256 symbols, full-width deepspeech2 — runs 3 rounds through
``run()``, then ``StreamingFLServer`` runs one round on the fading
channel (the gained fold). The script asserts finite losses, a cohort
that mixes storage classes with int4 among them, and that the fold and
the retrieval ran the compiled kernels (the ``path`` label of the
``ota.rows`` and ``retrieval.queries`` counters), and it checks each
server's last aggregate against the jnp references run on the chip.

Four chips: one FL round with ``mesh_data_shards=4`` and one query of a
row-sharded retrieval arena, each compared bit for bit with mesh off on
the same chips (a structural contract, DESIGN.md §15), and a check that
the fold's shards sit on four distinct devices. Nothing else runs.

Timing lines name the device they ran on. Without a TPU the script
exits non-zero before printing any result. The last line of standard
output is one JSON object: ``{"ok": true, "device": {...}}``.
"""

import argparse
import collections
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARENA_ROWS = 1 << 18  # records of the row-sharded arena (D = 256)


def _jax():
    import jax

    # the persistent compile cache: where the environment names one,
    # JAX reads it from there; otherwise a fixed path in the checkout
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    return jax


def _require(ok, what):
    """A failed check ends the run with a non-zero exit (kept under -O,
    unlike assert)."""
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def _record_last_fold(ota):
    """Wrap ``ota._fold_groups`` so the last fold's inputs and output
    can be checked against the references afterwards."""
    last = {}
    fold = ota._fold_groups

    def recording(acc, kinds, datas, scales, wg, **kw):
        out = fold(acc, kinds, datas, scales, wg, **kw)
        last["fold"] = (acc, kinds, datas, scales, wg, kw.get("gains"), out)
        return out

    ota._fold_groups = recording
    return last


def _check_against_reference(rec):
    """The kernel fold vs ``ref.ota_packed_ref``/``ota_fold_ref`` on the
    same rows, group by group as the fold chains them. Both sides form
    the same products; only the order of each group's K-row f32 sum
    differs, so the difference stays within the sum of the groups'
    ``ref.ota_fold_bound`` (the f32 summation-order bound)."""
    import jax.numpy as jnp

    from repro.kernels import ref

    acc, kinds, datas, scales, wg, gains, out = rec
    want, bound, off = acc, 0.0, 0
    for (kind, qblock), data, scale in zip(kinds, datas, scales):
        kg = scale.shape[0]
        w = wg[off : off + kg]
        g = None if gains is None else gains[off : off + kg]
        off += kg
        kw = dict(gains=g, qblock=qblock, packed4=kind == "int4")
        bound = bound + ref.ota_fold_bound(want, data, scale, w, **kw)
        if want is None:
            want = ref.ota_packed_ref(data, scale, w, **kw)
        else:
            want = ref.ota_fold_ref(want, data, scale, w, **kw)
    err = jnp.abs(out - want)
    _require(bool(jnp.all(jnp.isfinite(out))), "non-finite aggregate")
    excess = float(jnp.max(err - bound))
    _require(excess <= 0, f"kernel vs reference beyond the bound by {excess}")
    return float(jnp.max(err)), float(jnp.max(err / bound))


def _paths(snapshot, name):
    """{(kind-or-None, path): count} of one counter's labelled series."""
    out = {}
    for series, v in snapshot["counters"].items():
        if series.startswith(name + "{"):
            labels = dict(p.split("=") for p in series[len(name) + 1 : -1].split(","))
            out[(labels.get("kind"), labels.get("path"))] = v
    return out


def one_chip(jax, kind):
    from repro import obs
    from repro.configs.base import FLConfig
    from repro.core import ota, packing
    from repro.fl.server import FLServer, StreamingFLServer

    reg = obs.metrics.REGISTRY
    last = _record_last_fold(ota)

    compile0 = reg.get("jax.compile_seconds", 0.0)
    t0 = time.perf_counter()
    server = FLServer(FLConfig())
    setup_s = time.perf_counter() - t0
    print(f"[{kind}] FLServer(FLConfig()) set-up s: {setup_s}")
    print(f"[{kind}] deepspeech2 params: {server.layout.size}")

    round_s = []
    run_round = server.run_round

    def timed_round(r):
        t = time.perf_counter()
        log = run_round(r)
        jax.block_until_ready(server._master)
        round_s.append(time.perf_counter() - t)
        return log

    server.run_round = timed_round
    logs = server.run(3)
    compile_s = reg.get("jax.compile_seconds", 0.0) - compile0
    print(f"[{kind}] compile s (rounds 0-2, all programs): {compile_s}")
    print(f"[{kind}] FLServer round s: {round_s}")
    for log in logs:
        mix = collections.Counter(packing.wire_kind(b) for b in log.bits.values())
        print(
            f"[{kind}] round {log.round} bits mix {dict(sorted(mix.items()))} "
            f"loss {log.train_loss} uplink bytes {log.uplink_bytes}"
        )
        _require(math.isfinite(log.train_loss), f"round {log.round} loss")
    err, ratio = _check_against_reference(last["fold"])
    print(f"[{kind}] round 2 aggregate vs reference: max |err| {err}, "
          f"max err/bound {ratio}")

    t = time.perf_counter()
    stream = StreamingFLServer(FLConfig(channel_model="fading"))
    (slog,) = stream.run(1)
    jax.block_until_ready(stream._master)
    print(f"[{kind}] StreamingFLServer fading round s (incl. set-up and "
          f"compile): {time.perf_counter() - t}")
    _require(math.isfinite(slog.train_loss), "fading round loss")
    _require(last["fold"][5] is not None, "the fading round folded without gains")
    err, ratio = _check_against_reference(last["fold"])
    print(f"[{kind}] fading aggregate vs reference: max |err| {err}, "
          f"max err/bound {ratio}")

    snap = reg.snapshot()
    rows = _paths(snap, "ota.rows")
    queries = _paths(snap, "retrieval.queries")
    print(f"[{kind}] fold rows by (storage, path): {rows}")
    print(f"[{kind}] retrieval queries by path: {queries}")
    _require({p for _, p in rows} == {"kernel"}, f"fold paths {rows}")
    _require({p for _, p in queries} == {"kernel"}, f"retrieval paths {queries}")
    kinds = {k for k, _ in rows}
    _require("int4" in kinds and len(kinds) >= 2, f"storage classes {kinds}")


def four_chips(jax, kind):
    import numpy as np

    from repro import obs
    from repro.configs.base import FLConfig
    from repro.core import ota
    from repro.fl.server import FLServer
    from repro.launch.mesh import make_data_mesh
    from repro.retrieval.arena import ArenaStore
    from repro.retrieval.engine import RetrievalEngine

    _require(len(jax.devices()) == 4, f"{len(jax.devices())} devices")
    placed = []
    build = ota._sharded_group_program

    def spy(*args):
        fn = build(*args)

        def run(*ops):
            out = fn(*ops)
            placed.append(frozenset(d.id for d in out.sharding.device_set))
            return out

        return run

    ota._sharded_group_program = spy
    t = time.perf_counter()
    meshed = FLServer(FLConfig(mesh_data_shards=4))
    meshed.run(1)
    jax.block_until_ready(meshed._master)
    print(f"[{kind} x4] mesh_data_shards=4 round s (incl. set-up and compile): "
          f"{time.perf_counter() - t}")
    ota._sharded_group_program = build
    t = time.perf_counter()
    plain = FLServer(FLConfig())
    plain.run(1)
    jax.block_until_ready(plain._master)
    print(f"[{kind} x4] mesh-off round s (incl. set-up and compile): "
          f"{time.perf_counter() - t}")
    print(f"[{kind} x4] fold output shards on devices: {sorted(map(sorted, placed))}")
    _require(placed and all(len(p) == 4 for p in placed), f"shards on {placed}")
    a, b = np.asarray(meshed._master), np.asarray(plain._master)
    _require(a.tobytes() == b.tobytes(), "mesh round params differ from mesh off")

    rng = np.random.RandomState(0)
    for storage in ("f32", "int8"):
        store = ArenaStore(256, storage=storage, capacity=ARENA_ROWS)
        recs = rng.randn(ARENA_ROWS, 256).astype(np.float32)
        store.add_batch(recs / np.linalg.norm(recs, axis=1, keepdims=True))
        q = rng.randn(20, 256).astype(np.float32)
        q /= np.linalg.norm(q, axis=1, keepdims=True)
        t = time.perf_counter()
        s4, i4 = RetrievalEngine(store, mesh=make_data_mesh(4)).topk(q, 32)
        print(f"[{kind} x4] sharded {storage} top-32 of {len(store)} s "
              f"(incl. upload and compile): {time.perf_counter() - t}")
        s1, i1 = RetrievalEngine(store).topk(q, 32)
        same = s4.tobytes() == s1.tobytes() and i4.tobytes() == i1.tobytes()
        _require(same, f"{storage} sharded top-k differs from mesh off")
    queries = _paths(obs.metrics.snapshot(), "retrieval.queries")
    _require({p for _, p in queries} == {"kernel"}, f"retrieval paths {queries}")
    print(f"[{kind} x4] mesh == mesh-off: FL params and top-k bitwise equal")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    jax = _jax()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    sys.path.insert(0, str(ROOT / "src"))
    if args.chips == 4:
        four_chips(jax, dev.device_kind)
    else:
        one_chip(jax, dev.device_kind)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

"""Telemetry layer (DESIGN.md §14): span tracer semantics (nesting,
disabled no-op, Perfetto export, spans on the profiler's clock), the
metrics registry (labels, histograms, reset), the jit-retrace counter's
regression guard, and the instrumented round loop's acceptance
properties — FLServer and StreamingFLServer emit the same span names
down to each client's local-update stages, the per-step spans match the
``fl.local_steps`` counter, the metrics byte counters agree bit-for-bit
with the ``RoundLog`` that fed them, the arena upload is counted once
per device-cache miss, and a disabled tracer leaves round outputs
byte-identical."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import ota, packing, wire
from repro.fl import FLServer, StreamingFLServer


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_nested_span_order_and_depth():
    with obs.enabled() as t:
        with obs.span("outer", tag=1):
            with obs.span("inner"):
                pass
            with obs.span("inner"):
                pass
    evs = t.events
    # children record on exit, before their parent
    assert [e.name for e in evs] == ["inner", "inner", "outer"]
    outer = evs[-1]
    assert outer.depth == 0 and outer.args == {"tag": 1}
    for inner in evs[:2]:
        assert inner.depth == 1
        # interval containment: the Perfetto nesting invariant
        assert inner.ts_us >= outer.ts_us
        assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1e-3


def test_disabled_tracer_records_nothing():
    t = obs.get_tracer()
    t.reset()
    assert not obs.is_enabled()
    # the disabled fast path returns the shared no-op singleton:
    # no allocation, no clock read, nothing recorded
    s = obs.span("anything", k=1)
    assert s is obs.NULL_SPAN
    with s:
        pass
    assert t.events == [] and t.span_names() == set()


def test_span_dropped_if_disabled_mid_flight():
    t = obs.get_tracer()
    with obs.enabled():
        s = obs.span("doomed")
        with s:
            t.disable()
        t.enable()
    assert "doomed" not in t.span_names()


def test_enabled_restores_prior_state():
    assert not obs.is_enabled()
    with obs.enabled():
        assert obs.is_enabled()
        with obs.disabled():
            assert not obs.is_enabled()
        assert obs.is_enabled()
    assert not obs.is_enabled()


def test_spans_sit_on_the_profiler_clock(tmp_path):
    """While enabled, each span is a profiler annotation of its name: it
    lands in the ``.xplane.pb`` host plane, within 1 ms of where an
    anchor annotation opened at a known ``perf_counter`` places it (the
    benchmark harness's alignment of the tracer's events)."""
    import time

    from jax.profiler import ProfileData

    names = ("probe.outer", "probe.inner")
    with obs.enabled() as t:
        epoch = time.perf_counter_ns()  # enabled() has just reset the epoch
        jax.profiler.start_trace(str(tmp_path))
        t_pc0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("probe.window"):
            with obs.span(names[0]):
                time.sleep(0.002)
                with obs.span(names[1]):
                    jnp.ones(3).block_until_ready()
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, float(ev.start_ns))
    assert set(names) | {"probe.window"} <= set(host)
    for e in t.events:
        placed = host["probe.window"] + e.ts_us * 1e3 + epoch - t_pc0
        assert abs(host[e.name] - placed) < 1e6, e.name


def test_perfetto_export_roundtrips():
    with obs.enabled() as t:
        with obs.span("a", k=3):
            with obs.span("b"):
                pass
    doc = json.loads(t.export_perfetto())
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "cat"):
            assert key in ev
        assert ev["ph"] == "X"
    # sorted by start time: parent first in the export
    assert [e["name"] for e in evs] == ["a", "b"]
    assert evs[0]["args"] == {"k": 3}


def test_perfetto_export_writes_file(tmp_path):
    with obs.enabled() as t:
        with obs.span("x"):
            pass
    path = tmp_path / "trace.json"
    text = t.export_perfetto(str(path))
    assert json.loads(path.read_text()) == json.loads(text)


def test_spans_keep_their_thread_id():
    with obs.enabled() as t:
        with obs.span("main_thread"):
            pass
        # record a span wholly on the worker thread
        def work():
            with t.span("worker"):
                pass
        th = threading.Thread(target=work)
        th.start()
        th.join()
    tids = {e.name: e.tid for e in t.events}
    assert tids["main_thread"] != tids["worker"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_histograms():
    r = obs.metrics.Registry()
    r.inc("c")
    r.inc("c", 2.5)
    r.set_gauge("g", 7.0)
    r.set_gauge("g", 8.0)  # last write wins
    for v in (1.0, 3.0, 2.0):
        r.observe("h", v)
    snap = r.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 8.0
    h = snap["histograms"]["h"]
    assert h == {"count": 3, "total": 6.0, "min": 1.0, "max": 3.0}
    r.reset()
    assert r.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_registry_labels_make_distinct_series():
    r = obs.metrics.Registry()
    r.inc("rows", 2, kind="int4")
    r.inc("rows", 3, kind="f32")
    r.inc("rows", 1, kind="int4")
    snap = r.snapshot()["counters"]
    assert snap["rows{kind=int4}"] == 3
    assert snap["rows{kind=f32}"] == 3
    assert r.get("rows", kind="int4") == 3


def test_jsonl_sink_and_dump(tmp_path):
    r = obs.metrics.Registry()
    r.inc("fl.uplink_bytes", 128)
    with obs.enabled() as t:
        with obs.span("round"):
            pass
    jsonl = tmp_path / "events.jsonl"
    trace = tmp_path / "trace.json"
    s = obs.export.dump_telemetry(str(jsonl), str(trace), registry=r,
                                  tracer=t)
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    kinds = {(ln["kind"], ln["name"]) for ln in lines}
    assert ("counter", "fl.uplink_bytes") in kinds
    assert ("span", "round") in kinds
    assert s["metrics"]["counters"]["fl.uplink_bytes"] == 128
    assert json.loads(trace.read_text())["traceEvents"]


# ---------------------------------------------------------------------------
# jit-retrace regression guard
# ---------------------------------------------------------------------------


def _packed_round(key, seed):
    """One mixed-bit packed aggregation round (fresh values, same shapes)."""
    rng = np.random.RandomState(seed)
    tree = {"w": jnp.zeros((2048,), jnp.float32)}
    layout = packing.make_layout(tree)
    bits = [4, 8, 16, 32]
    sr = ota.derive_sr_seed(key)
    rows = [
        ota.quantize_uplink(
            jnp.asarray(rng.randn(layout.padded_size).astype(np.float32)),
            b, sr, i)
        for i, b in enumerate(bits)
    ]
    out, _ = ota.ota_aggregate_packed(
        key, rows, bits, [1.0, 2.0, 1.0, 3.0], layout,
        ota.OTAConfig(snr_db=20.0))
    jax.block_until_ready(jax.tree.leaves(out))


def test_jit_retrace_counter_flat_on_second_round():
    """Round 2 of an identical-composition cohort must hit the jit cache:
    the ``jax.retraces`` counter (fed by the jax.monitoring hook) stays
    flat — the regression guard for shape/dtype-unstable round code."""
    _packed_round(jax.random.key(0), seed=0)  # warm every program
    obs.metrics.reset()
    _packed_round(jax.random.key(1), seed=1)
    warm = obs.metrics.get("jax.retraces")
    _packed_round(jax.random.key(2), seed=2)
    assert obs.metrics.get("jax.retraces") == warm, (
        "aggregation retraced on an identical cohort composition")


# ---------------------------------------------------------------------------
# instrumented round loop
# ---------------------------------------------------------------------------


def _cfg(**kw):
    base = dict(n_clients=6, clients_per_round=3, n_rounds=2, local_steps=1,
                local_batch=2, lr=1e-3, planner="unified", seed=0)
    base.update(kw)
    return FLConfig(**base)


def _run_one_round(server_cls, *, enabled):
    ctx = obs.enabled() if enabled else obs.disabled()
    with ctx:
        n0 = len(obs.get_tracer().events)
        obs.metrics.reset()
        srv = server_cls(_cfg(), shard_size=4)
        log = srv.run_round(0)
        names = {e.name for e in obs.get_tracer().events[n0:]}
        snap = obs.metrics.snapshot()
    return srv, log, names, snap


CLIENT_SPANS = ("client.update", "client.setup", "client.batch",
                "client.dispatch", "client.loss_read", "client.delta",
                "uplink_encode")


def test_servers_emit_same_span_names():
    """No deadline, full fill: the streaming engine's trace is the
    synchronous engine's trace — identical span name sets (and >= 7
    distinct pipeline stages, the acceptance floor)."""
    _, _, sync_names, _ = _run_one_round(FLServer, enabled=True)
    _, _, stream_names, _ = _run_one_round(StreamingFLServer, enabled=True)
    assert sync_names == stream_names
    assert len(sync_names) >= 7
    assert {"round", "plan", "client_train", "uplink_encode", "fold",
            "finalize", "optimizer", "broadcast_encode",
            "feedback"} <= sync_names
    assert set(CLIENT_SPANS) <= sync_names


@pytest.mark.parametrize("server_cls", [FLServer, StreamingFLServer])
def test_client_spans_nest_and_count_local_steps(server_cls):
    """Both round loops run each client's update under ``client_train``
    with the same stage spans; the per-step spans number exactly the
    ``fl.local_steps`` counter, local_steps x trained clients, and the
    round waits on the device once, in one ``client.loss_read`` under
    ``client_train`` after every client's update."""
    steps = 2
    with obs.enabled() as t:
        obs.metrics.reset()
        srv = server_cls(_cfg(local_steps=steps), shard_size=4)
        srv.run_round(0)
        evs = t.events
    by = {n: [e for e in evs if e.name == n] for n in CLIENT_SPANS}
    by["client_train"] = [e for e in evs if e.name == "client_train"]

    def parent(e, name):
        (p,) = [
            p for p in by[name]
            if p.ts_us <= e.ts_us
            and e.ts_us + e.dur_us <= p.ts_us + p.dur_us + 1e-3
        ]
        assert e.depth == p.depth + 1, (e.name, name)

    trained = len(by["client.update"])
    assert trained == srv.cfg.clients_per_round
    for e in by["client.update"]:
        parent(e, "client_train")
    for name in CLIENT_SPANS[1:]:
        assert by[name], name
        for e in by[name]:
            parent(e, "client_train" if name == "client.loss_read"
                   else "client.update")
    local_steps = obs.metrics.get("fl.local_steps")
    assert local_steps == steps * trained
    for name in ("client.batch", "client.dispatch"):
        assert len(by[name]) == local_steps, name
    for name in ("client.setup", "client.delta", "uplink_encode"):
        assert len(by[name]) == trained, name
    (read,) = by["client.loss_read"]
    last = max(e.ts_us + e.dur_us for e in by["client.update"])
    assert read.ts_us >= last


def test_metrics_bytes_match_roundlog_bitwise():
    _, log, _, snap = _run_one_round(FLServer, enabled=True)
    assert snap["counters"]["fl.uplink_bytes"] == log.uplink_bytes
    assert snap["counters"]["fl.downlink_bytes"] == log.downlink_bytes
    assert snap["counters"]["ota.uplink_bytes"] == log.uplink_bytes
    assert snap["gauges"]["fl.n_participating"] == log.n_participating
    assert "ota.truncation_rate" in snap["gauges"]


def test_disabled_tracer_leaves_round_byte_identical():
    """Telemetry only observes: enabled vs disabled rounds produce
    bit-identical params and logs (spans/metrics never fork the math)."""
    srv_on, log_on, names_on, _ = _run_one_round(FLServer, enabled=True)
    srv_off, log_off, names_off, _ = _run_one_round(FLServer, enabled=False)
    assert names_on and not names_off
    assert log_on.uplink_bytes == log_off.uplink_bytes
    assert log_on.train_loss == log_off.train_loss
    for a, b in zip(jax.tree.leaves(srv_on.params),
                    jax.tree.leaves(srv_off.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_round_log_publishes_stream_metrics():
    _, log, _, snap = _run_one_round(StreamingFLServer, enabled=True)
    assert snap["counters"]["stream.on_time"] == log.n_on_time
    assert snap["counters"]["stream.lost"] == log.n_lost
    assert snap["gauges"]["stream.sim_seconds"] == log.sim_seconds


# ---------------------------------------------------------------------------
# arena upload and wire codec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", ["f32", "int8"])
def test_arena_upload_counts_the_slab_once_per_live_count(storage):
    """A device-cache miss uploads the whole capacity slab (and its int8
    scales) and counts its bytes; a repeat query at the same live count
    uploads nothing; an append misses again."""
    from repro.retrieval.arena import ArenaStore
    from repro.retrieval.engine import RetrievalEngine

    rng = np.random.RandomState(0)
    st = ArenaStore(128, storage=storage, capacity=256)
    st.add_batch(rng.randn(40, 128).astype(np.float32))
    data, scales = st.raw()
    slab = data.nbytes + (0 if scales is None else scales.nbytes)
    eng = RetrievalEngine(st, use_kernel=True)
    queries = rng.randn(2, 128).astype(np.float32)

    def uploaded():
        get = obs.metrics.get
        return get("retrieval.upload_bytes", 0.0), get("retrieval.uploads", 0.0)

    obs.metrics.reset()
    with obs.enabled() as t:
        eng.topk(queries, 5)
        assert uploaded() == (slab, 1)
        eng.topk(queries, 5)
        assert uploaded() == (slab, 1)
        st.add_batch(rng.randn(3, 128).astype(np.float32))
        eng.topk(queries, 5)
        assert uploaded() == (2 * slab, 2)
    ups = [e for e in t.events if e.name == "retrieval.upload"]
    assert len(ups) == 2 and all(e.depth == 1 for e in ups)


def test_encode_row_same_traced_or_not_and_records_nothing():
    """The codec reads nothing from the device for telemetry: a row
    encodes to the same bytes with tracing on and off, and no histogram
    is recorded."""
    key = jax.random.key(3)
    row = jax.random.normal(key, (1024,), jnp.float32)
    seed = ota.derive_sr_seed(key)
    obs.metrics.reset()
    for bits in (4, 8, 16):
        with obs.disabled():
            off = wire.encode_row(row, bits, seed, 1, block=256)
        with obs.enabled():
            on = wire.encode_row(row, bits, seed, 1, block=256)
        np.testing.assert_array_equal(np.asarray(on.data), np.asarray(off.data))
        np.testing.assert_array_equal(np.asarray(on.scale), np.asarray(off.scale))
        assert (on.bits, on.qblock) == (off.bits, off.qblock)
    assert obs.metrics.snapshot()["histograms"] == {}

"""The per-layer readers of the program's client-training and arena-upload
spans and counters, on hand-made contexts with known answers (CPU only),
and every per-layer metric's cells against the benchmark's cells."""

import json
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from chipbench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def read(name, spans, counters, rounds=2, window_s=2.0):
    ctx = types.SimpleNamespace(
        spans=spans, counters=counters, rounds=[{}] * rounds, window_s=window_s
    )
    return spec.load_module("metrics", name).read(ctx)


# (name, start_s, dur_s, depth): two clients of one round, 2 steps each
SPANS = [
    ("round", 0.0, 1.0, 0),
    ("plan", 0.0, 0.1, 1),
    ("retrieval.query", 0.0, 0.05, 2),
    ("retrieval.upload", 0.0, 0.02, 3),
    ("retrieval.query", 0.05, 0.05, 2),
    ("retrieval.upload", 0.05, 0.03, 3),
    ("client_train", 0.1, 0.8, 1),
] + [
    (name, 0.0, dur, 4)
    for name, dur in [
        ("client.batch", 0.002), ("client.dispatch", 0.001),
        ("client.loss_read", 0.005),
    ] * 4
]
COUNTERS = {"fl.local_steps": 4.0, "retrieval.upload_bytes": 4 * 2**20}


def test_readers_of_the_traced_spans_and_counters():
    assert read("local_step_ms", SPANS, COUNTERS) == pytest.approx(8.0)
    assert read("train_input_share", SPANS, COUNTERS) == pytest.approx(0.4)
    assert read("train_wait_share", SPANS, COUNTERS) == pytest.approx(1.0)
    assert read("arena_upload_share", SPANS, COUNTERS) == pytest.approx(2.5)
    assert read("arena_upload_mib", SPANS, COUNTERS) == pytest.approx(2.0)


@pytest.mark.parametrize(
    "name",
    ["local_step_ms", "train_input_share", "train_wait_share",
     "arena_upload_mib", "arena_upload_share"],
)
def test_readers_find_nothing_in_a_program_without_the_spans(name):
    """A program without the client and upload instrumentation: its
    window has only the stage spans and the query counters."""
    spans = [s for s in SPANS if not s[0].startswith(("client.", "retrieval.upload"))]
    counters = {"retrieval.query_rows": 1e6, "jax.compiles": 0.0}
    assert read(name, spans, counters) is None


def test_nothing_uploaded_reads_zero_mib():
    spans = [s for s in SPANS if s[0] != "retrieval.upload"]
    counters = {"fl.local_steps": 4.0}
    assert read("arena_upload_mib", spans, counters) == 0.0
    assert read("arena_upload_share", spans, counters) is None
    assert read("local_step_ms", spans, counters) == pytest.approx(8.0)


def test_per_layer_workloads_name_only_the_benchmarks_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]

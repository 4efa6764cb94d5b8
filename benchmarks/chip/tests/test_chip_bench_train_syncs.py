"""The reader of the ``fl.train_syncs`` counter on hand-made contexts
with known answers (CPU only), and its entry in the benchmark."""

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


def read(counters, rounds=3):
    ctx = types.SimpleNamespace(
        spans=[], counters=counters, rounds=[{}] * rounds, window_s=3.0
    )
    return spec.load_module("metrics", "train_syncs_per_round").read(ctx)


@pytest.mark.parametrize(
    "counters, want",
    [
        ({"fl.local_steps": 240.0, "fl.train_syncs": 3.0}, 1.0),
        ({"fl.local_steps": 240.0, "fl.train_syncs": 6.0}, 2.0),
        # a program that waits on every step counts no reads
        ({"fl.local_steps": 240.0, "jax.compiles": 0.0}, None),
        # no client trained in the window
        ({"fl.train_syncs": 3.0}, None),
        ({}, None),
    ],
)
def test_train_syncs_per_round(counters, want):
    got = read(counters)
    assert got == (None if want is None else pytest.approx(want))


def test_train_syncs_per_round_is_read_in_both_cells():
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == "train_syncs_per_round"]
    assert m["source"] == "program_counter" and m["moves"] == "round_s"
    assert set(m["workloads"]) == {w["name"] for w in BENCH["workloads"]}

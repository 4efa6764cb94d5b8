"""Cohort training as an asynchronous pipeline on the host: a client's
local update never waits on the device (its losses stay device scalars,
its delta + pack is one compiled program, bitwise the eager ops), and a
round reads the cohort's losses once, counted by ``fl.train_syncs``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import ota, packing
from repro.fl import FLServer, StreamingFLServer
from repro.fl.client import FLClient, client_delta


def _cfg(**kw):
    base = dict(n_clients=6, clients_per_round=3, n_rounds=2, local_steps=2,
                local_batch=2, lr=1e-3, planner="unified", seed=0)
    base.update(kw)
    return FLConfig(**base)


def test_local_update_returns_device_losses_and_reads_nothing():
    srv = FLServer(_cfg(), shard_size=4)
    with obs.enabled() as t:
        n0 = len(t.events)
        row, m = srv.clients[0].local_update(
            srv.params, 8, local_steps=2, local_batch=2, lr=1e-3,
            layout=srv.layout, sr_seed=ota.derive_sr_seed(jax.random.key(0)))
        names = [e.name for e in t.events[n0:]]
    for key in ("loss_first", "loss_last"):
        assert isinstance(m[key], jax.Array), (key, type(m[key]))
        assert m[key].shape == ()
        assert np.isfinite(float(m[key]))  # what the benchmark's recorder reads
    assert "client.loss_read" not in names
    assert names.count("client.dispatch") == 2
    assert names.count("client.delta") == 1
    assert isinstance(row, packing.PackedRow)


@pytest.mark.parametrize("packed", [True, False])
def test_client_delta_is_bitwise_the_eager_subtract_and_pack(packed):
    srv = FLServer(_cfg(), shard_size=4)
    old = srv.params
    leaves, treedef = jax.tree.flatten(old)
    keys = jax.random.split(jax.random.key(3), len(leaves))
    new = jax.tree.unflatten(treedef, [
        (l.astype(jnp.float32)
         + 1e-3 * jax.random.normal(k, l.shape, jnp.float32)).astype(l.dtype)
        for l, k in zip(leaves, keys)
    ])
    layout = srv.layout if packed else None
    eager = jax.tree.map(
        lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32), new, old)
    if packed:
        eager = packing.pack(eager, layout)
    fused = client_delta(new, old, layout)
    want, got = jax.tree.leaves(eager), jax.tree.leaves(fused)
    assert jax.tree.structure(fused) == jax.tree.structure(eager)
    for w, g in zip(want, got):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint32), np.asarray(w).view(np.uint32))


def _round_reading_losses_per_client(server_cls, monkeypatch, **kw):
    """One round with each client's last loss also read on its own, as
    the update did when it waited on every step."""
    read = []
    local_update = FLClient.local_update

    def reading(*a, **k):
        out = local_update(*a, **k)
        read.append(float(out[1]["loss_last"]))
        return out

    monkeypatch.setattr(FLClient, "local_update", reading)
    obs.metrics.reset()
    log = server_cls(_cfg(**kw), shard_size=4).run_round(0)
    return log, read, obs.metrics.get("fl.train_syncs", 0.0)


@pytest.mark.parametrize("server_cls", [FLServer, StreamingFLServer])
def test_round_reads_the_cohorts_losses_once(server_cls, monkeypatch):
    log, read, syncs = _round_reading_losses_per_client(server_cls, monkeypatch)
    assert syncs == 1
    assert len(read) == 3
    assert isinstance(log.train_loss, float)
    assert log.train_loss == float(np.mean(read))


def test_fading_round_adds_one_read_of_the_gains(monkeypatch):
    log, read, syncs = _round_reading_losses_per_client(
        FLServer, monkeypatch, channel_model="fading", fade_threshold=1e-6,
        tx_power_budget=4.0)
    assert syncs == 2
    assert read and log.train_loss == float(np.mean(read))


def test_round_with_nobody_trained_reads_nothing(monkeypatch):
    log, read, syncs = _round_reading_losses_per_client(
        FLServer, monkeypatch, dropout_prob=1.0)
    assert not read and np.isnan(log.train_loss)
    assert syncs == 0

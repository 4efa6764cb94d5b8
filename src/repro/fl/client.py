"""FL client: local training at the planned precision level.

A client owns a simulated user (ground truth), a device spec, and a data
shard. ``local_update`` runs local SGD steps with the model fake-quantized
to the planned bits (STE gradients) and returns the parameter delta — the
thing the OTA channel superposes. With a ``layout`` the delta is returned
already flat-packed (``core.packing``): the client is the one that
modulates its update onto the analog symbol stream, so the pytree never
crosses the client/server boundary and the server stacks rows straight
into the (K, M) aggregation matrix. With the round's dither seed as well,
the client also *quantizes and bit-packs* its row through the symmetric
wire codec (``wire.encode_row`` -> ``packing.PackedRow``): a 4-bit
client's uplink is two symbols per byte + one f32 scale, 1/8 the f32 row
(DESIGN.md §6). The same codec decodes the server's compressed downlink
broadcast (``wire.decode_broadcast``, DESIGN.md §13).

The module also hosts the seeded ``LatencyModel`` — per-client lognormal
compute + uplink delay derived from the ``DeviceSpec`` — that gives every
uplink a simulated arrival time for the streaming round loop
(DESIGN.md §11).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import packing
from repro.core.profiling.hardware import DeviceSpec
from repro.core.profiling.users import UserTruth
from repro.data.voice import ClientShard, batchify
from repro.launch.steps import make_quantized_train_step
from repro.models.registry import Model
from repro.optim import sgd

Pytree = Any


# process-wide compiled-step cache: all clients at the same (arch, bits,
# lr) share one XLA executable — compile once, reuse across the federation.
_STEP_CACHE: Dict[Tuple[str, int, float], Tuple[Callable, Any]] = {}


@functools.partial(jax.jit, static_argnames=("layout",))
def client_delta(
    new_params: Pytree, old_params: Pytree, layout: Optional[packing.Layout] = None
) -> Any:
    """The client's update ``new - old`` in f32, as one compiled program.

    With ``layout`` the delta comes back flat-packed (``packing.pack``:
    the (padded_size,) f32 row), else as the delta pytree. The same f32
    subtract and concatenate the eager ops would run, so the result is
    bitwise theirs; the program keys on the layout and the trees' shapes
    only, never on the bits or the number of local steps.
    """
    delta = jax.tree.map(
        lambda new, old: new.astype(jnp.float32) - old.astype(jnp.float32),
        new_params,
        old_params,
    )
    return delta if layout is None else packing.pack(delta, layout)


# simulated uplink rate per device class (Mbit/s). ``DeviceSpec`` carries
# no radio field, so the device class is the proxy: flagships and laptops
# on good WiFi/5G, IoT hubs on constrained links.
UPLINK_MBPS: Dict[str, float] = {
    "flagship_phone": 20.0,
    "midrange_phone": 10.0,
    "smart_speaker": 8.0,
    "iot_hub": 2.0,
    "laptop": 40.0,
}


@dataclasses.dataclass(frozen=True)
class LatencyModel:
    """Seeded per-round client latency + dropout simulation (DESIGN.md
    §11).

    Gives every uplink an arrival *time* so the streaming round loop has
    an order and a clock. Compute time is the round's training work over
    the device's sustained flops (``DeviceSpec.cpu_gflops``) times a
    lognormal multiplier — ``sigma`` tunes the straggler tail, with
    p95/p50 = exp(1.645 * sigma). Uplink time is the packed row's wire
    bytes over the device class's link rate (``UPLINK_MBPS``) with its
    own (lighter) lognormal jitter. Low-battery devices throttle by
    ``low_battery_slowdown``. ``drop_prob`` is the per-round
    never-reports probability (doubled on low battery) — the scheduling
    simulation's dropout knob, on top of ``FLConfig.dropout_prob`` which
    the training loop itself applies. All draws come from the caller's
    ``numpy.random.RandomState``, so a seeded round replays exactly.
    """

    work_flops: float = 5e9  # proxy for local_steps x batch x model cost
    sigma: float = 0.6  # compute lognormal spread (straggler tail)
    net_sigma: float = 0.25  # uplink jitter
    low_battery_slowdown: float = 2.0
    drop_prob: float = 0.0

    @classmethod
    def with_tail(cls, p95_over_p50: float, **kw) -> "LatencyModel":
        """Model whose compute-latency p95/p50 ratio is the given tail."""
        import math

        return cls(sigma=math.log(p95_over_p50) / 1.645, **kw)

    def p95_over_p50(self) -> float:
        return float(np.exp(1.645 * self.sigma))

    def sample(
        self, spec: DeviceSpec, rng: np.random.RandomState, *, uplink_bytes: int
    ) -> float:
        """One arrival latency (seconds) for this device and uplink."""
        compute = self.work_flops / (spec.cpu_gflops * 1e9)
        if spec.power_state == "low_battery":
            compute *= self.low_battery_slowdown
        compute *= rng.lognormal(0.0, self.sigma)
        rate = UPLINK_MBPS.get(spec.device_class, 10.0) * 1e6 / 8.0
        uplink = (uplink_bytes / rate) * rng.lognormal(0.0, self.net_sigma)
        return float(compute + uplink)

    def dropped(self, spec: DeviceSpec, rng: np.random.RandomState) -> bool:
        """Whether this client silently never reports this round."""
        p = self.drop_prob
        if spec.power_state == "low_battery":
            p = min(1.0, 2.0 * p)
        return p > 0 and bool(rng.rand() < p)


@dataclasses.dataclass
class FLClient:
    user: UserTruth
    spec: DeviceSpec
    shard: ClientShard
    model: Model

    def _step_fn(
        self, bits: int, lr: float, fedprox_mu: float = 0.0
    ) -> Tuple[Callable, Any]:
        key = (self.model.cfg.name, bits, lr, fedprox_mu)
        if key not in _STEP_CACHE:
            opt = sgd(lr)
            step = make_quantized_train_step(
                self.model, opt, bits, fedprox_mu=fedprox_mu
            )
            _STEP_CACHE[key] = (jax.jit(step), opt)
        return _STEP_CACHE[key]

    def local_update(
        self,
        global_params: Pytree,
        bits: int,
        *,
        local_steps: int = 4,
        local_batch: int = 8,
        lr: float = 5e-4,
        seed: int = 0,
        max_frames: int = 320,
        max_labels: int = 40,
        fedprox_mu: float = 0.0,
        layout: Optional[packing.Layout] = None,
        sr_seed: Optional[jnp.ndarray] = None,
        uplink_row: int = 0,
        quant_block: int = 0,
        channel_gain: Optional[float] = None,
        channel_habs: Optional[float] = None,
    ) -> Tuple[Any, Dict[str, Any]]:
        """Run local steps; return (delta, metrics).

        With ``layout`` alone, delta is the flat-packed (padded_size,) f32
        row ready to stack into the OTA aggregation matrix. With
        ``sr_seed`` too (the round dither seed, ``ota.derive_sr_seed``;
        ``uplink_row`` = this client's row in the round cohort), delta is
        the quantized+bit-packed wire row (``packing.PackedRow``) — the
        client modulates its own uplink, at ``bits``, and only
        sub-byte-packed symbols plus the scale vector cross to the
        server. ``quant_block`` > 0 quantizes with blockwise scales (one
        f32 per ``quant_block`` symbols, the round config's
        ``FLConfig.quant_block``; 0 = one per-update scale). Without
        ``layout``: the parameter-delta pytree (legacy shape).

        ``channel_gain``/``channel_habs``: this round's realised channel
        state for the client (``core.channel``, DESIGN.md §12) — echoed
        into the returned metrics as uplink metadata, the per-round
        radio report that rides alongside the packed row.

        The update never waits on the device: ``loss_first`` and
        ``loss_last`` are 0-d device arrays, so the host enqueues step
        s+1 (and the next client's work) while step s runs. The caller
        reads them when it needs them (``FLServer._train_cohort`` reads
        the whole cohort's at once).
        """
        # Host spans of the update's stages (DESIGN.md §14): ``client.batch``
        # (host batch + transfers) and ``client.dispatch`` (the jitted step's
        # enqueue) split each local step's wall time; ``client.delta`` is the
        # enqueue of the compiled delta (+ pack).
        with obs.span("client.update", user=self.user.user_id):
            jitted, opt = self._step_fn(bits, lr, fedprox_mu)
            with obs.span("client.setup"):
                state = {
                    "params": global_params,
                    "opt": opt.init(global_params),
                    "step": jnp.zeros((), jnp.int32),
                }
                if fedprox_mu > 0.0:
                    state["anchor"] = global_params
            rng = np.random.RandomState(seed * 1009 + self.user.user_id)
            losses = []
            utts = self.shard.utterances
            for s in range(local_steps):
                with obs.span("client.batch"):
                    idx = rng.randint(0, len(utts), size=min(local_batch, len(utts)))
                    batch = batchify(
                        [utts[i] for i in idx],
                        max_frames=max_frames,
                        max_labels=max_labels,
                    )
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with obs.span("client.dispatch"):
                    state, metrics = jitted(state, batch)
                losses.append(metrics["loss"])
            obs.metrics.inc("fl.local_steps", local_steps)
            with obs.span("client.delta"):
                delta = client_delta(state["params"], global_params, layout)
            if layout is not None and sr_seed is not None:
                from repro.core import wire

                with obs.span("uplink_encode", bits=bits):
                    delta = wire.encode_row(
                        delta, bits, sr_seed, uplink_row, block=quant_block
                    )
        metrics = {
            "loss_first": losses[0],
            "loss_last": losses[-1],
            "n_samples": len(utts),
        }
        if channel_gain is not None:
            metrics["channel_gain"] = float(channel_gain)
        if channel_habs is not None:
            metrics["channel_habs"] = float(channel_habs)
        return delta, metrics

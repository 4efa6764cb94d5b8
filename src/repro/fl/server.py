"""The MP-OTA-FL server: client selection, multi-client quantization
planning (via the paper's RAG planner or the unified baseline), OTA
aggregation, and per-round feedback collection into the RAG databases.

Two round loops share the same planning/training/feedback stages
(DESIGN.md §11):

- ``FLServer.run_round`` — the synchronous barrier: select -> all K
  clients train -> one aggregation. Wall-clock per round is set by the
  slowest straggler, and a single dropout stalls the whole cohort.
- ``StreamingFLServer.run_round`` — the event-driven buffered engine:
  every uplink gets a simulated arrival time (``fl/client.LatencyModel``),
  aggregation fires on cohort-fill or deadline (``plan_stream``), rows
  landing inside the grace window fold in late with a staleness
  discount, and everything folds into one persistent
  ``core/ota.OtaAccumulator``. With no deadline and a full fill target
  the engine degenerates to the barrier and is bit-identical to the
  synchronous path (the equivalence oracle).
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig, FLConfig, get_arch
from repro.core import channel as chanmod
from repro.core import ota, packing, wire
from repro.optim.optimizers import state_nbytes
from repro.core.profiling.hardware import make_fleet
from repro.core.profiling.planner import (
    BasePlanner,
    RAGPlanner,
    UnifiedTierPlanner,
    plan_round,
)
from repro.core.profiling.users import (
    drift_device,
    drift_user,
    make_users,
    satisfaction_score,
    true_performance,
)
from repro.data.voice import Utterance, batchify, make_client_shard, make_eval_set
from repro.fl.client import FLClient, LatencyModel
from repro.models.deepspeech2 import ds2_greedy_decode
from repro.models.registry import build_model

Pytree = Any


def make_planner(cfg: FLConfig) -> BasePlanner:
    if cfg.planner == "unified":
        return UnifiedTierPlanner()
    if cfg.planner == "rag":
        return RAGPlanner(strategy=cfg.strategy, seed=cfg.seed)
    if cfg.planner == "rag_energy":
        return RAGPlanner(strategy=cfg.strategy, energy_priority=8.0, seed=cfg.seed)
    raise ValueError(f"unknown planner {cfg.planner!r}")


def _mix_stream(*parts: int) -> int:
    """Hash-combine stream coordinates into one 32-bit RNG seed.

    Boost-style avalanche mix: every part perturbs the whole state, so
    distinct (seed, rnd, salt) triples land in distinct streams. The
    previous ``seed * salt + rnd`` collapsed at seed = 0 (the FLConfig
    default!): every salt named the SAME stream, so the dropout draw and
    the streaming latency draw were correlated copies of each other —
    the latent seed-reuse hazard tests/test_channel.py now pins closed.
    """
    h = 0
    for p in parts:
        h ^= (int(p) & 0xFFFFFFFF) + 0x9E3779B9 + \
            ((h << 6) & 0xFFFFFFFF) + (h >> 2)
        h &= 0xFFFFFFFF
    return h


def round_rng(seed: int, rnd: int, salt: int = 1237) -> np.random.RandomState:
    """Seeded per-round numpy RNG (dropout draws, latency draws, ...).

    One helper shared by both round loops so a (seed, rnd, salt) triple
    names exactly one stream (``_mix_stream``) — the streaming server's
    extra draws use distinct salts and never perturb the synchronous
    streams, at every seed including 0.
    """
    return np.random.RandomState(_mix_stream(seed, rnd, salt))


def round_drift_rng(seed: int, rnd: int) -> random.Random:
    """Seeded per-round stdlib RNG for the context/hardware drift stage."""
    return random.Random(_mix_stream(seed, rnd, 7919))


@dataclasses.dataclass
class RoundLog:
    """Typed per-round report (the round-loop side of ``ota.AggregateInfo``).

    ``uplink_bytes``/``downlink_bytes`` are the round's two wire legs —
    the cohort's packed uplink rows and the one broadcast row every
    client receives (DESIGN.md §13) — so round-trip accounting reads
    straight off the log. ``publish`` pushes the same values into the
    ``obs.metrics`` registry (DESIGN.md §14) — the log stays the
    per-round record, the registry the cross-round rollup, and the two
    agree bit-for-bit because one feeds the other.
    """

    round: int
    bits: Dict[int, int]
    mean_satisfaction: float
    mean_energy: float
    n_participating: int
    train_loss: float
    uplink_bytes: int = 0
    downlink_bytes: int = 0

    def publish(self, registry=None) -> "RoundLog":
        m = registry or obs.metrics.REGISTRY
        m.inc("fl.rounds")
        m.inc("fl.uplink_bytes", self.uplink_bytes)
        m.inc("fl.downlink_bytes", self.downlink_bytes)
        m.set_gauge("fl.n_participating", self.n_participating)
        if not math.isnan(self.train_loss):
            m.set_gauge("fl.train_loss", self.train_loss)
        m.set_gauge("fl.mean_satisfaction", self.mean_satisfaction)
        m.set_gauge("fl.mean_energy", self.mean_energy)
        return self


class FLServer:
    """Owns the global model and runs the federated rounds."""

    def __init__(
        self,
        fl_cfg: FLConfig,
        arch: Optional[ArchConfig] = None,
        *,
        shard_size: int = 24,
    ):
        self.cfg = fl_cfg
        self.arch = arch or get_arch("deepspeech2")
        self.model = build_model(self.arch)
        self.users = make_users(fl_cfg.n_clients, seed=fl_cfg.seed)
        self.fleet = make_fleet(fl_cfg.n_clients, seed=fl_cfg.seed)
        self.clients = [
            FLClient(
                u,
                s,
                make_client_shard(u, base_size=shard_size, seed=fl_cfg.seed),
                self.model,
            )
            for u, s in zip(self.users, self.fleet)
        ]
        self.planner = make_planner(fl_cfg)
        self.params = self.model.init(jax.random.key(fl_cfg.seed))
        # one flat layout for the whole federation: clients pack their
        # deltas onto it, the OTA data plane aggregates rows (core/ota.py)
        self.layout = packing.make_layout(self.params)
        # server-side flat state (DESIGN.md §13): ``_master`` is the f32
        # optimizer-side params vector, ``_bcast`` the fleet's replica —
        # what the clients reconstructed from the last downlink broadcast
        # (== master under the f32 passthrough; under a quantized
        # downlink, master - bcast is the residual the next broadcast
        # re-sends: implicit error feedback). ``self.params`` is always
        # the unpacked ``_bcast`` — server and clients train/evaluate on
        # the same reconstruction.
        self._master = packing.pack(self.params, self.layout)
        self._bcast = self._master
        self.last_broadcast: Optional[packing.PackedRow] = None
        self.last_downlink_bytes = 0
        # physical OTA channel (DESIGN.md §12): None = legacy ideal path
        if fl_cfg.channel_model == "fading":
            self.channel: Optional[chanmod.ChannelModel] = chanmod.ChannelModel(
                chanmod.ChannelConfig(
                    fade_threshold=fl_cfg.fade_threshold,
                    power_budget=fl_cfg.tx_power_budget,
                    pathloss_spread_db=fl_cfg.pathloss_spread_db,
                )
            )
        elif fl_cfg.channel_model == "ideal":
            self.channel = None
        else:
            raise ValueError(f"unknown channel_model {fl_cfg.channel_model!r}")
        # mesh-sharded OTA data plane (DESIGN.md §15): both round loops
        # aggregate on this mesh when the knob is set; the sharded fold
        # is bit-identical to the single-host one, so the knob never
        # changes a run's trajectory.
        if fl_cfg.mesh_data_shards > 1:
            from repro.launch.mesh import make_data_mesh
            self.mesh = make_data_mesh(fl_cfg.mesh_data_shards)
        else:
            self.mesh = None
        self._chan_hist: Dict[int, List[int]] = {}  # id -> [n_trunc, n_seen]
        self.round_logs: List[RoundLog] = []
        self._rng = np.random.RandomState(fl_cfg.seed + 7)

    def _log_round(self, log: RoundLog) -> RoundLog:
        """Record the round log and publish it into ``obs.metrics``."""
        self.round_logs.append(log.publish())
        return log

    # -- client selection (round-robin batches, paper default scheduling)
    def select(self, rnd: int) -> List[int]:
        n = self.cfg.n_clients
        k = self.cfg.clients_per_round
        start = (rnd * k) % n
        return [(start + i) % n for i in range(k)]

    # ---- round stages, shared by the synchronous and streaming loops ----

    def _apply_drift(self, rnd: int, users, specs) -> None:
        # context / hardware drift (paper §III-A interview triggers 2/3):
        # users move devices, schedules shift, batteries drain — changed
        # clients get re-profiled by the planner's next interview pass.
        drift_rng = round_drift_rng(self.cfg.seed, rnd)
        n_context_changes = sum(drift_user(u, drift_rng) for u in users)
        n_hw_changes = sum(drift_device(s, drift_rng) for s in specs)
        self.last_drift = (n_context_changes, n_hw_changes)

    def _plan(self, users, specs):
        # multi-client quantization planning (profiling pipeline):
        # cohort-batched — one RAG engine query per store for the whole
        # round instead of a per-client scan (DESIGN.md §10)
        decisions = plan_round(self.planner.plan_cohort(users, specs))
        bits = {d.user_id: d.bits for d in decisions}
        return decisions, bits

    def _train_cohort(self, decisions, ids: List[int], rnd: int, sr_seed,
                      chan_state=None):
        """Local training at the planned precision (stragglers drop out).

        Returns (deltas, weights, losses, active_ids, row_gains) with
        ``deltas[j]`` packed for uplink row j — the cohort order both
        round loops fold in. ``chan_state``: this round's sampled
        ``channel.ChannelState`` over the cohort (None = ideal channel);
        truncated clients are planned around — they skip local training
        entirely (the server knows they cannot invert their channel this
        round) — and ``row_gains[j]`` is row j's effective receive gain,
        aligned with ``deltas`` (None when ideal).

        The host blocks on the device only where it needs a value: once
        for the channel's gains (fading only), and once after the loop for
        every client's last loss, so the cohort's steps run while the
        host prepares the next ones. Each such wait counts ``fl.train_syncs``.
        """
        deltas, weights, losses, active_ids = [], [], [], []
        row_gains: Optional[List[float]] = None
        gains_np = habs_np = None
        if chan_state is not None:
            row_gains = []
            gains_np, habs_np = map(
                np.asarray, jax.device_get((chan_state.gains, chan_state.habs))
            )
            obs.metrics.inc("fl.train_syncs")
        drop_rng = round_rng(self.cfg.seed, rnd)
        for pos, (d, i) in enumerate(zip(decisions, ids)):
            if gains_np is not None and gains_np[pos] <= 0.0:
                continue  # deep fade: truncated, planned around
            if self.cfg.dropout_prob and drop_rng.rand() < self.cfg.dropout_prob:
                continue  # straggler: never reports this round
            chan_kw = {}
            if gains_np is not None:
                chan_kw = dict(channel_gain=float(gains_np[pos]),
                               channel_habs=float(habs_np[pos]))
            delta, m = self.clients[i].local_update(
                self.params,
                d.bits,
                local_steps=self.cfg.local_steps,
                local_batch=self.cfg.local_batch,
                lr=self.cfg.lr,
                seed=self.cfg.seed * 97 + rnd,
                fedprox_mu=self.cfg.fedprox_mu,
                layout=self.layout,
                sr_seed=sr_seed,
                uplink_row=len(deltas),
                quant_block=self.cfg.quant_block,
                **chan_kw,
            )
            deltas.append(delta)
            if row_gains is not None:
                row_gains.append(m["channel_gain"])
            # FedAvg weight = samples x estimated contribution C_q (the
            # strategy's lever: class-equal upweights minority-rich
            # clients' updates, majority-centric the reverse; plain
            # fedavg has C_q == quantity x precision-quality only).
            contrib = 1.0
            if d.levels:
                sel = next((l for l in d.levels if l.bits == d.bits), None)
                if sel is not None:
                    contrib = sel.contribution
            weights.append(m["n_samples"] * contrib)
            losses.append(m["loss_last"])
            active_ids.append(i)
        if losses:
            with obs.span("client.loss_read"):
                losses = [float(x) for x in jax.device_get(losses)]
            obs.metrics.inc("fl.train_syncs")
        return deltas, weights, losses, active_ids, row_gains

    def _sample_round_channel(self, round_key, ids: List[int]):
        """Sample this round's physical channel over the selected cohort.

        Drawn over the FULL cohort (before dropouts) so barrier and
        streaming rounds share the same realisation for the same round
        key and a client's draw doesn't depend on who else dropped.
        Records the realised radio state on each ``DeviceSpec``
        (``channel_snr_db`` EMA + running ``truncation_rate``) — the
        profiling features the RAG planner sees next round. Returns the
        ``ChannelState`` or None on the ideal channel.
        """
        if self.channel is None:
            return None
        with obs.span("channel_sample", cohort=len(ids)):
            state = self.channel.sample(round_key, len(ids))
        snr = np.asarray(jax.device_get(state.snr_db(self.cfg.snr_db)))
        trunc = np.asarray(jax.device_get(state.truncated))
        for pos, i in enumerate(ids):
            hist = self._chan_hist.setdefault(i, [0, 0])
            hist[0] += int(trunc[pos])
            hist[1] += 1
            spec = self.fleet[i]
            spec.truncation_rate = hist[0] / hist[1]
            prev = spec.channel_snr_db
            spec.channel_snr_db = (
                float(snr[pos]) if prev is None
                else 0.7 * prev + 0.3 * float(snr[pos])
            )
        return state

    def _apply_update(self, agg: Pytree, round_key) -> None:
        """Server optimizer step + compressed downlink broadcast (§13).

        FedAvgM momentum and the param update run on the flat f32 master
        vector (same float ops, in the same order, as the pre-§13
        per-leaf ``tree.map`` — packing is a concat). With
        ``FLConfig.quantize_server_state`` the velocity is *stored* bf16
        (0.5x f32 resident bytes) and dequantized to f32 for the math.

        The broadcast then goes through the same wire codec as the
        uplink (``core/wire.py``): f32 passthrough (``downlink_bits`` >=
        32) ships the absolute params vector — byte-for-byte today's
        broadcast, and the reconstruction is exactly the master; a
        quantized downlink encodes the delta against the fleet's current
        replica ONCE with the round's downlink dither seed
        (``ota.derive_dl_seed``), and every client decodes the same row
        to bit-identical params. The server adopts the reconstruction as
        ``self.params``, so the quantization residual stays in
        ``master - bcast`` and rides the next round's broadcast.
        """
        with obs.span("optimizer"):
            u = packing.pack(agg, self.layout)
            if self.cfg.server_momentum > 0.0:
                if not hasattr(self, "_velocity"):
                    self._velocity = jnp.zeros_like(u, jnp.float32)
                v = (
                    self.cfg.server_momentum * self._velocity.astype(jnp.float32)
                    + u
                )
                self._velocity = (
                    v.astype(jnp.bfloat16)
                    if self.cfg.quantize_server_state
                    else v
                )
                u = v
            self._master = self._master + u

        with obs.span("broadcast_encode", bits=self.cfg.downlink_bits):
            if packing.wire_kind(self.cfg.downlink_bits) == "float32":
                payload = self._master  # absolute params: passthrough oracle
            else:
                payload = self._master - self._bcast
            row = wire.encode_row(
                payload,
                self.cfg.downlink_bits,
                ota.derive_dl_seed(round_key),
                0,
                block=self.cfg.downlink_block,
            )
            self._bcast = wire.decode_broadcast(row, self._bcast)
            self.last_broadcast = row
            self.last_downlink_bytes = row.wire_nbytes
            self.params = packing.unpack(self._bcast, self.layout)

    @property
    def server_state_nbytes(self) -> int:
        """Resident bytes of the server optimizer state (0 before any
        momentum step; bf16 halves it under ``quantize_server_state``)."""
        v = getattr(self, "_velocity", None)
        return 0 if v is None else state_nbytes(v)

    def _observe_feedback(self, decisions, users, specs):
        # feedback: realised satisfaction -> RAG databases
        sats, energies = [], []
        for d, u, s in zip(decisions, users, specs):
            sat = satisfaction_score(u, s, d.bits)
            perf = true_performance(u, s, d.bits)
            self.planner.observe_feedback(u, s, d.bits, sat, perf)
            sats.append(sat)
            energies.append(perf["energy"])
        return sats, energies

    def run_round(self, rnd: int) -> RoundLog:
        # The whole round runs under one ``round`` span; each pipeline
        # stage gets its own nested span (DESIGN.md §14) — same span
        # names as the streaming loop, so traces from either engine
        # line up in one Perfetto view.
        with obs.span("round", round=rnd):
            ids = self.select(rnd)
            users = [self.users[i] for i in ids]
            specs = [self.fleet[i] for i in ids]
            with obs.span("plan", cohort=len(ids)):
                self._apply_drift(rnd, users, specs)
                decisions, bits = self._plan(users, specs)

            # The round key is fixed before the client loop so clients can
            # quantize + bit-pack their uplinks at the edge with the round's
            # shared dither stream (ota.derive_sr_seed); the server only ever
            # sees PackedRow wire rows, never the f32 (K, M) matrix.
            round_key = jax.random.key(self.cfg.seed * 131 + rnd)
            sr_seed = ota.derive_sr_seed(round_key)
            chan_state = self._sample_round_channel(round_key, ids)
            with obs.span("client_train"):
                deltas, weights, losses, active_ids, row_gains = (
                    self._train_cohort(decisions, ids, rnd, sr_seed, chan_state)
                )
            if not deltas:  # everyone dropped (or truncated): skip aggregation
                return self._log_round(
                    RoundLog(rnd, bits, 0.0, 0.0, 0, float("nan"))
                )

            # ---- mixed-precision OTA aggregation: the clients' quantized,
            # bit-packed wire rows go straight into the fused dequant +
            # superpose data plane (grouped per storage class, DESIGN.md §5).
            # Under the fading channel the reporting rows' effective gains
            # ride inside the fused pass (gains=, DESIGN.md §12).
            agg, info = ota.ota_aggregate_packed(
                round_key,
                deltas,
                [bits[self.users[i].user_id] for i in active_ids],
                weights,
                self.layout,
                ota.OTAConfig(snr_db=self.cfg.snr_db),
                gains=None
                if row_gains is None
                else jnp.asarray(row_gains, jnp.float32),
                mesh=self.mesh,
            )
            self.last_uplink_bytes = info["uplink_bytes"]
            self._apply_update(agg, round_key)
            info.downlink_bytes = self.last_downlink_bytes
            with obs.span("feedback"):
                sats, energies = self._observe_feedback(decisions, users, specs)

            return self._log_round(
                RoundLog(
                    round=rnd,
                    bits=bits,
                    mean_satisfaction=float(np.mean(sats)),
                    mean_energy=float(np.mean(energies)),
                    n_participating=info["n_participating"],
                    train_loss=float(np.mean(losses)),
                    uplink_bytes=info["uplink_bytes"],
                    downlink_bytes=self.last_downlink_bytes,
                )
            )

    def run(self, n_rounds: Optional[int] = None, *, verbose: bool = False):
        for r in range(n_rounds or self.cfg.n_rounds):
            log = self.run_round(r)
            if verbose:
                print(
                    f"round {r:3d} loss={log.train_loss:.3f} "
                    f"sat={log.mean_satisfaction:.3f} "
                    f"energy={log.mean_energy:.3f} "
                    f"clients={log.n_participating}"
                )
        return self.round_logs

    # ---- evaluation (word/char accuracy + CTC loss per category, Fig. 4)
    def evaluate(
        self,
        eval_set: Optional[List[Utterance]] = None,
        batch: int = 24,
        with_loss: bool = False,
    ) -> Dict[str, float]:
        eval_set = eval_set or make_eval_set(seed=self.cfg.seed + 999)
        correct: Dict[str, int] = {}
        total: Dict[str, int] = {}
        loss_sum: Dict[str, float] = {}
        loss_n: Dict[str, int] = {}
        from repro.models.deepspeech2 import ctc_loss, ds2_logits
        import jax.numpy as jnp

        for i in range(0, len(eval_set), batch):
            chunk = eval_set[i : i + batch]
            if len(chunk) < batch:  # keep shapes static for the jit cache
                chunk = list(chunk) + [chunk[-1]] * (batch - len(chunk))
            b = batchify(chunk, max_frames=320, max_labels=40)
            ids = ds2_greedy_decode(
                self.model_params_fn(), jnp.asarray(b["frames"]), self.arch
            )
            ids = np.asarray(ids)
            if with_loss:
                # per-utterance CTC loss (the accuracy metric is blind
                # during CTC's early blank-collapse phase; loss is not)
                lp = ds2_logits(
                    self.model_params_fn(), jnp.asarray(b["frames"]), self.arch
                )
                in_len = jnp.minimum(jnp.asarray(b["frame_len"]) // 4, lp.shape[1])
                for j, u in enumerate(chunk):
                    lj = float(
                        ctc_loss(
                            lp[j : j + 1],
                            jnp.asarray(b["labels"][j : j + 1]),
                            in_len[j : j + 1],
                            jnp.asarray(b["label_len"][j : j + 1]),
                        )
                    )
                    loss_sum[u.category] = loss_sum.get(u.category, 0.0) + lj
                    loss_n[u.category] = loss_n.get(u.category, 0) + 1
            for j, u in enumerate(chunk):
                # char accuracy: collapse decoded, compare to reference
                dec = [t for t in ids[j] if t != 0]
                ref = list(u.label_ids)
                n = max(len(ref), 1)
                # simple alignment-free prefix match score
                m = sum(1 for a, b_ in zip(dec, ref) if a == b_)
                correct[u.category] = correct.get(u.category, 0) + m
                total[u.category] = total.get(u.category, 0) + n
        out = {c: correct.get(c, 0) / max(total.get(c, 1), 1) for c in total}
        if with_loss:
            for c in loss_sum:
                out["loss_" + c] = loss_sum[c] / max(loss_n[c], 1)
        return out

    def model_params_fn(self):
        return self.params


# ---------------------------------------------------------------------------
# streaming rounds: event-driven buffered aggregation (DESIGN.md §11)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StreamPlan:
    """One round's arrival plan: who folds when (``plan_stream``).

    ``on_time``/``late``/``lost`` partition the uplink-row indices;
    ``staleness`` is aligned with ``late``. ``t_trigger`` is when the
    aggregation fires (cohort-fill or deadline, whichever first);
    ``t_close`` is when the round actually ends — the trigger, or the
    last counted late arrival inside the grace window.
    """

    on_time: Tuple[int, ...]
    late: Tuple[int, ...]
    lost: Tuple[int, ...]
    staleness: Tuple[float, ...]
    t_trigger: float
    t_close: float

    @property
    def counted(self) -> Tuple[int, ...]:
        """All folded row indices, in cohort (uplink-row) order."""
        return tuple(sorted(self.on_time + self.late))


def plan_stream(
    times: Sequence[float],
    *,
    fill: int,
    deadline: Optional[float] = None,
    grace: float = 0.0,
    gamma: float = 0.5,
) -> StreamPlan:
    """Plan one buffered round from simulated arrival times.

    ``times[j]`` is uplink row j's arrival (seconds; ``inf`` = never
    reports). The aggregation fires at the earlier of the ``fill``-th
    arrival (cohort-fill) and ``deadline``; if neither ever happens
    (fill target unreachable, no deadline) it degenerates to the
    synchronous barrier and fires at the last finite arrival. Rows
    landing within ``grace`` seconds after the trigger fold in late with
    the ``core.ota.staleness_weights`` discount ``gamma ** (lag /
    grace)``; later (or never-arriving) rows are lost.
    """
    t = [float(x) for x in times]
    finite = sorted(x for x in t if math.isfinite(x))
    t_fill = finite[fill - 1] if 0 < fill <= len(finite) else math.inf
    t_trigger = t_fill if deadline is None else min(t_fill, float(deadline))
    if not math.isfinite(t_trigger):
        t_trigger = finite[-1] if finite else 0.0
    g = max(float(grace), 1e-9)
    on_time, late, lost, stale = [], [], [], []
    for j, x in enumerate(t):
        if x <= t_trigger:
            on_time.append(j)
        elif x <= t_trigger + grace:
            late.append(j)
            stale.append(min(1.0, max(min(gamma, 1.0), gamma ** ((x - t_trigger) / g))))
        else:
            lost.append(j)
    t_close = max([t_trigger] + [t[j] for j in late])
    return StreamPlan(
        tuple(on_time), tuple(late), tuple(lost), tuple(stale), t_trigger, t_close
    )


@dataclasses.dataclass
class StreamRoundLog(RoundLog):
    sim_seconds: float = 0.0  # simulated wall-clock of the round
    n_on_time: int = 0
    n_late: int = 0
    n_lost: int = 0

    def publish(self, registry=None) -> "StreamRoundLog":
        m = registry or obs.metrics.REGISTRY
        super().publish(m)
        m.inc("stream.on_time", self.n_on_time)
        m.inc("stream.late", self.n_late)
        m.inc("stream.lost", self.n_lost)
        m.set_gauge("stream.sim_seconds", self.sim_seconds)
        return self


class StreamingFLServer(FLServer):
    """Event-driven buffered round loop (FedBuff-style, DESIGN.md §11).

    Same select/drift/plan/train stages as ``FLServer`` (identical seeded
    draws), but instead of the synchronous barrier every uplink gets a
    simulated arrival time (``LatencyModel``) and the round is an event
    queue: aggregation triggers on cohort-fill (``fill_fraction``) or
    ``deadline_s``, rows inside ``grace_s`` after the trigger fold in
    with the ``staleness_gamma`` discount, and everything folds into one
    persistent ``ota.OtaAccumulator``. The channel draw + weight
    renormalisation run once, at trigger time, over the full counted
    arrival set in cohort order — so with the defaults (full fill, no
    deadline, no latency dropouts) the round is bit-identical to
    ``FLServer.run_round``: the synchronous path is the oracle.
    """

    def __init__(
        self,
        fl_cfg: FLConfig,
        arch: Optional[ArchConfig] = None,
        *,
        shard_size: int = 24,
        fill_fraction: float = 1.0,
        deadline_s: Optional[float] = None,
        grace_s: float = 0.0,
        staleness_gamma: float = 0.5,
        latency: Optional[LatencyModel] = None,
    ):
        super().__init__(fl_cfg, arch, shard_size=shard_size)
        self.fill_fraction = fill_fraction
        self.deadline_s = deadline_s
        self.grace_s = grace_s
        self.staleness_gamma = staleness_gamma
        self.latency = latency if latency is not None else LatencyModel()

    def _sample_arrivals(self, deltas, active_ids: List[int], rnd: int) -> List[float]:
        """Simulated arrival time per uplink row (inf = never reports)."""
        lat_rng = round_rng(self.cfg.seed, rnd, salt=4099)
        times = []
        for r, i in zip(deltas, active_ids):
            t = self.latency.sample(self.fleet[i], lat_rng, uplink_bytes=r.wire_nbytes)
            if self.latency.dropped(self.fleet[i], lat_rng):
                t = math.inf
            times.append(t)
        return times

    def run_round(self, rnd: int) -> StreamRoundLog:
        # Same span names as the synchronous loop (DESIGN.md §14): the
        # arrival simulation and wave bookkeeping live inside the shared
        # stage spans, so a no-deadline streaming trace and a barrier
        # trace show the identical pipeline.
        with obs.span("round", round=rnd):
            return self._run_round_inner(rnd)

    def _run_round_inner(self, rnd: int) -> StreamRoundLog:
        ids = self.select(rnd)
        users = [self.users[i] for i in ids]
        specs = [self.fleet[i] for i in ids]
        with obs.span("plan", cohort=len(ids)):
            self._apply_drift(rnd, users, specs)
            decisions, bits = self._plan(users, specs)

        round_key = jax.random.key(self.cfg.seed * 131 + rnd)
        sr_seed = ota.derive_sr_seed(round_key)
        chan_state = self._sample_round_channel(round_key, ids)
        with obs.span("client_train"):
            deltas, weights, losses, active_ids, row_gains = self._train_cohort(
                decisions, ids, rnd, sr_seed, chan_state
            )
        if not deltas:  # everyone dropped in training: skip aggregation
            return self._log_round(
                StreamRoundLog(rnd, bits, 0.0, 0.0, 0, float("nan"))
            )

        # ---- arrival simulation + round plan (trigger/late/lost)
        times = self._sample_arrivals(deltas, active_ids, rnd)
        n = len(deltas)
        fill = (
            n
            if self.fill_fraction >= 1.0
            else max(1, math.ceil(self.fill_fraction * n))
        )
        plan = plan_stream(
            times,
            fill=fill,
            deadline=self.deadline_s,
            grace=self.grace_s,
            gamma=self.staleness_gamma,
        )
        self.last_times, self.last_plan = times, plan  # introspection
        counted = list(plan.counted)
        if not counted:  # every uplink lost in the air: skip aggregation
            return self._log_round(
                StreamRoundLog(
                    rnd,
                    bits,
                    0.0,
                    0.0,
                    0,
                    float("nan"),
                    sim_seconds=plan.t_close,
                    n_lost=n,
                )
            )

        # ---- channel + weight renormalisation over the counted set, in
        # cohort order, at trigger time (one draw per round — the same
        # key split as the synchronous path, ota.round_channel). Under
        # the fading channel the legacy coin-flip is replaced by the
        # realised gains: truncated rows never trained (planned around),
        # so every counted row has gain > 0; weights renormalise over
        # the counted set and the gains ride inside the fused fold.
        ocfg = ota.OTAConfig(snr_db=self.cfg.snr_db)
        w_counted = jnp.asarray([weights[j] for j in counted], jnp.float32)
        if row_gains is None:
            g_counted = None
            habs, participate, w = ota.round_channel(
                round_key, w_counted, cfg=ocfg)
        else:
            g_counted = jnp.asarray(
                [row_gains[j] for j in counted], jnp.float32)
            participate = g_counted > 0
            w = chanmod.combine_weights(w_counted, g_counted)

        # ---- fold arrivals into the persistent accumulator: the on-time
        # wave at the trigger, then the staleness-discounted late wave
        pos = {j: p for p, j in enumerate(counted)}
        acc = ota.OtaAccumulator(self.layout, ocfg, mesh=self.mesh)

        def _gsel(idx):
            if g_counted is None:
                return None
            return g_counted[jnp.asarray([pos[j] for j in idx], jnp.int32)]

        if plan.late:
            stale = dict(zip(plan.late, plan.staleness))
            on_sorted, late_sorted = sorted(plan.on_time), sorted(plan.late)
            w_on = w[jnp.asarray([pos[j] for j in on_sorted], jnp.int32)]
            w_late = w[jnp.asarray([pos[j] for j in late_sorted], jnp.int32)]
            acc.fold([deltas[j] for j in on_sorted], w_on,
                     gains=_gsel(on_sorted))
            acc.fold(
                [deltas[j] for j in late_sorted],
                w_late,
                staleness=[stale[j] for j in late_sorted],
                gains=_gsel(late_sorted),
            )
        else:  # single wave: identical fold to the synchronous barrier
            acc.fold([deltas[j] for j in counted], w, gains=g_counted)
        agg, info = acc.finalize(round_key)
        self.last_uplink_bytes = info["uplink_bytes"]
        self._apply_update(agg, round_key)
        info.downlink_bytes = self.last_downlink_bytes
        with obs.span("feedback"):
            sats, energies = self._observe_feedback(decisions, users, specs)

        return self._log_round(
            StreamRoundLog(
                round=rnd,
                bits=bits,
                mean_satisfaction=float(np.mean(sats)),
                mean_energy=float(np.mean(energies)),
                n_participating=int(jax.device_get(participate).sum()),
                train_loss=float(np.mean([losses[j] for j in counted])),
                uplink_bytes=info["uplink_bytes"],
                downlink_bytes=self.last_downlink_bytes,
                sim_seconds=plan.t_close,
                n_on_time=len(plan.on_time),
                n_late=len(plan.late),
                n_lost=len(plan.lost),
            )
        )

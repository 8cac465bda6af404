"""DSP implementations of the Demeter executor protocols.

Two layers live here:

* the scalar :class:`DSPExecutor` — one target job behind the legacy
  :class:`repro.core.Executor` protocol (what the paper-protocol runner
  drives); lift it onto the batched control plane with
  :class:`repro.core.ScalarAdapter` when a batch-native caller needs it.
* the sweep executors :class:`BatchedSweepExecutor` /
  :class:`ScalarSweepExecutor` — whole scenario grids behind the
  :class:`repro.core.BatchExecutor` protocol, registered in
  :data:`repro.core.registry.SIM_ENGINES` as ``"batched"`` / ``"scalar"``.
  They own the struct-of-arrays simulation state, the telemetry history and
  per-scenario profiling costs; :class:`repro.core.ScenarioView` serves one
  of their rows back to a per-scenario controller.

Profiling runs follow the paper's lifecycle (§2.3, Fig. 3): deploy clones at
the predicted rate -> 2-minute stabilization -> 1-minute latency measurement
-> inject a timeout failure -> measure recovery with the online-ARIMA anomaly
detector over (throughput, consumer lag) until full catch-up or the 360 s
timeout. Profiling resource-time is accounted so experiments can report
Demeter's *net* savings like the paper does. The lifecycle and the
usage/cost normalizations are module-level functions so every executor
shares one implementation.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import numpy as np

from .. import obs
from ..core.anomaly import RecoveryTracker
from ..core.executor import ProfileSpec
from ..core.registry import SIM_ENGINES
from ..core.segments import LATENCY, RECOVERY, USAGE
from .simulator import (BatchedNormals, BatchState, ClusterModel, JobConfig,
                        SimJob, step_batch_arrays)


def _x64():
    """Run a dispatch under float64 (the sharded engine's numerics must
    match the float64 numpy reference paths); lazy so the numpy-only
    engines never touch jax."""
    import jax
    return jax.enable_x64()

#: Profiling lifecycle constants (paper §3.2).
STABILIZATION_S = 120.0
MEASURE_S = 60.0
RECOVERY_TIMEOUT_S = 360.0


@dataclass
class ProfileCost:
    cpu_s: float = 0.0      # core-seconds consumed by profiling clones
    mem_mb_s: float = 0.0   # MB-seconds consumed by profiling clones

    def add(self, m: Mapping[str, float], dt: float) -> None:
        """Charge a profiling clone's *used* resources for one sim step."""
        self.cpu_s += m["usage_cpu"] * dt
        self.mem_mb_s += m["usage_mem_mb"] * dt


def usage_norm_values(model: ClusterModel, cmax: JobConfig, cpu, mem):
    """C_max-normalized 50/50 CPU+memory usage; elementwise over arrays."""
    return (0.5 * cpu / model.allocated_cpu(cmax)
            + 0.5 * mem / model.allocated_mem_mb(cmax))


def usage_norm(model: ClusterModel, cmax: JobConfig,
               window: List[Dict[str, float]]) -> float:
    """C_max-normalized 50/50 CPU+memory usage scalar over a metric window."""
    cpu = np.mean([m["usage_cpu"] for m in window])
    mem = np.mean([m["usage_mem_mb"] for m in window])
    return float(usage_norm_values(model, cmax, cpu, mem))


def allocated_cost(model: ClusterModel, cmax: JobConfig,
                   config: Mapping[str, float]) -> float:
    """Deterministic allocated-resource scalar, normalized against C_max."""
    cfg = JobConfig.from_dict(config)
    cpu = model.allocated_cpu(cfg) / model.allocated_cpu(cmax)
    mem = model.allocated_mem_mb(cfg) / model.allocated_mem_mb(cmax)
    return 0.5 * cpu + 0.5 * mem


def observe_digest(model: ClusterModel, cmax: JobConfig,
                   window: List[Dict[str, float]]) -> Dict[str, float]:
    """The observation Demeter's optimizing process consumes: mean rate and
    latency plus the C_max-normalized usage scalar over a metric window."""
    if not window:
        return {}
    return {"rate": float(np.mean([m["rate"] for m in window])),
            "latency": float(np.mean([m["latency"] for m in window])),
            "usage": usage_norm(model, cmax, window)}


def profile_one(model: ClusterModel, cmax: JobConfig, cfg: JobConfig,
                rate: float, dt: float, seed: int,
                account: Optional[Callable[[Dict[str, float]], None]] = None,
                detector_backend: str = "scalar"
                ) -> Optional[Dict[str, float]]:
    """Run one profiling clone through the paper's lifecycle.

    Returns the USAGE / LATENCY / RECOVERY observation, or None for a failed
    run. ``account`` is called with each step's metrics so callers can charge
    the clone's resource-time; ``detector_backend`` picks the §2.3 anomaly
    detector path (see :data:`repro.core.registry.DETECTOR_BACKENDS`)."""
    clone = SimJob(model, cfg, seed=seed)
    tracker = RecoveryTracker(detector_backend=detector_backend)
    t = 0.0
    lat_samples: List[float] = []
    usage_samples: List[Dict[str, float]] = []

    while t < STABILIZATION_S + MEASURE_S:
        t += dt
        m = clone.step(rate, dt)
        if account is not None:
            account(m)
        tracker.observe(t, {"throughput": m["throughput"],
                            "consumer_lag": m["consumer_lag"]})
        if t > STABILIZATION_S:
            lat_samples.append(m["latency"])
            usage_samples.append(m)

    lavg = float(np.mean(lat_samples))
    usage = usage_norm(model, cmax, usage_samples)

    clone.inject_failure()
    t_fail, recovered = t, None
    while t - t_fail < RECOVERY_TIMEOUT_S:
        t += dt
        m = clone.step(rate, dt)
        if account is not None:
            account(m)
        tracker.observe(t, {"throughput": m["throughput"],
                            "consumer_lag": m["consumer_lag"]})
        if tracker.last_recovery_s is not None and clone.caught_up:
            recovered = t - t_fail
            break
    if not np.isfinite(lavg):
        return None
    # An un-recovered run still informs the models: pin R at the timeout.
    recovery = tracker.last_recovery_s if recovered is not None \
        else RECOVERY_TIMEOUT_S
    return {USAGE: usage, LATENCY: lavg, RECOVERY: float(recovery)}


@dataclass
class DSPExecutor:
    """Owns the target job and serves Demeter's executor protocol."""

    model: ClusterModel
    cmax: JobConfig
    seed: int = 0
    dt: float = 5.0
    job: SimJob = field(init=False)
    profile_cost: ProfileCost = field(default_factory=ProfileCost)
    _metrics_window: List[Dict[str, float]] = field(default_factory=list)

    def __post_init__(self) -> None:
        self.job = SimJob(self.model, self.cmax, seed=self.seed)

    # -- simulation plumbing (driven by the runner) -------------------------
    def step(self, rate: float) -> Dict[str, float]:
        m = self.job.step(rate, self.dt)
        self._metrics_window.append(m)
        if len(self._metrics_window) > int(600 / self.dt):
            self._metrics_window.pop(0)
        return m

    def window(self, seconds: float) -> List[Dict[str, float]]:
        n = max(int(seconds / self.dt), 1)
        return self._metrics_window[-n:]

    # -- Executor protocol ----------------------------------------------------
    def cmax_config(self) -> Dict[str, float]:
        return self.cmax.to_dict()

    def current_config(self) -> Dict[str, float]:
        return self.job.config.to_dict()

    def reconfigure(self, config: Mapping[str, float]) -> None:
        self.job.reconfigure(JobConfig.from_dict(config))

    def observe(self) -> Dict[str, float]:
        return observe_digest(self.model, self.cmax, self.window(60.0))

    def allocated_cost(self, config: Mapping[str, float]) -> float:
        return allocated_cost(self.model, self.cmax, config)

    # -- profiling lifecycle ---------------------------------------------------
    def profile(self, configs: List[Dict[str, float]], rate: float
                ) -> List[Optional[Dict[str, float]]]:
        return [profile_one(self.model, self.cmax, JobConfig.from_dict(c),
                            rate, self.dt,
                            seed=self.seed * 1009 + i + int(rate),
                            account=lambda m: self.profile_cost.add(m, self.dt))
                for i, c in enumerate(configs)]


# ---------------------------------------------------------------------------
# sweep executors: whole scenario grids behind the BatchExecutor protocol
# ---------------------------------------------------------------------------

#: Metric keys kept as full per-scenario history (controller windows +
#: sweep result arrays both read from these).
HIST_KEYS = ("rate", "latency", "utilization", "throughput", "consumer_lag",
             "usage_cpu", "usage_mem_mb")

#: What the Demeter optimizing process digests from a metric window.
OBSERVE_KEYS = ("rate", "latency", "usage_cpu", "usage_mem_mb")

#: Telemetry window behind ``observe()`` (the paper's 1-minute window).
OBSERVE_WINDOW_S = 60.0
#: names of the config-derived ``[S]`` operands the device engines put on
#: the mesh, in ``_device_configs`` order
DEVICE_CONFIGS = ("workers", "cpu_cores", "memory_mb", "task_slots",
                  "cap_base")


class SweepExecutorBase:
    """The sweep-executor contract: BatchExecutor + the simulation surface.

    Owns everything per-scenario that is *not* the stepping backend:
    telemetry history (struct-of-arrays over the whole run), reconfiguration
    counts, profiling cost accounting, and the C_max anchor — so it can
    serve the full :class:`repro.core.BatchExecutor` protocol while the
    subclasses only provide the simulation stepping.

    This class — not the bare ``BatchExecutor`` protocol — is what
    :data:`repro.core.registry.SIM_ENGINES` entries must provide: the sweep
    engine additionally drives :meth:`step`, :meth:`inject_failure`,
    :meth:`config_of`, :meth:`caught_up`, :meth:`window_dicts` and reads
    ``hist`` / ``workers_hist`` / ``reconf_count`` / ``profile_costs``.
    Third-party engines should subclass it and implement the stepping hooks
    (``_step_impl`` / ``_reconfigure_impl`` / ``inject_failure`` /
    ``config_of`` / ``workers`` / ``caught_up``).
    """

    def __init__(self, model: ClusterModel, configs: Sequence[JobConfig],
                 seeds: Sequence[int], *, dt: float, n_steps: int,
                 cmax: Optional[JobConfig] = None,
                 detector_backend: str = "scalar",
                 devices: Optional[int] = None):
        S = len(configs)
        self.model = model
        self.dt = float(dt)
        self.seeds = [int(s) for s in seeds]
        self.cmax = cmax if cmax is not None else JobConfig()
        self.detector_backend = detector_backend
        #: device-placement hint (EngineConfig.devices); only the sharded
        #: engine acts on it, but every engine accepts it so the sweep
        #: engine can pass one uniform constructor signature.
        self.devices = devices
        self.hist = {k: np.zeros((S, n_steps)) for k in HIST_KEYS}
        self.workers_hist = np.zeros((S, n_steps))
        self.profile_costs = [ProfileCost() for _ in range(S)]
        self.reconf_count = np.zeros(S, dtype=int)
        self.step_index = -1               # last recorded history column

    # -- simulation stepping (driven by the sweep engine) -------------------
    def step(self, rates: np.ndarray) -> Dict[str, np.ndarray]:
        """Advance every scenario one step; record telemetry history."""
        with obs.timed_phase("simulate", "engine.step"):
            m = self._step_impl(np.asarray(rates, float), self.dt)
        obs.inc("sweep.ticks")
        obs.inc("sweep.scenario_ticks", len(self.seeds))
        self.step_index += 1
        for k in HIST_KEYS:
            self.hist[k][:, self.step_index] = m[k]
        self.workers_hist[:, self.step_index] = self.workers()
        return m

    def window_dicts(self, idx: int, seconds: float,
                     keys: Sequence[str] = HIST_KEYS
                     ) -> List[Dict[str, float]]:
        """Scenario ``idx``'s last ``seconds`` of telemetry as metric dicts
        (the shape decide()-style controllers consume)."""
        i = self.step_index
        n = max(int(seconds / self.dt), 1)
        lo = max(i - n + 1, 0)
        cols = [self.hist[k][idx, lo:i + 1] for k in keys]
        return [dict(zip(keys, row)) for row in zip(*cols)]

    # -- BatchExecutor protocol ---------------------------------------------
    def n_scenarios(self) -> int:
        return len(self.seeds)

    def cmax_config(self, idx: int) -> Dict[str, float]:
        return self.cmax.to_dict()

    def current_config(self, idx: int) -> Dict[str, float]:
        return self.config_of(idx).to_dict()

    def reconfigure(self, mask: np.ndarray,
                    configs: Sequence[Optional[Mapping[str, float]]],
                    restart_s: Optional[float] = None) -> np.ndarray:
        mask = np.asarray(mask, bool)
        applied = np.zeros(len(mask), bool)
        for j in np.flatnonzero(mask):
            cfg = configs[j]
            if cfg is None:
                continue
            if not isinstance(cfg, JobConfig):
                cfg = JobConfig.from_dict(cfg)
            applied[j] = self.reconfigure_one(j, cfg, restart_s)
        return applied

    def reconfigure_one(self, idx: int, cfg: JobConfig,
                        restart_s: Optional[float] = None) -> bool:
        """Apply one scenario's reconfiguration; counts applied changes."""
        applied = self._reconfigure_impl(idx, cfg, restart_s)
        if applied:
            self.reconf_count[idx] += 1
            obs.inc("sweep.reconfigurations")
        return applied

    def observe(self) -> Dict[str, np.ndarray]:
        """The §2.4 telemetry digest for *all* scenarios at once."""
        i = self.step_index
        if i < 0:
            return {}
        n = max(int(OBSERVE_WINDOW_S / self.dt), 1)
        lo = max(i - n + 1, 0)
        cpu = self.hist["usage_cpu"][:, lo:i + 1].mean(axis=1)
        mem = self.hist["usage_mem_mb"][:, lo:i + 1].mean(axis=1)
        return {"rate": self.hist["rate"][:, lo:i + 1].mean(axis=1),
                "latency": self.hist["latency"][:, lo:i + 1].mean(axis=1),
                "usage": usage_norm_values(self.model, self.cmax, cpu, mem)}

    def observe_one(self, idx: int) -> Dict[str, float]:
        return observe_digest(self.model, self.cmax,
                              self.window_dicts(idx, OBSERVE_WINDOW_S,
                                                keys=OBSERVE_KEYS))

    def profile(self, specs: Sequence[ProfileSpec]
                ) -> List[Optional[Dict[str, float]]]:
        # Per-scenario enumeration within one call preserves the profiling
        # clone seeds of the scalar protocol (seed = s*1009 + k + rate).
        counters: Dict[int, int] = {}
        out: List[Optional[Dict[str, float]]] = []
        obs.inc("sweep.profile_runs", len(specs))
        with obs.span("engine.profile", runs=len(specs)):
            for idx, cfg, rate in specs:
                k = counters.get(idx, 0)
                counters[idx] = k + 1
                cost = self.profile_costs[idx]
                out.append(profile_one(
                    self.model, self.cmax, JobConfig.from_dict(cfg), rate,
                    self.dt, seed=self.seeds[idx] * 1009 + k + int(rate),
                    account=lambda m, _c=cost: _c.add(m, self.dt),
                    detector_backend=self.detector_backend))
        return out

    def allocated_cost(self, idx: int, config: Mapping[str, float]) -> float:
        return allocated_cost(self.model, self.cmax, config)

    # -- provided by the stepping subclasses --------------------------------
    def _step_impl(self, rates: np.ndarray, dt: float
                   ) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def _reconfigure_impl(self, idx: int, cfg: JobConfig,
                          restart_s: Optional[float]) -> bool:
        raise NotImplementedError

    def inject_failure(self, idx: int) -> None:
        raise NotImplementedError

    def config_of(self, idx: int) -> JobConfig:
        raise NotImplementedError

    def workers(self) -> np.ndarray:
        raise NotImplementedError

    def caught_up(self) -> np.ndarray:
        raise NotImplementedError

    def device_buffers(self) -> Dict[str, Any]:
        """Device arrays the engine keeps across dispatches, by name (the
        host engines keep none)."""
        return {}


@SIM_ENGINES.register("batched")
class BatchedSweepExecutor(SweepExecutorBase):
    """All scenarios advance through one vectorized ``step_batch`` call."""

    def __init__(self, model: ClusterModel, configs: Sequence[JobConfig],
                 seeds: Sequence[int], **kwargs):
        super().__init__(model, configs, seeds, **kwargs)
        self.state = BatchState.from_configs(configs)
        self.rngs = BatchedNormals(seeds)
        # Config-derived values only change on reconfiguration; cache them.
        self._cap_base = model.capacity_batch(self.state)
        self._cfg_cache = list(configs)

    def _step_impl(self, rates: np.ndarray, dt: float
                   ) -> Dict[str, np.ndarray]:
        return self.model.step_batch(self.state, rates, dt, self.rngs,
                                     capacity_base=self._cap_base)

    def inject_failure(self, idx: int) -> None:
        self.model.inject_failure_batch(self.state, idx)

    def _reconfigure_impl(self, idx: int, cfg: JobConfig,
                          restart_s: Optional[float]) -> bool:
        applied = self.model.reconfigure_batch(self.state, idx, cfg,
                                               restart_s)
        if applied:
            self._cap_base[idx] = self.model.capacity(cfg)
            self._cfg_cache[idx] = cfg
        return applied

    def config_of(self, idx: int) -> JobConfig:
        return self._cfg_cache[idx]

    def workers(self) -> np.ndarray:
        return self.state.workers

    def caught_up(self) -> np.ndarray:
        return self.state.caught_up


@SIM_ENGINES.register("sharded")
class ShardedSweepExecutor(SweepExecutorBase):
    """The batched step, laid out over a ``scenario`` device mesh.

    The scenario axis of :class:`~repro.dsp.simulator.BatchState` is
    struct-of-arrays and every per-step operation is elementwise over it,
    so the whole grid shards over a flat 1-D mesh
    (:func:`repro.distributed.mesh.scenario_mesh`) with **zero
    cross-scenario collectives**. Ragged grids are padded to the mesh size
    with dummy C_max rows (simulated for shape uniformity, sliced off every
    result).

    Split of responsibilities:

    * **device** — the hot elementwise update
      (:func:`~repro.dsp.simulator.step_batch_arrays`), jitted once per
      executor with the consumer-lag vector *donated* (the only persistent
      device buffer) and every ``[S]`` operand laid out with
      ``NamedSharding(mesh, P("scenario"))``;
    * **host** — a full :class:`~repro.dsp.simulator.BatchState` mirror
      carrying the control-flow state the numpy engine mutates in place:
      downtime/checkpoint clocks (their update rules are deterministic, so
      the mirror never needs a device read-back), per-job RNG streams
      (:class:`~repro.dsp.simulator.BatchedNormals` — bit-identical to the
      ``"batched"`` engine's draws), failure injection and reconfiguration.

    Results are therefore equivalent to :class:`BatchedSweepExecutor` on a
    shared seed — pinned by ``tests/test_sweep_sharded.py`` under 1/2/4
    virtual devices.
    """

    def __init__(self, model: ClusterModel, configs: Sequence[JobConfig],
                 seeds: Sequence[int], **kwargs):
        super().__init__(model, configs, seeds, **kwargs)
        import jax

        from ..distributed.mesh import (pad_to_multiple, scenario_mesh,
                                        scenario_sharding)

        S = len(configs)
        self.mesh = scenario_mesh(self.devices)
        self.n_devices = int(self.mesh.devices.size)
        #: padded scenario-axis length (mesh-divisible)
        self.n_rows = pad_to_multiple(S, self.n_devices)
        pad_rows = self.n_rows - S

        # Host mirror: full struct-of-arrays state, padded with C_max rows.
        self.state = BatchState.from_configs(configs).pad(self.n_rows)
        # Padding rows draw from their own disjoint streams; real rows keep
        # the scenario seeds, so draws are bit-identical to "batched".
        self.rngs = BatchedNormals(
            list(self.seeds) + [2 ** 33 + r for r in range(pad_rows)])
        self._cap_base = model.capacity_batch(self.state)
        self._cfg_cache = list(configs)
        #: rollback lag staged by inject_failure, folded into the next step
        self._lag_add = np.zeros(self.n_rows)

        self._row_sharding = scenario_sharding(self.mesh)
        with _x64():
            self._lag = jax.device_put(
                np.zeros(self.n_rows), self._row_sharding)
        self._dev_cfg: Optional[tuple] = None     # rebuilt when configs move
        self._step_fn = jax.jit(
            step_batch_arrays,
            static_argnames=("model", "dt"),
            donate_argnums=(1,),                  # lag: the persistent buffer
            in_shardings=self._row_sharding,
            out_shardings=self._row_sharding)

    # -- device plumbing ----------------------------------------------------
    def _device_configs(self) -> tuple:
        """Config-derived operands, device-put lazily after every
        reconfiguration (configs change per decision, not per step)."""
        if self._dev_cfg is None:
            import jax
            st = self.state
            arrays = (st.workers, st.cpu_cores, st.memory_mb,
                      st.task_slots, self._cap_base)
            with _x64():
                self._dev_cfg = tuple(
                    jax.device_put(a, self._row_sharding) for a in arrays)
            if obs.enabled():
                obs.inc("sweep.device_config_rebuilds")
                obs.inc("transfer.h2d_bytes",
                        sum(np.asarray(a).nbytes for a in arrays))
        return self._dev_cfg

    def device_buffers(self) -> Dict[str, Any]:
        """The donated lag carry and the config operands, by name."""
        return {"lag": self._lag,
                **dict(zip(DEVICE_CONFIGS, self._device_configs()))}

    def _step_operands(self) -> tuple:
        """One full positional operand tuple for ``step_batch_arrays``
        (dummy rate/flag rows), shared by :meth:`lower_step` and
        :meth:`contract_probe` so introspection always sees the exact
        argument layout of the real dispatch."""
        zeros = np.zeros(self.n_rows)
        flags = np.zeros(self.n_rows, bool)
        return (self.model, self._lag, zeros, zeros, *self._device_configs(),
                flags, flags, zeros, zeros, self.dt)

    def lower_step(self):
        """The jitted step lowered for this executor's mesh (introspection
        hook; :meth:`contract_probe` is the contract-checked face of it)."""
        with _x64():
            return self._step_fn.lower(*self._step_operands())

    def contract_probe(self):
        """This executor's step packaged for
        :func:`repro.analysis.contracts.run_probe`: the compiled module must
        contain zero cross-scenario collectives and must honor the
        consumer-lag donation (see :data:`SHARDED_STEP_CONTRACT`)."""
        from ..analysis.contracts import ContractProbe
        args = self._step_operands()
        return ContractProbe(contract=SHARDED_STEP_CONTRACT, fn=self._step_fn,
                             args=args, x64=True,
                             static_argnums=(0, len(args) - 1))

    # -- stepping -----------------------------------------------------------
    def _step_impl(self, rates: np.ndarray, dt: float
                   ) -> Dict[str, np.ndarray]:
        S = len(self.seeds)
        st = self.state
        r = np.zeros(self.n_rows)
        r[:S] = rates

        # Host half of step_batch: downtime / checkpoint clocks + RNG draws
        # (identical order to the numpy engine: z1, then masked |z2|).
        down_pre = st.downtime_left_s > 0.0
        st.downtime_left_s = np.where(
            down_pre, np.maximum(st.downtime_left_s - dt, 0.0),
            st.downtime_left_s)
        since = np.where(down_pre, st.since_checkpoint_s,
                         st.since_checkpoint_s + dt)
        since = np.where(~down_pre & (since >= st.checkpoint_interval_s),
                         0.0, since)
        st.since_checkpoint_s = since
        down_post = st.downtime_left_s > 0.0
        z1 = self.rngs.draw()
        z2 = np.abs(self.rngs.draw(~down_post))

        with obs.span("engine.sharded.step"), _x64():
            self._lag, m = self._step_fn(
                self.model, self._lag, self._lag_add, r,
                *self._device_configs(), down_pre, down_post, z1, z2, dt)
        self._lag_add = np.zeros(self.n_rows)
        # Forced copy (the device buffer is donated into the next dispatch,
        # so the host mirror must not alias it).
        st.from_device(self._lag)
        st.last_rate = r
        out = {k: np.asarray(v)[:S] for k, v in m.items()}
        if obs.enabled():
            obs.inc("transfer.h2d_bytes",
                    self._lag_add.nbytes + r.nbytes + down_pre.nbytes
                    + down_post.nbytes + z1.nbytes + z2.nbytes)
            obs.inc("transfer.d2h_bytes",
                    self._lag.nbytes
                    + sum(v.nbytes for v in out.values()))
            obs.track_jit_cache("sharded_step",
                                int(self._step_fn._cache_size()))
        return out

    def inject_failure(self, idx: int) -> None:
        # Mirror of ClusterModel.inject_failure_batch, except the rollback
        # lag is staged (see step_batch_arrays) instead of scattered into
        # the device buffer.
        st = self.state
        state_mb = self.model.state_size_mb(float(st.last_rate[idx]))
        restore = state_mb / (self.model.restore_mb_per_s
                              * max(float(st.workers[idx]), 1.0))
        st.downtime_left_s[idx] = self.model.failure_detect_s \
            + self.model.redeploy_s + restore
        self._lag_add[idx] += st.last_rate[idx] * st.since_checkpoint_s[idx]
        st.since_checkpoint_s[idx] = 0.0

    def _reconfigure_impl(self, idx: int, cfg: JobConfig,
                          restart_s: Optional[float]) -> bool:
        if self._cfg_cache[idx] == cfg:
            return False
        st = self.state
        st.set_config(idx, cfg)
        st.downtime_left_s[idx] = max(
            float(st.downtime_left_s[idx]),
            self.model.reconfig_restart_s if restart_s is None else restart_s)
        st.since_checkpoint_s[idx] = 0.0
        self._cap_base[idx] = self.model.capacity(cfg)
        self._cfg_cache[idx] = cfg
        self._dev_cfg = None
        return True

    def config_of(self, idx: int) -> JobConfig:
        return self._cfg_cache[idx]

    def workers(self) -> np.ndarray:
        return self.state.workers[:len(self.seeds)]

    def caught_up(self) -> np.ndarray:
        return self.state.caught_up[:len(self.seeds)]


@SIM_ENGINES.register("scalar")
class ScalarSweepExecutor(SweepExecutorBase):
    """Reference oracle: one SimJob per scenario, stepped in a Python loop."""

    def __init__(self, model: ClusterModel, configs: Sequence[JobConfig],
                 seeds: Sequence[int], **kwargs):
        super().__init__(model, configs, seeds, **kwargs)
        self.jobs = [SimJob(model, c, seed=s)
                     for c, s in zip(configs, seeds)]

    def _step_impl(self, rates: np.ndarray, dt: float
                   ) -> Dict[str, np.ndarray]:
        ms = [job.step(float(r), dt) for job, r in zip(self.jobs, rates)]
        return {k: np.array([m[k] for m in ms]) for k in ms[0]}

    def inject_failure(self, idx: int) -> None:
        self.jobs[idx].inject_failure()

    def _reconfigure_impl(self, idx: int, cfg: JobConfig,
                          restart_s: Optional[float]) -> bool:
        if self.jobs[idx].config == cfg:
            return False
        self.jobs[idx].reconfigure(cfg, restart_s=restart_s)
        return True

    def config_of(self, idx: int) -> JobConfig:
        return self.jobs[idx].config

    def workers(self) -> np.ndarray:
        return np.array([float(j.config.workers) for j in self.jobs])

    def caught_up(self) -> np.ndarray:
        return np.array([j.caught_up for j in self.jobs])


# ---------------------------------------------------------------------------
# compilation contracts (see repro.analysis and docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

def _sharded_step_contract():
    from ..analysis.contracts import COLLECTIVE_HLO_OPS, CompilationContract
    return CompilationContract(
        name="engine:sharded",
        # The scenario axis is struct-of-arrays and every per-step operation
        # is elementwise over it, so sharding must be communication-free.
        forbidden_hlo=COLLECTIVE_HLO_OPS,
        # The consumer-lag vector is the one persistent device buffer;
        # its donation must survive in the compiled module.
        donation=True,
        # float64 is deliberate: the sharded step mirrors the float64 numpy
        # engine bit-for-bit (pinned by tests/test_sweep_sharded.py).
        dtype_ceiling="float64",
        max_primitives=256,
        forbid_callbacks=True,
        note="scenario-sharded sim step: zero cross-scenario collectives, "
             "lag buffer donated, no host round-trips")


#: The sharded engine's step invariants (constructing the declarative
#: contract is jax-free; only *checking* it compiles anything).
SHARDED_STEP_CONTRACT = _sharded_step_contract()


def _sharded_probe():
    ex = ShardedSweepExecutor(ClusterModel(), [JobConfig(), JobConfig()],
                              seeds=[0, 1], dt=5.0, n_steps=4)
    args = ex._step_operands()
    # Companion probe: tracing the same step with obs instrumentation
    # forced on must yield the identical primitive count (spans/metrics are
    # strictly host-side of the jit boundary) and no callbacks.
    obs_probe = obs.instrumentation_probe(
        "engine:sharded+obs", step_batch_arrays, args,
        static_argnums=(0, len(args) - 1), x64=True)
    return [ex.contract_probe(), obs_probe]


def _host_engine_probe(name: str, why: str):
    from ..analysis.contracts import host_probe
    return host_probe(f"engine:{name}", why)


SIM_ENGINES.attach_contract("sharded", _sharded_probe)
SIM_ENGINES.attach_contract("batched", lambda: _host_engine_probe(
    "batched", "vectorized numpy stepping — no XLA dispatch to pin"))
SIM_ENGINES.attach_contract("scalar", lambda: _host_engine_probe(
    "scalar", "per-job python reference oracle — no XLA dispatch to pin"))

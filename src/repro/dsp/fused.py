"""The fused sweep engine: whole decision intervals on-device.

The ``"sharded"`` engine (:class:`~repro.dsp.executor.ShardedSweepExecutor`)
still wakes the host every simulator tick: one jitted dispatch per ``dt``,
with the failure/recovery/policy event loop interleaved between dispatches.
But the sweep's event loop is *sparse* — failures fire every tens of
minutes, policies every decision interval — while the simulator ticks every
5 s. This module closes that gap: the engine registered as ``"fused"``
advances a whole host-quiet run of ticks (everything between two scheduled
events) through **one** jitted donated-carry :func:`jax.lax.scan`, so the
host only wakes at decision/optimization-interval boundaries.

What moves on-device per interval:

* the simulator tick itself (:func:`~repro.dsp.simulator.step_batch_arrays`
  unchanged, as the scan body — which is exactly what makes the K-tick scan
  equal K host-driven step calls, pinned by
  ``tests/test_simulator_props.py``);
* failure injection, lowered to arrays: the sweep engine precomputes each
  interval's per-tick injection schedule and the executor stages the
  rollback lag into a per-tick ``lag_add`` plane (identical semantics to
  the sharded engine's staged injection, just K ticks at a time);
* an anomaly-detector observe + rank-1 RLS update per tick on
  ``y = log1p(consumer_lag)``, with policy-trigger flags accumulated into a
  per-scenario counter (:attr:`FusedSweepExecutor.anomaly_triggers`) —
  auxiliary telemetry for trigger-style policies; it feeds nothing back
  into the simulation, so all four engines stay result-equivalent. The
  lag+detector tick is the pure-jnp oracle
  (:func:`repro.kernels.ref.fused_tick_ref`), whose lag arithmetic is
  bit-identical to ``step_batch_arrays``, on every backend: the carry is
  float64 and Mosaic lowers no float64 kernel, so the fused Pallas kernel
  (:mod:`repro.kernels.fused_tick`) is never chosen behind the caller's
  back, and asking for it (``use_pallas=True``) is refused.

Host/device split (what remains host-side, per tick but vectorized numpy):
the downtime/checkpoint clocks and the per-row RNG streams — their update
rules are deterministic and their draws must stay bit-identical to the
``"batched"`` engine (``BatchedNormals`` row order: z1 for all rows, then
masked ``|z2|``), so they are precomputed for the whole interval and lowered
as ``[K, S]`` operand planes. The consumer-lag vector and the detector state
are the persistent device buffers, donated through every scan dispatch.

Interval lengths are padded to power-of-two multiples of ``chunk`` ticks
(invalid ticks masked out of every carry), so a sweep over mixed interval
lengths compiles the scan once per scenario-axis width instead of once per
distinct K — the ≤2-traces budget in :data:`FUSED_INTERVAL_CONTRACT`,
enforced by ``scripts/check_contracts.py`` and regression-tested (seeded
red) in ``tests/test_sweep_sharded.py``.

Composes with ``EngineConfig(devices=N)``: every ``[S]``-shaped operand is
laid out over the same 1-D ``scenario`` mesh as the sharded engine (the
``[K, S]`` planes with ``P(None, "scenario")``), and every per-tick
operation is elementwise over scenarios, so the compiled scan contains zero
cross-scenario collectives.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import obs
from ..core.registry import SIM_ENGINES
from .executor import DEVICE_CONFIGS, SweepExecutorBase, _x64
from .simulator import (BatchedNormals, BatchState, ClusterModel, JobConfig,
                        step_batch_arrays)

#: AR order of the on-device detector: bias + previous log-lag sample.
DET_ORDER = 2
#: RLS forgetting factor / trigger threshold of the on-device detector.
DET_LAMBDA = 0.995
DET_THRESH = 3.0


def fused_interval_scan(model: ClusterModel, lag, det_w, det_p, det_y,
                        det_trig, rates, lag_add, down_pre, down_post,
                        z1, z2, valid, workers, cpu_cores, memory_mb,
                        task_slots, cap_base, det_lam, det_thresh,
                        dt: float, use_pallas: bool):
    """One decision interval as a single donated-carry ``lax.scan``.

    Carries ``(lag [S], det_w [S,k], det_p [S,k,k], det_y [S],
    det_trig [S])`` — the persistent device buffers, donated by the jitted
    caller. The ``[K, S]`` planes (``rates``/``lag_add``/``down_pre``/
    ``down_post``/``z1``/``z2``) are the host-precomputed control state for
    K ticks; ``valid [K]`` masks the padding ticks (every carry holds, so
    the final carry equals the state after the last *real* tick).

    Returns ``(carry', metrics)`` with ``metrics`` the
    :func:`~repro.dsp.simulator.step_batch_arrays` dict stacked to
    ``[K, S]`` per key. ``model``/``dt``/``use_pallas`` are static.
    """
    import jax
    import jax.numpy as jnp

    if use_pallas:
        from ..kernels.ops import fused_tick as _tick
    else:
        from ..kernels.ref import fused_tick_ref as _tick

    def body(carry, xs):
        lag_c, w, p, y_prev, trig = carry
        r, la, dpre, dpost, zz1, zz2, vk = xs
        new_lag, m = step_batch_arrays(
            model, lag_c, la, r, workers, cpu_cores, memory_mb, task_slots,
            cap_base, dpre, dpost, zz1, zz2, dt)
        # Fused lag+detector tick: the pure-jnp oracle (its lag arithmetic
        # is step_batch_arrays', op for op) or, when asked for, the Pallas
        # kernel. The tick's new_lag is the authoritative carry.
        lag_k, w2, p2, err, flag = _tick(
            lag_c, la, r, m["capacity"], dpre, w, p, y_prev,
            det_lam, det_thresh, dt)
        y = jnp.log1p(lag_k)
        carry = (jnp.where(vk, lag_k, lag_c),
                 jnp.where(vk, w2, w),
                 jnp.where(vk, p2, p),
                 jnp.where(vk, y, y_prev),
                 trig + jnp.where(vk & flag, 1, 0))
        return carry, m

    xs = (rates, lag_add, down_pre, down_post, z1, z2, valid)
    return jax.lax.scan(body, (lag, det_w, det_p, det_y, det_trig), xs)


def _scan_jit():
    import jax
    return jax.jit(fused_interval_scan,
                   static_argnames=("model", "dt", "use_pallas"),
                   donate_argnums=(1, 2, 3, 4, 5))


#: The one process-wide jitted scan (shared cache: every executor reuses
#: the same traces, which is what keeps a sweep at ≤2 compilations).
_FUSED_SCAN = None


def _fused_scan():
    global _FUSED_SCAN
    if _FUSED_SCAN is None:
        _FUSED_SCAN = _scan_jit()
    return _FUSED_SCAN


@SIM_ENGINES.register("fused")
class FusedSweepExecutor(SweepExecutorBase):
    """Sweep executor advancing whole decision intervals per dispatch.

    Same host-mirror layout as the sharded engine (padded
    :class:`~repro.dsp.simulator.BatchState`, per-row RNG streams, staged
    failure rollback) but the stepping surface is
    :meth:`step_interval`: the sweep engine hands it K ticks of rates plus
    a precomputed ``[K, S]`` injection schedule, the host precomputes the
    clock/RNG planes for all K ticks, and one jitted donated-carry scan
    advances the device state (see :func:`fused_interval_scan`).

    ``supports_intervals`` is the capability flag the sweep engine keys its
    chunked driver on; :meth:`step` remains available for direct
    tick-at-a-time stepping (a one-tick interval), so the executor still
    serves the full :class:`~repro.dsp.executor.SweepExecutorBase`
    contract. Works on any mesh width ≥ 1 (``devices=None`` = all visible
    devices).
    """

    #: the sweep engine drives interval stepping when this is True
    supports_intervals = True

    def __init__(self, model: ClusterModel, configs: Sequence[JobConfig],
                 seeds: Sequence[int], *, chunk: int = 16,
                 use_pallas: bool = False, **kwargs):
        if use_pallas:
            raise ValueError(
                "use_pallas=True needs a float32 carry: the fused engine "
                "carries float64 state and Mosaic lowers no float64 Pallas "
                "kernel; the float64 path is the jnp tick "
                "(repro.kernels.ref.fused_tick_ref, use_pallas=False)")
        super().__init__(model, configs, seeds, **kwargs)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..distributed.mesh import (SCENARIO, pad_to_multiple,
                                        scenario_mesh, scenario_sharding)

        S = len(configs)
        #: tick quantum: interval lengths are padded to power-of-two
        #: multiples of this, bounding the scan's distinct trace shapes
        self.chunk = int(chunk)
        self.use_pallas = bool(use_pallas)
        self.mesh = scenario_mesh(self.devices)
        self.n_devices = int(self.mesh.devices.size)
        self.n_rows = pad_to_multiple(S, self.n_devices)
        pad_rows = self.n_rows - S

        # Host mirror: full struct-of-arrays state, padded with C_max rows;
        # padding rows draw from disjoint RNG streams so real rows stay
        # bit-identical to the "batched" engine (same scheme as sharded).
        self.state = BatchState.from_configs(configs).pad(self.n_rows)
        self.rngs = BatchedNormals(
            list(self.seeds) + [2 ** 33 + r for r in range(pad_rows)])
        self._cap_base = model.capacity_batch(self.state)
        self._cfg_cache = list(configs)
        #: rollback lag staged by inject_failure between intervals,
        #: folded into the first tick of the next dispatch
        self._lag_add = np.zeros(self.n_rows)

        self._row_sharding = scenario_sharding(self.mesh)
        self._plane_sharding = NamedSharding(
            self.mesh, PartitionSpec(None, SCENARIO))
        with _x64():
            put = lambda a, r=1: jax.device_put(  # noqa: E731
                a, scenario_sharding(self.mesh, rank=r))
            n = self.n_rows
            self._lag = put(np.zeros(n))
            # detector state: AR(1)+bias RLS on log1p(lag) per scenario
            self._det_w = put(np.zeros((n, DET_ORDER)), 2)
            self._det_p = put(
                np.broadcast_to(10.0 * np.eye(DET_ORDER),
                                (n, DET_ORDER, DET_ORDER)).copy(), 3)
            self._det_y = put(np.zeros(n))
            self._det_trig = put(np.zeros(n, dtype=np.int64))
        self._dev_cfg: Optional[tuple] = None     # rebuilt when configs move

    # -- device plumbing ----------------------------------------------------
    def _device_configs(self) -> tuple:
        """Config-derived ``[S]`` operands, device-put lazily after every
        reconfiguration (configs change per decision, not per tick)."""
        if self._dev_cfg is None:
            import jax
            st = self.state
            with _x64():
                self._dev_cfg = tuple(
                    jax.device_put(a, self._row_sharding)
                    for a in (st.workers, st.cpu_cores, st.memory_mb,
                              st.task_slots, self._cap_base))
        return self._dev_cfg

    def device_buffers(self) -> Dict[str, Any]:
        """The donated scan carry (lag + detector state) and the config
        operands, by name."""
        return {"lag": self._lag, "det_w": self._det_w,
                "det_p": self._det_p, "det_y": self._det_y,
                "det_trig": self._det_trig,
                **dict(zip(DEVICE_CONFIGS, self._device_configs()))}

    def _bucket(self, K: int) -> int:
        """Padded tick count: the smallest ``chunk * 2**m >= K``."""
        Kp = self.chunk
        while Kp < K:
            Kp *= 2
        return Kp

    # -- interval stepping ---------------------------------------------------
    def step_interval(self, rates_ks: np.ndarray,
                      inject_ks: Optional[np.ndarray] = None
                      ) -> Dict[str, np.ndarray]:
        """Advance every scenario through K ticks in one scan dispatch.

        ``rates_ks`` is ``[K, S]``; ``inject_ks`` (optional ``[K, S]``
        bool) marks failures to inject *after* tick k — exactly where the
        sweep engine's per-tick loop calls ``inject_failure`` — with the
        rollback lag staged into tick k+1's ``lag_add`` plane (or carried
        into the next interval when k is the last tick). Telemetry history
        is recorded for all K columns; returns the metric dict as
        ``[K, S]`` arrays.
        """
        import jax

        # Three spans tile the step: the host build of the K-tick planes,
        # the dispatch (device_put + scan call), and the read back, which
        # waits for the device.
        with obs.span("engine.fused.prepare"):
            rates_ks = np.asarray(rates_ks, float)
            K, S = rates_ks.shape
            if S != len(self.seeds):
                raise ValueError(f"expected {len(self.seeds)} scenario "
                                 f"columns, got {S}")
            st = self.state
            n = self.n_rows
            dt = self.dt
            Kp = self._bucket(K)

            R = np.zeros((Kp, n))
            R[:K, :S] = rates_ks
            dpre = np.zeros((Kp, n), bool)
            dpost = np.zeros((Kp, n), bool)
            z1 = np.zeros((Kp, n))
            z2 = np.zeros((Kp, n))
            lag_add = np.zeros((Kp, n))
            valid = np.zeros(Kp, bool)
            valid[:K] = True
            lag_add[0] = self._lag_add
            self._lag_add = np.zeros(n)

            # Host half, precomputed for the whole interval:
            # downtime/checkpoint clocks + RNG draws in the exact batched
            # order (z1 all rows, then masked |z2|), with tick-k injections
            # applied between tick k and tick k+1 — identical sequencing to
            # the per-tick engines.
            for k in range(K):
                down_pre = st.downtime_left_s > 0.0
                st.downtime_left_s = np.where(
                    down_pre, np.maximum(st.downtime_left_s - dt, 0.0),
                    st.downtime_left_s)
                since = np.where(down_pre, st.since_checkpoint_s,
                                 st.since_checkpoint_s + dt)
                since = np.where(
                    ~down_pre & (since >= st.checkpoint_interval_s),
                    0.0, since)
                st.since_checkpoint_s = since
                down_post = st.downtime_left_s > 0.0
                dpre[k] = down_pre
                dpost[k] = down_post
                z1[k] = self.rngs.draw()
                z2[k] = np.abs(self.rngs.draw(~down_post))
                st.last_rate = R[k]
                if inject_ks is not None and inject_ks[k].any():
                    stage = lag_add[k + 1] if k + 1 < K else self._lag_add
                    for j in np.nonzero(inject_ks[k])[0]:
                        self._stage_failure(int(j), stage)

        with obs.timed_phase("simulate", "engine.fused.interval",
                             K=K, Kp=Kp, scenarios=S), _x64():
            plane = self._plane_sharding
            xs = tuple(jax.device_put(a, plane)
                       for a in (R, lag_add, dpre, dpost, z1, z2))
            carry, ms = _fused_scan()(
                self.model, self._lag, self._det_w, self._det_p,
                self._det_y, self._det_trig, *xs, valid,
                *self._device_configs(), DET_LAMBDA, DET_THRESH,
                dt, self.use_pallas)

        with obs.span("engine.fused.readback"):
            (self._lag, self._det_w, self._det_p, self._det_y,
             self._det_trig) = carry
            if obs.enabled():
                obs.inc("sweep.intervals")
                obs.inc("sweep.ticks", K)
                obs.inc("sweep.scenario_ticks", K * S)
                obs.inc("transfer.h2d_bytes",
                        R.nbytes + lag_add.nbytes + dpre.nbytes
                        + dpost.nbytes + z1.nbytes + z2.nbytes
                        + valid.nbytes)
                obs.track_jit_cache("fused_scan",
                                    int(_fused_scan()._cache_size()))
            # Forced copy into the mirror: the device buffer is donated
            # into the next dispatch. Valid-tick masking makes the final
            # carry the lag after the last real tick.
            st.from_device(self._lag)

            out = {key: np.asarray(v)[:K, :S] for key, v in ms.items()}
            if obs.enabled():
                obs.inc("transfer.d2h_bytes",
                        sum(v.nbytes for v in out.values())
                        + self.state.lag_events.nbytes)
            i0 = self.step_index + 1
            for key in self.hist:
                self.hist[key][:, i0:i0 + K] = out[key].T
            # configs only change at interval boundaries -> constant workers
            self.workers_hist[:, i0:i0 + K] = st.workers[:S, None]
            self.step_index += K
        return out

    @property
    def tick(self) -> str:
        """Which tick implementation the scan runs."""
        return ("pallas:kernels.fused_tick" if self.use_pallas
                else "jnp:kernels.ref.fused_tick_ref")

    @property
    def anomaly_triggers(self) -> np.ndarray:
        """Per-scenario count of detector trigger flags accumulated inside
        the scan (auxiliary telemetry; feeds nothing back into results)."""
        return np.asarray(self._det_trig)[:len(self.seeds)]

    # -- SweepExecutorBase stepping hooks -----------------------------------
    def step(self, rates: np.ndarray) -> Dict[str, np.ndarray]:
        """Tick-at-a-time stepping = a one-tick interval (history recording
        included, so the base-class bookkeeping is not repeated here)."""
        m = self.step_interval(np.asarray(rates, float)[None, :])
        return {k: v[0] for k, v in m.items()}

    def _stage_failure(self, idx: int, stage: np.ndarray) -> None:
        """Mirror of ClusterModel.inject_failure_batch with the rollback
        lag staged into ``stage`` (a future tick's lag_add plane, or the
        cross-interval carry) instead of scattered into the device buffer."""
        st = self.state
        state_mb = self.model.state_size_mb(float(st.last_rate[idx]))
        restore = state_mb / (self.model.restore_mb_per_s
                              * max(float(st.workers[idx]), 1.0))
        st.downtime_left_s[idx] = self.model.failure_detect_s \
            + self.model.redeploy_s + restore
        stage[idx] += st.last_rate[idx] * st.since_checkpoint_s[idx]
        st.since_checkpoint_s[idx] = 0.0

    def inject_failure(self, idx: int) -> None:
        self._stage_failure(idx, self._lag_add)

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

    # -- introspection / contracts ------------------------------------------
    def _scan_operands(self, K: Optional[int] = None) -> tuple:
        """One full positional operand tuple for ``fused_interval_scan``
        (dummy planes), shared by :meth:`lower_interval` and
        :meth:`contract_probe` so introspection sees the exact argument
        layout of the real dispatch."""
        Kp = self._bucket(K if K is not None else 1)
        n = self.n_rows
        plane = np.zeros((Kp, n))
        flags = np.zeros((Kp, n), bool)
        valid = np.ones(Kp, bool)
        return (self.model, self._lag, self._det_w, self._det_p,
                self._det_y, self._det_trig, plane, plane, flags, flags,
                plane, plane, valid, *self._device_configs(),
                DET_LAMBDA, DET_THRESH, self.dt, self.use_pallas)

    def lower_interval(self, K: Optional[int] = None):
        """The jitted interval scan lowered for this executor's mesh
        (introspection hook; :meth:`contract_probe` is the
        contract-checked face of it)."""
        with _x64():
            return _fused_scan().lower(*self._scan_operands(K))

    def contract_probe(self):
        """This executor's scan packaged for
        :func:`repro.analysis.contracts.run_probe`; see
        :data:`FUSED_INTERVAL_CONTRACT` for the invariants and
        :func:`interval_arg_sets` for the recompile-budget workload."""
        from ..analysis.contracts import ContractProbe, count_traces
        args = self._scan_operands()
        return ContractProbe(
            contract=FUSED_INTERVAL_CONTRACT, fn=_fused_scan(), args=args,
            x64=True,
            # statics: model (0) and the trailing (dt, use_pallas) pair
            static_argnums=(0, len(args) - 2, len(args) - 1),
            traces=lambda: count_traces(
                fused_interval_scan,
                interval_arg_sets(chunk=self.chunk),
                x64=True,
                static_argnames=("model", "dt", "use_pallas"),
                donate_argnums=(1, 2, 3, 4, 5)))


# ---------------------------------------------------------------------------
# compilation contract (see repro.analysis and docs/ANALYSIS.md)
# ---------------------------------------------------------------------------

def _fused_interval_contract():
    from ..analysis.contracts import COLLECTIVE_HLO_OPS, CompilationContract
    return CompilationContract(
        name="engine:fused",
        # Elementwise over the scenario axis tick by tick: partitioning the
        # scan over the mesh must be communication-free.
        forbidden_hlo=COLLECTIVE_HLO_OPS,
        # lag + detector state are the persistent device buffers; their
        # donation must survive into the compiled module.
        donation=True,
        # float64 is deliberate: the fused scan mirrors the float64 numpy
        # engines (pinned by the four-way differential harness).
        dtype_ceiling="float64",
        # measured ~120 today (sim step + fused tick + scan plumbing);
        # 256 leaves room for model tweaks without hiding an unroll
        max_primitives=256,
        # A host callback inside the scan body would wake the host per tick
        # — the exact failure mode this engine exists to remove.
        forbid_callbacks=True,
        # Chunk-bucketed interval padding: a sweep over mixed interval
        # lengths must reuse the same trace; <=2 covers two scenario-axis
        # widths in one process (see interval_arg_sets).
        max_traces=2,
        note="whole-interval scan: zero cross-scenario collectives, "
             "lag/detector carries donated, no host wakeups inside the "
             "interval, chunk-bucketed recompile budget")


FUSED_INTERVAL_CONTRACT = _fused_interval_contract()


def interval_arg_sets(shapes: Sequence[Tuple[int, int]] = ((2, 5), (2, 12),
                                                           (3, 8), (3, 16)),
                      chunk: Optional[int] = 16) -> List[tuple]:
    """Canonical recompile-budget workload: ``(S, K)`` interval shapes as
    positional arg-sets for :func:`fused_interval_scan`.

    With ``chunk`` bucketing (the engine's behavior) every K here pads to
    one shape per scenario width — 2 traces for the two widths above.
    ``chunk=None`` lowers the *raw* interval lengths, which is the seeded
    failure mode: one trace per distinct K, blowing the ≤2 budget (the red
    case of the recompile regression test).
    """
    model = ClusterModel()
    sets = []
    for S, K in shapes:
        Kp = K
        if chunk is not None:
            Kp = chunk
            while Kp < K:
                Kp *= 2
        plane = np.zeros((Kp, S))
        flags = np.zeros((Kp, S), bool)
        valid = np.zeros(Kp, bool)
        valid[:K] = True
        rows = np.ones(S)
        args = (model, np.zeros(S), np.zeros((S, DET_ORDER)),
                np.broadcast_to(np.eye(DET_ORDER),
                                (S, DET_ORDER, DET_ORDER)).copy(),
                np.zeros(S), np.zeros(S, dtype=np.int64),
                plane, plane, flags, flags, plane, plane, valid,
                rows * 4.0, rows, rows * 4096.0, rows,
                rows * 40_000.0, DET_LAMBDA, DET_THRESH)
        sets.append((args, {"dt": 5.0, "use_pallas": False}))
    return sets


def _fused_probe():
    from ..analysis.contracts import ContractProbe
    from ..kernels.fused_tick import fused_tick, fused_tick_contract

    ex = FusedSweepExecutor(ClusterModel(), [JobConfig(), JobConfig()],
                            seeds=[0, 1], dt=5.0, n_steps=4)
    n = 4
    rows = np.ones(n)
    kernel_probe = ContractProbe(
        contract=fused_tick_contract(),
        fn=fused_tick,
        args=(rows * 10.0, np.zeros(n), rows * 5e4, rows * 4e4,
              np.zeros(n, bool), np.zeros((n, DET_ORDER)),
              np.broadcast_to(np.eye(DET_ORDER),
                              (n, DET_ORDER, DET_ORDER)).copy(),
              np.zeros(n), DET_LAMBDA, DET_THRESH),
        kwargs={"dt": 5.0, "interpret": True},
        x64=True)
    # Companion probe: tracing the interval scan with obs instrumentation
    # forced on must yield the identical primitive count — the span/counter
    # layer lives strictly on the host side of the dispatch boundary.
    args = ex._scan_operands()
    obs_probe = obs.instrumentation_probe(
        "engine:fused+obs", fused_interval_scan, args,
        static_argnums=(0, len(args) - 2, len(args) - 1), x64=True)
    return [ex.contract_probe(), kernel_probe, obs_probe]


SIM_ENGINES.attach_contract("fused", _fused_probe)

"""Ahead-of-time compiles of the main path for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present, and refuses what the chip's
compiler would refuse (unsupported dtypes, unaligned blocks, programs that
do not fit). Each test lowers one hot-path program at the size the system
runs it and compiles it for one v5e chip (or the 2x2 mesh); nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a test file that
decided at import whether its tests exist would give pytest-xdist workers
different collections. Where it cannot be described, every test here skips
from the fixture. The persistent compilation cache is off while these
tests run: a compile for a described device is written to it but cannot be
read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import ForecastBank
from repro.core.forecast_bank import _arima_chunk_roll_jit
from repro.core.gp_bank import _fit_packed
from repro.distributed.mesh import SCENARIO
from repro.dsp import ClusterModel, FusedSweepExecutor, JobConfig
from repro.dsp.fused import _fused_scan
from repro.fleet.ingest import INGEST_KEYS, _epoch_reduce
from repro.kernels.fused_tick import fused_tick
from repro.kernels.rls_update import rls_rank1_update

ROWS = 1024            # fleet capacity / kernel batch
SCENARIOS = 64         # the smoke sweep grid
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices), (SCENARIO,))


def _shape(a, sharding):
    """A described-device stand-in for array ``a``."""
    a = np.asarray(a) if not hasattr(a, "dtype") else a
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _tick_operands(dtype, sharding):
    s = lambda shape, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    return (s((ROWS,)), s((ROWS,)), s((ROWS,)), s((ROWS,)),
            s((ROWS,), jnp.bool_), s((ROWS, 2)), s((ROWS, 2, 2)),
            s((ROWS,)))


def _compile_tick(dtype, sharding):
    fn = jax.jit(lambda *a: fused_tick(*a, 0.995, 3.0, 5.0))
    return fn.lower(*_tick_operands(dtype, sharding)).compile()


def test_fused_tick_float32_compiles(one_chip):
    text = _compile_tick(jnp.float32, one_chip).as_text()
    assert "tpu_custom_call" in text          # the Mosaic kernel is there


def test_fused_tick_float64_is_refused(one_chip):
    # Why the fused engine's float64 carry runs the jnp tick: Mosaic has
    # no float64, so FusedSweepExecutor(use_pallas=True) refuses up front.
    with jax.enable_x64():
        with pytest.raises(NotImplementedError, match="64-bit"):
            _compile_tick(jnp.float64, one_chip)


def test_rls_rank1_update_float32_compiles(one_chip):
    k = 9                                     # AR(8) + bias
    s = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    text = rls_rank1_update.lower(s((ROWS, k, k)), s((ROWS, k)),
                                  s((ROWS,))).compile().as_text()
    assert "tpu_custom_call" in text


def _engine_scan_operands(K: int = 128):
    """The fused engine's own interval-scan operands for the smoke grid
    (built on the host CPU device; only shapes and dtypes are used)."""
    with jax.enable_x64():
        ex = FusedSweepExecutor(ClusterModel(), [JobConfig()] * SCENARIOS,
                                list(range(SCENARIOS)), dt=5.0,
                                n_steps=K)
        return ex._scan_operands(K)


def _scan_shapes(ops, row_sharding):
    """Array operands -> described-device shapes; statics pass through.
    ``row_sharding(ndim, is_plane)`` places each operand."""
    out = []
    for i, a in enumerate(ops):
        if not hasattr(a, "shape") or np.ndim(a) == 0:
            out.append(a)
            continue
        is_plane = 6 <= i <= 12                # the [K, S] planes + valid
        out.append(_shape(a, row_sharding(np.ndim(a), is_plane)))
    return out


def test_fused_interval_scan_compiles_one_chip(one_chip):
    ops = _scan_shapes(_engine_scan_operands(), lambda nd, plane: one_chip)
    with jax.enable_x64():
        compiled = _fused_scan().lower(*ops).compile()
    assert compiled.as_text()


def test_fused_interval_scan_on_4_chip_mesh_has_no_collectives(mesh4):
    def place(ndim, plane):
        if plane:                              # [K, S] planes; valid is [K]
            return NamedSharding(mesh4, P(None, SCENARIO) if ndim == 2
                                 else P())
        return NamedSharding(mesh4, P(SCENARIO, *([None] * (ndim - 1))))

    ops = _scan_shapes(_engine_scan_operands(), place)
    with jax.enable_x64():
        text = _fused_scan().lower(*ops).compile().as_text()
    found = [c for c in COLLECTIVES if c in text]
    assert not found, f"cross-scenario collectives in the fused scan: {found}"


def test_gp_bank_fit_compiles_at_fleet_batch(one_chip):
    # ~100 due controllers x a few stale (segment, metric) GPs per epoch
    # at 1024 jobs -> the pow2 bucket of 256 members, 16 points each, over
    # the paper's 5-parameter Flink space, 2 restarts.
    B, n, d, R = 256, 16, 5, 2
    s = lambda shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    compiled = _fit_packed.lower(s((B, n, d)), s((B, n)), s((B, n)),
                                 s((B, R, d + 2)), max_iter=60).compile()
    assert compiled.as_text()


def test_ingest_drain_compiles(one_chip):
    plane = jax.ShapeDtypeStruct((ROWS, 8, len(INGEST_KEYS)), jnp.float32,
                                 sharding=one_chip)
    assert _epoch_reduce.lower(plane).compile().as_text()


def test_forecast_bank_flush_compiles(one_chip):
    # The fleet's per-epoch TSF flush + rollout: 1024 ARIMA streams.
    bank = ForecastBank.from_kinds(["arima"] * ROWS)
    fam = bank._fams["arima"]
    with jax.enable_x64():
        state = jax.tree.map(lambda a: _shape(a, one_chip), fam.state)
        params = jax.tree.map(lambda a: _shape(a, one_chip), fam.params)
        vals = jax.ShapeDtypeStruct((4, fam.b), jnp.float64,
                                    sharding=one_chip)
        compiled = _arima_chunk_roll_jit.lower(
            state, params, vals, steps=bank.horizon,
            use_pallas=False).compile()
    assert compiled.as_text()

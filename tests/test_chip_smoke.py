"""``chip_smoke.py``'s phases at a tiny size on the CPU.

The script itself refuses to run anywhere but a TPU, so between chip runs
these tests keep its phase functions honest: they drive each phase through
the same entry points at a size the CPU finishes quickly, check that a
phase with an empty result fails instead of passing, and check that the
device guard refuses the CPU platform.
"""
import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import device_env

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.import_repo()
    return mod


def test_device_guard_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        smoke.require_tpu()


def test_script_exits_nonzero_on_cpu_without_a_result():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=300, env={**device_env(1),
                                            "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_follows_env_else_checkout(monkeypatch):
    import jax

    from repro.compile_cache import enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax-cache")
    assert enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir == was   # left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_fleet_phase(smoke):
    out = smoke.phase_fleet(n_jobs=16, epochs=8)
    assert out["decisions"] > 0 and out["warm"] > 0
    assert out["ingest_accepted"] > 0
    assert len(out["decision_digest"]) == 64


def test_fleet_phase_fails_when_no_controller_warms(smoke):
    # 2 epochs < the 5 observed epochs a job needs to leave the cold
    # baseline: no decisions and no warm controller must fail the phase.
    with pytest.raises(smoke.SmokeFailure, match="fleet: .* is 0"):
        smoke.phase_fleet(n_jobs=16, epochs=2)


def test_sweep_phase(smoke):
    specs = smoke.sweep_specs(traces=("diurnal",),
                              controllers=("reactive", "demeter"),
                              seeds=(0, 1), hours=2.0)
    out = smoke.phase_sweep(specs)
    assert out["fused"]["scenarios"] == 4
    assert out["fused"]["n_model_fits"] > 0
    assert out["fused"]["tick"] == "jnp:kernels.ref.fused_tick_ref"
    assert out["max_rel_err_vs_batched"] <= smoke.SWEEP_RTOL


def test_golden_phase(smoke):
    out = smoke.phase_golden()
    assert out["max_rel_err"] <= smoke.GOLDEN_RTOL


def test_compare_sweeps_flags_a_perturbed_scenario(smoke):
    from repro.core import EngineConfig
    from repro.dsp import run_sweep
    specs = smoke.sweep_specs(traces=("flash",), controllers=("static",),
                              seeds=(0, 1), hours=0.25)
    a = run_sweep(specs, config=EngineConfig())
    b = run_sweep(specs, config=EngineConfig())
    assert smoke.compare_sweeps(a, b, 1e-12) == (0.0, [])
    b.scenarios[1].consumer_lag = b.scenarios[1].consumer_lag + 1.0
    worst, bad = smoke.compare_sweeps(a, b, 1e-12)
    assert worst > 1e-12 and bad == [b.scenarios[1].name]


def test_mesh_phase_on_4_virtual_devices():
    # The --chips 4 path: fused + sharded at devices=4 with every
    # persistent buffer spread over the mesh, against the host engine.
    # A Demeter scenario makes the sweep hold the shared forecast bank.
    code = ("import chip_smoke as cs; cs.import_repo(); "
            "cs.phase_mesh(cs.sweep_specs(('diurnal',), "
            "('static', 'demeter'), (0, 1, 2), hours=0.25), devices=4)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=600,
                          env=device_env(4))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.count("scenario(s) outside") == 2
    lines = [json.loads(ln[len("[mesh] "):]) for ln in proc.stdout.splitlines()
             if ln.startswith("[mesh] {") and "buffer_device_counts" in ln]
    spans = {ln["engine"]: ln["buffer_device_counts"] for ln in lines}
    configs = {f"executor.{k}" for k in ("workers", "cpu_cores", "memory_mb",
                                         "task_slots", "cap_base")}
    bank = {f"forecast_bank.arima.{k}" for k in ("w", "P", "lags", "tails",
                                                 "count", "last", "err",
                                                 "err_n")}
    assert set(spans["sharded"]) == {"executor.lag"} | configs | bank
    assert set(spans["fused"]) == ({"executor.lag", "executor.det_w",
                                    "executor.det_p", "executor.det_y",
                                    "executor.det_trig"} | configs | bank)
    assert (len(spans["fused"]), len(spans["sharded"])) == (18, 14)
    assert set(spans["fused"].values()) == set(spans["sharded"].values()) == {4}

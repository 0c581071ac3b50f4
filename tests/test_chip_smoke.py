"""chip_smoke.py off the chip: it refuses the CPU, its failure checks are
fatal, and its phases pass against their references here at a tiny size
(Pallas in interpret mode; 4 of the 8 virtual CPU devices for the mesh).
The compile cache stays off on the CPU."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402


@pytest.fixture
def metrics():
    from automerge_tpu.obs.metrics import enabled_metrics

    with enabled_metrics() as reg:
        reg.reset()
        yield reg


def test_refuses_the_cpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("farm", [
    SimpleNamespace(degraded={3}, quarantine={}),
    SimpleNamespace(degraded=set(), quarantine={5: ValueError("poison")}),
])
def test_degraded_or_quarantined_docs_are_fatal(metrics, farm):
    with pytest.raises(cs.SmokeFailure):
        cs.check_no_fallback([farm])


def test_fallback_calls_are_fatal(metrics):
    metrics.counter("farm.fallback.calls").inc()
    with pytest.raises(cs.SmokeFailure):
        cs.check_no_fallback([])


def test_farm_phase(metrics):
    cs.run_farm(jax.devices()[0], num_docs=64, rounds=3, streams=16)


def test_kernel_phase(monkeypatch):
    monkeypatch.setattr(cs, "FILTERS", 3)
    monkeypatch.setattr(cs, "ENTRIES", 300)
    monkeypatch.setattr(cs, "QUERIES", 200)
    monkeypatch.setattr(cs, "LEB_VALUES", 500)
    cs.run_kernels(interpret=True)


def test_mesh_phase(metrics):
    cs.run_mesh(jax.devices()[:4], docs_per_chip=32, rounds=3, streams=16)


def test_compile_cache_is_off_on_the_cpu():
    from automerge_tpu.tpu.compile_cache import enable_compile_cache

    assert enable_compile_cache() is None
    assert not jax.config.jax_enable_compilation_cache

"""Platform rules (``repro.device``) and per-kind device peaks
(``pipeline.cost.device_profile``): a run meant for the chip never
finishes on the host looking as if it had used the chip."""
import jax
import pytest

from repro import device
from repro.pipeline.backend import JaxBackend
from repro.pipeline.cost import (DEFAULT_HW, HOST_HW, PLANNING_TPU_KIND,
                                 TPU_PEAKS, device_profile)


def test_interpret_only_where_jax_is_held_to_the_cpu(monkeypatch):
    assert jax.default_backend() == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert device.held_to_cpu() and device.default_interpret() is True
    # a CPU that JAX fell back to (here: asked for a TPU first) raises
    for value in ("tpu,cpu", ""):
        monkeypatch.setenv("JAX_PLATFORMS", value)
        assert not device.held_to_cpu()
        with pytest.raises(RuntimeError, match="platform 'cpu'"):
            device.default_interpret()
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            JaxBackend()


def test_interpret_backend_reports_its_flavour():
    b = JaxBackend()
    assert b.interpret is True and b.name == "jax-interpret"
    assert b.device_kind == jax.devices()[0].device_kind


@pytest.mark.parametrize("env_dir", (None, "elsewhere"))
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The in-checkout directory is used only when
    JAX_COMPILATION_CACHE_DIR is unset; the path never varies by run.
    (jax.config.update is captured, so the test turns no cache on.)"""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(device.REPO_ROOT / ".jax_cache")
        assert device.enable_compile_cache() == want
        assert device.enable_compile_cache() == want
        assert calls["jax_compilation_cache_dir"] == want
    else:
        path = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", path)
        assert device.enable_compile_cache() == path
        assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
    assert (device.REPO_ROOT / "src" / "repro" / "device.py").is_file()


def test_tpu_peaks_keyed_by_device_kind():
    p = device_profile("tpu", "TPU v5 lite")
    assert (p.flops_per_s, p.mem_bw) == TPU_PEAKS["TPU v5 lite"]
    assert (p.flops_per_s, p.mem_bw) == (197e12, 819e9)
    assert DEFAULT_HW["tpu"] == device_profile("tpu", PLANNING_TPU_KIND)


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(ValueError, match="no published peaks"):
        device_profile("tpu", "TPU v9 imaginary")


def test_cpu_device_plans_as_the_host():
    p = device_profile("tpu", "cpu")
    assert p.name == "tpu"
    assert (p.flops_per_s, p.mem_bw) == (HOST_HW.flops_per_s, HOST_HW.mem_bw)

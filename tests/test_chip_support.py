"""CPU-side pieces of the chip path: the compile-cache placement and the
device guard of `chip_smoke.py`."""
import importlib.util
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore JAX's compilation-cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # not overridden


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch,
                                                   cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.setup_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.setup_compile_cache() == path   # stable


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("chips", [1, 4])
def test_chip_smoke_device_guard_refuses_cpu(capsys, chips):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        _chip_smoke().device_label(chips)
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err


def test_chip_smoke_main_exits_before_any_phase_on_cpu(monkeypatch, capsys):
    for var in ("REPRO_DECODE_KERNEL", "REPRO_FASTMAX_BWD", "REPRO_AUTOTUNE"):
        monkeypatch.setenv(var, "0")   # restored by monkeypatch afterwards
    with pytest.raises(SystemExit) as e:
        _chip_smoke().main([])
    assert e.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert "[serve]" not in out

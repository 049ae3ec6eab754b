"""Entry-point plumbing: the compile-cache helper and the benchmark
runner's exit status."""
import sys

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them, so no test
    turns the persistent cache on."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_fixed_dir_in_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.enable()
    assert d == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
    assert config_updates == [("jax_compilation_cache_dir", d)]
    assert compile_cache.enable() == d          # same path every run


def test_compile_cache_env_dir_stands(monkeypatch, config_updates,
                                      tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert config_updates == []


def test_benchmark_runner_exits_nonzero_when_a_module_fails(monkeypatch,
                                                            capsys):
    from benchmarks import run
    monkeypatch.setattr(run, "MODULES", ["no_such_benchmark"])
    monkeypatch.setattr(sys, "argv", ["run", "no_such_benchmark"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "no_such_benchmark FAILED" in capsys.readouterr().out

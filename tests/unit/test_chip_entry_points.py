"""What the chip entry points do with no chip, the per-chip peak table, and
where the compile cache goes. Fast, CPU only."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_refuses_to_run_without_a_chip():
    """No fallback: on the CPU backend the script exits non-zero before it
    builds a model, and prints no result."""
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert '"metric"' not in proc.stdout
    assert "platform='cpu'" in proc.stderr
    assert "DeepSpeedEngine ready" not in proc.stderr + proc.stdout


def test_peak_table_knows_v5e_and_raises_on_unknown_kind(monkeypatch):
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator
    acc = TPU_Accelerator()
    monkeypatch.setattr(acc, "device_kind", lambda: "TPU v5 lite")
    assert acc.peak_flops() == 197e12
    assert acc.peak_hbm_bandwidth() == 819e9
    monkeypatch.setattr(acc, "device_kind", lambda: "TPU v9 imaginary")
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        acc.peak_flops()
    with pytest.raises(KeyError, match="no published peaks"):
        acc.peak_hbm_bandwidth()


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    from deepspeed_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code
    assert compile_cache.export({"A": "1"}) == {"A": "1", compile_cache.ENV: str(tmp_path)}


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    from deepspeed_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    assert compile_cache.cache_dir() == compile_cache.cache_dir()
    child = compile_cache.export({compile_cache.ENV: "/elsewhere"})
    assert child[compile_cache.ENV] == "/elsewhere"  # an outside placement wins

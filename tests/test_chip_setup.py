"""Chip set-up helpers: the per-device peak table, the compile-cache rule,
and ``chip_smoke.py`` refusing to report a result without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import hw
from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


class TestPeakTable:
    def test_v5e_published_peaks(self):
        p = hw.peaks("TPU v5 lite")
        assert (p.flops_bf16, p.hbm_bw, p.hbm_bytes) == (197e12, 819e9, 16e9)
        assert "TPU v5e" in p.source

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
    def test_unknown_device_kind_raises(self, kind):
        with pytest.raises(KeyError, match="no published peaks"):
            hw.peaks(kind)


class TestCompileCache:
    def test_env_var_wins_and_nothing_is_set_in_code(self, monkeypatch, tmp_path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_gitignored_dir_in_the_checkout(self, monkeypatch):
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            first = compile_cache.configure_compile_cache()
            assert jax.config.jax_compilation_cache_dir == first
            assert compile_cache.configure_compile_cache() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
            compilation_cache.reset_cache()
        assert Path(first) == REPO / ".jax_compile_cache"
        ignored = (REPO / ".gitignore").read_text().split()
        assert ".jax_compile_cache/" in ignored


def _run_smoke(script: Path, cwd: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )


class TestChipSmokeRefuses:
    def test_no_tpu_exits_nonzero_without_result(self):
        proc = _run_smoke(REPO / "chip_smoke.py", REPO)
        assert proc.returncode != 0
        assert "no TPU present" in proc.stderr
        assert '"ok"' not in proc.stdout

    def test_script_alone_exits_nonzero_without_result(self, tmp_path):
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        proc = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
        assert proc.returncode != 0
        assert "run it from a checkout" in proc.stderr
        assert '"ok"' not in proc.stdout

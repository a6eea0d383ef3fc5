"""Launchers and the chip smoke script, on the CPU: the compile-cache rule,
train -> restore -> resume on one NVCache file system, the serving journal,
and a chip_smoke.py that refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import serve, train
from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir is None   # JAX reads the env


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO / ".jax_cache") == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the launchers' CPU compiles out of the checkout's cache."""
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)


def test_train_restore_then_resume_on_one_fs(no_cache):
    fs = train.open_fs(log_mib=4)
    args = ["--smoke", "--n-layers", "1", "--batch", "1", "--seq", "16",
            "--ckpt-every", "1"]
    first = train.main(args + ["--steps", "2"], fs=fs)
    assert first["n_layers"] == 1 and first["steps"] == 2
    assert len(first["save_s"]) == 2
    restored = train.main(args + ["--steps", "2"], fs=fs)
    assert restored["steps"] == 0
    assert restored["state_sha256"] == first["state_sha256"]
    resumed = train.main(args + ["--steps", "3"], fs=fs)
    assert resumed["resumed_from"] == 2 and resumed["steps"] == 1
    fs.nv.shutdown()


def test_serve_journals_both_lines(no_cache):
    fs = serve.open_fs()
    out = serve.main(["--smoke", "--batch", "2", "--prompt-len", "8",
                      "--tokens", "3"], fs=fs)
    assert out["completed"] == 6
    fd = fs.open(serve.JOURNAL)
    lines = fs.pread(fd, fs.size(fd), 0).decode().splitlines()
    fs.nv.shutdown()
    recs = [json.loads(line) for line in lines]
    assert recs[0] == {"batch": 2, "prompt_len": 8}
    assert recs[1]["completed"] == 6


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, str(script)], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if where == "checkout":
        assert "device phase" in out.stderr and "phase" not in out.stdout

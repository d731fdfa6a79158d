"""Entry-point set-up (``repro.launch.entry``): the platform decision and
where the persistent compile cache lives."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch.entry import CACHE_DIR, check_platform

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("platform,env", [
    ("tpu", None), ("tpu", "cpu"), ("cpu", "cpu"), ("cpu", "tpu,cpu")])
def test_platform_accepted(platform, env):
    assert check_platform(platform, env) == platform


@pytest.mark.parametrize("platform,env", [
    ("cpu", None), ("cpu", ""), ("cpu", "tpu"), ("gpu", "cpu")])
def test_platform_refused_without_explicit_cpu(platform, env):
    with pytest.raises(RuntimeError, match="no TPU found"):
        check_platform(platform, env)


_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.entry import use_compile_cache
print("DIR=" + use_compile_cache())
print("CFG=" + str(jax.config.jax_compilation_cache_dir))
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = dict(l.split("=", 1) for l in out.stdout.splitlines() if "=" in l)
    return lines["DIR"], lines["CFG"]


def _entries(d: Path) -> set:
    return set(os.listdir(d)) if d.is_dir() else set()


def test_compile_cache_follows_env(tmp_path):
    before = _entries(CACHE_DIR)
    d, cfg = _probe(tmp_path, compile_=True)
    assert d == cfg == str(tmp_path)
    assert _entries(tmp_path), "no cache entry written to the env directory"
    assert _entries(CACHE_DIR) == before


def test_compile_cache_defaults_to_checkout():
    d, cfg = _probe(None, compile_=False)
    assert d == cfg == str(ROOT / ".jax_cache")
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_peaks_keyed_by_device_kind():
    from repro.launch.roofline import V5E, peaks
    assert peaks(V5E)["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peak rates"):
        peaks("cpu")

"""The --arch CLI launchers run every registered architecture's reduced
config end to end (subprocess; cheap archs only to bound runtime)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(mod, *args, timeout=600):
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-m", mod, *args], env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["starcoder2-3b", "gin-tu", "dcn-v2", "bst"])
def test_train_launcher(arch):
    out = _run("repro.launch.train", "--arch", arch, "--steps", "6",
               "--batch", "8", "--seq", "32")
    assert "loss" in out


@pytest.mark.slow
def test_serve_launcher():
    out = _run("repro.launch.serve", "--items", "2000", "--queries", "64",
               "--batch", "32")
    assert "recall@10" in out


@pytest.mark.slow
def test_serve_launcher_continuous():
    out = _run("repro.launch.serve", "--runtime", "continuous",
               "--items", "2000", "--queries", "32", "--lanes", "8",
               "--offered-qps", "300", "--ef", "32")
    assert "recall@10" in out
    assert "lane-occupancy" in out
    assert "time-in-queue" in out


@pytest.mark.slow
def test_serve_continuous_fails_without_ok_completions():
    """Every request shed (a zero-depth queue) and no --chaos plan: the
    launcher reports the run and exits non-zero instead of passing."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--runtime",
         "continuous", "--items", "500", "--queries", "8", "--lanes", "4",
         "--max-queue", "0", "--ef", "16"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode != 0
    assert "no ok completions" in out.stdout
    assert "no --chaos plan" in out.stderr


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits
    at the fixed <repo>/.jax_cache."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = ("import jax; from repro.utils import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    want = str(tmp_path) if env_dir else os.path.join(
        os.path.abspath(ROOT), ".jax_cache")
    assert out.stdout.split() == [want, want]

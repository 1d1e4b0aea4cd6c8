"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, and the three
ways it must fail.  The script has no CPU success mode — every run here ends
non-zero with ``"ok": false`` on its last line; what the rehearsal proves is
that the phases before the verdict work (cluster, warm-up, write, read-back,
oracle equality, sealed containers, counters, a JAX-free parent)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--block-mb", "2", "--container-mb", "1"]


def _run(argv, cwd=REPO, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         *argv], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    return out, lines


@pytest.fixture
def checkout(tmp_path):
    """The script and the package in a directory of their own: the script
    rebuilds the native library of its checkout (``make -B``), and the
    checkout's own library is the one every other test process loads, so
    the rebuild must not rewrite it under them."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    shutil.copytree(os.path.join(REPO, "hdrf_tpu"), tmp_path / "hdrf_tpu",
                    ignore=shutil.ignore_patterns("__pycache__", "*.so"))
    return tmp_path


def _run_in(checkout, argv):
    return _run(argv, cwd=str(checkout),
                script=str(checkout / "chip_smoke.py"))


def test_rehearsal_runs_every_phase_then_fails_on_the_platform(checkout):
    out, lines = _run_in(checkout, TINY + ["--worker-backend", "native"])
    assert out.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and "not 'tpu'" in last["error"]
    notes = {d["phase"]: d for d in map(json.loads, lines[:-1])}
    assert {"config", "oracle", "worker", "warm", "write_read", "containers",
            "device", "legitimate", "parent"} <= set(notes)
    assert notes["write_read"]["index"]["unique_chunks"] == \
        notes["oracle"]["unique_chunks"]
    assert notes["containers"]["sealed"] >= 2
    assert notes["parent"]["jax_backends"] == []
    assert all(v == 0 for v in notes["device"]["give_way"].values())
    assert not any('"ok": true' in ln for ln in lines)


def test_no_accelerator_means_the_worker_refuses_and_the_smoke_fails(
        checkout):
    out, lines = _run_in(checkout, TINY)   # the default worker backend: tpu
    assert out.returncode != 0
    assert json.loads(lines[-1])["ok"] is False
    assert "--backend tpu but JAX reports platform 'cpu'" in out.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out, lines = _run([], cwd=str(tmp_path),
                      script=str(tmp_path / "chip_smoke.py"))
    assert out.returncode != 0
    assert json.loads(lines[-1])["ok"] is False


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and code sets nothing; unset, the
    cache is <checkout>/.jax_cache — and a compile lands there."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    code = ("from hdrf_tpu.utils import device_env; "
            "import jax, jax.numpy as jnp; "
            "d = device_env.enable_compile_cache(); "
            "jax.jit(lambda a: a * 3 + 1)(jnp.arange(7)).block_until_ready(); "
            "print(d); print(jax.config.jax_compilation_cache_dir); "
            "print(device_env.compile_seconds())")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    used, configured, secs = out.stdout.strip().splitlines()[-3:]
    assert used == configured == want
    assert os.listdir(want), "no cache entry was written"
    assert "jit(<lambda>)" in secs

"""The chip smoke's boot checks, on the CPU: ``chip_smoke.py`` refuses to
run (nonzero exit, no result line) without a TPU or without the
repository beside it, its result checks fail on a degraded server or a
logit mismatch, the compile cache lands where the environment or the
checkout says, and a TPU host refuses more spawned workers than one."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_a_cpu_backend():
    out = _run_smoke(ROOT, ROOT / "chip_smoke.py")
    assert out.returncode != 0
    assert "JAX found no TPU" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_smoke_refuses_to_run_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert "src/ is not beside" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_smoke_device_check_names_the_platform(smoke):
    with pytest.raises(SystemExit, match="platform 'cpu'"):
        smoke.require_tpu(jax, 1)


def _stats(degraded=0, failed=0, lost=0, requests=8):
    return {"totals": {"requests": requests, "failed": failed,
                       "lost_requests": lost},
            "workers": {"w0": {"engine": {"robustness": {
                "degraded_batches": degraded}}}}}


@pytest.mark.parametrize("bad", [dict(degraded=1), dict(failed=1),
                                 dict(lost=1), dict(requests=7)])
def test_smoke_fails_on_fallback_or_lost_work(smoke, bad):
    smoke.check_server_stats(_stats(), 8)
    with pytest.raises(SystemExit, match="FAIL"):
        smoke.check_server_stats(_stats(**bad), 8)


def test_smoke_fails_on_logit_mismatch(smoke):
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((3, 1000)).astype(np.float32)
    smoke.check_logits("t", ref * (1 + 1e-7), ref)
    off = ref.copy()
    off[1, 0] += 2 * smoke.RTOL * np.abs(ref[1]).max()
    with pytest.raises(SystemExit, match="image 1"):
        smoke.check_logits("t", off, ref)
    swapped = ref.copy()
    top = int(ref[2].argmax())
    swapped[2, top] = ref[2].min()              # within no tolerance
    with pytest.raises(SystemExit, match="image 2"):
        smoke.check_logits("t", swapped, ref)
    nan = ref.copy()
    nan[0, 5] = np.nan
    with pytest.raises(SystemExit, match="non-finite"):
        smoke.check_logits("t", nan, ref)


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.CACHE_ENV)
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_tpu_host_refuses_more_spawned_workers_than_one(monkeypatch):
    from repro.launch import server
    monkeypatch.setattr(server, "visible_tpu_chips", lambda: 1)
    with pytest.raises(ValueError, match="a chip belongs to one process"):
        server.start_server("vgg16", n_workers=2, spawn=True)


def test_launcher_imports_initialize_no_backend():
    """A load generator's ``--boot`` parent imports the launchers; if that
    opened a backend, the parent would hold the chip its server needs."""
    prog = ("import sys; sys.path[:0] = ['src', '.']\n"
            "import repro.launch.server, repro.launch.serve\n"
            "import benchmarks.run_async_requests\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n"
            "print('NO_BACKEND')")
    out = subprocess.run([sys.executable, "-c", prog], cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert "NO_BACKEND" in out.stdout, out.stderr[-2000:]

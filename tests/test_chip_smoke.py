"""``chip_smoke.py`` refuses to run off the chip: with no TPU (and with
no repro package beside it) it exits nonzero after one line saying why,
before any phase runs and without printing a result."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)          # the script finds src/ itself
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_fails_fast_without_tpu(where, tmp_path):
    if where == "repo":
        proc = _run(ROOT, SMOKE)
        reason = "no TPU"
    else:
        script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
        proc = _run(tmp_path, str(script))
        reason = "repro package"
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0
    assert len(lines) == 1, proc.stdout
    assert lines[0].startswith("chip_smoke: FAIL: ") and reason in lines[0]
    assert '"ok"' not in proc.stdout and '"phase"' not in proc.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_dir(from_env, monkeypatch, tmp_path):
    """The cache directory is ``$JAX_COMPILATION_CACHE_DIR`` untouched
    when it is set, else the fixed ``<repo>/.jax_cache``."""
    import jax

    from repro.compile_cache import CACHE_ENV, enable_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    if from_env:
        monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    else:
        monkeypatch.delenv(CACHE_ENV, raising=False)
    try:
        path = enable_compile_cache()
        configured = jax.config.jax_compilation_cache_dir
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
    if from_env:
        assert path == str(tmp_path)
        assert configured == saved["jax_compilation_cache_dir"]
    else:
        assert path == os.path.join(ROOT, ".jax_cache")
        assert configured == path

"""``chip_smoke.py`` rehearsed on the CPU: its phases and host-reference
comparison at a tiny size, and its refusal to report a run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_agree_with_host_reference(chip_smoke):
    """A, B and C replay one churning stream with no reconstruction
    fallback; B equals A and all three match ``leastcost_python``."""
    stream = dict(rounds=6, warmup=3, base_rate=6.0, churn_period=3,
                  churn_down=2)
    res = chip_smoke.run_smoke(leaf_nodes=4, stream=stream,
                               ref_submissions=12, kernel_impl="ref")
    assert res["reference"]["C"].startswith("whole stream")


def test_compare_rejects_a_cost_mismatch(chip_smoke):
    rec = [{"active": [0, 1], "tickets": [(1.0, (0,), (0,)), (2.0, (1,), (1,))]}]
    bad = [{"active": [0, 1], "tickets": [(1.0, (0,), (0,)), (3.0, (1,), (1,))]}]
    chip_smoke.compare("X", rec, rec)
    with pytest.raises(AssertionError, match="costs differ"):
        chip_smoke.compare("X", rec, bad)
    with pytest.raises(AssertionError, match="admitted sets differ"):
        chip_smoke.compare("X", rec, [dict(bad[0], active=[0])])


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_tpu(where, tmp_path):
    """Under ``JAX_PLATFORMS=cpu``, or with no repository around it, the
    script exits non-zero and prints no result line."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins; otherwise the cache sits at a
    fixed path in the checkout, and every compile is kept."""
    import jax

    from repro.core.device import enable_compile_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache(str(tmp_path))
        want = tmp_path / "env" if env_dir else tmp_path / ".jax_cache"
        assert path == str(want)
        if not env_dir:
            assert jax.config.jax_compilation_cache_dir == str(want)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)

"""``chip_smoke.py`` on the CPU: its phases at smoke size, and its refusals.

The script itself runs only on a TPU.  Its phases take a config and
sizes, so here they run at each model's ``reduced_config()`` with the
kernels interpreted, which keeps the script from rotting between chip
runs.  The platform check is the script's ``main``; it must refuse the
CPU before any phase runs.
"""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from conftest import REPO_ROOT, subproc_env
from repro.configs.base import get_config

SCRIPT = pathlib.Path(REPO_ROOT) / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_reduced(smoke):
    out = smoke.serve_phase(get_config("starcoder2-7b", reduced=True),
                            slots=4, max_seq=256, n_requests=6,
                            prompt_range=(20, 150), prefix_len=48,
                            new_tokens=4, chunk=32)
    assert out["tokens_generated"] == 6 * 4
    assert out["prefill_tokens_saved"] > 0
    # f32 smoke weights: the cached decode matches the forward closely
    assert out["logp_rel_err"] < 1e-4


def test_train_phase_reduced(smoke):
    out = smoke.train_phase(get_config("mamba2-370m", reduced=True),
                            steps=3, batch=2, seq=64)
    assert len(out["losses"]) == 3 and len(out["step_s"]) == 3


def test_compile_phase_small(smoke):
    out = smoke.compile_phase(m=128, k=256, n=512, chain_shape=(64, 256))
    assert out["epilogue"]["kinds"] == ["epilogue"]
    assert out["chain"]["kinds"] == ["elementwise"]
    assert out["chain"]["dispatches"] == 1


def test_serve_check_fails_loudly(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "SERVE_LOGP_REL", -1.0)
    with pytest.raises(smoke.CheckFailed, match="LM.forward"):
        smoke.serve_phase(get_config("starcoder2-7b", reduced=True),
                          slots=2, max_seq=128, n_requests=4,
                          prompt_range=(20, 60), prefix_len=32,
                          new_tokens=2, chunk=32)


_MESH = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {root!r})
    import jax, json
    import chip_smoke
    from repro.configs.base import get_config
    out = chip_smoke.mesh_phase(get_config("mamba2-370m", reduced=True),
                                devices=jax.devices(), batch=8, seq=64)
    print("RESULT " + json.dumps(out["max_abs_diff"]))
""")


def test_mesh_phase_four_cpu_devices():
    r = subprocess.run([sys.executable, "-c", _MESH.format(root=REPO_ROOT)],
                       capture_output=True, text=True, env=subproc_env(),
                       timeout=600, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")][-1]
    diffs = json.loads(line[len("RESULT "):])
    assert set(diffs) == {"2x2/baseline", "2x2/fsdp"}
    assert max(diffs.values()) < 1e-4          # f32 smoke weights


def test_main_refuses_the_cpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_script_alone_refuses_to_run(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "checkout of the repository" in r.stderr


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        used = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert used == str(compile_cache.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == used
            assert compile_cache.CACHE_DIR.parent == pathlib.Path(REPO_ROOT)
        else:
            assert used == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()

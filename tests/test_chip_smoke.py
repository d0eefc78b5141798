"""CPU rehearsal of ``chip_smoke.py``: its phase functions at tiny sizes,
with the device gate left out (these runs are on the CPU), plus the gate
itself refusing the CPU."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402


@pytest.fixture
def chip_selection_branch(monkeypatch):
    """Make ``stc_compress_tree`` take the branch it takes on a TPU
    (histogram + refine) instead of the CPU's direct top-k shortcut."""
    import repro.core.distributed as dist
    monkeypatch.setattr(dist, "resolve_interpret", lambda _: False)


def test_gate_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_fails_without_the_repo(tmp_path):
    """Alone in a directory, the script exits non-zero with no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_model_round_rehearsal(chip_selection_branch):
    cfg = get_smoke_config("smollm-135m")
    res = cs.phase_model_round(cfg, batch=2, seq=64, rounds=3, lr=cs.LR,
                               seed=0)
    assert res["losses"][-1] < res["losses"][0]
    assert len(res["round_s"]) == 3


def test_flat_server_rehearsal():
    res = cs.phase_flat_server(rounds=2, n_clients=4, seed=0, n_train=2000)
    assert res["kernel"]["bits_up"] == res["jnp"]["bits_up"] > 0
    assert res["kernel"]["bits_down"] == res["jnp"]["bits_down"] > 0
    assert "HloModule" in res["encode_hlo"]


def test_mesh_round_rehearsal():
    """The ``--chips 4`` path on four virtual CPU devices."""
    code = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
import repro.core.distributed as dist
from repro.configs import get_smoke_config
dist.resolve_interpret = lambda _: False
res = cs.phase_mesh_round(get_smoke_config("smollm-135m"), n_clients=4,
                          batch=4, seq=32, rounds=2, lr=cs.LR, seed=0)
print("MESH_OK", res)
""".format(repo=REPO)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]


def test_compile_cache_placement(monkeypatch):
    """The environment's directory wins and is left to JAX; otherwise the
    cache sits at one fixed path in the checkout."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == REPO_CACHE_DIR
        assert REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_roofline_peaks_by_device_kind():
    """Peaks are looked up by the device kind JAX reports; an unknown kind
    raises instead of borrowing another chip's numbers."""
    from benchmarks import roofline
    assert roofline.chip_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="cpu"):
        roofline.chip_peaks("cpu")


def test_resolve_interpret_by_backend(monkeypatch):
    from repro.core import selection
    assert selection.resolve_interpret(True) is True
    assert selection.resolve_interpret(False) is False
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert selection.resolve_interpret(None) is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        selection.resolve_interpret(None)

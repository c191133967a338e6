"""chip_smoke.py: its result line, its refusal to run without a chip,
its phases at a tiny size on the CPU (rehearsals 1 and 2 of the
on-chip-measurement guide, kept as tests), and the compile cache's
place."""

import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(n=6000, d=3000, k=6, entities=60, seed=3)


def test_last_line_has_exactly_the_contract_keys():
    device = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    line = chip_smoke.last_line(device, 1)
    assert "\n" not in line
    rec = json.loads(line)
    assert set(rec) == {"ok", "device"}
    assert set(rec["device"]) == {"platform", "kind", "count"}
    assert rec == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_without_a_chip_it_fails_before_building_anything(tmp_path):
    """Under JAX_PLATFORMS=cpu the script exits non-zero at once: no
    result line, no phase started, nothing written."""
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert time.time() - t0 < 60
    assert proc.stdout == ""
    said = [json.loads(ln) for ln in proc.stderr.splitlines()
            if ln.startswith("{")]
    assert [r.get("phase") for r in said if "phase" in r] == ["device"]
    assert said[0]["platform"] == "cpu"
    assert any("no TPU" in r.get("error", "") for r in said)
    assert os.listdir(tmp_path) == []


def test_source_never_steers_the_platform():
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        src = f.read()
    assert "JAX_PLATFORMS" not in src and "jax_platforms" not in src


def test_driver_phase_on_cpu(tmp_path):
    """files → ETL → fit → model on disk → read back → scores, through
    the drivers' own ``run``, everything written under ``tmp_path``."""
    out = chip_smoke.run_drivers(root=str(tmp_path))
    chip_smoke.check_drivers(out)
    assert out["scoring"]["n"] == 400
    assert os.path.exists(
        tmp_path / "examples" / "out" / "config1" / "model")
    assert out["scoring"]["output_path"].startswith(str(tmp_path))


@pytest.fixture(scope="module")
def tiny_config5():
    return chip_smoke.make_config5_data(**TINY)


def test_config5_and_kernel_phases_on_cpu(tiny_config5):
    """Config 5 through GameEstimator.fit, then GRR against ELL at the
    trained coefficients — the GRR plan on its jnp reference path here,
    which is why main(), not the phase, insists on the Mosaic kernel."""
    train, valid = tiny_config5
    fit = chip_smoke.fit_config5(train, valid, sparse_layout="GRR")
    assert 0.6 < fit["auc"] < 1.0
    w = fit["model"].models["global"].coefficients.means
    assert np.asarray(w).shape == (TINY["d"] + 1,)
    out = chip_smoke.check_kernel(fit["estimator"], train, w)
    assert out["layout"] == "GRR"
    assert out["tpu_custom_call"] is False
    assert out["value_rel_diff"] <= chip_smoke.VALUE_RTOL
    assert out["grad_rel_diff"] <= chip_smoke.GRAD_RTOL

    # AUTO off a TPU resolves to ELL: the phase reports it, main fails.
    auto = chip_smoke.fit_config5(train, valid)
    assert chip_smoke.check_kernel(
        auto["estimator"], train, w)["layout"] == "ELL"


def test_mesh_phases_on_four_virtual_devices(tiny_config5):
    """The --chips 4 path on four of the forced CPU devices: the mesh
    fit agrees with the one-device fit and its batch is spread."""
    train, valid = tiny_config5
    one = chip_smoke.fit_config5(train, valid, sparse_layout="GRR")
    mesh = chip_smoke.fit_config5(train, valid, sparse_layout="GRR",
                                  n_devices=4)
    assert abs(mesh["auc"] - one["auc"]) <= chip_smoke.MESH_AUC_ATOL
    spread = chip_smoke.check_spread(mesh["estimator"], train, 4)
    assert spread["layout"] == "GRR"
    assert len({d["device"] for d in spread["devices"]}) == 4
    assert all(d["shard_bytes"] > 0 for d in spread["devices"])
    with pytest.raises(chip_smoke.SmokeFailure, match="devices"):
        chip_smoke.check_spread(mesh["estimator"], train, 8)


_PRINT_CACHE_DIR = (
    "import jax; from photon_ml_tpu.cache import enable_compilation_cache;"
    "before = jax.config.jax_compilation_cache_dir;"
    "print(before); print(enable_compilation_cache());"
    "print(jax.config.jax_compilation_cache_dir)")


def _cache_dirs(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", _PRINT_CACHE_DIR],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.split()


def test_compile_cache_dir_is_jaxs_own_when_the_environment_sets_it(
        tmp_path):
    env_dir = str(tmp_path / "from_env")
    assert _cache_dirs(env_dir) == [env_dir, env_dir, env_dir]


def test_compile_cache_dir_is_fixed_in_the_checkout_otherwise():
    fixed = os.path.join(REPO, ".jax_cache")
    first, second = _cache_dirs(None), _cache_dirs(None)
    assert first == second == ["None", fixed, fixed]

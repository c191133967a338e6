"""Distributed-objective tests on the simulated 8-device mesh.

The tier-2 "Spark local mode" analog (SURVEY.md §4): shard_map/psum code
paths exercised single-process on 8 virtual CPU devices.  Gates:
equality with the single-device objective, and an unchanged optimizer
converging on top of the distributed objective.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression

from photon_ml_tpu.data.batch import make_dense_batch, make_sparse_batch
from photon_ml_tpu.data.normalization import (
    NormalizationContext,
    NormalizationType,
    compute_normalization,
)
from photon_ml_tpu.data.statistics import compute_statistics
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optim import OptimizerConfig, lbfgs_solve, tron_solve
from photon_ml_tpu.parallel import (
    DistributedGLMObjective,
    data_parallel_mesh,
    padded_rows,
    shard_batch,
)
from photon_ml_tpu.utils.synthetic import make_a1a_like


@pytest.fixture(scope="module")
def mesh():
    m = data_parallel_mesh()
    assert m.devices.size == 8, "conftest must force 8 CPU devices"
    return m


def _problem(rng, n=333, d=12, norm=None):
    x = rng.normal(0, 1, (n, d))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    w = rng.normal(0, 0.5, d).astype(np.float32)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(0.8),
        norm=norm or NormalizationContext.identity(),
    )
    return x, y, jnp.asarray(w), obj


def test_distributed_equals_local(rng, mesh):
    x, y, w, obj = _problem(rng)
    n = x.shape[0]
    local = make_dense_batch(x, y)
    sharded_host = make_dense_batch(x, y, pad_to=padded_rows(n, 8))
    sharded = shard_batch(sharded_host, mesh)
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)

    v_l, g_l = obj.value_and_gradient(w, local)
    v_d, g_d = dist.value_and_gradient(w, sharded)
    np.testing.assert_allclose(v_d, v_l, rtol=1e-6)
    np.testing.assert_allclose(g_d, g_l, rtol=1e-5, atol=1e-5)

    np.testing.assert_allclose(dist.value(w, sharded), obj.value(w, local),
                               rtol=1e-6)

    v = jnp.asarray(np.asarray(rng.normal(0, 1, x.shape[1]), np.float32))
    np.testing.assert_allclose(
        dist.hessian_vector(w, v, sharded),
        obj.hessian_vector(w, v, local),
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        dist.hessian_diagonal(w, sharded),
        obj.hessian_diagonal(w, local),
        rtol=1e-5, atol=1e-5,
    )


def test_distributed_with_normalization_equals_local(rng, mesh):
    x, y, w, _ = _problem(rng, n=200, d=6)
    # Shift+factor normalization stresses the linearity argument (Σr term).
    local = make_dense_batch(x, y)
    stats = compute_statistics(local)
    norm = compute_normalization(
        stats.mean, stats.std, stats.max_abs, NormalizationType.STANDARDIZATION
    )
    obj = GLMObjective(
        loss=losses.LOGISTIC, reg=RegularizationContext.l2(0.5), norm=norm
    )
    sharded = shard_batch(make_dense_batch(x, y, pad_to=padded_rows(200, 8)),
                          mesh)
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)
    v_l, g_l = obj.value_and_gradient(w, local)
    v_d, g_d = dist.value_and_gradient(w, sharded)
    np.testing.assert_allclose(v_d, v_l, rtol=1e-6)
    np.testing.assert_allclose(g_d, g_l, rtol=1e-5, atol=1e-5)


def test_sparse_distributed_equals_local(rng, mesh):
    rows, labels, _ = make_a1a_like(n=500, seed=3)
    dim = 123
    local = make_sparse_batch(rows, dim, labels)
    sharded = shard_batch(
        make_sparse_batch(rows, dim, labels, pad_to=padded_rows(500, 8)), mesh
    )
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(1.0),
        norm=NormalizationContext.identity(),
    )
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)
    w = jnp.asarray(np.random.default_rng(0).normal(0, 0.3, dim), jnp.float32)
    v_l, g_l = obj.value_and_gradient(w, local)
    v_d, g_d = dist.value_and_gradient(w, sharded)
    np.testing.assert_allclose(v_d, v_l, rtol=1e-6)
    np.testing.assert_allclose(g_d, g_l, rtol=1e-5, atol=1e-4)


def test_lbfgs_on_distributed_objective_matches_sklearn(rng, mesh):
    """The north-star composition: unchanged L-BFGS over the shard_mapped
    objective — the reference's broadcast/treeAggregate loop as one jitted
    program."""
    n, d, l2 = 400, 10, 1.0
    x = rng.normal(0, 1, (n, d))
    p = 1 / (1 + np.exp(-(x @ rng.normal(0, 1, d))))
    y = (rng.uniform(size=n) < p).astype(np.float64)
    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(l2),
        norm=NormalizationContext.identity(),
    )
    sharded = shard_batch(make_dense_batch(x, y, pad_to=padded_rows(n, 8)),
                          mesh)
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)
    res = jax.jit(
        lambda b, w0: lbfgs_solve(
            lambda w: dist.value_and_gradient(w, b), w0,
            OptimizerConfig(max_iters=200, tolerance=1e-6),
        )
    )(sharded, jnp.zeros(d, jnp.float32))
    assert bool(res.converged)
    clf = LogisticRegression(C=1.0 / l2, fit_intercept=False, tol=1e-10,
                             max_iter=10000)
    clf.fit(x, y)
    np.testing.assert_allclose(res.w, clf.coef_.ravel(), rtol=5e-3, atol=5e-4)


def test_tron_on_distributed_objective(rng, mesh):
    n, d = 320, 8
    x = rng.normal(0, 1, (n, d))
    y = x @ rng.normal(0, 1, d) + rng.normal(0, 0.1, n)
    obj = GLMObjective(
        loss=losses.SQUARED,
        reg=RegularizationContext.l2(2.0),
        norm=NormalizationContext.identity(),
    )
    sharded = shard_batch(make_dense_batch(x, y, pad_to=padded_rows(n, 8)),
                          mesh)
    dist = DistributedGLMObjective(objective=obj, mesh=mesh)
    res = jax.jit(
        lambda b, w0: tron_solve(
            lambda w: dist.value_and_gradient(w, b),
            lambda w, v: dist.hessian_vector(w, v, b),
            w0, OptimizerConfig(max_iters=100, tolerance=1e-6),
        )
    )(sharded, jnp.zeros(d, jnp.float32))
    w_ref = np.linalg.solve(x.T @ x + 2.0 * np.eye(d), x.T @ y)
    np.testing.assert_allclose(res.w, w_ref, rtol=1e-3, atol=1e-4)


def test_shard_batch_requires_divisible_rows(rng, mesh):
    batch = make_dense_batch(rng.normal(0, 1, (13, 3)), np.zeros(13))
    with pytest.raises(ValueError, match="not divisible"):
        shard_batch(batch, mesh)


_TWO_PROC_WORKER = r'''
import os, sys
sys.path.insert(0, os.environ["PML_REPO"])
os.environ["JAX_PLATFORMS"] = "cpu"   # before any backend init
import jax

from photon_ml_tpu.cli.game_training_driver import distributed_init_from_env
distributed_init_from_env()           # the driver's multi-host entry

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.data.batch import DenseBatch, make_dense_batch
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.parallel import DistributedGLMObjective
from photon_ml_tpu.parallel.mesh import data_parallel_mesh

assert jax.process_count() == 2, jax.process_count()
pid = jax.process_index()

# Identical synthetic data on both processes; each holds half the rows.
rng = np.random.default_rng(0)
n, d = 64, 5
x = rng.normal(0, 1, (n, d)).astype(np.float32)
y = (rng.uniform(size=n) < 0.5).astype(np.float32)
full = make_dense_batch(x, y)

mesh = data_parallel_mesh()          # both processes' devices
assert mesh.devices.size == 2
per = n // 2
sharding = NamedSharding(mesh, P("data"))
dev0 = jax.local_devices()[0]

def place(a):
    a = np.asarray(a)
    local = jnp.asarray(a[pid * per:(pid + 1) * per])
    return jax.make_array_from_single_device_arrays(
        a.shape, sharding, [jax.device_put(local, dev0)])

batch = jax.tree.map(place, full)
obj = GLMObjective(loss=losses.LOGISTIC,
                   reg=RegularizationContext.l2(0.5),
                   norm=NormalizationContext.identity())
dist = DistributedGLMObjective(objective=obj, mesh=mesh)
w_np = rng.normal(0, 0.3, d).astype(np.float32)
w = jax.make_array_from_single_device_arrays(
    (d,), NamedSharding(mesh, P()),
    [jax.device_put(jnp.asarray(w_np), dev0)])

v, g = dist.value_and_gradient(w, batch)     # psum ACROSS processes
v_ref, g_ref = obj.value_and_gradient(jnp.asarray(w_np), full)
np.testing.assert_allclose(float(v), float(v_ref), rtol=1e-5)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                           rtol=1e-4, atol=1e-5)
print(f"TWO_PROC_OK pid={pid} value={float(v):.6f}", flush=True)
'''


def test_two_process_psum_objective(tmp_path):
    """Round-3 verdict #5: a REAL cross-process collective.  Two
    subprocesses join via jax.distributed.initialize (the driver's
    distributed_init path) and one psum-reduced objective step runs
    across them, matching the single-process full-batch value."""
    import os
    import socket
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text(_TWO_PROC_WORKER)
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update({
            "PML_REPO": os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))),
            "JAX_COORDINATOR_ADDRESS": f"localhost:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(pid),
            "XLA_FLAGS": "",  # no virtual-device forcing in workers
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out)
    from distributed_helpers import skip_if_multiprocess_wall

    skip_if_multiprocess_wall(outs)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-3000:]}"
        assert "TWO_PROC_OK" in out, out[-3000:]
    # Both processes saw the SAME psum'd value (replicated output).
    v0 = [ln for ln in outs[0].splitlines() if "TWO_PROC_OK" in ln][0]
    v1 = [ln for ln in outs[1].splitlines() if "TWO_PROC_OK" in ln][0]
    assert v0.split("value=")[1] == v1.split("value=")[1]

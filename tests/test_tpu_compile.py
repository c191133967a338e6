"""The main path's programs compile for a TPU v5e that is described, not
attached, at the real shapes of ``chip_smoke.py``'s config-5 run.

The TPU's compiler is installed beside the CPU backend and compiles for
a topology description, so what it would refuse on the chip (a tile
that does not fit VMEM, an unaligned slice, a kernel that cannot be
partitioned, a loop carry whose dtype drifts with x64 off) is refused
here, at no chip time.  Nothing runs: these say nothing about results
or times.

All such compiles live in THIS file: only one process may load the
TPU's library, the topology is described inside a module-scoped fixture
(never at import, so every xdist worker collects the same tests), and a
second file could land on another worker whose fixture would skip.

Shapes: the GRR planner's own choices for the smoke's data
(``examples/kdd_scale.py`` synthesize(n=10⁶, d=10⁵, k=10, 10⁵ + 10⁵
power-law entities), 980,000 training rows, intercept → dim 100,001),
read off a host-side plan build and the entity grouping.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from photon_ml_tpu.data.batch import SparseBatch
from photon_ml_tpu.data.grr import GrrDirection, GrrPair
from photon_ml_tpu.ops import grr_kernel
from photon_ml_tpu.ops.grr_kernel import DENSE_B, TILE

N_TRAIN, DIM = 980_000, 100_001
V5E_HBM_BYTES = 16 * 2**30

# One plan level: (dense_grid, n_supertiles, cap, n_gw, n_ow, n_spill).
# One device: the whole training set in one plan.
ROW_LEVELS = [(True, 1680, 4, 7, 240, 0), (True, 1680, 4, 7, 240, 0),
              (False, 241, 4, 7, 240, 0), (False, 240, 4, 7, 240, 0),
              (False, 240, 4, 7, 240, 41504)]
COL_LEVELS = [(True, 1680, 4, 60, 25, 0), (True, 3120, 8, 60, 49, 0),
              (False, 189, 8, 60, 49, 4168)]
MID_LEVELS = [(False, 120, 64, 60, 2, 24)]
N_HOT, N_MID = 49, 307
# Four devices: per-shard plans over 245,000 rows each; leaf shapes are
# GLOBAL (four shards concatenated on the leading axis, P("data")).
N_SHARDS = 4
SHARD_ROW_LEVELS = [(True, 1680, 4, 7, 60, 0),
                    (True, 1680, 4, 7, 60, 555072)]
SHARD_COL_LEVELS = [(True, 1680, 4, 15, 25, 0),
                    (True, 3120, 8, 15, 49, 94560)]
SHARD_MID_LEVELS = [(False, 120, 64, 15, 2, 64)]
# Per-user random effect, p = 2: one of its six size buckets (entities,
# capacity) — [(15157, 4), (75069, 16), (9260, 64), (387, 256),
# (17, 1024), (1, 4096)].  The compile time of this program grows faster
# than the entity count (3.5 s here at 9,260 entities, 65-95 s at
# 75,069, which also compiles), so the test takes a mid-sized one; and
# one of ``game5-kdd12``'s, over the bound past which a bucket is solved
# 16,384 entities at a time (9 s; in one shot 148 s).
USER_BUCKETS = [(9260, 64), (182_062, 4)]
# ``glmix-kdd12``'s per-user effect over a sparse shard, each entity in
# the subspace of the columns it saw: its bucket of capacity 64, padded
# to the bucket's widest subspace (entities, capacity, width): 1.85 GB,
# solved in one shot (4.5 s to compile).
PROJECTED_BUCKET = (18_515, 64, 391)


@pytest.fixture(scope="module")
def topo():
    """A described 2x2 v5e, with x64 off (as on the chip) and the
    persistent compilation cache off (an entry written for a described
    chip cannot be read back without one; the next run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        if old_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("data",))


@pytest.fixture
def on_tpu(monkeypatch):
    """``GrrDirection.contract`` asks ``jax.default_backend()`` and
    would take its CPU branch here; steer it to the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(sharding):
    """``leaf(shape, dtype)``: an array that is described, not held."""
    def leaf(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return leaf


def _direction(levels, table_len, n_segments, sharding):
    """Abstract ``GrrDirection`` chain (level 1 → overflow levels)."""
    leaf = _abstract(sharding)
    out = None
    for dense, n_st, cap, n_gw, n_ow, n_spill in reversed(levels):
        tiles = (n_st, TILE, TILE)
        n_idx = n_st // DENSE_B if dense else n_st
        n_run = 0 if dense else n_st
        out = GrrDirection(
            g1=leaf(tiles, jnp.int8), g2=leaf(tiles, jnp.int8),
            g3=leaf(tiles, jnp.int8), vals=leaf(tiles, jnp.float32),
            gw_of_st=leaf((n_idx,), jnp.int32),
            ow_of_st=leaf((n_run,), jnp.int32),
            first_of_ow=leaf((n_run,), jnp.int32),
            spill_idx=leaf((n_spill,), jnp.int32),
            spill_seg=leaf((n_spill,), jnp.int32),
            spill_val=leaf((n_spill,), jnp.float32),
            table_len=table_len, n_segments=n_segments, cap=cap,
            n_gw=n_gw, n_ow=n_ow, dense_grid=dense, overflow=out)
    return out


def _grr_batch(sharding, n_shards=1):
    """Abstract GRR ``SparseBatch`` as the estimator builds it for the
    smoke (``keep_ell=False``: zero-width ELL placeholders)."""
    leaf = _abstract(sharding)
    rows = N_TRAIN // n_shards
    row, col, mid = ((ROW_LEVELS, COL_LEVELS, MID_LEVELS) if n_shards == 1
                     else (SHARD_ROW_LEVELS, SHARD_COL_LEVELS,
                           SHARD_MID_LEVELS))
    pair = GrrPair(
        row_dir=_direction(row, DIM, rows, sharding),
        col_dir=_direction(col, rows, DIM, sharding),
        hot_ids=leaf((N_HOT * n_shards,), jnp.int32),
        x_hot=leaf((N_TRAIN, N_HOT)),
        mid_ids=leaf((N_MID * n_shards,), jnp.int32),
        col_mid=_direction(mid, rows, N_MID, sharding))
    return SparseBatch(
        values=leaf((N_TRAIN, 0)), col_ids=leaf((N_TRAIN, 0), jnp.int32),
        labels=leaf((N_TRAIN,)), weights=leaf((N_TRAIN,)),
        offsets=leaf((N_TRAIN,)), mask=leaf((N_TRAIN,)),
        dim=DIM, grr=pair)


def _objective(sharding, dim=DIM, intercept_index=DIM - 1):
    """The estimator's logistic L2 objective, leaves abstract."""
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import (
        RegularizationContext,
        exclude_intercept_mask,
    )

    obj = GLMObjective(
        loss=losses.LOGISTIC,
        reg=RegularizationContext.l2(
            1.0, exclude_intercept_mask(dim, intercept_index)),
        norm=NormalizationContext.identity())
    leaf = _abstract(sharding)
    return jax.tree.map(lambda a: leaf(a.shape, a.dtype), obj)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("n_st,cap,n_gw", [
    (1680, 4, 7), (1680, 4, 60), (3120, 8, 60),      # one device
    (420, 4, 15), (780, 8, 15),                      # one shard of four
])
def test_dense_grid_kernel_compiles(one_chip, n_st, cap, n_gw):
    leaf = _abstract(one_chip)
    tiles = (n_st, TILE, TILE)
    compiled = jax.jit(
        grr_kernel.grr_contract_kernel_dense,
        static_argnames=("n_ow_p", "cap"),
    ).lower(
        leaf((n_gw, TILE, TILE), jnp.float32), leaf(tiles, jnp.int8),
        leaf(tiles, jnp.int8), leaf(tiles, jnp.int8),
        leaf(tiles, jnp.float32), leaf((n_st // DENSE_B,), jnp.int32),
        n_ow_p=n_st // n_gw, cap=cap,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_st,cap,n_gw,n_ow", [
    (241, 4, 7, 240), (189, 8, 60, 49), (120, 64, 60, 2),
])
def test_revisiting_kernel_compiles(one_chip, n_st, cap, n_gw, n_ow):
    leaf = _abstract(one_chip)
    tiles = (n_st, TILE, TILE)
    compiled = jax.jit(
        grr_kernel.grr_contract_kernel, static_argnames=("n_ow", "cap"),
    ).lower(
        leaf((n_gw, TILE, TILE), jnp.float32), leaf(tiles, jnp.int8),
        leaf(tiles, jnp.int8), leaf(tiles, jnp.int8),
        leaf(tiles, jnp.float32), leaf((n_st,), jnp.int32),
        leaf((n_st,), jnp.int32), leaf((n_st,), jnp.int32),
        n_ow=n_ow, cap=cap,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fixed_effect_value_and_gradient_compiles(one_chip, on_tpu):
    """The whole fused value+gradient step over the real GRR pair."""
    compiled = jax.jit(
        lambda obj, w, batch: obj.value_and_gradient(w, batch)
    ).lower(_objective(one_chip), _abstract(one_chip)((DIM,)),
            _grr_batch(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


# ``game5-kdd12``'s fixed effect (ISSUE 30): the full width, its planned
# columns, training rows and the tail's entries.
KDD12_WIDTH, KDD12_PLANNED = 54_686_453, 191_182
KDD12_ROWS, KDD12_TAIL_NNZ = 3_185_000, 16_468_262


def _tailed_batch(sharding):
    """Abstract ``game5-kdd12`` batch: plans over the planned columns,
    a COO tail, the full width in ``w`` and the gradient."""
    from photon_ml_tpu.data.grr import GrrTail

    leaf = _abstract(sharding)
    width, planned, rows, tail_nnz = (KDD12_WIDTH, KDD12_PLANNED,
                                      KDD12_ROWS, KDD12_TAIL_NNZ)
    pair = GrrPair(
        row_dir=_direction([(True, 9360, 4, 12, 778, 0),
                            (False, 778, 4, 12, 778, 125_456)],
                           planned, rows, sharding),
        col_dir=_direction([(True, 9360, 4, 195, 47, 383_528)],
                           rows, planned, sharding),
        hot_ids=leaf((30,), jnp.int32), x_hot=leaf((rows, 30)),
        mid_ids=leaf((41,), jnp.int32),
        col_mid=_direction([(False, 195, 64, 195, 1, 8)], rows, 41,
                           sharding),
        planned_ids=leaf((planned,), jnp.int32),
        tail=GrrTail(
            row_seg=leaf((tail_nnz,), jnp.int32),
            row_idx=leaf((tail_nnz,), jnp.int32), row_val=leaf((tail_nnz,)),
            col_seg=leaf((tail_nnz,), jnp.int32),
            col_idx=leaf((tail_nnz,), jnp.int32), col_val=leaf((tail_nnz,)),
            n_rows=rows, dim=width),
        width=width)
    return SparseBatch(
        values=leaf((rows, 0)), col_ids=leaf((rows, 0), jnp.int32),
        labels=leaf((rows,)), weights=leaf((rows,)), offsets=leaf((rows,)),
        mask=leaf((rows,)), dim=width, grr=pair)


def test_tailed_value_and_gradient_compiles_at_the_public_width(one_chip,
                                                               on_tpu):
    """``game5-kdd12``'s pair (ISSUE 30): plans over the 191,182 planned
    columns, a COO tail of 16,468,262 entries, the full width of
    54,686,453 in ``w`` and the gradient.  The tail's two contractions
    carry their named scopes in the compiled program's metadata (the
    v5e's trace drops it: the benchmark's readers hold on to the tail's
    length instead)."""
    width = KDD12_WIDTH
    compiled = jax.jit(
        lambda obj, w, b: obj.value_and_gradient(w, b)
    ).lower(_objective(one_chip, width, width - 1),
            _abstract(one_chip)((width,)), _tailed_batch(one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "photon/fe_tail_dot/" in text and "photon/fe_tail_tdot/" in text
    assert f"f32[{KDD12_TAIL_NNZ}]" in text
    _assert_fits(compiled)


def _segment_sums(jaxpr, loops=0):
    """(enclosing ``while`` loops, name stack) of every segment-sum
    (``scatter-add``) in ``jaxpr`` and whatever it calls."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            found.append((loops, str(eqn.source_info.name_stack)))
        inner = loops + (eqn.primitive.name == "while")
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _segment_sums(sub, inner)
    return found


def test_tailed_solve_contracts_the_tail_once_an_iteration(one_chip, on_tpu):
    """The fixed-effect solve of ``game5-kdd12`` (ISSUE 31): the line
    search walks the margins, so the iteration's body holds one X.d and
    one X^T r of the tail (each one segment-sum under its scope) and
    the line search's loop, nested in it, holds neither; and the whole
    solve, S and Y and their second copies included, fits the chip."""
    from photon_ml_tpu.game.coordinates import (
        _fixed_train_local_donating,
        _fixed_train_local_impl,
    )
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    leaf = _abstract(one_chip)
    static = (OptimizerType.LBFGS, OptimizerConfig(max_iters=30), False)
    args = (_objective(one_chip, KDD12_WIDTH, KDD12_WIDTH - 1),
            _tailed_batch(one_chip), leaf((KDD12_ROWS,)), None, None,
            leaf((KDD12_WIDTH,)))
    by_scope = {}
    for loops, stack in _segment_sums(jax.make_jaxpr(
            lambda *a: _fixed_train_local_impl(*static, *a))(*args).jaxpr):
        for scope in ("photon/fe_tail_dot", "photon/fe_tail_tdot"):
            if stack.endswith(scope):
                by_scope.setdefault(scope, []).append(loops)
    # before the loop (the start's margins and gradient), then once an
    # iteration; nothing two loops deep
    assert by_scope == {"photon/fe_tail_dot": [0, 1],
                        "photon/fe_tail_tdot": [0, 1]}

    compiled = _fixed_train_local_donating.lower(*static, *args).compile()
    text = compiled.as_text()
    assert "/while/body/photon/fe_tail_dot/" in text
    assert "/while/body/photon/fe_tail_tdot/" in text
    assert "/while/body/while/body/photon/fe_tail" not in text
    assert "tpu_custom_call" in text
    _assert_fits(compiled)


def test_owlqn_poisson_solve_compiles_at_the_public_width(one_chip, on_tpu):
    """The fixed-effect solve of ``poisson-enet-kdd12`` (ISSUE 37): the
    Poisson loss under an elastic net, so OWL-QN: the tail's X.w in the
    line search's loop, in the branch of a trial the orthant projection
    clipped (it contracts its own point), and X.d in the branch of one
    it clipped nothing of, under the test of whether the search has it
    yet; and one X^T r an iteration from the margins
    the last trial kept, with no X.w of the accepted point's own; an L1
    vector of the full width beside ``w``; and the whole solve fits the
    chip."""
    from photon_ml_tpu.data.normalization import NormalizationContext
    from photon_ml_tpu.game.coordinates import _fixed_train_local_donating
    from photon_ml_tpu.ops import losses
    from photon_ml_tpu.ops.objective import GLMObjective
    from photon_ml_tpu.ops.regularization import (
        RegularizationContext,
        exclude_intercept_mask,
    )
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    leaf = _abstract(one_chip)
    objective = jax.tree.map(
        lambda a: leaf(a.shape, a.dtype),
        GLMObjective(
            loss=losses.POISSON,
            reg=RegularizationContext.elastic_net(
                1.0, 0.5, exclude_intercept_mask(KDD12_WIDTH,
                                                 KDD12_WIDTH - 1)),
            norm=NormalizationContext.identity()))
    compiled = _fixed_train_local_donating.lower(
        OptimizerType.LBFGS, OptimizerConfig(max_iters=30), True,
        objective, _tailed_batch(one_chip), leaf((KDD12_ROWS,)), None, None,
        leaf((KDD12_WIDTH,))).compile()
    text = compiled.as_text()
    trial = "/while/body/while/body/cond/"
    assert trial + "branch_1_fun/photon/fe_tail_dot/" in text
    assert trial + "branch_0_fun/cond/branch_0_fun/photon/fe_tail_dot/" \
        in text
    assert "/while/body/photon/fe_tail_tdot/" in text
    assert "/while/body/while/body/photon/fe_tail_tdot/" not in text
    assert "tpu_custom_call" in text
    _assert_fits(compiled)


def test_fixed_effect_solve_compiles(one_chip, on_tpu):
    """The program the coordinate really dispatches: the whole L-BFGS
    solve (while_loop carries included) with the warm start donated."""
    from photon_ml_tpu.game.coordinates import _fixed_train_local_donating
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    leaf = _abstract(one_chip)
    compiled = _fixed_train_local_donating.lower(
        OptimizerType.LBFGS, OptimizerConfig(max_iters=30), False,
        _objective(one_chip), _grr_batch(one_chip), leaf((N_TRAIN,)),
        None, None, leaf((DIM,)),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)


@pytest.mark.parametrize("bucket", USER_BUCKETS + [PROJECTED_BUCKET],
                         ids=str)
def test_random_effect_bucket_solve_compiles(one_chip, bucket):
    """The vmapped per-entity solve over a per-user bucket
    [E_b, cap_b, p]: p = 2 for the dense cells, the subspace's width
    for the projected one, whose contractions reach the matrix unit and
    must multiply in float32 there (no bfloat16 in the program)."""
    from photon_ml_tpu.game.coordinates import _re_train_donating
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    leaf = _abstract(one_chip)
    *bucket, p = bucket if len(bucket) == 3 else (*bucket, 2)
    buckets = [tuple(bucket)]
    n_b = [min(e * c, N_TRAIN) for e, c in buckets]
    blocks = (
        [leaf((e, c, p)) for e, c in buckets],
        [leaf((e, c)) for e, c in buckets],
        [leaf((e, c)) for e, c in buckets],
        [leaf((e, c)) for e, c in buckets],
        [leaf((n,), jnp.int32) for n in n_b],
        [leaf((n,), jnp.int32) for n in n_b],
        [leaf((n,), jnp.int32) for n in n_b],
    )
    compiled = _re_train_donating.lower(
        OptimizerType.LBFGS, OptimizerConfig(max_iters=10), False,
        _objective(one_chip, dim=1, intercept_index=None), blocks,
        leaf((N_TRAIN,)), [leaf((e, p)) for e, _ in buckets],
    ).compile()
    _assert_fits(compiled)
    if p > 2:
        assert "convolution" in compiled.as_text()
        assert "bf16" not in compiled.as_text()


@pytest.mark.parametrize("program", ["solve", "variances"])
def test_random_effect_bucket_toward_a_prior_compiles(one_chip, program):
    """A daily refresh's per-user bucket (``glmix-kdd12``'s of
    capacity 64): every entity's Gaussian prior toward yesterday's
    posterior, [E_b, p_b] blocks of means and precisions beside its
    coefficients, in the vmapped solve and in its SIMPLE variances."""
    from photon_ml_tpu.game.coordinates import (
        _re_train_donating,
        _re_variances,
    )
    from photon_ml_tpu.ops.prior import EntityPriors
    from photon_ml_tpu.optim.base import OptimizerConfig, OptimizerType

    leaf = _abstract(one_chip)
    e, c, p = PROJECTED_BUCKET
    blocks = ([leaf((e, c, p))], [leaf((e, c))], [leaf((e, c))],
              [leaf((e, c))], *[[leaf((e * c,), jnp.int32)]] * 3)
    objective = _objective(one_chip, dim=1, intercept_index=None)
    prior = EntityPriors(means=[leaf((e, p))], precisions=[leaf((e, p))],
                         weight=leaf(()))
    if program == "solve":
        lowered = _re_train_donating.lower(
            OptimizerType.LBFGS, OptimizerConfig(max_iters=10), False,
            objective, blocks, leaf((N_TRAIN,)), [leaf((e, p))], prior)
    else:
        lowered = _re_variances.lower(objective, blocks, [leaf((e, p))],
                                      leaf((N_TRAIN,)), prior)
    compiled = lowered.compile()
    _assert_fits(compiled)
    assert "bf16" not in compiled.as_text()


def test_sharded_grr_step_compiles_on_four_chips(four_chips, on_tpu):
    """The shard_mapped value+gradient over example-sharded GRR plans:
    the kernel under shard_map, partials met by one all-reduce."""
    from photon_ml_tpu.parallel import DistributedGLMObjective

    sharded = NamedSharding(four_chips, P("data"))
    replicated = NamedSharding(four_chips, P())
    dist = DistributedGLMObjective(
        objective=_objective(replicated), mesh=four_chips)
    compiled = jax.jit(
        lambda d, w, batch: d.value_and_gradient(w, batch)
    ).lower(dist, _abstract(replicated)((DIM,)),
            _grr_batch(sharded, N_SHARDS)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    _assert_fits(compiled)

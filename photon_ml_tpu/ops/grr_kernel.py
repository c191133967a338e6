"""The GRR (gather-route-reduce) Pallas kernel: sparse contraction at
vector speed on TPU.

This is the compute half of the framework's sparse hot loop — the
replacement for XLA's scalar gather/scatter lowering of
``out[s] = Σ_e val_e · table[idx_e]`` (measured ~1 GB/s on v5e, ~800×
off the HBM roofline).  The layout half (how nonzeros are blocked,
placed, and routed) lives in ``data.grr``; this module only executes
the precomputed plan.

Per supertile (16384 nonzero slots, one grid step):

1. **gather** — one lane-gather ``take_along_axis(W, G1, axis=1)``
   pulls each slot's table value out of the supertile's 128×128 VMEM
   window (row s of the window IS ``table[gw·WIN + 128·s ...]`` — the
   ETL placed every element in the sublane matching its table index's
   window sub-tile, and ``G1`` carries the lane residue, pre-composed
   with the route's first stage; no window transpose needed).
2. **route** — two more lane-gathers with a transpose between
   (the classical 3-stage Clos form, switches precomputed by König
   edge-coloring — ``ops.crossbar``) move every product to its
   reduction slot.
3. **reduce** — capacity planes are contiguous 16-row blocks, so the
   per-segment sum is CAP static-slice adds; the [GROUP,128] partial
   accumulates into the output window, which Pallas keeps resident in
   VMEM across the supertiles of one segment-window run (grid ordered
   by (ow, gw); ``first_of_ow`` marks run starts).

The only dynamic-indexing primitive used is ``tpu.DynamicGather`` via
``take_along_axis`` on equal [128,128] shapes — the one fast irregular
data-movement op the TensorCore has.  Measured on v5e: ~7 Gslot/s
(vs ~0.06 Gnnz/s for the XLA scatter path).

Reference counterpart: the aggregator fold + treeAggregate hot loop
(SURVEY.md §2.2 [mount unavailable]); the reference's JVM scattered
writes have no TPU equivalent, hence this design.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

TILE = 128
SLOTS = TILE * TILE        # nonzero slots per supertile


def grr_contract_kernel(
    table_t: Array,        # [n_gw,128,128] f32 — windows, row s = table[gw*WIN+128s...]
    g1: Array,             # [n_st, 128, 128] i8 — gather ∘ route stage 1
    g2: Array,             # [n_st, 128, 128] i8 — route stage 2 (transposed)
    g3: Array,             # [n_st, 128, 128] i8 — route stage 3
    vals: Array,           # [n_st, 128, 128] f32 — values in final slot order
    gw_of_st: Array,       # [n_st] i32 — table-window id per supertile
    ow_of_st: Array,       # [n_st] i32 — output-window id per supertile
    first_of_ow: Array,    # [n_st] i32 — 1 at the first supertile of an ow run
    n_ow: int,
    cap: int,
    interpret: bool = False,
) -> Array:
    """Run the contraction plan; returns out2d [n_ow, 128//cap, 128].

    Flat segment s lives at ``out2d.reshape(-1)[s]`` (segment-window
    ow = s // (16384//cap), then row-major within the window).
    """
    n_st = vals.shape[0]
    group = TILE // cap

    def kernel(gw_ref, ow_ref, first_ref, wt_ref, g1_ref, g2_ref, g3_ref,
               v_ref, out_ref):
        st = pl.program_id(0)
        wt = wt_ref[0]
        x1 = jnp.take_along_axis(wt, g1_ref[0].astype(jnp.int32), axis=1)
        x2t = jnp.take_along_axis(x1.T, g2_ref[0].astype(jnp.int32), axis=1)
        x3 = jnp.take_along_axis(x2t.T, g3_ref[0].astype(jnp.int32), axis=1)
        c = x3 * v_ref[0]
        partial = c[0:group, :]
        for q in range(1, cap):
            partial = partial + c[q * group:(q + 1) * group, :]

        @pl.when(first_ref[st] == 1)
        def _start_run():
            out_ref[0] = partial

        @pl.when(first_ref[st] == 0)
        def _accumulate():
            out_ref[0] += partial

    stream = lambda: pl.BlockSpec(
        (1, TILE, TILE), lambda i, gw, ow, first: (i, 0, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_st,),
        in_specs=[
            pl.BlockSpec((1, TILE, TILE),
                         lambda i, gw, ow, first: (gw[i], 0, 0),
                         memory_space=pltpu.VMEM),
            stream(), stream(), stream(), stream(),
        ],
        out_specs=pl.BlockSpec((1, group, TILE),
                               lambda i, gw, ow, first: (ow[i], 0, 0),
                               memory_space=pltpu.VMEM),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_ow, group, TILE), jnp.float32,
                                       vma=jax.typeof(vals).vma),
        interpret=interpret,
    )(gw_of_st, ow_of_st, first_of_ow, table_t, g1, g2, g3, vals)


DENSE_B = 4  # supertiles per grid step in the dense-grid kernel


def grr_contract_kernel_dense(
    table_t: Array,        # [n_gw,128,128] f32 — windows, row s = table[gw*WIN+128s...]
    g1: Array,             # [n_st_p, 128, 128] i8 — (gw-major full grid)
    g2: Array,
    g3: Array,
    vals: Array,           # [n_st_p, 128, 128] f32
    gwg: Array,            # [n_st_p // B] i32 — window id per B-group
    n_ow_p: int,
    cap: int,
    interpret: bool = False,
) -> Array:
    """Dense-grid execution: tiles ordered gw-major over the FULL
    (gw × ow_p) block grid (missing blocks are zero dummy tiles), B=4
    supertiles per grid step.  Emits per-tile partials; the ow reduction
    is a reshape-sum outside (``contract``).  Measured on v5e: 520
    ns/tile vs 650 for the revisiting kernel — bigger DMA blocks, one
    window fetch per gw run, and no out-block write-back stalls."""
    n_st_p = vals.shape[0]
    group = TILE // cap
    B = DENSE_B

    def kernel(gwg_ref, wt_ref, g1_ref, g2_ref, g3_ref, v_ref, out_ref):
        wt = wt_ref[0]
        for b in range(B):
            x1 = jnp.take_along_axis(wt, g1_ref[b].astype(jnp.int32), axis=1)
            x2t = jnp.take_along_axis(x1.T, g2_ref[b].astype(jnp.int32),
                                      axis=1)
            x3 = jnp.take_along_axis(x2t.T, g3_ref[b].astype(jnp.int32),
                                     axis=1)
            c = x3 * v_ref[b]
            partial = c[0:group, :]
            for q in range(1, cap):
                partial = partial + c[q * group:(q + 1) * group, :]
            out_ref[b] = partial

    stream = lambda: pl.BlockSpec(
        (B, TILE, TILE), lambda i, gwg: (i, 0, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_st_p // B,),
        in_specs=[
            pl.BlockSpec((1, TILE, TILE), lambda i, gwg: (gwg[i], 0, 0),
                         memory_space=pltpu.VMEM),
            stream(), stream(), stream(), stream(),
        ],
        out_specs=pl.BlockSpec((B, group, TILE), lambda i, gwg: (i, 0, 0),
                               memory_space=pltpu.VMEM),
    )
    parts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_st_p, group, TILE), jnp.float32,
                                       vma=jax.typeof(vals).vma),
        interpret=interpret,
    )(gwg, table_t, g1, g2, g3, vals)
    # ow reduction: position in the full grid IS the (gw, ow) pair, so
    # the segment-sum collapses to a dense axis sum — no scatter.
    n_gw = n_st_p // n_ow_p
    return parts.reshape(n_gw, n_ow_p, group, TILE).sum(0)


def grr_contract_jnp_dense(
    table_t: Array, g1: Array, g2: Array, g3: Array, vals: Array,
    n_ow_p: int, cap: int,
) -> Array:
    """Pure-jnp execution of the dense-grid plan (CPU tests / semantic
    reference)."""
    group = TILE // cap
    i32 = jnp.int32
    n_st_p = vals.shape[0]
    n_gw = n_st_p // n_ow_p
    gw_of_st = jnp.repeat(jnp.arange(n_gw, dtype=i32), n_ow_p)
    wt = table_t[gw_of_st]
    x1 = jnp.take_along_axis(wt, g1.astype(i32), axis=2)
    x2t = jnp.take_along_axis(x1.transpose(0, 2, 1), g2.astype(i32), axis=2)
    x3 = jnp.take_along_axis(x2t.transpose(0, 2, 1), g3.astype(i32), axis=2)
    c = x3 * vals
    partial = c.reshape(n_st_p, cap, group, TILE).sum(1)
    return partial.reshape(n_gw, n_ow_p, group, TILE).sum(0)


def grr_contract_jnp(
    table_t: Array, g1: Array, g2: Array, g3: Array, vals: Array,
    gw_of_st: Array, ow_of_st: Array, n_ow: int, cap: int,
) -> Array:
    """Pure-jnp execution of the same plan (CPU tests, non-TPU backends,
    and the semantic reference the kernel is tested against)."""
    group = TILE // cap
    i32 = jnp.int32
    wt = table_t[gw_of_st]                                    # [n_st,128,128]
    x1 = jnp.take_along_axis(wt, g1.astype(i32), axis=2)
    x2t = jnp.take_along_axis(x1.transpose(0, 2, 1), g2.astype(i32), axis=2)
    x3 = jnp.take_along_axis(x2t.transpose(0, 2, 1), g3.astype(i32), axis=2)
    c = x3 * vals
    n_st = vals.shape[0]
    partial = c.reshape(n_st, cap, group, TILE).sum(1)
    return jax.ops.segment_sum(partial, ow_of_st, num_segments=n_ow)

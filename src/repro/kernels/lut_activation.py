"""Pallas TPU kernel: VMEM-resident lookup-table activation.

The BRAM→VMEM adaptation of the paper's constant-table activations.  The
table (built at trace time by :mod:`repro.core.tables`) rides into VMEM
once per block via a replicated BlockSpec; each input block is mapped to
table indices on the VPU and gathered (plus an optional linear
interpolation — two gathers and an FMA).  This replaces transcendental
``exp/tanh/erf`` evaluations, which are the slow path on the VPU, with a
gather — the same trade the paper's BRAM tables make against DSP/LUT logic.

Layout: the wrapper flattens any input to (rows, LANES) with LANES=128 so
the last dimension is lane-aligned; ``block_rows`` rows are processed per
grid step (8 sublanes × k).  The table rides as a (n/128, 128) tile
(:func:`table_tile`): Mosaic gathers only along one axis of a 2-D array
of the index's own shape, so a lookup is one lane gather per table row,
each kept where the index's high bits select that row.  VMEM working set
per step: ``block_rows*128*4`` bytes for x/out + ``4*n`` bytes for the
table — a 1024-entry table is 4 KiB, the BRAM-sized footprint the paper
targets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.tables import TableSpec, get_table

__all__ = ["lut_activation_pallas", "apply_table", "table_tile"]

LANES = 128


def table_tile(spec: TableSpec) -> jnp.ndarray:
    """The table of ``spec`` as a (ceil(n/128), 128) f32 tile; the
    padding past ``n`` is never indexed."""
    vals = get_table(spec).np_values.astype(np.float32)
    pad = (-vals.shape[0]) % LANES
    return jnp.asarray(np.pad(vals, (0, pad), mode="edge")
                       .reshape(-1, LANES))


def _lookup(t: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for int32 ``idx`` (R, C), C a multiple of 128,
    from the (rows, 128) tile ``t``: per 128-lane column block, one lane
    gather from each broadcast table row, selected by ``idx >> 7``."""
    lo = jnp.bitwise_and(idx, LANES - 1)
    hi = jnp.right_shift(idx, 7)
    cols = []
    for c in range(0, idx.shape[1], LANES):
        lo_c, hi_c = lo[:, c:c + LANES], hi[:, c:c + LANES]
        z = jnp.zeros(lo_c.shape, t.dtype)
        for r in range(t.shape[0]):
            row = jnp.broadcast_to(t[r:r + 1, :], lo_c.shape)
            g = jnp.take_along_axis(row, lo_c, axis=1,
                                    mode="promise_in_bounds")
            z = jnp.where(hi_c == r, g, z)
        cols.append(z)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def apply_table(y: jnp.ndarray, t: jnp.ndarray, *, lo: float,
                step_inv: float, n: int, indexing: str,
                gated: bool = False) -> jnp.ndarray:
    """In-kernel LUT lookup on a VMEM-resident (R, 128·k) tile against
    the :func:`table_tile` ``t`` (the Mosaic-lowerable form of
    :func:`repro.core.tables.table_lookup`).

    Shared by this kernel and the fused qmatmul epilogue so the
    interp/nearest/trunc numerics have exactly one in-kernel
    implementation.  ``gated=True`` returns ``y * table(y)`` (the exact
    gated silu/gelu form).
    """
    pos = (y - lo) * step_inv
    if indexing == "interp":
        pos = jnp.clip(pos, 0.0, n - 1.0)
        i0f = jnp.floor(pos)
        frac = pos - i0f
        i0 = i0f.astype(jnp.int32)
        i1 = jnp.minimum(i0 + 1, n - 1)
        z = _lookup(t, i0) * (1.0 - frac) + _lookup(t, i1) * frac
    else:
        if indexing == "nearest":
            idx = jnp.clip(jnp.round(pos), 0, n - 1).astype(jnp.int32)
        else:  # trunc — hls4ml-faithful
            idx = jnp.clip(jnp.floor(pos), 0, n - 1).astype(jnp.int32)
        z = _lookup(t, idx)
    return y * z if gated else z


def _kernel(x_ref, t_ref, o_ref, *, lo: float, step_inv: float, n: int,
            indexing: str):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = apply_table(x, t_ref[...], lo=lo, step_inv=step_inv, n=n,
                             indexing=indexing)


@functools.partial(jax.jit, static_argnames=("spec", "block_rows", "interpret"))
def lut_activation_pallas(x: jnp.ndarray, spec: TableSpec, *,
                          block_rows: int = 256,
                          interpret: bool = False) -> jnp.ndarray:
    """Apply the table described by ``spec`` to ``x`` (any shape)."""
    table = table_tile(spec)
    n = spec.n
    orig_shape, orig_dtype = x.shape, x.dtype

    flat = x.reshape(-1)
    cols = LANES
    pad = (-flat.shape[0]) % (block_rows * cols)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, cols)
    rows = x2.shape[0]
    grid = (rows // block_rows,)

    out = pl.pallas_call(
        functools.partial(_kernel, lo=spec.lo, step_inv=1.0 / spec.step,
                          n=n, indexing=spec.indexing),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
            # the table is replicated into VMEM for every block
            pl.BlockSpec(table.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, table)

    out = out.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(orig_shape).astype(orig_dtype)

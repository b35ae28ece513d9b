"""Pallas TPU segment-combine — the COMB primitive's compute hot-spot.

GPU shuffle combiners use hash tables or atomic scatter-add; neither maps to the TPU.
The TPU-native restatement: per VMEM tile of messages, build the one-hot
``[block_n, num_segments]`` destination matrix and accumulate ``onehot^T @ vals`` on
the MXU into a per-(segment, feature-tile) VMEM accumulator carried across the
innermost grid dimension.  One pass, no data-dependent control flow, MXU-shaped.

Used by: MoE expert combine (weighted sum of expert outputs per token), gradient
bucket reduction, and as the jittable COMB for mesh-side shuffle templates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_D = 512


def _combine_kernel(ids_ref, vals_ref, out_ref, acc_ref, *, num_segments: int,
                    block_n: int):
    i = pl.program_id(1)                       # innermost: message tiles
    ni = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ids = ids_ref[...]                         # [bn, 1] int32
    vals = vals_ref[...].astype(jnp.float32)   # [bn, bd]
    seg = jax.lax.broadcasted_iota(jnp.int32, (block_n, num_segments), 1)
    onehot = (ids == seg).astype(jnp.float32)  # [bn, S]; ids == -1 rows are dropped
    acc_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "block_n", "block_d", "interpret"))
def _segment_combine(
    seg_ids: jax.Array,    # [n] int32, -1 = drop
    vals: jax.Array,       # [n, d]
    *,
    num_segments: int,
    block_n: int,
    block_d: int,
    interpret: bool,
) -> jax.Array:
    """Jitted core; ``interpret`` is static — resolve it ONCE via the probe
    in :func:`segment_combine` so repeated calls never retrace."""
    n, d = vals.shape
    assert seg_ids.shape == (n,)
    n_p = -(-n // block_n) * block_n
    block_d = min(block_d, d)
    d_p = -(-d // block_d) * block_d
    ids = seg_ids.astype(jnp.int32)
    if n_p != n:
        ids = jnp.pad(ids, (0, n_p - n), constant_values=-1)
        vals = jnp.pad(vals, ((0, n_p - n), (0, 0)))
    if d_p != d:
        vals = jnp.pad(vals, ((0, 0), (0, d_p - d)))
    ids2 = ids[:, None]

    grid = (d_p // block_d, n_p // block_n)    # d tiles parallel, n tiles innermost
    out = pl.pallas_call(
        functools.partial(_combine_kernel, num_segments=num_segments,
                          block_n=block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, block_d), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((num_segments, block_d), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d_p), vals.dtype),
        scratch_shapes=[pltpu.VMEM((num_segments, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids2, vals)
    return out[:, :d]


def segment_combine(
    seg_ids: jax.Array,
    vals: jax.Array,
    *,
    num_segments: int,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jax.Array:
    """Sum ``vals`` rows into ``num_segments`` buckets by ``seg_ids`` (COMB for +).

    ``interpret=None`` (the default) resolves through the process-wide
    backend probe :func:`repro.kernels.ops.default_interpret` — compiled on
    TPU, interpreted elsewhere — so callers neither retrace the static
    ``interpret`` jit arg nor silently run interpreted on real hardware.
    """
    if interpret is None:
        from .ops import default_interpret
        interpret = default_interpret()
    return _segment_combine(seg_ids, vals, num_segments=num_segments,
                            block_n=block_n, block_d=block_d,
                            interpret=interpret)

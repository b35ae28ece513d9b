"""Pallas TPU partition (PART) — bucket permutation as a one-hot MXU matmul.

The PART primitive routes each message row to a destination slot (expert buffer
slot, shuffle bucket, ...).  The GPU implementation is a radix scatter with atomic
slot counters; TPUs have neither atomics nor efficient data-dependent scatter.  The
TPU-native restatement: a *permutation matmul* — for each (output tile, input tile)
pair build the one-hot matrix ``P[o, i] = (slot[i] == o)`` in VREGs and accumulate
``P @ vals`` on the MXU.  Rows whose slot is -1 (dropped / over capacity) never
match and vanish.  Each output row has at most one contributor, so the accumulated
result IS the permutation (and the same kernel doubles as scatter-add when slots
collide — it degrades gracefully into COMB).

Grid: (d tiles parallel, out tiles parallel, in tiles sequential-innermost); the
out-tile accumulator lives in VMEM scratch across the in-tile dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_IN = 256
DEFAULT_BLOCK_OUT = 256
DEFAULT_BLOCK_D = 512


def _partition_kernel(slots_ref, vals_ref, out_ref, acc_ref, *, block_in: int,
                      block_out: int):
    oj = pl.program_id(1)                     # output tile
    ii = pl.program_id(2)                     # input tile (innermost, sequential)
    ni = pl.num_programs(2)

    @pl.when(ii == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    slots = slots_ref[...]                    # [block_in, 1] int32 (global slot ids)
    vals = vals_ref[...].astype(jnp.float32)  # [block_in, bd]
    out_rows = oj * block_out + jax.lax.broadcasted_iota(
        jnp.int32, (block_in, block_out), 1)
    onehot = (slots == out_rows).astype(jnp.float32)      # [bi, bo]
    acc_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ii == ni - 1)
    def _finish():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "num_out", "block_in", "block_out", "block_d", "interpret"))
def _partition_permute(
    slots: jax.Array,          # [n] int32 destination slot per row; -1 = drop
    vals: jax.Array,           # [n, d]
    *,
    num_out: int,
    block_in: int,
    block_out: int,
    block_d: int,
    interpret: bool,
) -> jax.Array:
    """Jitted core; ``interpret`` is static — resolve it ONCE via the probe
    in :func:`partition_permute` so repeated calls never retrace."""
    n, d = vals.shape
    assert slots.shape == (n,)
    block_out = min(block_out, num_out)
    block_d = min(block_d, d)
    n_p = -(-n // block_in) * block_in
    o_p = -(-num_out // block_out) * block_out
    d_p = -(-d // block_d) * block_d
    ids = slots.astype(jnp.int32)
    if n_p != n:
        ids = jnp.pad(ids, (0, n_p - n), constant_values=-1)
        vals = jnp.pad(vals, ((0, n_p - n), (0, 0)))
    if d_p != d:
        vals = jnp.pad(vals, ((0, 0), (0, d_p - d)))

    grid = (d_p // block_d, o_p // block_out, n_p // block_in)
    out = pl.pallas_call(
        functools.partial(_partition_kernel, block_in=block_in,
                          block_out=block_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_in, 1), lambda j, o, i: (i, 0)),
            pl.BlockSpec((block_in, block_d), lambda j, o, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_out, block_d), lambda j, o, i: (o, j)),
        out_shape=jax.ShapeDtypeStruct((o_p, d_p), vals.dtype),
        scratch_shapes=[pltpu.VMEM((block_out, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ids[:, None], vals)
    return out[:num_out, :d]


def partition_permute(
    slots: jax.Array,
    vals: jax.Array,
    *,
    num_out: int,
    block_in: int = DEFAULT_BLOCK_IN,
    block_out: int = DEFAULT_BLOCK_OUT,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool | None = None,
) -> jax.Array:
    """Scatter rows of ``vals`` into a [num_out, d] buffer by ``slots`` (PART).

    ``interpret=None`` (the default) resolves through the process-wide
    backend probe :func:`repro.kernels.ops.default_interpret` — compiled on
    TPU, interpreted elsewhere — so callers neither retrace the static
    ``interpret`` jit arg nor silently run interpreted on real hardware.
    """
    if interpret is None:
        from .ops import default_interpret
        interpret = default_interpret()
    return _partition_permute(slots, vals, num_out=num_out, block_in=block_in,
                              block_out=block_out, block_d=block_d,
                              interpret=interpret)

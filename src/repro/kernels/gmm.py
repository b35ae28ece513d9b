"""Pallas TPU grouped matmul (GMM) — the PART-then-compute hot path of MoE dispatch.

After PART routes tokens to experts, each expert applies its own weight matrix.  The
GPU solution (megablocks) uses block-sparse kernels; the TPU-native adaptation tiles
tokens into MXU-shaped row blocks **pre-sorted and padded so each row block belongs
to exactly one expert**, and uses Pallas *scalar prefetch* to index the right
expert's weight tile while the previous block is still computing (HBM->VMEM overlap
comes from the pipelined grid).

Inputs: ``x`` sorted by expert with per-expert counts padded to ``block_n``;
``tile_group_ids[i]`` = expert owning row tile ``i`` (computed by the router on
host/XLA side); ``w[num_groups, d, f]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_F = 512


def _gmm_kernel(gids_ref, x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_n", "block_d", "block_f", "interpret"))
def gmm(
    x: jax.Array,               # [n, d] rows sorted by group, padded per group
    w: jax.Array,               # [G, d, f]
    tile_group_ids: jax.Array,  # [n // block_n] int32: expert of each row tile
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_d: int = DEFAULT_BLOCK_D,
    block_f: int = DEFAULT_BLOCK_F,
    interpret: bool = True,
) -> jax.Array:
    n, d = x.shape
    g, dw, f = w.shape
    assert dw == d
    assert n % block_n == 0, "pad token count per group to block_n first"
    assert tile_group_ids.shape == (n // block_n,)
    block_d = min(block_d, d)
    block_f = min(block_f, f)
    assert d % block_d == 0 and f % block_f == 0, (d, block_d, f, block_f)

    grid = (n // block_n, f // block_f, d // block_d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k, gids: (i, k)),
            pl.BlockSpec((1, block_d, block_f), lambda i, j, k, gids: (gids[i], k, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_f), lambda i, j, k, gids: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_n, block_f), jnp.float32)],
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_group_ids.astype(jnp.int32), x, w)


def route_and_pad(
    expert_ids: jax.Array,      # [n] int32 expert per row
    num_experts: int,
    block_n: int = DEFAULT_BLOCK_N,
    *,
    capacity_tiles: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Host/XLA-side PART companion: sort rows by expert with per-expert padding.

    Returns ``(sorted_row_ids, tile_group_ids, valid_mask)`` where each expert
    occupies exactly ``capacity_tiles`` row tiles (tokens over capacity are dropped —
    standard MoE capacity semantics; the sampled histogram from
    ``meshops.estimate_tokens_per_expert`` sizes the capacity).
    """
    n = expert_ids.shape[0]
    cap = capacity_tiles * block_n
    # stable order of rows per expert
    order = jnp.argsort(expert_ids, stable=True)
    sorted_eids = expert_ids[order]
    pos_in_expert = jnp.arange(n) - jnp.searchsorted(sorted_eids, sorted_eids, side="left")
    keep = pos_in_expert < cap
    slot = sorted_eids * cap + pos_in_expert          # target slot, unique where kept
    slot = jnp.where(keep, slot, num_experts * cap)   # overflow bucket
    rows = jnp.full((num_experts * cap + 1,), n, dtype=jnp.int32)  # n = padding row
    rows = rows.at[slot].set(order.astype(jnp.int32), mode="drop")
    rows = rows[: num_experts * cap]
    tile_group_ids = jnp.repeat(jnp.arange(num_experts, dtype=jnp.int32),
                                capacity_tiles)
    valid = rows < n
    return rows, tile_group_ids, valid

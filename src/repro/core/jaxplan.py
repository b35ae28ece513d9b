"""Jitted plan replay: lower a CompiledPlan into one compiled JAX program.

The third executor.  The threaded path (:mod:`repro.core.templates`) is the
reference semantics; the vectorized path (:mod:`repro.core.vectorized`)
replays a cached plan as batched numpy.  This module lowers a frozen
:class:`~repro.core.plancache.CompiledPlan` one step further: the whole
replay — every hierarchical stage plus the global exchange and combine —
becomes a *single jitted JAX program*, with the stage loop compiled as one
rolled :func:`jax.lax.scan` over a dense ``[levels, nworkers]`` routing
table extracted from the plan.  Template differences (neighbor lists, fold
orders, ring rotation) are data in that table, not control flow, so one
trace serves every supported template shape.

Lowering model
--------------

All source buffers are stacked into flat arrays — ``keys [N]``,
``vals [N, d]``, ``owner [N]`` (position in ``srcs``) — and every primitive
becomes a whole-array operation:

* **PART** assigns each row a destination slot with the plan's partFunc
  (splitmix64 hash or range, replicated bit-for-bit in jnp under x64) and
  *moves* rows by one stable argsort on a ``(destination, fold-rank)``
  composite key.  The fold rank reproduces the receiver's concat order
  (own partition first, then group neighbors; ring rotation for
  ``coordinated``), so the physical array order after the sort IS the
  byte-order the numpy executor concatenates in.
* **COMB** stable-sorts each owner's segment by key and folds equal-key
  rows in rounds by rank within a segment: round 0 seeds every segment with
  its first row, round ``j`` folds every segment's row ``j`` into it.  Each
  segment is still an explicit left fold in element order, which is
  exactly the ``ufunc.at`` contract of
  :class:`repro.core.messages.Combiner`, so float64 SUM results are
  *bit-identical* to both other executors; the serial depth is the longest
  segment, not the row count.  Combined-away rows are marked dead and sort
  to the end; row capacity stays ``N`` throughout, keeping every shape
  static.

Irregular templates lower too.  ``bruck``'s log-round piece routing is
simulated symbolically at lower time (pieces move whole and never split, so
the final arrival order per destination is a static permutation of
origins): the simulation yields the ``global_rank`` fold table the generic
program consumes plus per-round wire flows the ledger replays.
``two_level`` runs a dedicated three-phase traced program (group-local
exchange, transpose handoff, final exchange) whose sorts replay the grid's
exact mailbox concat orders.  Skew-rebalanced plans freeze the hot-key
scatter (:func:`repro.core.skew.scatter_part_fn`'s occurrence-cycled share
slots) into the trace as static tables — a per-row occurrence index among
same-(owner, key) rows reproduces the positional cycle — and the final
owner merge replays Python-side, mirroring the vectorized executor.

The program also returns routing-count matrices; the Python wrapper
converts row counts to wire bytes and replays the reference executors'
exact :class:`~repro.core.primitives.CostLedger` charge sequence (same
epochs, same per-worker transfer/combine charges, same per-destination
recv accounting), so modelled bytes and costs are identical across all
three executors.

Batched dispatch: :func:`prepare_batch` stacks same-signature submissions
(same spec, shapes, and routing tables — the admission batcher groups
them) into ONE vmapped jit dispatch; each member's replay then consumes
its slice and charges its own tenant's ledger lanes exactly as a serial
run would, with the epoch barrier deferred until the whole batch settles —
per-tenant byte/cost lanes equal serial charges while modelled time pays
the barrier once.

Precision: the hot path runs in float64 under ``jax.enable_x64`` — byte
identity is the acceptance contract.  The 64-bit row arrays cross the
host<->device boundary as 32-bit words (:class:`_Words`) and are 64-bit
again on either side.

Decline conditions (the service falls back to the vectorized executor,
which may fall back to threaded):

* template outside :data:`JAX_TEMPLATES` (a custom registration this
  module has no lowering for — all six built-ins lower);
* streamed replays (``args.stream``), recovery contexts, or any cluster
  fault state (failed workers, delays, fault injections);
* partFuncs outside the jnp registry (hash / range) or combiners outside
  {sum, min, max}; mixed payload widths; an all-empty workload;
* ``coordinated`` with destinations outside the source ring, or ``bruck``
  with mismatched src/dst sets (``ring_mismatch``); ``two_level`` off a
  square src==dst grid (``grid_mismatch``);
* a triggered skew rebalance whose scatter cannot be frozen: the
  decision's slot space collides with a level's group size
  (``skew_group_collision``) or no longer matches the destination count
  (``skew_shape_mismatch``).

See ``docs/jaxplan.md`` for the full lowering rules and executor matrix.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from collections import OrderedDict
from typing import NamedTuple

import numpy as np

from .messages import Msgs
from .plancache import CompiledPlan, attach_lowering, get_lowering
from .primitives import LocalCluster, ShuffleArgs
from .skew import owner_merge_plan, scatter_tables
from .templates import ShuffleResult, aggregate_observed
from .vectorized import VECTORIZABLE

# Every built-in template lowers: the four regular replays share the rolled
# scan program; bruck rides the same program behind a lower-time routing
# simulation; two_level runs its own three-phase traced program.
JAX_TEMPLATES = frozenset(VECTORIZABLE | {"bruck", "two_level"})

_RANGE_NAME = re.compile(r"^range\[(\d+)\]$")
_JAX_COMBINERS = ("sum", "min", "max")

# Sentinel attached to a plan whose lowering was attempted and refused, so
# repeated calls don't re-derive the refusal.
_DECLINED = object()


class _PlanSpec(NamedTuple):
    """Static (hashable) half of the replay: one jit trace per distinct spec
    and input shape; routing tables and buffers are traced arrays."""

    template: str
    comb: str | None          # combiner name, or None (concat only)
    part: tuple               # ("hash",) | ("range", key_space)
    initial_comb: bool        # network_aware combines locally before stage 0
    ns: int                   # len(srcs)
    ndst: int                 # len(dsts)
    skew: bool                # frozen hot-key scatter at the global stage


@dataclasses.dataclass(frozen=True)
class JaxLowering:
    """Routing tables extracted once per CompiledPlan (template differences
    become data): frozen onto the plan via plancache.attach_lowering."""

    src_pos: dict[int, int]          # wid -> position in srcs
    dst_pos: dict[int, int]          # wid -> position in dsts
    gsize: np.ndarray                # [L, ns] int32: worker's group size per level
    slot_map: np.ndarray             # [L, ns, ns] int32: (worker, slot) -> src pos
    rank_map: np.ndarray             # [L, ns, ns] int32: (sender, receiver) -> fold rank
    active: np.ndarray               # [L] bool: level beneficial?
    global_rank: np.ndarray          # [ns, ndst] int32: (sender, dst) -> fold rank
    levels_staged: tuple             # per level: ((wid, peers), ...) in srcs order
    bruck_flows: tuple | None = None
    # ^ per src position: per round (peer wid, ((origin pos, dst pos), ...)) —
    #   the symbolic piece simulation's wire flows, replayed by the ledger
    skew_hot: np.ndarray | None = None    # [H] int64 sorted hot keys
    skew_share: np.ndarray | None = None  # [H, S] int32 padded share slots
    skew_len: np.ndarray | None = None    # [H] int32 share counts


def _part_spec(part_fn) -> tuple | None:
    """jnp-replicable partFuncs: the paper's hash default and range."""
    if part_fn.name == "hash":
        return ("hash",)
    m = _RANGE_NAME.match(part_fn.name)
    if m is not None:
        return ("range", int(m.group(1)))
    return None


def _bruck_sim(ns: int):
    """Symbolic bruck rounds over piece lists.

    A piece is (origin position, destination position): an origin's whole
    partition for one destination, which the algorithm moves whole and never
    splits.  Invariant: ``blocks[me][j]`` holds pieces destined for ring
    position ``(me + j) % ns``.  Returns the per-round flows (who sends which
    pieces to whom) and the final arrival order of origins per destination.
    """
    blocks = [[[(me, (me + j) % ns)] for j in range(ns)] for me in range(ns)]
    rounds = []
    step = 1
    while step < ns:
        js = [j for j in range(ns) if j & step]
        sent = {}
        flows = []
        for me in range(ns):
            pieces = []
            for j in js:
                pieces.extend(blocks[me][j])
                sent[(me, j)] = blocks[me][j]
                blocks[me][j] = []
            flows.append(((me + step) % ns, tuple(pieces)))
        for me in range(ns):
            peer_from = (me - step) % ns
            for j in js:
                blocks[me][j - step] = blocks[me][j - step] + sent[(peer_from, j)]
        rounds.append(flows)
        step *= 2
    arrival = [[o for (o, _d) in blocks[me][0]] for me in range(ns)]
    return rounds, arrival


def _is_square(ns: int) -> bool:
    q = int(round(ns ** 0.5))
    return q * q == ns


def lower_plan(plan: CompiledPlan) -> JaxLowering | None:
    """Extract the dense routing tables; None when the plan shape is not
    lowerable (unsupported template, ring/grid mismatch, unfreezable
    scatter)."""
    if plan_decline(plan) is not None:
        return None
    srcs, dsts = list(plan.srcs), list(plan.dsts)
    ns, ndst = len(srcs), len(dsts)
    src_pos = {w: i for i, w in enumerate(srcs)}
    dst_pos = {d: i for i, d in enumerate(dsts)}
    irregular = plan.template_id in ("bruck", "two_level")
    nlv = 0 if irregular else len(plan.levels)
    gsize = np.ones((nlv, ns), np.int32)
    slot_map = np.tile(np.arange(ns, dtype=np.int32), (nlv, ns, 1))
    rank_map = np.zeros((nlv, ns, ns), np.int32)
    active = np.zeros((nlv,), bool)
    levels_staged = []
    for li in range(nlv):
        ld = plan.levels[li]
        active[li] = ld.eff_cost.beneficial
        staged = []
        for w in srcs:
            nbrs = list(ld.nbrs.get(w, (w,)))
            wp = src_pos[w]
            gsize[li, wp] = len(nbrs)
            for s, n in enumerate(nbrs):
                slot_map[li, wp, s] = src_pos[n]
            # receiver w folds [own partition] + [peers in group order]:
            # rank 0 for itself, pos+1 before its own position, pos after
            pos_w = nbrs.index(w)
            for pos_s, s in enumerate(nbrs):
                sp = src_pos[s]
                if s == w:
                    rank_map[li, sp, wp] = 0
                else:
                    rank_map[li, sp, wp] = pos_s + 1 if pos_s < pos_w else pos_s
            if len(nbrs) > 1:
                staged.append((w, tuple(n for n in nbrs if n != w)))
        levels_staged.append(tuple(staged))
    global_rank = np.zeros((ns, ndst), np.int32)
    bruck_flows = None
    if plan.template_id == "coordinated":
        # fetch_order[d][t] = srcs[(idx(d) - t) % n]  =>  rank(s at d) = idx(d) - idx(s) mod n
        for d in dsts:
            for s in srcs:
                global_rank[src_pos[s], dst_pos[d]] = \
                    (src_pos[d] - src_pos[s]) % ns
    elif plan.template_id == "bruck":
        rounds, arrival = _bruck_sim(ns)
        for me in range(ns):
            dp = dst_pos[srcs[me]]
            for rank, origin in enumerate(arrival[me]):
                global_rank[origin, dp] = rank
        bruck_flows = tuple(
            tuple((srcs[flows[me][0]],
                   tuple((o, dst_pos[srcs[dr]]) for o, dr in flows[me][1]))
                  for flows in rounds)
            for me in range(ns))
    else:
        # push / pull / network_aware / two_level fold arrivals in srcs order
        # (two_level's fold orders live inside its own traced program)
        global_rank[:] = np.arange(ns, dtype=np.int32)[:, None]
    skew_hot = skew_share = skew_len = None
    if plan.skew is not None and plan.skew.triggered:
        skew_hot, skew_share, skew_len = scatter_tables(plan.skew)
    return JaxLowering(
        src_pos=src_pos, dst_pos=dst_pos, gsize=gsize, slot_map=slot_map,
        rank_map=rank_map, active=active, global_rank=global_rank,
        levels_staged=tuple(levels_staged), bruck_flows=bruck_flows,
        skew_hot=skew_hot, skew_share=skew_share, skew_len=skew_len)


# ---------------------------------------------------------------------------
# The jitted programs
# ---------------------------------------------------------------------------

def _splitmix64(keys):
    """Bit-exact jnp mirror of messages.splitmix64 (seed 0); needs x64."""
    import jax.numpy as jnp
    z = keys.astype(jnp.uint64) + jnp.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return z ^ (z >> jnp.uint64(31))


def _slot_of(part: tuple, keys, ndst):
    """Per-row destination slot with a per-row slot count (PartFn.assign)."""
    import jax.numpy as jnp
    if part[0] == "hash":
        return (_splitmix64(keys) % ndst.astype(jnp.uint64)).astype(jnp.int32)
    key_space = part[1]
    g = ndst.astype(jnp.int64)
    per = (jnp.int64(key_space) + g - 1) // g          # ceil, like -(-ks // n)
    return jnp.minimum(jnp.floor_divide(keys, per), g - 1).astype(jnp.int32)


def _skew_slot(keys, owner, alive, base_slot, ns, hot_keys, share_slots,
               share_len):
    """The frozen hot-key scatter: scatter_part_fn's occurrence cycle as a
    whole-array op.  The cycle position of a hot row is its occurrence index
    among same-(owner, key) alive rows in array order — array order per
    owner IS that worker's buffer order, the byte-order invariant the sorts
    maintain — computed with one stable (owner, key) lexsort and a
    segment-relative position."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.named_scope("teshu/skew_slot"):
        n = keys.shape[0]
        pos = jnp.arange(n)
        so = jnp.where(alive, jnp.minimum(owner, ns - 1), ns)
        perm = jnp.argsort(jnp.where(alive, keys, jnp.int64(0)), stable=True)
        perm = perm[jnp.argsort(so[perm], stable=True)]
        sk, sso = keys[perm], so[perm]
        prev_same = ((sso == jnp.roll(sso, 1))
                     & (sk == jnp.roll(sk, 1))).at[0].set(False)
        seg_start = lax.cummax(jnp.where(~prev_same, pos, 0))
        occ = jnp.zeros((n,), jnp.int64).at[perm].set(pos - seg_start)
        hp = jnp.searchsorted(hot_keys, keys)
        hpc = jnp.minimum(hp, hot_keys.shape[0] - 1)
        is_hot = (hot_keys[hpc] == keys) & alive
        share = share_slots[
            hpc, (occ % jnp.maximum(share_len[hpc], 1)).astype(jnp.int32)]
        return jnp.where(is_hot, share.astype(jnp.int32), base_slot)


_FOLD_GROWTH = 8     # a fold phase's first round is 8x the previous phase's


def _segment_fold(op, vals, is_start):
    """The left fold of every segment (a run of rows opened by an
    ``is_start`` row), in rounds by rank within a segment.

    Segments are laid out longest first, so the segments still folding at
    round ``j`` (those longer than ``j``) are a prefix of that order.  Round
    0 seeds each segment's accumulator with its first row; round ``j``
    gathers every live segment's row ``j`` and folds it in with ``op``.
    Each segment thus computes ``op(...op(op(v0, v1), v2)..., v_last)``,
    the same operations in the same order as a row-serial scan, while the
    serial depth is the longest segment rather than the row count.  From
    round ``j0`` on, at most ``n // (j0 + 1)`` segments are live, so the
    rounds run in phases (first rounds 1, 8, 64, ...), each one loop over a
    static window of that many accumulators.  Payload columns fold as
    ``[d, n]``, rows along the lanes.

    Returns the folded rows, each segment's result at its last row (the
    other rows are never read), and the longest segment's length: the
    rounds the fold ran.
    """
    import jax.numpy as jnp
    from jax import lax

    n = vals.shape[0]
    # segment k starts at starts[k] (a sort: the v5e compiler runs out of
    # VMEM for a cumulative sum over millions of rows)
    k = jnp.arange(n, dtype=jnp.int32)
    nseg = is_start.sum(dtype=jnp.int32)
    starts = jnp.argsort(~is_start, stable=True).astype(jnp.int32)
    nxt = jnp.where(k + 1 < nseg, jnp.roll(starts, -1), n)
    lens = jnp.where(k < nseg, nxt - starts, 0)
    first = jnp.where(k < nseg, starts, n)       # padding: first n, length 0
    neg_lens, first = lax.sort((-lens, first), num_keys=1)
    lens = -neg_lens
    longest = lens[0]
    cols = vals.T
    acc = cols.at[:, first].get(mode="clip")
    j0 = 1
    while j0 < n:
        w = n // (j0 + 1)

        def fold_round(j, acc_w, first_w=first[:w], lens_w=lens[:w]):
            v = cols.at[:, first_w + j].get(mode="clip")
            return jnp.where(lens_w > j, op(acc_w, v), acc_w)

        acc = acc.at[:, :w].set(lax.fori_loop(
            j0, jnp.minimum(j0 * _FOLD_GROWTH, longest), fold_round,
            acc[:, :w]))
        j0 *= _FOLD_GROWTH
    # each segment's last row reads its accumulator
    last = jnp.where(lens > 0, first + lens - 1, n)
    slot = jnp.zeros((n,), jnp.int32).at[last].set(k, mode="drop")
    return acc[:, slot].T, longest


def _combine(comb: str, keys, vals, owner, alive, participate, sentinel: int):
    """Per-owner equal-key fold, bit-identical to messages.Combiner.

    Stable lexsort by (owner, key) — non-participating rows keep their
    relative order (their sort key is constant and owners never mix
    participation) — then :func:`_segment_fold` over the equal-(owner, key)
    segments: each is seeded with its first row and the rest fold in
    element order, which is numpy's ``ufunc.at`` contract exactly.
    Non-segment-end rows die (owner keeps its value; every later sort sends
    dead rows to the end via the alive mask).  Also returns the fold's
    rounds (the longest segment).
    """
    import jax
    import jax.numpy as jnp

    with jax.named_scope("teshu/comb_sort"):
        folds = participate & alive
        ckey = jnp.where(folds, keys, jnp.int64(0))
        perm = jnp.argsort(ckey, stable=True)
        so = jnp.where(alive, owner, sentinel)
        perm = perm[jnp.argsort(so[perm], stable=True)]
        keys, vals, owner, alive, folds = (
            keys[perm], vals[perm], owner[perm], alive[perm], folds[perm])
        prev_same = ((owner == jnp.roll(owner, 1))
                     & (keys == jnp.roll(keys, 1))).at[0].set(False)
        is_start = ~(prev_same & folds)
    op = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[comb]
    with jax.named_scope("teshu/comb_fold"):
        folded, rounds = _segment_fold(op, vals, is_start)
        seg_end = jnp.concatenate([is_start[1:], jnp.ones((1,), bool)])
    return keys, folded, owner, alive & seg_end, rounds


def _replay_impl(spec: _PlanSpec, keys, vals, owner,
                 gsize, slot_map, rank_map, active, global_rank,
                 hot_keys, share_slots, share_len):
    """The rolled-scan replay shared by the four regular templates and (with
    zero levels plus a simulated global_rank) bruck.  The last output is
    the fold rounds of every COMB it ran."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    ns, ndst = spec.ns, spec.ndst
    n = keys.shape[0]
    alive = jnp.ones((n,), bool)
    rounds = jnp.int32(0)
    if spec.initial_comb:
        keys, vals, owner, alive, rounds = _combine(
            spec.comb, keys, vals, owner, alive, alive, ns)

    def level_body(carry, xs):
        keys, vals, owner, alive, rounds = carry
        g_l, slot_l, rank_l, act = xs
        oc = jnp.minimum(owner, ns - 1)
        g = g_l[oc]
        part_row = act & alive & (g > 1)
        slot = _slot_of(spec.part, keys, jnp.maximum(g, 1))
        new_owner = jnp.where(part_row, slot_l[oc, slot], owner)
        noc = jnp.minimum(new_owner, ns - 1)
        rank = jnp.where(part_row, rank_l[oc, noc], 0)
        moved = jnp.zeros((ns, ns), jnp.int32).at[oc, noc].add(
            part_row.astype(jnp.int32))
        # the exchange: one stable sort by (receiver, fold rank); within
        # a (sender -> receiver) flow rows keep buffer order = the stable
        # argsort inside messages.partition
        with jax.named_scope("teshu/exchange"):
            sort_owner = jnp.where(alive, new_owner, ns)
            ck = sort_owner.astype(jnp.int64) * jnp.int64(ns + 1) + rank
            perm = jnp.argsort(ck, stable=True)
            keys2, vals2 = keys[perm], vals[perm]
            owner2, alive2 = new_owner[perm], alive[perm]
        staged_owner = act & (g_l[jnp.minimum(owner2, ns - 1)] > 1)
        if spec.comb is not None:
            keys2, vals2, owner2, alive2, r = _combine(
                spec.comb, keys2, vals2, owner2, alive2,
                staged_owner & alive2, ns)
            rounds = rounds + r
        post_row = (alive2 & act
                    & (g_l[jnp.minimum(owner2, ns - 1)] > 1))
        post = jnp.zeros((ns,), jnp.int32).at[
            jnp.minimum(owner2, ns - 1)].add(post_row.astype(jnp.int32))
        return ((keys2, vals2, owner2, alive2, rounds),
                (moved, moved.sum(0), post))

    (keys, vals, owner, alive, rounds), (lvl_moved, lvl_pre, lvl_post) = \
        lax.scan(level_body, (keys, vals, owner, alive, rounds),
                 (gsize, slot_map, rank_map, active))

    # ---- global exchange: every alive row repartitions over the dsts ----
    oc = jnp.minimum(owner, ns - 1)
    slot = _slot_of(spec.part, keys,
                    jnp.full((n,), ndst, jnp.int32))
    if spec.skew:
        slot = _skew_slot(keys, owner, alive, slot, ns,
                          hot_keys, share_slots, share_len)
    new_owner = jnp.where(alive, slot, ndst)
    sc = jnp.minimum(slot, ndst - 1)
    gmoved = jnp.zeros((ns, ndst), jnp.int32).at[oc, sc].add(
        alive.astype(jnp.int32))
    rank = jnp.where(alive, global_rank[oc, sc], 0)
    with jax.named_scope("teshu/exchange"):
        ck = new_owner.astype(jnp.int64) * jnp.int64(ns + 1) + rank
        perm = jnp.argsort(ck, stable=True)
        keys, vals = keys[perm], vals[perm]
        owner, alive = new_owner[perm], alive[perm]
    if spec.comb is not None:
        keys, vals, owner, alive, r = _combine(
            spec.comb, keys, vals, owner, alive, alive, ndst)
        rounds = rounds + r
    return (keys, vals, owner, alive, lvl_moved, lvl_pre, lvl_post, gmoved,
            rounds)


def _two_level_impl(spec: _PlanSpec, keys, vals, owner):
    """two_level's three-phase replay on a square src==dst grid.

    Every row's final slot ``d`` (a pure function of its key) determines all
    three hops: phase 1 sends it within the row group to member ``d // q``,
    phase 2 hands whole blocks to the transpose partner — a pure owner
    relabel, since blocks move unsplit (and, combined, already hold unique
    keys, so the threaded re-COMB is an order-preserving identity) — and
    phase 3 delivers within the destination group.  Each exchange is one
    stable sort on the grid's exact mailbox concat order: (receiver, sender
    member index, slot).  Returns the phase flow counts the ledger replays,
    then the fold rounds of its COMBs.
    """
    import jax
    import jax.numpy as jnp

    ns = spec.ns
    q = int(round(ns ** 0.5))
    n = keys.shape[0]
    alive = jnp.ones((n,), bool)
    nsv = jnp.full((n,), ns, jnp.int32)

    # phase 1: (g0, i0) routes each row toward its final slot's group column
    d = _slot_of(spec.part, keys, nsv)
    w1 = (owner // q) * q + d // q
    rank1 = (owner % q).astype(jnp.int64) * ns + d
    gmoved_init = jnp.zeros((ns, ns), jnp.int32).at[owner, d].add(1)
    with jax.named_scope("teshu/exchange"):
        ck = w1.astype(jnp.int64) * jnp.int64(q * ns) + rank1
        perm = jnp.argsort(ck, stable=True)
        keys, vals, owner, alive = (keys[perm], vals[perm], w1[perm],
                                    alive[perm])
    rounds = jnp.int32(0)
    if spec.comb is not None:
        keys, vals, owner, alive, rounds = _combine(
            spec.comb, keys, vals, owner, alive, alive, ns)
    post1 = jnp.zeros((ns,), jnp.int32).at[
        jnp.minimum(owner, ns - 1)].add(alive.astype(jnp.int32))

    # phase 2: (g, i) hands its whole block to the transpose partner (i, g)
    owner = (owner % q) * q + owner // q

    # phase 3: final partition within the destination group
    d = _slot_of(spec.part, keys, nsv)
    rank3 = owner % q
    p3moved = jnp.zeros((ns, ns), jnp.int32).at[
        jnp.minimum(owner, ns - 1), d].add(alive.astype(jnp.int32))
    with jax.named_scope("teshu/exchange"):
        so = jnp.where(alive, d, ns)
        ck = so.astype(jnp.int64) * jnp.int64(q) + rank3
        perm = jnp.argsort(ck, stable=True)
        keys, vals, alive = keys[perm], vals[perm], alive[perm]
        owner = d[perm]
    if spec.comb is not None:
        keys, vals, owner, alive, r = _combine(
            spec.comb, keys, vals, owner, alive, alive, ns)
        rounds = rounds + r
    return keys, vals, owner, alive, gmoved_init, post1, p3moved, rounds


# ---------------------------------------------------------------------------
# The wire format: 64-bit row arrays cross as 32-bit words
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Words:
    """A 64-bit array as 32-bit words, the form in which it crosses between
    host and device.  A pytree: ``words`` is its leaf; ``shape`` and
    ``dtype`` (the array's own), ``columns`` (of its rows: 1 for an array
    with no column axis) and ``pairs`` are static.

    ``words`` is one flat ``uint32`` array of planes: for each column, a
    low word of every row, then a high word.  The words are the halves of
    the value's bits, or where ``pairs`` is set (float64 on a TPU) the
    float32 pair that the TPU holds in place of a float64: the nearest
    float32 (high) and the nearest float32 to what it leaves (low), as the
    TPU runtime's own transfers split a float64.  The TPU holds a 64-bit
    value as two 32-bit halves and lays an ``[N, w]`` array out
    column-major, so a 64-bit array moved as it is costs the runtime a
    split (or join) and a relayout on the host, on every call.  A 1-D
    32-bit array has one linear layout, its planes are contiguous slices on
    the device (taking every other word there is a gather, about 8 ns a
    word on a v5e), and the words are put together and taken apart on the
    device."""

    words: object
    shape: tuple
    dtype: str
    columns: int
    pairs: bool


@functools.cache
def _register_words() -> None:
    import jax

    jax.tree_util.register_dataclass(
        _Words, data_fields=["words"],
        meta_fields=["shape", "dtype", "columns", "pairs"])


def _float_pairs() -> bool:
    """Whether float64 crosses as float32 pairs: on a TPU, whose bitcast to
    float64 truncates where its runtime's transfers round."""
    import jax

    return jax.default_backend() == "tpu"


def _float32_pair(c):
    """Device side: the float32 pair (low, high) whose sum stands for the
    float64 array ``c``, split as ``_host_float32_pairs`` splits."""
    import jax.numpy as jnp

    hi = c.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi),
                   (c - hi.astype(jnp.float64)).astype(jnp.float32), 0)
    return lo, hi


def _host_float32_pairs(rows: np.ndarray) -> np.ndarray:
    """Host side: the float32 pair of each value of the float64 ``rows``
    as planes ``[column, (low, high), row]``: high the nearest float32, low
    the nearest float32 to what high leaves (0 where high is not finite),
    the split of the TPU runtime's own transfers."""
    planes = np.empty((rows.shape[1], 2, rows.shape[0]), np.float32)
    rest = np.empty(rows.shape[0])
    with np.errstate(over="ignore", invalid="ignore"):
        for j, c in enumerate(rows.T):
            lo, hi = planes[j]
            hi[:] = c
            np.subtract(c, hi, out=rest)
            lo[:] = rest
    lo, hi = planes[:, 0], planes[:, 1]
    if not np.isfinite(hi).all():
        lo[~np.isfinite(hi)] = 0
    return planes


def _to_words(a: np.ndarray, columns: int):
    """Host side, on the way in: a 64-bit array with ``columns`` columns as
    its planes of words (one pass); a narrower one as it is."""
    if a.dtype.itemsize != 8:
        return a
    rows = a.reshape(-1, columns)
    pairs = a.dtype.kind == "f" and _float_pairs()
    if pairs:
        planes = _host_float32_pairs(rows).view(np.uint32)
    else:
        planes = np.ascontiguousarray(
            rows.view(np.uint32).reshape(-1, columns, 2).transpose(1, 2, 0))
    return _Words(planes.reshape(-1), a.shape, a.dtype.name, columns, pairs)


def _host_array(x) -> np.ndarray:
    """Host side, on the way back: the array a program result stands for,
    its words joined into one new array."""
    if not isinstance(x, _Words):
        return np.asarray(x)
    out = np.empty(x.shape, x.dtype)
    k = x.columns
    rows = out.reshape(-1, k)                               # a view
    planes = np.asarray(x.words).reshape(k, 2, -1)
    if x.pairs:
        for j in range(k):
            np.add(planes[j, 1].view(np.float32), planes[j, 0].view(np.float32),
                   out=rows[:, j], dtype=np.float64)
    else:
        rows.view(np.uint32).reshape(-1, k, 2)[:] = planes.transpose(2, 0, 1)
    return out


def _unpack(x):
    """Device side, on entry: the 64-bit array ``x``'s words stand for."""
    import jax.numpy as jnp
    from jax import lax

    if not isinstance(x, _Words):
        return x
    k = x.columns
    m = x.words.shape[0] // (2 * k)
    cols = []
    for j in range(k):
        lo = x.words[2 * j * m:(2 * j + 1) * m]
        hi = x.words[(2 * j + 1) * m:(2 * j + 2) * m]
        if x.pairs:
            # where low is 0, high alone: the TPU's float64 sum would lose
            # a NaN's payload bits and flush a float32 subnormal high part
            hi, lo = (lax.bitcast_convert_type(w, jnp.float32).astype(jnp.float64)
                      for w in (hi, lo))
            cols.append(jnp.where(lo == 0, hi, hi + lo))
        else:
            bits = (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)
            cols.append(lax.bitcast_convert_type(bits, jnp.dtype(x.dtype)))
    return jnp.stack(cols, axis=-1).reshape(x.shape)


def _bit_halves(c):
    """The (low, high) uint32 halves of a 64-bit array's bits."""
    import jax.numpy as jnp
    from jax import lax

    bits = lax.bitcast_convert_type(c, jnp.uint64)
    return (bits.astype(jnp.uint32),
            (bits >> jnp.uint64(32)).astype(jnp.uint32))


def _pack(x, like):
    """Device side, on exit: the result ``x`` in the form its operand
    ``like`` crossed in (a TPU refuses any bitcast from float64, so there
    it leaves as the float32 pair the device holds)."""
    import jax.numpy as jnp
    from jax import lax

    if not isinstance(like, _Words):
        return x
    k = like.columns
    halves = []
    for c in ([x[..., j] for j in range(k)] if k > 1 else [x]):
        c = c.reshape(-1)
        if like.pairs:
            halves += [lax.bitcast_convert_type(h, jnp.uint32)
                       for h in _float32_pair(c)]
        else:
            halves += _bit_halves(c)
    return _Words(jnp.concatenate(halves), x.shape, x.dtype.name, k,
                  like.pairs)


# ---------------------------------------------------------------------------
# The trace cache: one jit instance per (program kind, spec, shape), LRU
# ---------------------------------------------------------------------------

_PROGRAMS: OrderedDict = OrderedDict()
_REPLAY_LIMIT = 64
_TRACE_EVICTIONS = 0


def _vmapped(impl, spec: _PlanSpec, keys, vals, owner, *shared):
    """``impl`` over the leading (member) axis of a batch's row operands,
    the routing tables shared."""
    import jax

    return jax.vmap(lambda k, v, o: impl(spec, k, v, o, *shared))(
        keys, vals, owner)


def _program(kind: str, sig: tuple, batch: int = 0):
    """The jit instance for one (program kind, static spec, shape signature),
    creating and LRU-evicting under the replay-cache limit.  It takes the
    rows' keys and values in the wire format (``_Words`` where they are
    64-bit) and gives them back in the form they came in; a batched one
    vmaps the replay over the row operands' leading axis."""
    global _TRACE_EVICTIONS
    import jax

    _register_words()
    key = (kind, batch, sig)
    fn = _PROGRAMS.get(key)
    if fn is None:
        impl = _two_level_impl if kind == "two_level" else _replay_impl
        if batch:
            impl = functools.partial(_vmapped, impl)

        # a per-program closure: jit wrappers over the SAME function share
        # jax's compilation cache, which would make each entry's
        # _cache_size() report the union and break eviction accounting
        def entry(spec, keys, vals, *operands, _impl=impl):
            out = _impl(spec, _unpack(keys), _unpack(vals), *operands)
            return (_pack(out[0], keys), _pack(out[1], vals), *out[2:])
        fn = jax.jit(entry, static_argnames=("spec",))
        _PROGRAMS[key] = fn
    _PROGRAMS.move_to_end(key)
    while len(_PROGRAMS) > _REPLAY_LIMIT:
        _, old = _PROGRAMS.popitem(last=False)
        _TRACE_EVICTIONS += int(old._cache_size())
        old._clear_cache()
    return fn


def _program_inputs(spec: _PlanSpec, low: JaxLowering):
    """(program kind, shared traced tables) for a lowered plan."""
    if spec.template == "two_level":
        return "two_level", ()
    hot = low.skew_hot if low.skew_hot is not None else np.zeros((0,), np.int64)
    share = (low.skew_share if low.skew_share is not None
             else np.zeros((0, 1), np.int32))
    slen = low.skew_len if low.skew_len is not None else np.zeros((0,), np.int32)
    return "scan", (low.gsize, low.slot_map, low.rank_map, low.active,
                    low.global_rank, hot, share, slen)


def replay_cache_size() -> int:
    """Number of compiled replay programs (one per plan spec x shape) — the
    one-trace-per-plan acceptance hook."""
    return sum(int(fn._cache_size()) for fn in _PROGRAMS.values())


def replay_cache_limit() -> int:
    return _REPLAY_LIMIT


def set_replay_cache_limit(limit: int) -> int:
    """Cap the trace cache (LRU over jit instances); returns the previous
    limit.  Shrinking evicts oldest programs immediately, counted by
    :func:`trace_evictions` / the ``teshu_jit_trace_evictions`` gauge."""
    global _REPLAY_LIMIT, _TRACE_EVICTIONS
    prev, _REPLAY_LIMIT = _REPLAY_LIMIT, max(1, int(limit))
    while len(_PROGRAMS) > _REPLAY_LIMIT:
        _, old = _PROGRAMS.popitem(last=False)
        _TRACE_EVICTIONS += int(old._cache_size())
        old._clear_cache()
    return prev


def trace_evictions() -> int:
    """Traces dropped by the replay-cache LRU since process start."""
    return _TRACE_EVICTIONS


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------

def _call_decline(cluster: LocalCluster, args: ShuffleArgs,
                  bufs: dict[int, Msgs]) -> str | None:
    """Call-time decline cause (cluster/arg state the plan can't know), or
    ``None`` when the invocation itself is lowerable.  Reason codes are
    machine-checkable and surface through ``ShuffleResult.fallback_reason``
    / ``cluster.explain()``."""
    if args.plan is None:
        return "no_plan"
    if args.template_id not in JAX_TEMPLATES:
        return "template_not_lowerable"
    if args.stream is not None:
        return "streamed_replay"
    if args.recovery is not None:
        return "recovery_context"
    if args.storage is not None and args.storage.persist:
        # durable persistence writes PART blocks through the shuffle store;
        # the lowered kernel has no store hook, so it would silently skip the
        # durability contract — fall back to the (byte-identical) vectorized
        # executor, which persists
        return "storage_persist"
    if (cluster.failed_workers or cluster.worker_delays
            or cluster.fault_injections):
        return "cluster_fault_state"
    if args.comb_fn is not None and args.comb_fn.name not in _JAX_COMBINERS:
        return "unsupported_combiner"
    if _part_spec(args.part_fn) is None:
        return "unsupported_part_fn"
    widths = {m.width for m in bufs.values() if m.n}
    if len(widths) > 1:
        return "mixed_widths"
    if sum(m.n for m in bufs.values()) == 0:
        return "empty_workload"
    return None


def plan_decline(plan: CompiledPlan) -> str | None:
    """Plan-shape decline cause (mirrors :func:`lower_plan`'s refusals), or
    ``None`` when the plan shape is lowerable."""
    if plan.template_id not in JAX_TEMPLATES:
        return "template_not_lowerable"
    srcs, dsts = list(plan.srcs), list(plan.dsts)
    if plan.template_id == "coordinated" and any(d not in srcs for d in dsts):
        return "ring_mismatch"
    if plan.template_id == "bruck" and set(srcs) != set(dsts):
        return "ring_mismatch"              # the ring IS the destination set
    if plan.template_id == "two_level" and (
            tuple(srcs) != tuple(dsts) or not _is_square(len(srcs))):
        return "grid_mismatch"              # needs a square src==dst grid
    if plan.skew is not None and plan.skew.triggered:
        if plan.template_id == "two_level":
            # phase-3 re-partition would need fresh occurrence indices; the
            # registry marks two_level non-rebalanceable, so only a
            # hand-built plan can get here
            return "skew_shape_mismatch"
        if plan.skew.ndst != len(dsts):
            return "skew_shape_mismatch"    # scatter aimed at another width
        for ld in plan.levels:
            if not ld.eff_cost.beneficial:
                continue
            for w in srcs:
                if len(ld.nbrs.get(w, (w,))) == plan.skew.ndst:
                    # a level-local exchange the scattered partFunc would
                    # also rewrite — occurrence state the trace can't freeze
                    return "skew_group_collision"
    src_set = set(srcs)
    if plan.template_id not in ("bruck", "two_level"):
        for ld in plan.levels:
            for w in srcs:
                if any(n not in src_set for n in ld.nbrs.get(w, (w,))):
                    return "routing_off_srcs"   # a repaired plan routing off-srcs
    return None


def decline_reason(cluster: LocalCluster, args: ShuffleArgs,
                   bufs: dict[int, Msgs]) -> str | None:
    """Why :func:`try_run_jax` would decline this invocation (``None`` when
    it would run): the call-time cause if any, else the plan-shape cause."""
    reason = _call_decline(cluster, args, bufs)
    if reason is not None:
        return reason
    return plan_decline(args.plan)


def can_lower(cluster: LocalCluster, args: ShuffleArgs,
              bufs: dict[int, Msgs]) -> bool:
    """Cheap call-time decline checks (cluster/arg state the plan can't know)."""
    return _call_decline(cluster, args, bufs) is None


def _spec_of(args: ShuffleArgs) -> _PlanSpec:
    plan = args.plan
    return _PlanSpec(
        template=args.template_id,
        comb=args.comb_fn.name if args.comb_fn is not None else None,
        part=_part_spec(args.part_fn),
        initial_comb=(args.template_id == "network_aware"
                      and args.comb_fn is not None),
        ns=len(args.srcs), ndst=len(args.dsts),
        skew=bool(plan is not None and plan.skew is not None
                  and plan.skew.triggered))


def _attached_lowering(cluster, args) -> "JaxLowering | None":
    """The plan's lowering, deriving and attaching on first use (the lower
    span mirrors try_run_jax's solo path)."""
    plan = args.plan
    low = get_lowering(plan)
    if low is None:
        tracer = cluster.obs.tracer
        if tracer.enabled:
            with tracer.span("lower", shuffle_id=args.shuffle_id,
                             tenant=args.tenant,
                             template=args.template_id) as sp:
                low = lower_plan(plan)
                sp.set(declined=low is None)
        else:
            low = lower_plan(plan)
        attach_lowering(plan, _DECLINED if low is None else low)
    return None if low is _DECLINED else low


def try_run_jax(cluster: LocalCluster, args: ShuffleArgs,
                bufs: dict[int, Msgs], manager=None,
                batch_slot: "_BatchSlot | None" = None) -> ShuffleResult | None:
    """Replay ``args.plan`` as one jitted program; None = declined (the
    service falls back to the vectorized executor).  ``batch_slot`` is this
    submission's slice of a batched dispatch (:func:`prepare_batch`): the
    replay consumes it in place of a dispatch of its own, unless the plan
    has changed since the batch probe (then the slot stays unconsumed and
    :func:`finish_batches` settles it)."""
    if not can_lower(cluster, args, bufs):
        return None
    low = _attached_lowering(cluster, args)
    if low is None:
        return None
    if batch_slot is not None and batch_slot.plan is not args.plan:
        batch_slot = None                 # re-planned since the batch probe
    tracer = cluster.obs.tracer
    if not tracer.enabled:
        return _run_lowered(cluster, args, bufs, low, manager, batch_slot)
    with tracer.span("exec", shuffle_id=args.shuffle_id, tenant=args.tenant,
                     engine="jax", template=args.template_id,
                     batched=batch_slot is not None):
        return _run_lowered(cluster, args, bufs, low, manager, batch_slot)


# ---------------------------------------------------------------------------
# Batched dispatch: one vmapped program over same-signature submissions
# ---------------------------------------------------------------------------

class _BatchHandle:
    """One stacked dispatch covering ``size`` same-signature submissions.
    The shared epoch barrier closes once every member has either consumed
    its slice or been abandoned (declined solo / invalidated mid-batch)."""

    def __init__(self, size: int):
        self.pending = size
        self.consumed = 0

    def settle(self, ledger, consumed: bool) -> None:
        self.consumed += consumed
        self.pending -= 1
        if self.pending == 0 and self.consumed:
            ledger.advance_epoch()


@dataclasses.dataclass
class _BatchSlot:
    """One member's slice of a batched dispatch, handed to its replay."""

    handle: _BatchHandle
    plan: object                     # the probed CompiledPlan (identity check)
    outputs: tuple                   # this member's slice of the stacked run
    settled: bool = False

    def settle(self, ledger, consumed: bool) -> None:
        """Count this member out of the batch once, consumed or not."""
        if not self.settled:
            self.settled = True
            self.handle.settle(ledger, consumed)


def _stage_rows(srcs, per_w: list, low: JaxLowering) -> tuple:
    """One call's row operands from its sources' buffers ``per_w``, in
    source order: keys, values, and each row's owner (its source's
    position)."""
    keys = np.concatenate([m.keys for m in per_w])
    vals = np.concatenate([np.ascontiguousarray(m.vals) for m in per_w])
    owner = np.concatenate([np.full(m.n, low.src_pos[w], np.int32)
                            for w, m in zip(srcs, per_w)])
    return keys, vals, owner


def batch_signature(cluster: LocalCluster, args: ShuffleArgs,
                    bufs: dict[int, Msgs]):
    """Hashable grouping key for batched dispatch, or None when this
    submission would not run on the jax executor.  Submissions agreeing on
    the key share one trace AND identical routing tables, so one vmapped
    call replays all of them."""
    if decline_reason(cluster, args, bufs) is not None:
        return None
    low = _attached_lowering(cluster, args)
    if low is None:
        return None
    spec = _spec_of(args)
    width = next((m.width for m in bufs.values() if m.n), 1)
    nrows = sum(bufs.get(w, Msgs.empty(width)).n for w in args.srcs)
    skew_sig = None if low.skew_hot is None else (
        low.skew_hot.tobytes(), low.skew_share.tobytes(),
        low.skew_len.tobytes())
    return (spec, tuple(args.srcs), tuple(args.dsts), nrows, width,
            low.gsize.tobytes(), low.slot_map.tobytes(),
            low.rank_map.tobytes(), low.active.tobytes(),
            low.global_rank.tobytes(), low.bruck_flows, skew_sig)


def prepare_batch(cluster: LocalCluster, members) -> "list[_BatchSlot] | None":
    """Run ONE stacked (vmapped) jit dispatch for ``members`` — a list of
    ``(args, bufs)`` sharing :func:`batch_signature` — and return each
    member's slice of it, in order, for its own replay, which charges its
    own tenant's ledger lanes exactly as a serial run would.

    With tracing on, the dispatch is a ``batch_dispatch`` span {members,
    rows}: ``stage_batch`` (the host concatenation and stacking), then the
    dispatch's ``to_device`` / ``jit_replay`` / ``to_host``."""
    if len(members) < 2:
        return None
    args0, bufs0 = members[0]
    low = get_lowering(args0.plan)
    if low is None or low is _DECLINED:
        return None
    tracer = cluster.obs.tracer
    spec = _spec_of(args0)
    width = next((m.width for m in bufs0.values() if m.n), 1)
    with tracer.span("batch_dispatch", members=len(members)) as sp:
        with tracer.span("stage_batch"):
            staged = [_stage_rows(a.srcs, [b.get(w, Msgs.empty(width))
                                            for w in a.srcs], low)
                      for a, b in members]
            keys, vals, owner = (np.stack(x) for x in zip(*staged))
        sp.set(rows=int(keys.size))
        kind, shared = _program_inputs(spec, low)
        sig = (spec, keys.shape[1:], vals.shape[1:],
               tuple(a.shape for a in shared))
        arrs = _dispatch(tracer, _program(kind, sig, batch=len(members)),
                         spec, (keys, vals, owner, *shared), {},
                         rows=int(keys.size), batch=len(members))
    handle = _BatchHandle(len(members))
    return [_BatchSlot(handle=handle, plan=a.plan,
                       outputs=tuple(x[i] for x in arrs))
            for i, (a, _b) in enumerate(members)]


def finish_batches(slots, ledger) -> None:
    """Abandon every slot of a pass that no replay consumed (its member
    declined solo or was re-planned mid-batch), so each batch's shared
    epoch barrier still closes."""
    for slot in slots:
        slot.settle(ledger, consumed=False)


# ---------------------------------------------------------------------------
# Ledger replay of the irregular templates
# ---------------------------------------------------------------------------

def _charge_bruck(ledger, topo, args, low, gmoved, rowb: int) -> None:
    """bruck's wire flows from the lower-time simulation: per worker, one
    batched charge per round (totals per (worker, level, peer) are what the
    epoch folds, and the threaded sender's per-piece SENDs sum to exactly
    these), then the final self-delivery combine."""
    srcs, dsts = list(args.srcs), list(args.dsts)
    for me, w in enumerate(srcs):
        for peer, pieces in low.bruck_flows[me]:
            if not pieces:
                continue
            nbytes = sum(int(gmoved[o, dp]) for o, dp in pieces) * rowb
            ledger.charge_transfer(w, topo.crossing_level(w, peer), nbytes,
                                   dst=peer, tenant=args.tenant)
    if args.comb_fn is not None:
        for d in dsts:
            dp = low.dst_pos[d]
            ledger.charge_combine(d, int(gmoved[:, dp].sum()) * rowb,
                                  tenant=args.tenant)


def _charge_two_level(ledger, topo, args, low, gmoved_init, post1, p3moved,
                      rowb: int) -> None:
    """two_level's three phases from the traced flow counts, all in the one
    replay epoch (self-sends are free — crossing_level(w, w) < 0 — exactly
    like the threaded mailbox path)."""
    srcs = list(args.srcs)
    ns, q = len(srcs), int(round(len(srcs) ** 0.5))
    comb = args.comb_fn is not None
    # rows sender p holds for destination-group column j after phase 1
    groupsum = np.zeros((ns, q), np.int64)
    for p in range(ns):
        for d in range(ns):
            groupsum[p, d // q] += int(gmoved_init[p, d])
    for p, w in enumerate(srcs):
        g = p // q
        peers = [srcs[g * q + j] for j in range(q)]
        ledger.charge_transfers(
            w,
            np.fromiter((topo.crossing_level(w, n) for n in peers),
                        dtype=np.int64, count=q),
            groupsum[p] * rowb,
            dsts=np.asarray(peers, dtype=np.int64), tenant=args.tenant)
    if comb:
        for p, w in enumerate(srcs):
            g, j = divmod(p, q)
            pre = int(sum(groupsum[g * q + i, j] for i in range(q))) * rowb
            ledger.charge_combine(w, pre, tenant=args.tenant)
    transpose = [(p % q) * q + p // q for p in range(ns)]
    for p, w in enumerate(srcs):
        partner = srcs[transpose[p]]
        ledger.charge_transfer(w, topo.crossing_level(w, partner),
                               int(post1[p]) * rowb, dst=partner,
                               tenant=args.tenant)
    if comb:
        for p, w in enumerate(srcs):
            # the received (possibly own) block is re-COMBed whole
            ledger.charge_combine(w, int(post1[transpose[p]]) * rowb,
                                  tenant=args.tenant)
    for p, w in enumerate(srcs):
        g = p // q
        peers = [srcs[g * q + j] for j in range(q)]
        ledger.charge_transfers(
            w,
            np.fromiter((topo.crossing_level(w, n) for n in peers),
                        dtype=np.int64, count=q),
            np.fromiter((int(p3moved[p, g * q + j]) * rowb for j in range(q)),
                        dtype=np.int64, count=q),
            dsts=np.asarray(peers, dtype=np.int64), tenant=args.tenant)
    if comb:
        for p, w in enumerate(srcs):
            ledger.charge_combine(w, int(p3moved[:, p].sum()) * rowb,
                                  tenant=args.tenant)


def _dispatch(tracer, fn, spec: _PlanSpec, operands: tuple, ids: dict,
              rows: int, **attrs) -> tuple:
    """Run one replay program on host ``operands``; its outputs as host
    arrays, less the last (the fold rounds).  Traced or not, the call is
    the same three stages, each a span of its own: ``to_device`` (the host
    side of the wire format; ``words32`` counts the 64-bit arrays that
    cross as uint32 words), ``jit_replay`` (the program called on the host
    arrays, their copy to the device included) and ``to_host`` (each
    output copied back and joined).  Tracing adds measurement only: a wait
    for the outputs, which ``to_host``'s first read would make anyway, so
    ``jit_replay`` closes when the device is done; ``compiled``, whether
    this program traced anew; and ``fold_rounds``, how many rounds its
    COMB folds ran, per member of a batch."""
    import jax

    keys, vals, *rest = operands
    with jax.enable_x64(True):
        with tracer.span("to_device", **ids, **attrs) as sp:
            operands = (_to_words(keys, 1), _to_words(vals, vals.shape[-1]),
                        *rest)
            sp.set(words32=_count_words(operands))
        with tracer.span("jit_replay", **ids, rows=rows, **attrs) as sp:
            traces = fn._cache_size()
            out = fn(spec, *operands)
            if tracer.enabled:
                jax.block_until_ready(out)
                sp.set(compiled=fn._cache_size() > traces,
                       fold_rounds=np.asarray(out[-1]).tolist())
    with tracer.span("to_host", **ids, **attrs,
                     words32=_count_words(out[:-1])):
        return tuple(_host_array(a) for a in out[:-1])


def _count_words(arrays) -> int:
    return sum(isinstance(a, _Words) for a in arrays)


def _charge_replay(ledger, topo, args: ShuffleArgs, low: JaxLowering,
                   spec: _PlanSpec, arrs: tuple, per_w: list, rowb: int,
                   batched: bool) -> list[tuple]:
    """The reference executors' exact charge sequence, from the program's
    flow counts; returns the per-level observed (level, pre, post) bytes."""
    plan = args.plan
    srcs, dsts = list(args.srcs), list(args.dsts)
    observed: list[tuple] = []
    if spec.template == "two_level":
        gmoved_init, post1, p3moved = arrs[4:]
        _charge_two_level(ledger, topo, args, low, gmoved_init, post1,
                          p3moved, rowb)
        return observed
    lvl_moved, lvl_pre, lvl_post, gmoved = arrs[4:]
    if spec.initial_comb:
        for w, m in zip(srcs, per_w):     # network_aware local pre-combine
            ledger.charge_combine(w, m.nbytes, tenant=args.tenant)
    for li, ld in enumerate(plan.levels if spec.template != "bruck" else ()):
        if not ld.eff_cost.beneficial:
            continue
        if not batched:
            ledger.advance_epoch()        # the stage barrier (PLAN_STAGE)
        staged = low.levels_staged[li]
        for w, peers in staged:
            wp = low.src_pos[w]
            ledger.charge_transfers(
                w,
                np.fromiter((topo.crossing_level(w, n) for n in peers),
                            dtype=np.int64, count=len(peers)),
                np.fromiter(
                    (int(lvl_moved[li, wp, low.src_pos[n]]) * rowb
                     for n in peers), dtype=np.int64, count=len(peers)),
                dsts=np.asarray(peers, dtype=np.int64), tenant=args.tenant)
        for w, _peers in staged:
            pre = int(lvl_pre[li, low.src_pos[w]]) * rowb
            post = int(lvl_post[li, low.src_pos[w]]) * rowb
            if args.comb_fn is not None:
                ledger.charge_combine(w, pre, tenant=args.tenant)
            observed.append((ld.level, pre, post))

    if spec.template == "bruck":
        _charge_bruck(ledger, topo, args, low, gmoved, rowb)
        return observed
    if spec.template in ("vanilla_push", "network_aware"):
        for w in srcs:                    # push: the sender pays
            wp = low.src_pos[w]
            ledger.charge_transfers(
                w,
                np.fromiter((topo.crossing_level(w, d) for d in dsts),
                            dtype=np.int64, count=len(dsts)),
                gmoved[wp].astype(np.int64) * rowb,
                dsts=np.asarray(dsts, dtype=np.int64),
                tenant=args.tenant)
        fetch_order = {d: srcs for d in dsts}
        charge_receiver = False
    elif spec.template == "vanilla_pull":
        fetch_order = {d: srcs for d in dsts}
        charge_receiver = True
    else:                                 # coordinated: ring order, receiver pays
        n = len(srcs)
        fetch_order = {d: [srcs[(srcs.index(d) - t) % n]
                           for t in range(n)] for d in dsts}
        charge_receiver = True
    for d in dsts:
        dp = low.dst_pos[d]
        order = fetch_order[d]
        if charge_receiver:
            ledger.charge_transfers(
                d,
                np.fromiter((topo.crossing_level(s, d) for s in order),
                            dtype=np.int64, count=len(order)),
                np.fromiter((int(gmoved[low.src_pos[s], dp]) * rowb
                             for s in order), dtype=np.int64,
                            count=len(order)),
                dsts=np.full(len(order), d, dtype=np.int64),
                tenant=args.tenant)
        if args.comb_fn is not None:
            ledger.charge_combine(d, int(gmoved[:, dp].sum()) * rowb,
                                  tenant=args.tenant)
    return observed


def _owner_merge(ledger, topo, args: ShuffleArgs, out_bufs: dict) -> None:
    """The skew owner-merge stage, in place on ``out_bufs``: scattered hot
    rows travel back to their base destination — Python-side, mirroring the
    vectorized replay exactly."""
    merge = owner_merge_plan(args.plan.skew, args.part_fn, tuple(args.dsts))
    inbox: dict[int, list] = {}
    for owner_w, (owned_keys, sharers) in merge.items():
        got = []
        for s in sharers:
            hit = np.isin(out_bufs[s].keys, owned_keys)
            rows = out_bufs[s].take(np.nonzero(hit)[0])
            out_bufs[s] = out_bufs[s].take(np.nonzero(~hit)[0])
            ledger.charge_transfer(s, topo.crossing_level(s, owner_w),
                                   rows.nbytes, dst=owner_w,
                                   tenant=args.tenant)
            got.append(rows)
        inbox[owner_w] = got
    for owner_w, got in inbox.items():
        batch = Msgs.concat([out_bufs[owner_w]] + got)
        if args.comb_fn is not None:
            ledger.charge_combine(owner_w, batch.nbytes, tenant=args.tenant)
            out_bufs[owner_w] = args.comb_fn(batch)
        else:
            out_bufs[owner_w] = batch


def _run_lowered(cluster, args: ShuffleArgs, bufs: dict[int, Msgs],
                 low: JaxLowering, manager,
                 batch_slot: "_BatchSlot | None" = None) -> ShuffleResult:
    plan = args.plan
    topo = cluster.topology
    ledger = cluster.ledger
    tracer = cluster.obs.tracer
    ids = {"shuffle_id": args.shuffle_id, "tenant": args.tenant}
    srcs, dsts = list(args.srcs), list(args.dsts)
    participants = sorted(set(srcs) | set(dsts))
    width = next((m.width for m in bufs.values() if m.n), 1)
    rowb = 8 + 8 * width                  # the wire format Msgs.nbytes charges
    spec = _spec_of(args)

    if manager is not None:
        manager.get_template(args.template_id, wid=None)
        for w in participants:
            manager.record_start(w, args.shuffle_id, args.template_id,
                                 tenant=args.tenant)
    before = ledger.snapshot()

    # ---- the compiled data plane ------------------------------------------
    with tracer.span("stage_inputs", **ids):
        per_w = [bufs.get(w, Msgs.empty(width)) for w in srcs]
        if batch_slot is None:
            keys, vals, owner = _stage_rows(srcs, per_w, low)
            kind, shared = _program_inputs(spec, low)
            fn = _program(kind, (spec, keys.shape, vals.shape,
                                 tuple(a.shape for a in shared)))
    if batch_slot is not None:
        arrs = batch_slot.outputs         # this member's slice of the batch
    else:
        arrs = _dispatch(tracer, fn, spec, (keys, vals, owner, *shared), ids,
                         rows=int(keys.shape[0]))

    with tracer.span("ledger_replay", **ids):
        observed = _charge_replay(ledger, topo, args, low, spec, arrs, per_w,
                                  rowb, batched=batch_slot is not None)

    with tracer.span("split_outputs", **ids):
        f_keys, f_vals, f_owner, f_alive = arrs[:4]
        out_bufs: dict[int, Msgs] = {}
        for d in dsts:
            mask = (f_owner == low.dst_pos[d]) & f_alive
            out_bufs[d] = Msgs(f_keys[mask],
                               f_vals[mask].reshape(-1, width))
    if spec.skew:
        with tracer.span("owner_merge", **ids):
            _owner_merge(ledger, topo, args, out_bufs)
    if batch_slot is None:
        ledger.advance_epoch()            # shuffle completion is a barrier
    else:
        batch_slot.settle(ledger, consumed=True)   # the batch settles as one
    after = ledger.snapshot()
    if manager is not None:
        for w in participants:
            manager.record_end(w, args.shuffle_id, args.template_id,
                               tenant=args.tenant)
    return ShuffleResult(
        bufs=out_bufs,
        decisions=list(plan.decisions),
        stats=ledger.delta(before, after),
        observed=aggregate_observed([observed]),
        cached=True,
        vectorized=False,
        engine="jax",
        batched=batch_slot is not None,
    )

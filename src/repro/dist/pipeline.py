"""Pipeline parallelism over the "pod" axis (differentiable, schedulable).

``pipeline_apply`` runs S stacked stages on the mesh's pipeline axis under a
:mod:`repro.dist.schedules` tick plan: each rank holds its stage chunk(s),
microbatches flow rank-to-rank via ``ppermute``, and the last rank's outputs
are gathered with a masked psum.  Numerics match ``sequential_apply``
exactly for every schedule (same ops, same order per microbatch), and
gradients flow to every stage because ``ppermute`` transposes to the
reverse permutation.

Schedules (``schedule=`` / ``virtual_stages=``, see
``repro.dist.schedules``):

  * ``gpipe``        — reference: S ranks, one stage each, bubble S-1.
  * ``one_f_one_b``  — same forward order, in-flight capped at min(S, m).
  * ``interleaved``  — S = ranks x V stages, V chunks per rank; microbatches
    recirculate the ring V times and the bubble shrinks to ranks-1 ticks.

This executor is the *numerics reference*: it replicates the microbatch
array on every rank and autodiffs through the whole tick loop, so its own
peak memory does not depend on the schedule.  The schedule's
``in_flight`` / bubble numbers model what a production backward pass would
pay (the planner's ranking signal, ``repro.core.cost_model``), not this
reference's footprint.

The ``ppermute`` send is double-buffered: the tick-t+1 send is issued
directly off ``stage_fn``'s result, *before* that result is consumed by the
output capture, so XLA's async collective-permute (start/done) overlaps the
wire transfer with the capture/feed bookkeeping of the same tick.

When the mesh cannot host the pipeline (no pipeline axis, stage count not
hosted by the axis under the schedule, or batch not divisible by the
microbatch count) the sequential schedule runs instead — the same fallback
discipline as ``Rules``: an invalid plan must still compute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro.dist.schedules import get_schedule


def sequential_apply(stage_fn, stage_params, x):
    """Reference schedule: fold x through the stacked stages one by one."""

    def body(h, w):
        return stage_fn(w, h), None

    h, _ = jax.lax.scan(body, x, stage_params)
    return h


def _n_stages(stage_params) -> int:
    return jax.tree.leaves(stage_params)[0].shape[0]


def pipeline_apply(stage_fn, stage_params, x, mesh, *, microbatches: int = 1,
                   axis: str = "pod", schedule: str = "gpipe",
                   virtual_stages: int = 1):
    """Run ``stage_params`` (leading dim = stages) as a pipeline over
    ``mesh.shape[axis]`` ranks; x [B, ...] with B % microbatches == 0.

    ``schedule`` picks the tick plan (gpipe | one_f_one_b | interleaved)
    and ``virtual_stages`` the chunks per rank (interleaved only; the stage
    count must equal ranks x virtual_stages).
    """
    n_stages = _n_stages(stage_params)
    batch = x.shape[0]
    sched = get_schedule(schedule)
    plan = None
    if sched is not None and axis in mesh.axis_names \
            and batch % microbatches == 0:
        plan = sched.build(n_stages=n_stages, n_ranks=mesh.shape[axis],
                           microbatches=microbatches,
                           virtual_stages=virtual_stages)
    if plan is None:
        return sequential_apply(stage_fn, stage_params, x)

    m, n_ranks, v = plan.microbatches, plan.n_ranks, plan.virtual_stages
    mb = x.reshape((m, batch // m) + x.shape[1:])
    # stage c*R + r lives on rank r as chunk c: [S, ...] -> [R, V, ...]
    ws = jax.tree.map(
        lambda a: jnp.swapaxes(a.reshape((v, n_ranks) + a.shape[1:]), 0, 1),
        stage_params)
    fwd = [(r, (r + 1) % n_ranks) for r in range(n_ranks)]

    def body(w_local, mb):
        # w_local [1, V, ...]: this rank's stage chunks; mb [m, b, ...]
        # replicated.
        rank = jax.lax.axis_index(axis)
        w_chunks = jax.tree.map(lambda a: a[0], w_local)
        zero = jnp.zeros_like(mb[0])
        carry = zero
        outs = jnp.zeros_like(mb)
        # recirculation buffer: rank 0 parks chunk outputs wrapping around
        # the ring until their next pass starts (interleaved only)
        buf = jnp.zeros_like(mb) if v > 1 else None
        for t, tick in enumerate(plan.ticks):
            if tick.stash_buf >= 0:
                buf = buf.at[tick.stash_buf].set(carry)
            if tick.feed_mb >= 0:
                feed = mb[tick.feed_mb]
            elif tick.feed_buf >= 0:
                feed = buf[tick.feed_buf]
            else:
                # bubble/drain tick: feed zeros, never real data — re-feeding
                # a real microbatch here would recompute it for nothing and
                # overcharge HLO-based roofline scores
                feed = zero
            x_in = jnp.where(rank == 0, feed, carry)
            if v > 1:
                # which chunk this rank runs follows from its entry tick
                c = jnp.clip((t - rank) // plan.entry_stride, 0, v - 1)
                w = jax.tree.map(lambda a: a[c], w_chunks)
            else:
                w = jax.tree.map(lambda a: a[0], w_chunks)
            y = stage_fn(w, x_in)
            # double-buffered send: issue the permute feeding tick t+1
            # before y is consumed by the capture below
            send = jax.lax.ppermute(y, axis, fwd)
            if tick.capture_out >= 0:
                outs = outs.at[tick.capture_out].set(
                    jnp.where(rank == n_ranks - 1, y, jnp.zeros_like(y)))
            carry = send
        return jax.lax.psum(outs, axis)

    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(PartitionSpec(axis), PartitionSpec()),
                        out_specs=PartitionSpec(), axis_names={axis},
                        check_vma=False)(ws, mb)
    return out.reshape(x.shape)

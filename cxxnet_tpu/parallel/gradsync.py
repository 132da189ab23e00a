"""Layerwise-overlapped gradient sync: reduction groups + boundaries.

The reference's headline scaling trick is one async updater per weight
tensor that pushes that layer's gradient the moment its backprop
completes, with parameter-server priority = ``-layer_index`` so top
layers sync first (async_updater-inl.hpp; SURVEY.md §2.7). The SPMD
port had, until this module, the degenerate version: XLA inserts ONE
gradient all-reduce wherever its scheduler likes, usually after the
whole backward — correct, but the cross-host (DCN) traffic serializes
behind backprop instead of hiding under it.

This module is the structured equivalent:

* :func:`partition_groups` splits the weight tree into **reduction
  groups** ordered by REVERSE layer index — per-layer groups by
  default, or size-bucketed (``grad_sync_bucket_mb``) so tiny layers
  amortize one collective's latency floor. Every tensor lands in
  exactly one group (property-tested), and group 0 holds the topmost
  layers — the ones whose backward finishes first.
* :func:`apply_group_boundaries` pins a ``jax.custom_vjp`` identity
  around each group's parameters inside the differentiated loss. The
  forward is a no-op; the backward joins the group's cotangents (the
  gradients) with one ``jax.lax.optimization_barrier``, making each
  group an atomic, independently schedulable unit: XLA can no longer
  fuse the per-group all-reduces into one tail collective, and its
  latency-hiding scheduler is free to issue group g's reduction the
  moment g's backward completes — while the remaining (earlier-layer)
  backprop still runs. The issue order is the backprop completion
  order, i.e. reverse layer index — exactly the reference's priority
  rule, now emergent from data flow instead of a priority queue.

Numerically the boundary is the identity, so ``grad_sync = overlap``
is bit-identical to ``fused`` — same semantics, different schedule —
pinned by the dryrun parity tests at H=2 and H=4
(tests/test_gradsync.py).

:func:`measure_step_breakdown` is the measurement half: the
schema-validated ``step_breakdown`` record (backprop ms, reduce ms,
overlap ratio, optimizer-state bytes/host) behind :mod:`.scaling`.
A CPU dryrun's collectives are shared-memory copies, not DCN — the
record says so; device columns stay pending a chip window.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .topology import current_topology

GroupKey = Tuple[str, str]               # (layer key, weight tag)


@dataclass(frozen=True)
class ReductionGroup:
    """One reduction group: a contiguous run of the reverse-layer-
    ordered weight list that syncs as a single collective unit."""
    index: int                           # issue order (0 syncs first)
    keys: Tuple[GroupKey, ...]           # (layer, tag) members
    nbytes: int                          # summed logical bytes
    layer_span: Tuple[int, int]          # (max, min) layer index


def _leaf_bytes(leaf) -> int:
    return int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize


def partition_groups(params: Mapping[str, Mapping[str, Any]],
                     layer_index: Mapping[str, int],
                     bucket_mb: float = 0.0
                     ) -> List[ReductionGroup]:
    """Partition the weight tree into reduction groups.

    Weights are ordered by reverse layer index (top layers first — the
    reference's PS priority = ``-layer_index``), tie-broken by (layer
    key, tag) so the partition is deterministic for any dict order.
    ``bucket_mb <= 0``: one group per layer (all of a layer's tags sync
    together). ``bucket_mb > 0``: greedy size bucketing — a group
    closes once it holds at least ``bucket_mb`` MB, so sub-bucket
    layers merge into one collective (the latency floor of a DCN
    all-reduce dwarfs a small tensor's payload) while a tensor is
    never split across groups. Every (layer, tag) lands in exactly one
    group at any bucket size (tests/test_gradsync.py property test).
    """
    order = sorted(
        ((lk, tag) for lk, pt in params.items() for tag in pt),
        key=lambda kt: (-int(layer_index[kt[0]]), kt[0], kt[1]))
    groups: List[ReductionGroup] = []
    cur: List[GroupKey] = []
    cur_bytes = 0
    bucket_bytes = float(bucket_mb) * (1 << 20)

    def close():
        nonlocal cur, cur_bytes
        if not cur:
            return
        lis = [int(layer_index[lk]) for lk, _ in cur]
        groups.append(ReductionGroup(
            index=len(groups), keys=tuple(cur), nbytes=cur_bytes,
            layer_span=(max(lis), min(lis))))
        cur, cur_bytes = [], 0

    prev_li = None
    for lk, tag in order:
        li = int(layer_index[lk])
        if bucket_bytes <= 0 and prev_li is not None and li != prev_li:
            close()                      # per-layer mode: layer edge
        cur.append((lk, tag))
        cur_bytes += _leaf_bytes(params[lk][tag])
        prev_li = li
        if bucket_bytes > 0 and cur_bytes >= bucket_bytes:
            close()
    close()
    return groups


# -- the boundary: numeric identity, scheduling unit ----------------------

@jax.custom_vjp
def _group_boundary(xs):
    return xs


def _group_boundary_fwd(xs):
    return xs, None


def _group_boundary_bwd(_, cts):
    # joint barrier over the group's cotangents: the gradients become
    # one atomic bundle the scheduler places as a unit, and the
    # SPMD-inserted all-reduce that consumes them hangs off the bundle
    # as an independently issuable collective. Identity numerics.
    with jax.named_scope("grad_sync"):
        return (jax.lax.optimization_barrier(cts),)


_group_boundary.defvjp(_group_boundary_fwd, _group_boundary_bwd)


def apply_group_boundaries(params, groups: Sequence[ReductionGroup]):
    """Thread each group's parameters through its boundary; returns a
    tree with identical structure and values. Call INSIDE the
    differentiated loss so the backward barriers land in the gradient
    graph. Keys absent from ``params`` (a pruned tree) are skipped —
    the boundary set follows the tree it is applied to."""
    out = {lk: dict(pt) for lk, pt in params.items()}
    for g in groups:
        keys = [(lk, tag) for lk, tag in g.keys
                if lk in out and tag in out[lk]]
        if not keys:
            continue
        with jax.named_scope("grad_sync"):
            marked = _group_boundary(
                tuple(out[lk][tag] for lk, tag in keys))
        for (lk, tag), v in zip(keys, marked):
            out[lk][tag] = v
    return out


# -- byte accounting ------------------------------------------------------

def tree_logical_bytes(tree) -> int:
    """Summed logical (unsharded) bytes of every array leaf."""
    return sum(_leaf_bytes(x) for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "shape"))


def host_resident_bytes(tree) -> int:
    """Distinct bytes of ``tree`` resident on ONE host: unique shard
    slices across host 0's device block (the dryrun partitions
    ``jax.devices()`` into equal rank-ordered blocks; a real
    multi-process run's addressable shards are already one host's).
    Replicated leaves count once — each of the host's devices holds
    the same slice; ZeRO-sharded leaves count the host's disjoint
    1/world slices, i.e. ~1/hosts of the logical bytes."""
    topo = current_topology()
    host0 = set(jax.devices()[:topo.local_device_count])
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            if hasattr(leaf, "shape"):
                total += _leaf_bytes(leaf)
            continue
        seen = set()
        for s in leaf.addressable_shards:
            if s.device not in host0:
                continue
            key = tuple((sl.start, sl.stop, sl.step) for sl in s.index)
            if key in seen:
                continue
            seen.add(key)
            total += int(np.prod(s.data.shape)) \
                * np.dtype(s.data.dtype).itemsize
    return total


def frozen_group_count(opt_state) -> int:
    """(layer, tag) groups whose optimizer state was skipped (the
    ``lr_mult = 0`` frozen-group allocation skip, doc/updater.md)."""
    return sum(1 for tags in opt_state.values()
               for st in tags.values() if not st)


# -- the step_breakdown measurement ---------------------------------------

def _time_ms(fn: Callable[[], Any], repeats: int) -> float:
    """Best-of-``repeats`` wall of ``fn`` (first call warms/compiles
    outside the timed window), blocking on the result."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def measure_step_breakdown(trainer, batch, repeats: int = 3
                           ) -> Dict[str, Any]:
    """Measure the ``step_breakdown`` record on a live trainer.

    Times three programs on the trainer's current weights and the given
    batch: the gradient program alone (forward + backward + the grads'
    own reduction), a reduction-only program over gradient-shaped
    buffers (the collective at the mode's group granularity — one
    barrier-joined ``psum`` bundle per reduction group), and the full
    train step via real ``trainer.update`` dispatches. The overlap
    ratio is the fraction of a standalone reduce pass the full step
    hides: ``clamp01((backprop_ms + reduce_ms - step_ms) /
    reduce_ms)``. Optimizer-state bytes report both the logical
    (unsharded) footprint and the distinct bytes resident per host —
    under ``optim_shard = 1`` the per-host number drops to ~1/hosts.

    Honesty: this advances the trainer by ``repeats + 1`` real updates
    (call it at a measurement boundary, as the scaling sweep does), and on a
    CPU dryrun every collective is a shared-memory copy, not DCN — the
    timings bound the schedule shape only; device columns stay pending
    a chip window (doc/distributed.md).
    """
    data, labels, mask, extra = trainer._device_batch(batch)
    net = trainer.net
    mesh = trainer.mesh
    key = trainer._base_key
    net_state = trainer.net_state
    groups = getattr(trainer, "_sync_groups", None)
    if groups is None:                   # fused: one monolithic group
        groups = partition_groups(trainer.params, trainer._layer_index,
                                  bucket_mb=float("inf"))
    overlap = trainer.grad_sync == "overlap"

    def _loss(p):
        loss, _aux = net.loss_fn(
            p, net_state, data, labels, mask, extra=extra, rng=key,
            collect_nodes=())
        return loss

    def _grad_only(p):
        if overlap:
            p = apply_group_boundaries(p, groups)
        return jax.grad(_loss)(p)

    grad_prog = jax.jit(_grad_only)

    def _reduce_only(grads):
        def per_shard(g):
            out = {lk: dict(pt) for lk, pt in g.items()}
            for grp in groups:
                keys = [(lk, tag) for lk, tag in grp.keys
                        if lk in out and tag in out[lk]]
                if not keys:
                    continue
                red = jax.lax.optimization_barrier(tuple(
                    jax.lax.psum(out[lk][tag], "data")
                    for lk, tag in keys))
                for (lk, tag), v in zip(keys, red):
                    out[lk][tag] = v
            return out
        from jax.experimental.shard_map import shard_map
        return shard_map(per_shard, mesh=mesh,
                         in_specs=P(), out_specs=P())(grads)

    reduce_prog = jax.jit(_reduce_only)

    grads = grad_prog(trainer.params)
    backprop_ms = _time_ms(lambda: grad_prog(trainer.params), repeats)
    reduce_ms = _time_ms(lambda: reduce_prog(grads), repeats)

    def one_step():
        trainer.update(batch)
        return trainer.params

    step_ms = _time_ms(one_step, repeats)
    overlap_ratio = 0.0
    if reduce_ms > 0:
        overlap_ratio = max(0.0, min(
            1.0, (backprop_ms + reduce_ms - step_ms) / reduce_ms))
    opt_unsharded = tree_logical_bytes(trainer.opt_state)
    return {
        "hosts": current_topology().num_hosts,
        "grad_sync": trainer.grad_sync,
        "optim_shard": int(trainer.shard_optimizer),
        "groups": len(groups),
        "bucket_mb": float(trainer.grad_sync_bucket_mb),
        "backprop_ms": round(backprop_ms, 4),
        "reduce_ms": round(reduce_ms, 4),
        "step_ms": round(step_ms, 4),
        "overlap_ratio": round(overlap_ratio, 4),
        "grad_bytes": tree_logical_bytes(grads),
        "opt_state_bytes_unsharded": opt_unsharded,
        "opt_state_bytes_per_host": host_resident_bytes(
            trainer.opt_state),
        "frozen_groups": frozen_group_count(trainer.opt_state),
    }

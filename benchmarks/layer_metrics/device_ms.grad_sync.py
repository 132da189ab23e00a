"""Trainer / dispatch: time a chip's op line spends in the gradients'
exchange between the chips of a data-parallel step, in ms a trained batch
over the whole dispatches the trace holds, mean over the chips. Counted
are the collective ops of the scoped programs (all-reduce, reduce-scatter,
all-gather, ...: ``trace_reduce.categorize``, as the breakdown's
``collective`` class) under whatever scope they lie, and every op under
the trainer's ``grad_sync`` scope. The default ``grad_sync = fused`` opens
no such scope: the partitioner places its all-reduce under the layers'
scopes, so the op's name is what finds it. ``overlap`` opens the scope,
and what XLA fuses under it counts too. An op is counted once.

This is the exposed part of the exchange: a collective's duration
includes its wait for the slowest chip, and a transfer that runs beneath
other ops shows only as its start and done ops. 0.0 where the step has no
collective (one chip). A collective the program's record gives no scope
is not counted (scope_groups.walk's join): the breakdown's ``collective``
seconds over the traced batches are the check. Left out where under 90 %
of the scoped programs' op time maps to a scope, or where the program
writes no such record (scope_groups.py). One ``device_exchange`` line
before the result line names the instructions counted, each with its
scope and its ms. Moves train_img_per_s.
"""

from collections import defaultdict

import span_reduce

import scope_groups
import trace_reduce


def in_exchange(path: str, instruction: str) -> bool:
    return (trace_reduce.categorize(instruction) == "collective"
            or scope_groups.outer_kind(path) == "grad_sync")


def read(run):
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    by_instruction = defaultdict(float)
    for ms, path, name in ops:
        if in_exchange(path, name):
            by_instruction[(name, path)] += ms
    span_reduce.phase("device_exchange", instructions=[
        [n, p, ms] for (n, p), ms in
        sorted(by_instruction.items(), key=lambda kv: -kv[1])])
    return sum(by_instruction.values())

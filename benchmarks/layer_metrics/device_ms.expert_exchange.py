"""Layers / XLA fusions, the expert axis: time a chip's op line spends in
the expert layers' exchange between the chips, in ms a trained batch over
the whole dispatches the trace holds, mean over the chips. Counted are the
all-to-all ops of the scoped programs by name, under whatever scope they
lie, and every op under an expert layer's ``exchange`` scope
(``moe.<key>/exchange``: the gather that packs a pick's row into the send
buffer, the all-to-alls there and back, the gather that unpacks the
results; forward, recomputed forward and backward), each once: the rule
of ``device_ms.grad_sync``. This is the exposed part of the exchange: a
collective's duration includes its wait for the slowest chip, and a
transfer that runs beneath other ops shows only as its start and done
ops. Nothing to read (None) where the program opens no ``exchange`` scope
and holds no all-to-all (a program with no expert axis), or under
``scope_groups``' guard. One ``device_expert_exchange`` line before the
result line names the instructions counted, each with its scope and its
ms. Moves train_img_per_s.
"""

from collections import defaultdict

import span_reduce

import scope_groups


def in_exchange(path: str, instruction: str) -> bool:
    # (the TPU's compiler names them all_to_all.N)
    return instruction.startswith(("all-to-all", "all_to_all")) or (
        scope_groups.outer_kind(path) == "moe"
        and scope_groups.inner_part(path).split("/")[0] == "exchange")


def exchange_ms(run):
    """``{(instruction, scope path): ms a trained batch}`` of the
    exchange's ops; None under the guard."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    out = defaultdict(float)
    for ms, path, name in ops:
        if in_exchange(path, name):
            out[(name, path)] += ms
    return dict(out)


def read(run):
    by_instruction = exchange_ms(run)
    if not by_instruction:
        return None
    span_reduce.phase("device_expert_exchange", instructions=[
        [n, p, ms] for (n, p), ms in
        sorted(by_instruction.items(), key=lambda kv: -kv[1])])
    return sum(by_instruction.values())

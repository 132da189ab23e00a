"""The training driver for a language model: a resident batch of token ids.

``input = resident``  ``NetTrainer`` built from the configuration's
    netconfig, one seeded batch made on the device (ids uniform over the
    rows of the vocabulary slice the net holds, labels the ids one
    position later, one document a sequence), ``run_steps`` dispatches
    of ``steps_per_dispatch`` back to back until ``--seconds`` have
    passed. ``train_img_per_s`` follows ``drivers/train.py``'s rule (its
    ``finish``): the examples of the dispatches that completed inside the
    window over the time to the last completion. An example here is one
    sequence of ``seq_len`` positions, so tokens a second is ``seq_len``
    times it.

``correct``, besides the resident train cell's checks: before the window
the plain reference (``benchmarks/<config.reference>``: float32, highest
precision, dense routing, a sequence at a time, attention in blocks of
``reference_q_block`` queries, a layer recomputed at a time) starts from
the same seeded weights (the net's own initialiser under the trainer's
seed), takes ``steps_per_dispatch`` Adam steps on the same batch, and is
moved to the host; then the timed program, from its own seeded weights at
the timed shapes, makes its first dispatch. Compared, each against a
limit of the configuration's ``limits``:

``loss_rel``  the dispatch's loss (its LAST step's: the trainer's scan
    returns no other) against the reference's loss of that step, which
    depends on every gradient and on the update;
``step_rel``  the norm of (program's parameters - reference's) after the
    dispatch over the norm of (reference's - seeded): 0 is the
    reference's own step, 1 is what a state left unchanged reads.

The net's seeded weights must equal the reference's start exactly (a
checksum a tensor), every router product of the compiled step must be
float32 at ``highest`` precision (read from its text), the expert layers must report no dropped pick, and
the share of picks on held experts must lie within ``held_share_off`` of
held / all (a guard against gross misrouting, not a precision limit: the
share moves with the seeded router, PERF.md).

``reference_also = {"products": <dtype>}`` or ``{"router_dtype":
<dtype>}`` in a mix (no cell sets it) runs the reference once more with
every product's operands, or the router's, rounded to that dtype and
prints what THAT reads on both comparisons: the second reading a limit
is set from (PERF.md).
"""

import importlib.util
import os
import time
from typing import Any, Dict, Tuple

from harness import BenchFailure, Run, trace_options
from span_reduce import phase


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


HERE = os.path.dirname(os.path.abspath(__file__))
train = _load(os.path.join(HERE, "train.py"), "bench_driver_train")


def run(r: Run) -> None:
    if r.traffic["input"] != "resident":
        raise BenchFailure("train_tokens traffic: input = %r"
                           % r.traffic["input"])
    run_resident(r)


def reference_config(c: Dict[str, Any]) -> Tuple[Dict[str, Any], Tuple]:
    """The reference's sizes from the configuration file: the published
    keys as they stand, the router at its published width, and the
    experts held here as ``(first, count)``."""
    cfg = {k: c[k] for k in (
        "hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "kv_lora_rank", "rms_norm_eps", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
        "routed_scaling_factor", "norm_topk_prob", "vocab_size")}
    cfg["rope_theta"] = float(c["rope_theta"])
    cfg["n_routed_experts"] = int(c["published"]["n_routed_experts"])
    return cfg, (int(c["expert_first"]), int(c["n_routed_experts"]))


def tree_sq(a, b) -> float:
    """Sum of squares of ``a - b`` over two parameter trees, a tensor at
    a time (``b`` may lie on the host: only one of its tensors is on the
    device at once)."""
    import jax
    import jax.numpy as jnp
    return sum(float(jnp.sum((x - jnp.asarray(y)) ** 2)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))


def run_reference(r: Run, net, ids, labels, n_steps: int):
    """The reference's ``n_steps`` Adam steps from the net's seeded
    weights. Returns what the comparison needs, all on the host: the
    losses, the parameters after the steps, the squared norm of their
    change, a checksum a tensor of the start — and, with
    ``reference_also``, the same steps' readings at that precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    c, t = r.config, r.traffic
    ref = _load(os.path.join(r.root, "benchmarks", c["reference"]),
                "bench_reference")
    cfg, held = reference_config(c)
    adam = c["adam"]
    qb = int(t["reference_q_block"])

    def steps(**lower):
        grad = jax.jit(lambda p, b, i, l: ref.loss_and_grad(
            p, b, i, l, cfg, held=held, q_block=qb, remat=True, **lower))
        update = jax.jit(
            lambda p, g, s, step: ref.adam_step(
                p, g, s, step, adam["lr"], adam["beta1"], adam["beta2"]),
            static_argnums=(3,), donate_argnums=(0, 1, 2))
        params, state = net.init(jax.random.PRNGKey(r.seed32()))
        biases = {k: v["bias"] for k, v in state.items() if "bias" in v}
        opt, losses = None, []
        for step in range(1, n_steps + 1):
            # the gradient program peaks at 12.5 GB of a v5e's 16.9 (my
            # compile for a described chip, PR 28), so Adam's moments
            # (4.5 GB) wait on the host while it runs
            value, grads = grad(params, biases, ids, labels)
            losses.append(float(value))
            opt = ref.adam_init(params) if opt is None \
                else jax.tree_util.tree_map(jnp.asarray, opt)
            params, opt = update(params, grads, opt, step)
            if step < n_steps:
                opt = jax.tree_util.tree_map(np.asarray, opt)
        del opt, grads
        # the start again (the initialiser is a function of the seed):
        # a copy kept through the steps would not fit beside them
        start = net.init(jax.random.PRNGKey(r.seed32()))[0]
        moved = tree_sq(params, start)
        sums = jax.tree_util.tree_map(lambda w: float(jnp.sum(jnp.abs(w))),
                                      start)
        return losses, params, moved, sums

    t0 = time.time()
    losses, params, moved, sums = steps()
    out = {"losses": losses, "moved_sq": moved, "sums": sums,
           "params": jax.tree_util.tree_map(np.asarray, params)}
    del params
    out["wall_s"] = time.time() - t0
    also = t.get("reference_also")
    if also:
        t1 = time.time()
        l2, p2, _, _ = steps(**also)
        out["also"] = {
            "lower": also, "losses": l2,
            "loss_rel": abs(l2[-1] - losses[-1]) / abs(losses[-1]),
            "step_rel": (tree_sq(p2, out["params"]) / moved) ** 0.5,
            "wall_s": time.time() - t1}
        del p2
    return out


def run_resident(r: Run) -> None:
    import jax
    import jax.numpy as jnp
    from cxxnet_tpu.io.data import DataBatch
    from cxxnet_tpu.monitor import MemorySink, Monitor
    from cxxnet_tpu.monitor.schema import validate_records
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config

    t, c = r.traffic, r.config
    batch, n_steps = int(t["batch_size"]), int(t["steps_per_dispatch"])
    seq, vocab = int(t["seq_len"]), int(c["vocab_size"])
    pairs = parse_config(train.netconfig(c)) + [
        ("batch_size", str(batch)), ("dtype", c["dtype"]), ("silent", "1"),
        ("seed", str(r.seed32()))]

    # the batch, made on the device from the seed: seq + 1 ids a row
    ids = jax.jit(lambda key: jax.random.randint(
        key, (batch, seq + 1), 0, vocab, jnp.int32))(
            jax.random.PRNGKey(r.seed32()))
    data, label = ids[:, :seq], ids[:, 1:]

    # 1. the reference, alone on the device (its float32 state and the
    #    trainer's do not fit side by side), from the net's own initialiser
    from cxxnet_tpu.graph import NetGraph
    from cxxnet_tpu.nnet.net import FuncNet
    try:
        graph = NetGraph()
        graph.configure(pairs)
        net = FuncNet(graph, batch)
    except ValueError as e:
        # a program without these layer types (any commit before them)
        raise BenchFailure("this program cannot build configuration %s: %s"
                           % (c["name"], e))
    if tuple(graph.input_shape) != (1, 1, seq):
        raise BenchFailure("the netconfig's input_shape %r is not the mix's "
                           "seq_len %d" % (graph.input_shape, seq))
    ref = run_reference(r, net, data, label, n_steps)
    del net, graph
    phase("reference", losses=ref["losses"], wall_s=ref["wall_s"],
          moved_norm=ref["moved_sq"] ** 0.5, also=ref.get("also"))

    # 2. the program
    trainer = NetTrainer(pairs)
    trainer.init_model()
    if trainer.batch_size != batch:
        raise BenchFailure("the trainer took batch_size %d, not %d"
                           % (trainer.batch_size, batch))
    mesh = {k: int(v) for k, v in trainer.mesh.shape.items()}
    sums = jax.tree_util.tree_map(lambda w: float(jnp.sum(jnp.abs(w))),
                                  trainer.params)
    r.check(sums == ref["sums"],
            "the trainer's seeded weights are not the reference's start")
    b = DataBatch(data=trainer._put_batch_array(data),
                  label=trainer._put_batch_array(label.astype(jnp.float32)))
    sink = MemorySink()
    trainer.set_monitor(Monitor(sink))         # emits model_info + layout
    trainer.precompile(n_steps=n_steps, per_batch=False)
    (key,) = [k for k in trainer.programs.aot if k[0] == "run_steps"]
    hlo = trainer.programs.aot[key].as_text()
    placed = b.data.addressable_shards
    r.notes.update(mesh=mesh, batch_shards=len(placed),
                   batch_devices=len({s.device for s in placed}),
                   all_reduce="all-reduce" in hlo)
    if not r.rehearse:
        r.check(r.notes["batch_devices"] == r.chips == mesh.get("data"),
                "the batch lies on %d device(s), mesh %r, cell of %d chip(s)"
                % (r.notes["batch_devices"], mesh, r.chips))
        r.check(not r.notes["all_reduce"],
                "an all-reduce in a one-chip step")
        # a router in bfloat16 reads LESS on the two comparisons below
        # than the program's own bfloat16 products do (PERF.md, PR 28),
        # so its precision is held by the compiled step itself
        route = [ln for ln in hlo.splitlines()
                 if " convolution(" in ln and "/route/" in ln]
        r.notes["router_products"] = len(route)
        r.check(bool(route) and all(
            "operand_precision={highest,highest}" in ln and "= f32[" in ln
            for ln in route),
            "a router product of the compiled step is not float32 at "
            "highest precision (%d found)" % len(route))

    # 3. the timed program's first dispatch, from the seeded weights,
    #    against the reference (it doubles as the warm-up)
    trainer.run_steps(b, n_steps)
    loss_first = trainer.last_loss
    num = tree_sq(trainer.params, ref.pop("params"))
    limits = c["limits"]
    loss_rel = abs(loss_first - ref["losses"][-1]) / abs(ref["losses"][-1])
    step_rel = (num / ref["moved_sq"]) ** 0.5
    setup_records = list(sink.records)
    moes = [x for x in setup_records if x["event"] == "moe"]
    phase("compared", loss_program=loss_first,
          loss_reference=ref["losses"][-1], loss_rel=loss_rel,
          loss_rel_limit=limits["loss_rel"], step_rel=step_rel,
          step_rel_limit=limits["step_rel"],
          held_share=[m["held_share"] for m in moes],
          load_max_over_mean=[m["load_max_over_mean"] for m in moes])
    r.notes["compared"] = {"loss_rel": loss_rel, "step_rel": step_rel}
    r.check(loss_rel <= limits["loss_rel"],
            "loss after %d steps %r, reference %r: off by %.3g of it, limit "
            "%.3g" % (n_steps, loss_first, ref["losses"][-1], loss_rel,
                      limits["loss_rel"]))
    r.check(step_rel <= limits["step_rel"],
            "parameters after %d steps lie %.3g of the reference's step "
            "from the reference's, limit %.3g"
            % (n_steps, step_rel, limits["step_rel"]))
    share = float(c["n_routed_experts"]) / c["published"]["n_routed_experts"]
    r.check(bool(moes) and all(m["dropped"] == 0 for m in moes),
            "an expert layer dropped picks (or wrote no moe record)")
    r.check(all(abs(m["held_share"] - share) <= limits["held_share_off"]
                for m in moes),
            "share of picks on held experts %r, held / all = %.4f, limit "
            "+-%g" % ([m["held_share"] for m in moes], share,
                      limits["held_share_off"]))

    # 4. the window (drivers/train.py: run_resident's loop)
    sink.clear()
    first_traced = -1
    if r.trace:
        r.trace_dir = os.path.join(r.out_dir, "trace")
        first_traced = 2                       # third dispatch on
    t0 = time.time()
    i = 0
    t_trace = 0.0
    while time.time() - t0 < r.seconds:
        if i == first_traced:
            jax.profiler.start_trace(r.trace_dir,
                                     profiler_options=trace_options(t))
            t_trace = time.time()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            trainer.run_steps(b, n_steps)
        i += 1
        if r.trace and i == first_traced + int(t["trace_dispatches"]):
            r.trace_span_s = time.time() - t_trace   # not the profiler's work
            jax.profiler.stop_trace()
    if r.trace and not r.trace_span_s:
        raise BenchFailure("the window held %d dispatches, too few to trace "
                           "dispatches %d..%d" % (i, first_traced,
                                                  first_traced
                                                  + int(t["trace_dispatches"])))
    validate_records(sink.records)
    r.records = setup_records + list(sink.records)
    steps = [x for x in sink.records if x["event"] == "step"]
    train.finish(r, steps, t0)
    r.notes["loss_warm_up"] = loss_first
    r.notes["tokens_per_s"] = sum(s["tokens"] for s in steps) / r.window_s
    r.check(steps[-1]["loss"] < loss_first,
            "the last loss %r is not below the first dispatch's %r: %d "
            "updates on one batch must fit it"
            % (steps[-1]["loss"], loss_first, len(steps) * n_steps))
    r.check(all(m["dropped"] == 0 for m in r.in_window("moe")),
            "an expert layer dropped picks inside the window")

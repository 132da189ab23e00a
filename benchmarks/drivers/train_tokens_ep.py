"""The training driver for a language model whose experts lie on an expert
axis over the cell's chips: a resident batch of token ids, as
``drivers/train_tokens.py`` makes it, split over the chips.

``input = resident``  ``NetTrainer`` built from the configuration's
    netconfig (its ``moe`` layers name ``expert_axis = data``: the
    trainer's mesh puts the batch and the experts on the same axis), one
    seeded batch made on the device (ids uniform over the held rows of
    the vocabulary, labels the ids one position later, one document a
    sequence), ``run_steps`` dispatches of ``steps_per_dispatch`` back to
    back until ``--seconds`` have passed; ``train_img_per_s`` as
    ``drivers/train.py``'s ``finish`` gives it (an example is one
    sequence, for the whole cell).

The program is asked first whether it can run the configuration as its
deployment does: a program whose expert layers know no expert axis (any
commit before it) is refused with a ``BenchFailure`` before anything is
made on a device.

``correct``: before the window the plain reference
(``benchmarks/<config.reference>``: float32, highest precision, dense
routing over every expert, a sequence at a time, attention in blocks of
``reference_q_block`` queries, a layer recomputed at a time; its gradient
program takes as many sequences of the batch at a time as the cell has
chips, the parts' gradients summed, what is summed so far waiting on the
host) starts from the same seeded weights (``FuncNet.init_on``: each
tensor made on its chips, the experts' over the expert axis), takes
``steps_per_dispatch`` Adam steps on the same batch with its expert
tensors over the chips and the partitioner's own placement of the work
(Adam's moments wait on the host while the gradient program runs), and is
moved to the host; then the timed program makes its first dispatch.
Compared, each against the configuration's ``limits``: ``loss_rel`` (the
dispatch's last step's loss against the reference's) and ``step_rel``, by
the worst tensor: for each parameter tensor the norm of program's -
reference's after the dispatch over the norm of the reference's own move
of it (1 is what a tensor left unchanged reads), the largest of them, so
that a fault in a small part of the net (attention is a twentieth of its
parameters, the experts nine tenths) is not averaged away; the whole
tree's ratio, as ``drivers/train_tokens.py`` reads it, is printed beside
it. Besides: a TPU with the cell's chips, the batch and the mesh's expert
axis over all of them, the seeded weights equal to the reference's start
(a checksum a tensor), every router product float32 at highest precision,
the compiled step holding an all-to-all and an all-reduce and no
all-reduce or gather of an expert tensor, every loss finite, no compile
inside the window, no pick dropped in any dispatch, and the picks the
chips' experts received summing to tokens x ``num_experts_per_tok``.

``reference_also = {"products": <dtype>}`` in a mix (no cell sets it) runs
the reference once more with every product's operands but the router's
rounded to that dtype and prints what that reads on both comparisons: the
second reading a limit is set from (PERF.md).
"""

import importlib.util
import os
import re
import time
from typing import Any, Dict

from harness import BenchFailure, Run, trace_options
from span_reduce import phase

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tokens = _load(os.path.join(HERE, "train_tokens.py"),
               "bench_driver_train_tokens")
train = tokens.train


def run(r: Run) -> None:
    if r.traffic["input"] != "resident":
        raise BenchFailure("train_tokens_ep traffic: input = %r"
                           % r.traffic["input"])
    run_resident(r)


def reference_config(c: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's sizes from the configuration file: the published
    keys as they stand, ``layer_types`` cut to the published layers held
    (``layers_held``, indices into the published list)."""
    cfg = {k: c[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "rms_norm_eps",
        "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "norm_topk_prob", "rope_parameters", "vocab_size")}
    cfg["layer_types"] = tuple(c["layer_types"][i] for i in c["layers_held"])
    return cfg


def build(r: Run, pairs, batch: int):
    """The configuration's net, refused at once by a program that cannot
    spread its experts over an axis."""
    from cxxnet_tpu.graph import NetGraph
    from cxxnet_tpu.nnet.net import FuncNet
    try:
        graph = NetGraph()
        graph.configure(pairs)
        net = FuncNet(graph, batch)
    except ValueError as e:
        raise BenchFailure("this program cannot build configuration %s: %s"
                           % (r.config["name"], e))
    if not getattr(net, "leading_axes", dict)():
        raise BenchFailure(
            "this program has no expert axis: it cannot spread the experts "
            "of configuration %s over the cell's %d chips"
            % (r.config["name"], r.chips))
    return net


def expert_shapes(c: Dict[str, Any], chips: int):
    """The dims of an expert tensor whole and as one chip holds it, as the
    compiled text writes them (``64,2304,896``)."""
    d, w, e = c["hidden_size"], c["moe_intermediate_size"], c["num_experts"]
    return ["%d,%d,%d" % (n, a, b) for n in (e, e // chips)
            for a, b in ((d, w), (w, d))]


def leaf_sq(a, b) -> Dict[str, float]:
    """Sum of squares of ``a - b`` for each tensor of two parameter trees,
    by the tensor's path (``b`` may lie on the host: one of its tensors is
    on the device at a time)."""
    import jax
    import jax.numpy as jnp
    paths, _ = jax.tree_util.tree_flatten_with_path(a)
    return {jax.tree_util.keystr(k): float(jnp.sum((x - jnp.asarray(y)) ** 2))
            for (k, x), y in zip(paths, jax.tree_util.tree_leaves(b))}


def step_rels(off: Dict[str, float], moved: Dict[str, float]):
    """From ``leaf_sq``'s of program - reference and of the reference's own
    move: the worst tensor's ratio of the norms, its path, and the whole
    tree's ratio."""
    worst = max(moved, key=lambda k: off[k] / moved[k] if moved[k]
                else float("inf") if off[k] else 0.0)
    ratio = (off[worst] / moved[worst]) ** 0.5 if moved[worst] else (
        float("inf") if off[worst] else 0.0)
    return ratio, worst, (sum(off.values()) / sum(moved.values())) ** 0.5


def run_reference(r: Run, net, mesh, ids, labels, n_steps: int):
    """The reference's ``n_steps`` Adam steps from the net's seeded
    weights, on the mesh: the losses, the parameters after the steps (on
    the host), the squared norm of each tensor's change, a checksum a
    tensor of the start, and with ``reference_also`` that precision's
    readings."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    c, t = r.config, r.traffic
    ref = _load(os.path.join(r.root, "benchmarks", c["reference"]),
                "bench_reference")
    cfg, adam = reference_config(c), c["adam"]
    qb = int(t["reference_q_block"])
    key = jax.random.PRNGKey(r.seed32())
    repl = NamedSharding(mesh, PartitionSpec())
    # a sequence a chip at a time: the gradient program's size does not
    # grow with the batch
    batch = ids.shape[0]
    part = r.chips if batch % r.chips == 0 else batch
    parts = [jax.device_put((ids[i:i + part], labels[i:i + part]), repl)
             for i in range(0, batch, part)]

    def steps(**lower):
        params = net.init_on(mesh, key)[0]
        shard = jax.tree_util.tree_map(lambda w: w.sharding, params)
        grad = jax.jit(lambda p, i, l: ref.loss_and_grad(
            p, i, l, cfg, q_block=qb, remat=True, **lower),
            out_shardings=(repl, shard))
        update = jax.jit(
            lambda p, g, s, step: ref.adam_step(
                p, g, s, step, adam["lr"], adam["beta1"], adam["beta2"]),
            static_argnums=(3,), donate_argnums=(0, 1, 2),
            out_shardings=(shard, {"m": shard, "v": shard}))
        # the batch's mean from its parts' means
        scale = jax.jit(lambda g: jax.tree_util.tree_map(
            lambda a: a * (part / batch), g), donate_argnums=(0,),
            out_shardings=shard)
        add = jax.jit(lambda g, total: jax.tree_util.tree_map(
            lambda a, b: a * (part / batch) + b, g, total),
            donate_argnums=(0, 1), out_shardings=shard)
        opt, losses = None, []
        for step in range(1, n_steps + 1):
            value, grads = 0.0, None
            for n, (i, l) in enumerate(parts):
                v, g = grad(params, i, l)
                value += float(v) * part / batch
                grads = scale(g) if grads is None \
                    else add(g, jax.device_put(grads, shard))
                del g
                if n + 1 < len(parts):
                    # the sum waits on the host while the next part's
                    # program runs
                    grads = jax.tree_util.tree_map(np.asarray, grads)
            losses.append(value)
            opt = ref.adam_init(params) if opt is None \
                else jax.device_put(opt, {"m": shard, "v": shard})
            params, opt = update(params, grads, opt, step)
            if step < n_steps:
                # the moments wait on the host while the next gradient
                # program runs
                opt = jax.tree_util.tree_map(np.asarray, opt)
        del opt, grads
        start = net.init_on(mesh, key)[0]
        moved = leaf_sq(params, start)
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
        worst, at, whole = step_rels(leaf_sq(p2, out["params"]), moved)
        out["also"] = {
            "lower": also, "losses": l2,
            "loss_rel": abs(l2[-1] - losses[-1]) / abs(losses[-1]),
            "step_rel": worst, "step_rel_at": at, "step_rel_tree": whole,
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
    from cxxnet_tpu.parallel import default_data_axis, make_mesh
    from cxxnet_tpu.utils.config import parse_config

    t, c = r.traffic, r.config
    batch, n_steps = int(t["batch_size"]), int(t["steps_per_dispatch"])
    seq, vocab = int(t["seq_len"]), int(c["vocab_size"])
    pairs = parse_config(train.netconfig(c)) + [
        ("batch_size", str(batch)), ("dtype", c["dtype"]), ("silent", "1"),
        ("seed", str(r.seed32()))]
    net = build(r, pairs, batch)
    if tuple(net.graph.input_shape) != (1, 1, seq):
        raise BenchFailure("the netconfig's input_shape %r is not the mix's "
                           "seq_len %d" % (net.graph.input_shape, seq))
    # the trainer's own mesh rule: every chip on the data axis, which the
    # expert layers name as their expert axis
    mesh = make_mesh(default_data_axis(batch), 1)

    # the batch, made on the device from the seed: seq + 1 ids a row
    ids = jax.jit(lambda key: jax.random.randint(
        key, (batch, seq + 1), 0, vocab, jnp.int32))(
            jax.random.PRNGKey(r.seed32()))
    data, label = ids[:, :seq], ids[:, 1:]

    # 1. the reference, alone on the chips (its float32 state and the
    #    trainer's do not fit side by side)
    ref = run_reference(r, net, mesh, data, label, n_steps)
    del net
    phase("reference", losses=ref["losses"], wall_s=ref["wall_s"],
          moved_norm=sum(ref["moved_sq"].values()) ** 0.5,
          also=ref.get("also"))

    # 2. the program
    trainer = NetTrainer(pairs)
    trainer.init_model()
    if trainer.batch_size != batch:
        raise BenchFailure("the trainer took batch_size %d, not %d"
                           % (trainer.batch_size, batch))
    shape = {k: int(v) for k, v in trainer.mesh.shape.items()}
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
    layout = next(x for x in sink.records if x["event"] == "layout")
    # (an all-reduce over groups of one chip is no exchange: the CPU's
    # partitioner writes such over the mesh's model axis)
    reduces = [ln for ln in hlo.splitlines()
               if re.search(r"all-reduce(-start)?\(", ln)
               and not re.search(r"replica_groups=\{\{\d+\}(,\{\d+\})*\}",
                                 ln)]
    whole = expert_shapes(c, r.chips)
    r.notes.update(
        mesh=shape, batch_shards=len(placed),
        batch_devices=len({s.device for s in placed}),
        expert_axis_size=layout.get("expert_axis_size"),
        all_to_all=len(re.findall(r"all-to-all(-start)?\(", hlo)),
        all_reduce=len(reduces),
        expert_reduced=sum(1 for ln in reduces
                           if any(s in ln for s in whole)),
        expert_gathered=sum(1 for ln in hlo.splitlines()
                            if re.search(r"all-gather(-start)?\(", ln)
                            and any(s in ln for s in whole)))
    if not r.rehearse:
        r.check(r.notes["batch_devices"] == r.chips == shape.get("data")
                == r.notes["expert_axis_size"],
                "the batch lies on %d device(s), mesh %r, expert axis %r, "
                "cell of %d chip(s)" % (r.notes["batch_devices"], shape,
                                        r.notes["expert_axis_size"], r.chips))
        r.check(r.notes["all_to_all"] > 0 and r.notes["all_reduce"] > 0,
                "the compiled step holds %d all-to-all and %d all-reduce: "
                "both are owed" % (r.notes["all_to_all"],
                                   r.notes["all_reduce"]))
        r.check(r.notes["expert_reduced"] == 0
                and r.notes["expert_gathered"] == 0,
                "an expert tensor is all-reduced (%d) or gathered (%d) in "
                "the compiled step: a chip owns its experts"
                % (r.notes["expert_reduced"], r.notes["expert_gathered"]))
        route = [ln for ln in hlo.splitlines()
                 if " convolution(" in ln and "/route/" in ln]
        r.notes["router_products"] = len(route)
        r.check(bool(route) and all(
            "operand_precision={highest,highest}" in ln and "= f32[" in ln
            for ln in route),
            "a router product of the compiled step is not float32 at "
            "highest precision (%d found)" % len(route))

    # 3. the timed program's first dispatch against the reference
    trainer.run_steps(b, n_steps)
    loss_first = trainer.last_loss
    off = leaf_sq(trainer.params, ref.pop("params"))
    limits = c["limits"]
    loss_rel = abs(loss_first - ref["losses"][-1]) / abs(ref["losses"][-1])
    step_rel, step_rel_at, step_rel_tree = step_rels(off, ref["moved_sq"])
    # the five worst tensors, for the limit's reasons
    ratios = sorted(((off[k] / v) ** 0.5, k)
                    for k, v in ref["moved_sq"].items() if v)[-5:]
    setup_records = list(sink.records)
    moes = [x for x in setup_records if x["event"] == "moe"]
    phase("compared", loss_program=loss_first,
          loss_reference=ref["losses"][-1], loss_rel=loss_rel,
          loss_rel_limit=limits["loss_rel"], step_rel=step_rel,
          step_rel_limit=limits["step_rel"], step_rel_at=step_rel_at,
          step_rel_tree=step_rel_tree, step_rel_worst=ratios[::-1],
          held_share=[m["held_share"] for m in moes],
          exchange_max_over_mean=[m.get("exchange_max_over_mean")
                                  for m in moes],
          load_max_over_mean=[m["load_max_over_mean"] for m in moes])
    r.notes["compared"] = {"loss_rel": loss_rel, "step_rel": step_rel,
                           "step_rel_tree": step_rel_tree}
    r.check(loss_rel <= limits["loss_rel"],
            "loss after %d steps %r, reference %r: off by %.3g of it, limit "
            "%.3g" % (n_steps, loss_first, ref["losses"][-1], loss_rel,
                      limits["loss_rel"]))
    r.check(step_rel <= limits["step_rel"],
            "tensor %s after %d steps lies %.3g of the reference's step "
            "from the reference's, limit %.3g"
            % (step_rel_at, n_steps, step_rel, limits["step_rel"]))
    check_exchange(r, moes, "before the window")

    # 4. the window (drivers/train_tokens.py's loop; a traced run goes on
    #    past ``--seconds`` until its traced dispatches are done: a
    #    dispatch here takes seconds)
    sink.clear()
    first_traced = -1
    if r.trace:
        r.trace_dir = os.path.join(r.out_dir, "trace")
        first_traced = 1                       # second dispatch on
    t0 = time.time()
    i = 0
    t_trace = 0.0
    while time.time() - t0 < r.seconds or (r.trace and not r.trace_span_s):
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
    check_exchange(r, r.in_window("moe"), "inside the window")


def check_exchange(r: Run, moes, when: str) -> None:
    """No pick dropped, and every pick received: on an expert axis the
    experts of all the chips are held, so the picks they received are
    tokens x experts a token (``held_share`` 1)."""
    r.check(bool(moes) and all(m["dropped"] == 0 for m in moes),
            "an expert layer dropped picks %s (or wrote no moe record)"
            % when)
    r.check(all(abs(m["held_share"] - 1.0) < 1e-9 for m in moes),
            "the chips' experts received %r of the picks %s, not all"
            % (sorted({m["held_share"] for m in moes}), when))

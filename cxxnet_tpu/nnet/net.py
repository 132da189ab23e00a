"""The functional net: graph -> pure init/forward/loss functions.

This replaces the reference's mutable ``NeuralNet`` (node buffers +
in-place layer Forward/Backprop, ``neural_net-inl.hpp:24-318``) with a
single pure function over pytrees. Backprop is ``jax.grad`` of
``loss_fn`` — there is no hand-written backward pass; gradient
accumulation, data parallelism, and optimizer updates compose around
this function inside one jitted XLA program.

Weight tying (kSharedLayer, neural_net-inl.hpp:259-265): shared
connections reuse the primary layer's parameter subtree; autodiff sums
the gradients from every use site automatically (the reference relied on
gwmat accumulation across connections for the same effect).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graph import NetGraph
from ..layers import Layer, Shape3, create_layer
from ..layers.base import BLOCK_REMAT_KEEPS

Params = Dict[str, Dict[str, jnp.ndarray]]
NetState = Dict[str, Dict[str, jnp.ndarray]]


class FuncNet:
    """Layer instances + shape inference for a NetGraph."""

    def __init__(self, graph: NetGraph, batch_size: int):
        self.graph = graph
        self.batch_size = batch_size
        self.layer_objs: List[Layer] = []
        self.node_shapes: List[Optional[Shape3]] = \
            [None] * graph.num_nodes
        # (mean, scale) a non-floating input is normalised with at the
        # top of forward; None = cast only. Set by the trainer that
        # adopted it from an iterator chain (NetTrainer.set_input_norm)
        self.input_norm = None
        self.block_remat = False     # the trainer's remat = block
        self._build()
        # does the first layer read integer ids (an ``embed`` on node
        # 0)? Then a batch is int32 and nothing normalises it
        self.ids_input = any(
            graph.effective_type(li) == "embed" and 0 in info.nindex_in
            for li, info in enumerate(graph.layers))
        # positions of a sequence net's example (the label a position
        # its loss takes); 1 for every other net
        seq = [self.node_shapes[graph.layers[li].nindex_in[0]]
               for li in self.loss_layer_indices()]
        self.tokens_per_example = max(
            (s.y for s in seq if s.is_seq), default=1)

    # -- construction ----------------------------------------------------

    def _build(self) -> None:
        g = self.graph
        self.node_shapes[0] = Shape3(*g.input_shape)
        for i in range(g.extra_data_num):
            self.node_shapes[1 + i] = Shape3(*g.extra_shape[i])
        for li, info in enumerate(g.layers):
            pli = g.param_layer_index(li)
            if info.type == "share":
                layer = self.layer_objs[pli]
                # re-apply nothing: primary layer's params govern
            else:
                cfg = list(g.defcfg) + list(g.layercfg[li])
                kwargs = {}
                if g.effective_type(li) == "split":
                    kwargs["n_out"] = len(info.nindex_out)
                layer = create_layer(info.type, cfg, **kwargs)
                if layer.is_loss and layer.batch_size == 0:
                    layer.batch_size = self.batch_size
            self.layer_objs.append(layer)
            # shape inference for this connection
            in_shapes = []
            for ni in info.nindex_in:
                s = self.node_shapes[ni]
                if s is None:
                    raise ValueError(
                        "layer %d reads node %d before it is produced"
                        % (li, ni))
                in_shapes.append(s)
            if layer.self_loop or info.nindex_in == info.nindex_out:
                if info.nindex_in != info.nindex_out:
                    raise ValueError(
                        "layer %d (%s) is a self-loop layer"
                        % (li, info.type))
            out_shapes = layer.infer_shape(in_shapes)
            for ni, s in zip(info.nindex_out, out_shapes):
                prev = self.node_shapes[ni]
                if prev is not None and ni not in info.nindex_in:
                    if prev != s:
                        raise ValueError(
                            "node %d shape conflict: %s vs %s"
                            % (ni, prev, s))
                self.node_shapes[ni] = s
        self._fusion_passes()
        from .layout import plan_channel_layouts
        plan_channel_layouts(self)

    # -- graph-level fusion passes ---------------------------------------

    def _net_flag(self, name: str, default: int = 0) -> int:
        """Net-level knob from the global (default) layer config."""
        val = default
        for n, v in self.graph.defcfg:
            if n == name:
                val = int(v)
        return val

    def _fusion_passes(self) -> None:
        """Epilogue fusion over the built graph.

        ``bn_fuse_relu = 1``: a relu that is the SOLE consumer of a
        batch-norm output runs inside the BN layer (one fused epilogue)
        and the relu connection becomes identity. Same math, exactly:
        relu(bn(x)).

        ``bn_fold_eval = 1``: on the eval/pred path, a moving-average
        batch_norm that solely consumes a conv's output folds its
        running-stats scale/shift into the conv weights (w*scale is a
        small per-out-channel multiply); the BN connection runs as
        identity. Training is untouched — running stats keep updating
        from batch moments. Parity is pinned by tests (reassociation-
        level rounding only: the scale multiplies the weight before
        the contraction instead of the output after it).

        Both fusions change what INTERIOR nodes hold (the BN output
        node carries the post-relu value; at eval the conv output node
        carries the folded conv+BN value) — extraction or metrics
        bound to those interior nodes read the fused values. Logical
        net outputs are identical; the knobs are opt-in.
        """
        g = self.graph
        self._identity_layers = set()     # relus folded into their BN
        self._fold_pairs = {}             # conv li -> bn li (eval fold)
        self._fold_bns = set()
        self._bn_fold_eval = bool(self._net_flag("bn_fold_eval"))
        consumers = g.node_consumers()
        # a SHARED layer reuses its primary's object: mutating the
        # primary (fuse_relu) would drag the fusion to every share
        # site, whose consumers may not be relus — exclude them
        shared_primaries = set(info.primary_layer_index
                               for info in g.layers
                               if info.type == "share")
        if self._net_flag("bn_fuse_relu"):
            for li, info in enumerate(g.layers):
                if info.type not in ("batch_norm", "batch_norm_no_ma"):
                    continue
                if li in shared_primaries:
                    continue
                out = info.nindex_out[0]
                cons = consumers.get(out, [])
                if len(cons) != 1:
                    continue
                lj = cons[0]
                if g.layers[lj].type == "relu":
                    self.layer_objs[li].fuse_relu = True
                    self._identity_layers.add(lj)
        if self._net_flag("bn_fold_eval"):
            for li, info in enumerate(g.layers):
                if info.type != "conv":
                    continue
                out = info.nindex_out[0]
                cons = consumers.get(out, [])
                if len(cons) != 1:
                    continue
                lj = cons[0]
                if (g.layers[lj].type == "batch_norm"
                        and self.layer_objs[lj].moving_avg):
                    self._fold_pairs[li] = lj
                    self._fold_bns.add(lj)

    def _fold_entries(self, params: Params, state: NetState,
                      conv_li: int):
        """Per-out-channel scale/shift the eval fold injects into a
        conv's params (from its BN partner's running stats)."""
        import jax.lax
        bn_li = self._fold_pairs[conv_li]
        bn = self.layer_objs[bn_li]
        bkey = self.graph.layer_key(self.graph.param_layer_index(bn_li))
        bp, bs = params[bkey], state[bkey]
        scale = bp["wmat"] * jax.lax.rsqrt(bs["running_var"] + bn.eps)
        shift = bp["bias"] - bs["running_exp"] * scale
        out = {"_fold_scale": scale, "_fold_shift": shift}
        if bn.fuse_relu:
            out["_fold_relu"] = True
        return out

    # -- init ------------------------------------------------------------

    def init(self, key: jax.Array) -> Tuple[Params, NetState]:
        g = self.graph
        params: Params = {}
        state: NetState = {}
        for li, info in enumerate(g.layers):
            if info.type == "share":
                continue
            lkey = g.layer_key(li)
            p = self.layer_objs[li].init_params(
                jax.random.fold_in(key, li))
            if p:
                params[lkey] = p
            s = self.layer_objs[li].init_state()
            if s:
                state[lkey] = s
        return params, state

    def bind_mesh(self, mesh) -> None:
        """Tell the layers that run across chips (an expert layer on an
        expert axis) the mesh their program runs on."""
        for layer in self.layer_objs:
            if hasattr(layer, "bind_mesh"):
                layer.bind_mesh(mesh)

    def leading_axes(self) -> Dict[str, Dict[str, str]]:
        """``{layer key: {tag: mesh axis}}``: the parameters sharded on
        their leading axis (parallel.param_sharding)."""
        g = self.graph
        out = {}
        for li, info in enumerate(g.layers):
            axes = getattr(self.layer_objs[li], "leading_axes", dict)()
            if axes and info.type != "share":
                out[g.layer_key(li)] = axes
        return out

    def init_on(self, mesh, key: jax.Array,
                model_parallel_min: int = 0) -> Tuple[Params, NetState]:
        """``init`` made on the mesh in one program, each parameter on its
        devices from the start (parallel.param_sharding), the state
        replicated: a tensor sharded over an expert axis never lies whole
        on one chip. The values are the program's own, so every caller
        that starts from them (the trainer, a reference) gets the same
        ones."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel import param_sharding
        params, state = jax.eval_shape(self.init, key)
        shard = param_sharding(mesh, params, model_parallel_min,
                               self.leading_axes())
        repl = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, PartitionSpec()), state)
        return jax.jit(self.init, out_shardings=(shard, repl))(key)

    # -- forward ---------------------------------------------------------

    def layer_scope(self, li: int) -> str:
        """``<type>.<layer_key>``: the ``jax.named_scope`` of one
        layer's ops (a shared layer under its primary's type)."""
        g = self.graph
        return "%s.%s" % (g.effective_type(li),
                          g.layer_key(li).replace("/", "_"))

    @property
    def scope_names(self) -> Tuple[str, ...]:
        """Every scope a layer opens: its own, and the named parts
        inside it where a layer type has some (``sub_scopes``)."""
        inner = {n for layer in self.layer_objs
                 for n in getattr(layer, "sub_scopes", ())}
        return tuple(self.layer_scope(li)
                     for li in range(len(self.graph.layers))) \
            + tuple(sorted(inner))

    def _run_layers(self, lo: int, hi: int, params: Params,
                    new_state: NetState, nodes: List, loss_inputs: Dict,
                    is_train: bool, rng, collect_logits: bool, mask) -> None:
        """Connections ``lo .. hi`` in config order, writing ``nodes``,
        ``new_state`` and ``loss_inputs`` in place."""
        g = self.graph
        fold_eval = self._bn_fold_eval and not is_train
        for li in range(lo, hi):
            info = g.layers[li]
            if li in self._identity_layers \
                    or (fold_eval and li in self._fold_bns):
                # epilogue already ran fused inside the producer (relu
                # inside BN / BN inside the folded conv): pass through
                v = nodes[info.nindex_in[0]]
                for ni in info.nindex_out:
                    nodes[ni] = v
                continue
            layer = self.layer_objs[li]
            pkey = g.layer_key(g.param_layer_index(li))
            p = params.get(pkey, {})
            s = new_state.get(pkey, {})
            if fold_eval and li in self._fold_pairs \
                    and "_fold_scale" not in p \
                    and "_r_shift" not in p \
                    and "_r_shift_relu" not in p:
                # inject the fold scale/shift computed in-graph — UNLESS
                # the frozen serve weight tree already carries them (or
                # the pre-folded weight + effective shift) as leaves
                # (trainer.freeze_serve_weights)
                p = dict(p)
                p.update(self._fold_entries(params, new_state, li))
            if li in self._depad_layers:
                # layout barrier: this layer sees logical channels
                ins = [self.depad_node(ni, nodes[ni])
                       for ni in info.nindex_in]
            else:
                ins = [nodes[ni] for ni in info.nindex_in]
            lrng = (jax.random.fold_in(rng, li)
                    if rng is not None else None)
            if collect_logits and layer.is_loss:
                loss_inputs[li] = ins[0]
            # metadata only: names the layer in every op it lowers to
            # (op_name), forward and, under jax.grad, backward as
            # transpose(jvp(<type>.<key>)); a layer whose epilogue ran
            # fused in its producer (above) opens none
            with jax.named_scope(self.layer_scope(li)):
                if layer.needs_mask:
                    outs, s2 = layer.forward(p, s, ins, is_train, lrng,
                                             mask=mask)
                else:
                    outs, s2 = layer.forward(p, s, ins, is_train, lrng)
            if s2:
                new_state[pkey] = s2
            for ni, v in zip(info.nindex_out, outs):
                nodes[ni] = v

    def _segments(self) -> List[Tuple[int, int]]:
        """``remat = block``'s cuts: a segment ends after each ``add``
        layer (the join that closes a residual block); what follows the
        last one (final norm, head) is a segment too."""
        cuts = [li + 1 for li, info in enumerate(self.graph.layers)
                if self.graph.effective_type(li) == "add"]
        ends = sorted(set(cuts + [len(self.graph.layers)]))
        return list(zip([0] + ends[:-1], ends))

    def _run_segment(self, lo: int, hi: int, keep, params, new_state, nodes,
                     loss_inputs, rng, collect_logits, mask) -> None:
        """One segment under ``jax.checkpoint``: only what later layers
        (or the caller, ``keep``) read of it is stored, and what its
        layers have named as dear to make again (``BLOCK_REMAT_KEEPS``:
        the fused attention core's two outputs, the delta rule's
        triangular inverse; a segment with neither names nothing); the
        rest of its inside is recomputed
        when the backward pass reaches it, one segment at a time (the
        barriers of ``prevent_cse`` tie each recomputation to the
        cotangent that needs it)."""
        g = self.graph
        made = {ni for li in range(lo, hi) for ni in g.layers[li].nindex_out}
        read_in = {ni for li in range(lo, hi) for ni in g.layers[li].nindex_in}
        later = {ni for li in range(hi, len(g.layers))
                 for ni in g.layers[li].nindex_in} | set(keep)
        live_in = {ni: nodes[ni] for ni in sorted(read_in)
                   if nodes[ni] is not None}
        live_out = sorted(made & later)
        keys = sorted({g.layer_key(g.param_layer_index(li))
                       for li in range(lo, hi)})

        def run(p_seg, s_seg, live, rng):
            seg_nodes = [None] * g.num_nodes
            for ni, v in live.items():
                seg_nodes[ni] = v
            st, logits = dict(s_seg), {}
            self._run_layers(lo, hi, p_seg, st, seg_nodes, logits, True,
                             rng, collect_logits, mask)
            return ({ni: seg_nodes[ni] for ni in live_out},
                    {k: st[k] for k in keys if k in st}, logits)

        outs, st, logits = jax.checkpoint(
            run, policy=jax.checkpoint_policies.save_only_these_names(
                *BLOCK_REMAT_KEEPS))(
            {k: params[k] for k in keys if k in params},
            {k: new_state[k] for k in keys if k in new_state}, live_in, rng)
        for ni, v in outs.items():
            nodes[ni] = v
        new_state.update(st)
        loss_inputs.update(logits)

    def forward(self, params: Params, state: NetState,
                data: jnp.ndarray,
                extra: Sequence[jnp.ndarray] = (),
                is_train: bool = False,
                rng: Optional[jax.Array] = None,
                collect_logits: bool = False,
                mask: Optional[jnp.ndarray] = None,
                keep_nodes: Sequence[int] = ()):
        """Run all connections in config order.

        With ``block_remat`` set (the trainer's ``remat = block``) a
        training pass runs segment by segment under ``jax.checkpoint``
        (``_segments``); a node no later layer reads is then None in
        the returned list unless ``keep_nodes`` names it.

        Returns (node_values, new_state, loss_inputs) where loss_inputs
        maps layer index -> pre-transform logits of each loss layer
        (only when collect_logits).
        """
        g = self.graph
        nodes: List[Optional[jnp.ndarray]] = [None] * g.num_nodes
        if self.ids_input:
            pass        # integer ids: the embed layer's to look up
        elif not jnp.issubdtype(data.dtype, jnp.floating):
            # uint8 pipeline: pixels ship to the device raw and are
            # normalized here (4x less host->device traffic), in
            # float32 and in the host augmenter's order (buf -= mean;
            # buf *= scale), so the first layer sees the host path's
            # values to the bit. A floating input is some host's
            # finished work and passes untouched
            with jax.named_scope("input_norm"):
                data = self._normalize_raw(data, mask)
        nodes[0] = data
        for i in range(g.extra_data_num):
            nodes[1 + i] = extra[i]
        new_state: NetState = dict(state)
        loss_inputs: Dict[int, jnp.ndarray] = {}
        if is_train and self.block_remat:
            for lo, hi in self._segments():
                self._run_segment(lo, hi, keep_nodes, params, new_state,
                                  nodes, loss_inputs, rng, collect_logits,
                                  mask)
        else:
            self._run_layers(0, len(g.layers), params, new_state, nodes,
                             loss_inputs, is_train, rng, collect_logits,
                             mask)
        return nodes, new_state, loss_inputs

    def _normalize_raw(self, data, mask):
        data = data.astype(jnp.float32)
        if self.input_norm is None:
            return data
        mean, scale = self.input_norm
        if mean is not None:
            data = data - mean
        if scale != 1:
            data = data * scale
        if mask is not None:
            # the host path zero-fills a short batch's tail AFTER it
            # normalised; here the filler is raw zeros, and the rows
            # the pad mask excludes are set to zero instead (no layer
            # or loss reads them: that is what the mask says)
            keep = mask.reshape((-1,) + (1,) * (data.ndim - 1)) > 0
            data = jnp.where(keep, data, 0.0)
        return data

    # -- loss ------------------------------------------------------------

    def loss_fn(self, params: Params, state: NetState,
                data: jnp.ndarray, labels: jnp.ndarray,
                mask: jnp.ndarray,
                extra: Sequence[jnp.ndarray] = (),
                rng: Optional[jax.Array] = None,
                collect_nodes: Sequence[int] = ()):
        """Total training loss (sum over loss layers) + aux.

        labels: (batch, label_width) matrix; each loss layer's ``target``
        selects its column range via the graph's label_vec map.
        Returns (loss, (new_state, collected)) where collected holds the
        post-forward values of ``collect_nodes`` (for on-the-fly train
        metrics, nnet_impl-inl.hpp:191-197).
        """
        nodes, new_state, loss_inputs = self.forward(
            params, state, data, extra=extra, is_train=True, rng=rng,
            collect_logits=True, mask=mask, keep_nodes=collect_nodes)
        slices = {name: (a, b) for name, a, b in self.graph.label_slices()}
        total = jnp.float32(0.0)
        for li, logit in loss_inputs.items():
            layer = self.layer_objs[li]
            assert layer.is_loss
            if layer.target not in slices:
                raise ValueError("loss layer: unknown target=%s"
                                 % layer.target)
            a, b = slices[layer.target]
            with jax.named_scope("loss"):
                total = total + layer.loss_value(logit, labels[:, a:b],
                                                 mask)
        collected = [self.depad_node(ni, nodes[ni])
                     for ni in collect_nodes]
        return total, (new_state, collected)

    # -- utilities -------------------------------------------------------

    def depad_node(self, ni: int, v):
        """Slice a node value back to its logical channels (identity
        for plain nodes) — extraction, metrics and layout barriers all
        read logical tensors."""
        from .layout import is_padded, take_valid
        lay = self.node_layouts[ni] if ni < len(self.node_layouts) \
            else None
        if v is None or not is_padded(lay):
            return v
        return take_valid(v, lay)

    def analytic_flops_per_example(self) -> float:
        """Analytic forward FLOPs per example (2*MACs over the logical
        conv/dense contractions; a training step is ~3x — one forward
        plus two backward GEMMs per contraction). XLA's own
        cost_analysis undercounts fused TPU convolutions ~15x
        (doc/perf_profile.md), so MFU telemetry uses this count. An
        example of a sequence net is one sequence: a ``fullc`` counts
        every position, and the sequence layers count themselves
        (``flops_per_example``: causal attention at half the square or,
        under a window, at its band; routed experts at the picks that land on held experts)."""
        g = self.graph
        total = 0
        for li in range(len(g.layers)):
            layer = self.layer_objs[li]
            t = g.effective_type(li)
            if t == "conv":
                p = layer.param
                out = layer.out_shapes[0]
                total += (2 * p.kernel_height * p.kernel_width
                          * (p.num_input_channel // p.num_group)
                          * out.ch * out.y * out.x)
            elif t in ("fullc", "fixconn"):
                p = layer.param
                s = layer.in_shapes[0]
                total += 2 * p.num_input_node * p.num_hidden \
                    * (s.y if s.is_seq else 1)
            elif t == "embed":
                # rows looked up cost nothing; applied to a sequence node
                # it is the tied head, h E^T at every position
                s = self.node_shapes[g.layers[li].nindex_in[0]]
                if s.is_seq:
                    total += 2 * s.y * s.x * layer.nvocab
            elif hasattr(layer, "flops_per_example"):
                total += layer.flops_per_example()
        return float(total)

    def loss_layer_indices(self) -> List[int]:
        return [li for li, l in enumerate(self.layer_objs)
                if l.is_loss]

    def node_index_by_name(self, name: str) -> int:
        g = self.graph
        if name in g.node_name_map:
            return g.node_name_map[name]
        # allow "top[-k]" addressing like ExtractFeature
        # (nnet_impl-inl.hpp:217-240): top = last node
        if name.startswith("top"):
            k = 0
            if name != "top":
                k = int(name[4:-1]) if name[3] == "[" else 0
            return g.num_nodes - 1 + k
        raise ValueError("unknown node name %r" % name)

    def print_shapes(self) -> str:
        lines = []
        for i, s in enumerate(self.node_shapes):
            nm = self.graph.node_names[i] if i < len(
                self.graph.node_names) else str(i)
            lines.append("node %s: %s" % (nm, tuple(s) if s else None))
        return "\n".join(lines)

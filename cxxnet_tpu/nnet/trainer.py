"""The trainer: INetTrainer-equivalent over one jitted SPMD program.

Replaces the reference's ``CXXNetThreadTrainer`` + ``NeuralNetThread``
machinery (nnet_impl-inl.hpp:22-496, neural_net-inl.hpp:325-658): instead
of per-device worker threads, semaphore job loops, and an async parameter
server, the whole train step — forward, backward, gradient accumulation,
cross-device reduction, optimizer update — is ONE jitted XLA program
sharded over the mesh. The batch is sharded on the 'data' axis (the
``dev = gpu:0-3`` batch split, nnet_impl-inl.hpp:162-189); XLA's autodiff
inserts the gradient all-reduce over ICI, and its latency-hiding
scheduler overlaps it with compute — the capability the reference built
the layerwise async PS for (SURVEY.md §2.7.6).

API parity (nnet.h:18-92): set_param / init_model / save_model /
load_model / start_round / update / evaluate / predict / extract_feature
/ copy_model_from / set_weight / get_weight.

Semantics kept exactly:
- ``update_period`` gradient accumulation with the loss pre-scaled by
  grad_scale/batch_size and the accumulated gradient divided by
  update_period at apply time — algebraically identical to the
  reference's 1/(batch*update_period) pre-scaling
  (loss_layer_base-inl.hpp:61, nnet_impl-inl.hpp:166-167).
- per-(layer, tag) updaters with tag-scoped hyper-params; LR schedule
  evaluated host-side per applied update (epoch = update counter).
- optimizer state is NOT checkpointed (parity with the reference
  snapshot format, SURVEY.md §5 Checkpoint).
- train metrics accumulated from the training forward pass when
  ``eval_train`` (nnet_impl-inl.hpp:191-197).
"""

from __future__ import annotations

import re
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..artifact import registry as _areg
from ..graph import NetGraph
from ..io.data import DataBatch
from ..layers import pallas_kernels as _pallas
from ..monitor.spans import NULL_SPAN, STEP_SCOPES, scope_map
from ..parallel import (batch_sharding, make_mesh, opt_state_sharding,
                        param_sharding, replicated)
from ..updater import create_updater
from ..utils.compile_cache import put_with_layout
from ..utils.config import ConfigPairs
from ..utils.metric import MetricSet
from .net import FuncNet

_RE_METRIC = re.compile(r"^metric(?:\[([^\]]*)\])?$")


class FinetuneShapeError(ValueError):
    """A finetune source holds a parameter whose shape no longer
    matches the configured net and the layer was NOT declared in
    ``finetune_remap`` — the message names the layer so the fix is one
    config line. ``layer`` / ``tag`` carry the offending group."""

    def __init__(self, layer: str, tag: str, saved_shape, new_shape):
        self.layer = layer
        self.tag = tag
        super().__init__(
            "finetune: layer %r param %r changed shape %s -> %s but is "
            "not listed in finetune_remap — declare it "
            "(finetune_remap = %s) for a fresh re-init, or fix the net "
            "config (finetune_strict = 0 restores the silent "
            "skip-and-reinit behavior)"
            % (layer, tag, tuple(saved_shape), tuple(new_shape), layer))

# the one non-f32 float staging dtype _ship passes through unconverted
# (bf16-warmed serve ladders; numpy spells it via ml_dtypes through jnp)
_BF16 = np.dtype(jnp.bfloat16)


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _tree_zeros_like(t):
    return jax.tree_util.tree_map(jnp.zeros_like, t)


class NetTrainer:
    def __init__(self, cfg: ConfigPairs = (), mesh=None):
        self.cfg: List[Tuple[str, str]] = list(cfg)
        self.mesh = mesh
        # trainer-global knobs
        self.batch_size = 0
        self.update_period = 1
        self.eval_train = 1
        self.seed = 0
        self.silent = 0
        self.model_parallel_min = 0      # 0 = no model-parallel sharding
        self.shard_optimizer = 0         # ZeRO-1 (update_on_server analogue)
        self.grad_sync = "fused"         # overlap: per-group gradient
        #                                  reduction boundaries so
        #                                  cross-host sync overlaps the
        #                                  remaining backprop
        #                                  (parallel/gradsync.py); bit-
        #                                  identical to fused by
        #                                  construction
        self.grad_sync_bucket_mb = 0.0   # 0: one reduction group per
        #                                  layer; >0: greedy size
        #                                  buckets of at least this
        #                                  many MB (reverse-layer order
        #                                  either way)
        self.grad_dtype = "float32"      # bfloat16: bf16 cotangents +
        #                                  bf16 grad all-reduce, f32
        #                                  master weights in the updater
        self.save_optimizer = 0          # 1: checkpoint momentum/adam
        #                                  state for seamless resume
        self.remat = "none"              # rematerialization policy for
        #                                  the backward pass: none |
        #                                  full | dots | conv | block
        #                                  (see _wrap_loss_fn)
        self.remat_barrier = 1           # 0: drop checkpoint's CSE
        #                                  barriers (XLA then undoes
        #                                  the recompute — see
        #                                  _wrap_loss_fn)
        self.dispatch_period = 8         # multi-process lockstep window
        #                                  (shared with the CLI loop's
        #                                  windowed dispatch)
        self.compile_cache_dir = ""      # persistent XLA compilation
        #                                  cache (compile once per
        #                                  machine, not per run)
        self.input_norm = None           # (mean, scale) adopted from
        #                                  an iterator chain whose
        #                                  uint8 batches this trainer
        #                                  normalises in the step
        #                                  (set_input_norm); None =
        #                                  batches arrive normalised
        self.serve_dtype = "float32"     # eval/pred/serve compute
        #                                  dtype: float32 | bfloat16 |
        #                                  int8 | fp8 — int8/fp8 need a
        #                                  calibrated snapshot
        #                                  (task=quantize); training
        #                                  dispatch never consults it
        self.quant_tables = {}           # quant/<layer> range arrays
        self.quant_meta = {}             # __meta__["quantized"]
        self.quant_report = {"active": False}
        self.serve_weight_residency = 1  # 0: legacy per-dispatch weight
        #                                  fold/quantize in the traced
        #                                  eval graph; 1: fold+quantize
        #                                  ONCE at load into a device-
        #                                  resident serve weight tree
        #                                  shared by every pred
        #                                  executable (doc/serving.md
        #                                  "Device memory accounting")
        self.serve_device_mem_budget = 0.0  # MB; >0 rejects a model
        #                                  whose resident weight bytes
        #                                  exceed it (typed
        #                                  ResidencyBudgetError, not an
        #                                  OOM). 0 = unlimited
        self.serve_donate = 1            # donate the pred data/mask
        #                                  buffers to the serve-ladder
        #                                  executables (XLA may reuse
        #                                  them for outputs)
        self.input_layout = "none"       # rowmajor: pin the batch
        #                                  input's device layout with
        #                                  channels minor (lane dim) so
        #                                  the compiler cannot pick the
        #                                  batch-minor cliff layout;
        #                                  applied through precompile's
        #                                  AOT lowering + device_put
        self.input_layout_effective = "none"  # what took hold (set by
        #                                  _probe_input_layout; the
        #                                  layout record reports this)
        self.dist_topology_check = "warn"  # snapshot-vs-runtime
        #                                  topology comparison at load
        #                                  (doc/distributed.md): warn
        #                                  surfaces a changed mesh /
        #                                  world size (the elastic
        #                                  resume path), strict raises,
        #                                  off is silent
        self.resumed_topology = None     # the loaded snapshot's sealed
        #                                  topology dict, when present
        self.topology_changed = False    # load-time mismatch flag (the
        #                                  CLI emits dist_resize off it)
        self.sample_counter = 0          # within accumulation window
        self.update_counter = 0          # applied updates (schedule epoch)
        self.round = 0
        self._initialized = False
        # observability. Counters are always-on host ints (the wrapper
        # progress-poll surface); everything time-based lives behind
        # the monitor so monitor=none adds NO host<->device syncs to
        # the step path.
        self._mon = None                 # monitor.Monitor or None
        self._scoped: set = set()        # program keys whose
        #                                  program_scopes went out
        self._steps_total = 0            # dispatches (telemetry step id)
        self._examples_total = 0         # real (non-padded) local rows
        self._round_examples = 0
        self._round_t0 = None            # set by start_round
        self.last_round_examples_per_sec = 0.0   # of the closed round
        self._pending_data_wait = 0.0    # loop-measured iterator wait
        self.last_round_examples = 0     # set by end_round
        self.last_round_wall_s = 0.0
        # the program registry: every AOT executable this trainer owns,
        # keyed by (kind,) + dispatch signature, plus the compile-event
        # signature set and the sealed-artifact hit/rebuild accounting
        # (cxxnet_tpu.artifact.registry — serve/bench/pred consume it
        # through this trainer). Empty = every dispatch goes through jit
        self.programs = _areg.ProgramRegistry()
        self.precompile_wall_s = 0.0
        self.precompile_programs = 0

    # -- config ----------------------------------------------------------

    def set_param(self, name: str, val: str) -> None:
        self.cfg.append((name, val))

    def _absorb_globals(self) -> None:
        self.metric_cfg: List[Tuple[str, str, str]] = []  # (name,field,node)
        for name, val in self.cfg:
            if name == "batch_size":
                self.batch_size = int(val)
            if name == "update_period":
                self.update_period = int(val)
            if name in ("eval_train", "train_eval"):
                self.eval_train = int(val)
            if name == "seed":
                self.seed = int(val)
            if name == "silent":
                self.silent = int(val)
            if name == "model_parallel_min":
                self.model_parallel_min = int(val)
            if name == "grad_dtype":
                if val not in ("float32", "bfloat16"):
                    raise ValueError(
                        "grad_dtype must be float32 or bfloat16")
                self.grad_dtype = val
            if name == "save_optimizer":
                self.save_optimizer = int(val)
            if name == "remat":
                if val not in ("none", "0", "full", "dots", "conv",
                               "block"):
                    raise ValueError(
                        "remat must be none|full|dots|conv|block")
                self.remat = "none" if val == "0" else val
            if name == "remat_barrier":
                self.remat_barrier = int(val)
            if name == "dispatch_period":
                self.dispatch_period = max(1, int(val))
            if name == "compile_cache_dir":
                self.compile_cache_dir = val
            if name == "input_layout":
                if val not in ("none", "rowmajor"):
                    raise ValueError(
                        "input_layout must be none or rowmajor")
                self.input_layout = val
            if name == "serve_dtype":
                from .quantize import normalize_serve_dtype
                self.serve_dtype = normalize_serve_dtype(val)
            if name == "serve_weight_residency":
                self.serve_weight_residency = int(val)
            if name == "serve_device_mem_budget":
                self.serve_device_mem_budget = float(val)
            if name == "serve_donate":
                self.serve_donate = int(val)
            if name == "dist_topology_check":
                if val not in ("off", "warn", "strict"):
                    raise ValueError(
                        "dist_topology_check must be off|warn|strict")
                self.dist_topology_check = val
            if name in ("shard_optimizer", "update_on_server",
                        "optim_shard"):
                # update_on_server=1 meant "optimizer state lives off the
                # workers" (nnet_ps_server.cpp); here it means "optimizer
                # state is ZeRO-sharded across the data axis".
                # optim_shard is the ZeRO-1 spelling (doc/updater.md)
                self.shard_optimizer = int(val)
            if name == "grad_sync":
                if val not in ("fused", "overlap"):
                    raise ValueError("grad_sync must be fused|overlap")
                self.grad_sync = val
            if name == "grad_sync_bucket_mb":
                self.grad_sync_bucket_mb = float(val)
                if self.grad_sync_bucket_mb < 0:
                    raise ValueError("grad_sync_bucket_mb must be >= 0")
            m = _RE_METRIC.match(name)
            if m:
                spec = m.group(1)
                field, node = "label", ""
                if spec:
                    parts = [p.strip() for p in spec.split(",")]
                    field = parts[0] or "label"
                    if len(parts) > 1:
                        node = parts[1]
                self.metric_cfg.append((val, field, node))

    # -- model lifecycle -------------------------------------------------

    def init_model(self) -> None:
        self._absorb_globals()
        self.graph = NetGraph()
        self.graph.configure(self.cfg)
        if self.batch_size == 0:
            self.batch_size = self.graph.batch_size
        assert self.batch_size > 0, "batch_size must be set"
        self.net = FuncNet(self.graph, self.batch_size)
        key = jax.random.PRNGKey(self.seed)
        self.params, self.net_state = self.net.init(key)
        self._post_init()

    def _post_init(self) -> None:
        """Everything shared by init_model and load_model."""
        self._enable_persistent_cache()
        g = self.graph
        # one updater per (param layer, tag)
        self.updaters: Dict[str, Dict[str, Any]] = {}
        self._layer_index: Dict[str, int] = {}
        for lkey, ptree in self.params.items():
            li = g.layer_index(lkey) if lkey in g.layer_name_map \
                else int(lkey[5:])
            self._layer_index[lkey] = li
            self.updaters[lkey] = {}
            for tag in ptree:
                self.updaters[lkey][tag] = create_updater(
                    g.updater_type, tag, g.defcfg, g.layercfg[li])
        self.opt_state = {
            lk: {tag: self.updaters[lk][tag].init_state(w)
                 for tag, w in pt.items()}
            for lk, pt in self.params.items()}
        if self.mesh is None:
            from ..parallel import default_data_axis
            self.mesh = make_mesh(default_data_axis(self.batch_size), 1)
        # metric bindings -> node indices
        self._metrics = MetricSet()
        self._train_metrics = MetricSet()
        self._metric_nodes: List[int] = []
        top = self.graph.num_nodes - 1
        for mname, field, node in self.metric_cfg:
            self._metrics.add_metric(mname, field, node)
            self._train_metrics.add_metric(mname, field, node)
            ni = self.net.node_index_by_name(node) if node else top
            self._metric_nodes.append(ni)
        self._label_slices = self.graph.label_slices()
        # expert layers leave their counters in their state (_emit_moe)
        self._moe_keys = [lk for lk, st in self.net_state.items()
                          if "picks_held" in st]
        # their passes through the grouped kernels, as of the last record
        self._moe_grouped = {
            lk: int(self.net_state[lk]["grouped"]) for lk in self._moe_keys}
        # serve_dtype activation BEFORE the programs build: the specs
        # live on the layer objects and must be pinned before any
        # forward traces (nnet/quantize.attach)
        self._attach_quant()
        self._build_steps()
        self._put_all()
        self._initialized = True
        self._emit_model_records()

    def _attach_quant(self) -> None:
        from .quantize import attach
        self.quant_report = attach(self)

    def set_quantization(self, tables, meta,
                         dtype: Optional[str] = None) -> None:
        """Install calibration range tables (and optionally switch the
        serve dtype), then rebuild the dispatch programs so the next
        eval/pred traces the quantized graph. The tables ride in every
        subsequent snapshot as digest-covered ``quant/`` arrays
        (task=quantize is the canonical caller)."""
        assert self._initialized, "call init_model/load_model first"
        self.quant_tables = dict(tables)
        self.quant_meta = dict(meta)
        if dtype is not None:
            from .quantize import normalize_serve_dtype
            self.serve_dtype = normalize_serve_dtype(dtype)
        self._attach_quant()
        self._build_steps()
        self._put_all()
        self._emit_model_records()

    def _put_all(self) -> None:
        """Place params/state on the mesh with their shardings."""
        self.params = jax.device_put(self.params, self._p_shard)
        self.net_state = jax.device_put(
            self.net_state,
            jax.tree_util.tree_map(lambda _: self._repl, self.net_state))
        # optimizer state mirrors its weight's sharding (momentum of a
        # model-sharded fullc weight shards the same way), or is ZeRO-1
        # sharded across 'data' when shard_optimizer is set
        self.opt_state = jax.device_put(self.opt_state, self._o_shard)
        if self.update_period > 1:
            self.grad_acc = jax.device_put(
                _tree_zeros_like(self.params), self._p_shard)
        else:
            self.grad_acc = None

    # -- jitted programs -------------------------------------------------

    def _build_steps(self) -> None:
        mesh = self.mesh
        self.programs.reset()            # rebuilt programs orphan any
        #                                  earlier AOT executables
        self._scoped = set()
        self._bind_input_norm()          # a rebuilt net keeps the spec
        self._b_shard = batch_sharding(mesh)
        self._probe_input_layout()
        self._repl = replicated(mesh)
        self._repl_leaf = self._repl
        self._p_shard = param_sharding(mesh, self.params,
                                       self.model_parallel_min)
        # optimizer-state shardings (ZeRO-1 over 'data' when enabled)
        self._o_shard = {
            lk: {tag: jax.tree_util.tree_map(
                lambda leaf, _ps=self._p_shard[lk][tag]: opt_state_sharding(
                    leaf.shape, _ps.spec, mesh,
                    bool(self.shard_optimizer)),
                st)
                for tag, st in tags.items()}
            for lk, tags in self.opt_state.items()}
        net = self.net
        metric_nodes = tuple(self._metric_nodes)
        update_period = self.update_period
        # stable (layer, tag) -> row in the packed hyper array; packing
        # all per-step host float scalars (lr/momentum/wd) into ONE
        # small array keeps host->device traffic to a single transfer
        # per step (PCIe latency dominates tiny transfers). The
        # epoch rides as its own uint32 scalar beside it — a float32
        # slot silently rounds integers past 2^24, skewing Adam's bias
        # correction on long runs (same fix pattern as the RNG `step`)
        self._hyper_index = [(lk, tag)
                             for lk, tags in sorted(self.updaters.items())
                             for tag in sorted(tags)]
        self._base_key = jax.random.PRNGKey(self.seed + 1)

        def unpack_hyper(hyper_arr, idx, epoch):
            return {"learning_rate": hyper_arr[idx, 0],
                    "momentum": hyper_arr[idx, 1],
                    "wd": hyper_arr[idx, 2],
                    "epoch": epoch}

        hyper_row = {(lk, tag): i
                     for i, (lk, tag) in enumerate(self._hyper_index)}

        def apply_updates(params, opt_state, grads, hyper_arr, epoch):
            new_p, new_o = {}, {}
            for lk, ptree in params.items():
                new_p[lk], new_o[lk] = {}, {}
                for tag, w in ptree.items():
                    if not opt_state[lk][tag]:
                        # frozen group (lr_mult = 0): state allocation
                        # was skipped, the weight passes through
                        # untouched — bit-exact vs the pinned freeze
                        new_p[lk][tag] = w
                        new_o[lk][tag] = {}
                        continue
                    upd = self.updaters[lk][tag]
                    g = grads[lk][tag]
                    if update_period > 1:
                        g = g / float(update_period)
                    w2, s2 = upd.apply(
                        w, g, opt_state[lk][tag],
                        unpack_hyper(hyper_arr, hyper_row[(lk, tag)],
                                     epoch))
                    new_p[lk][tag] = w2
                    new_o[lk][tag] = s2
            return new_p, new_o

        grad_bf16 = self.grad_dtype == "bfloat16"
        if grad_bf16 and not any(
                k == "dtype" and v == "bfloat16" for k, v in self.cfg):
            raise ValueError(
                "grad_dtype=bfloat16 requires dtype=bfloat16 (layers "
                "must consume the bf16 weight shadow)")

        def _grad_cast(params):
            """bf16 shadow of the f32 master weights to differentiate
            against: cotangents then flow (and all-reduce across the
            'data' axis) in bf16 — half the gradient HBM/ICI bytes —
            while apply_updates reads the f32 masters (SURVEY §7 step 8
            mixed precision)."""
            if not grad_bf16:
                return params
            return jax.tree_util.tree_map(
                lambda w: w.astype(jnp.bfloat16)
                if w.dtype == jnp.float32 else w, params)

        def _grad_f32(grads):
            if not grad_bf16:
                return grads
            return jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads)

        def _wrap_loss_fn():
            """Rematerialization policy over the shared loss body.

            The reference trades compute for memory under an explicit
            budget (im2col chunking via temp_col_max,
            convolution_layer-inl.hpp:189-204); the TPU analogue is
            ``jax.checkpoint`` over the loss function, trading backward
            HBM activation traffic for recompute on the (mostly idle —
            doc/perf_profile.md roofline) MXU:

            * full — save only the step inputs; backward recomputes the
              entire forward.
            * dots — save dot_general (FC) outputs; recompute
              everything else (convs included — they are not dots).
            * conv — save ONLY conv-layer outputs (tagged ``conv_out``
              in layers/conv.py); FC dots, BN, activations and pools
              are recomputed.
            * block — the net itself runs segment by segment under
              ``jax.checkpoint``, a segment ending after each ``add``
              layer (``FuncNet._segments``): only the residual stream
              between a decoder's blocks is stored, and each block's
              inside is recomputed when the backward pass reaches it,
              one block at a time. (One checkpoint over the whole loss
              with a save-these-names policy stores the same but lets
              the compiler recompute every block at once: 0.6 GB over
              a v5e at 2 x 8k positions; my compile, PR 28.)

            remat_barrier=0 drops the optimization barriers
            (prevent_cse=False). Measured (doc/perf_profile.md r5):
            the forward and its backward recompute live in the SAME
            XLA computation here (value_and_grad inside one step), so
            without barriers XLA CSEs the recompute against the stored
            forward and the program returns to the remat=none baseline
            — no cost, but no memory savings either. Barriers stay the
            default because guaranteed recompute is the knob's purpose
            (HBM capacity).
            """
            fn = (lambda p, s, d, l, m, e, r:
                  net.loss_fn(p, s, d, l, m, extra=e, rng=r,
                              collect_nodes=metric_nodes))
            net.block_remat = self.remat == "block"
            if self.remat in ("none", "block"):
                return fn
            barrier = bool(self.remat_barrier)
            if self.remat == "full":
                return jax.checkpoint(fn, prevent_cse=barrier)
            if self.remat == "dots":
                return jax.checkpoint(
                    fn, prevent_cse=barrier,
                    policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            policy = jax.checkpoint_policies.save_only_these_names(
                "conv_out")
            return jax.checkpoint(fn, prevent_cse=barrier, policy=policy)

        loss_fn = _wrap_loss_fn()
        # grad_sync = overlap: thread each reduction group's params
        # through an identity custom-vjp boundary INSIDE the
        # differentiated loss. The backward barriers make each group's
        # gradients (and the SPMD all-reduce that consumes them) an
        # atomic schedulable unit, so XLA issues group g's cross-host
        # reduction as soon as g's backward finishes — overlapping DCN
        # traffic with the remaining (earlier-layer) backprop. Identity
        # numerics: bit parity with fused is by construction (pinned in
        # tests/test_gradsync.py at H=2,4).
        self._sync_groups = None
        if self.grad_sync == "overlap":
            from ..parallel import gradsync as _gradsync
            self._sync_groups = _gradsync.partition_groups(
                self.params, self._layer_index,
                bucket_mb=self.grad_sync_bucket_mb)
            _fused_loss = loss_fn
            _groups = self._sync_groups

            def loss_fn(p, s, d, l, m, e, r):
                return _fused_loss(
                    _gradsync.apply_group_boundaries(p, _groups),
                    s, d, l, m, e, r)

        def scan_step(params, opt_state, net_state, grad_acc,
                      data, labels, mask, extra, hyper_row, epoch,
                      do_up, step, base_key, collect):
            """The ONE train-step body all dispatch paths share
            (update / update_many / run_steps — a single definition so
            the math cannot drift between them). do_up may be traced
            (scan windows) or a static bool (per-batch update); the
            hyper row is per-step so the LR/momentum schedule advances
            inside scanned dispatches. ``step`` and ``epoch`` ride as
            their own uint32 scalars — a float32 hyper-array slot
            silently rounds past 2^24, repeating dropout/insanity RNG
            streams (step) and skewing Adam's bias correction (epoch)
            on long runs."""
            rng = jax.random.fold_in(base_key, step)
            with jax.named_scope("grad_cast"):
                shadow = _grad_cast(params)
            (loss, (new_state, preds)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(
                    shadow, net_state, data, labels, mask, extra, rng)
            preds = [p.astype(jnp.float32) for p in preds] if collect \
                else []
            with jax.named_scope("grad_cast"):
                grads = _grad_f32(grads)
            if update_period == 1:
                with jax.named_scope("update"):
                    params, opt_state = apply_updates(
                        params, opt_state, grads, hyper_row, epoch)
                return (params, opt_state, new_state, grad_acc, loss,
                        preds)
            # accumulate in f32 regardless of gradient dtype
            with jax.named_scope("update"):
                grad_acc = _tree_add(grad_acc, grads)

            def do_apply(args):
                p, o, acc = args
                with jax.named_scope("update"):
                    p2, o2 = apply_updates(p, o, acc, hyper_row, epoch)
                    return p2, o2, _tree_zeros_like(acc)

            params, opt_state, grad_acc = jax.lax.cond(
                do_up, do_apply, lambda a: a,
                (params, opt_state, grad_acc))
            return params, opt_state, new_state, grad_acc, loss, preds

        def train_step(params, opt_state, net_state, grad_acc,
                       data, labels, mask, extra, hyper_arr, epoch,
                       step, base_key, do_update):
            return scan_step(params, opt_state, net_state, grad_acc,
                             data, labels, mask, extra, hyper_arr,
                             epoch, do_update, step, base_key, True)

        donate = (0, 1, 3) if update_period > 1 else (0, 1)
        # pin output shardings: without this, GSPMD propagation from the
        # ZeRO-sharded optimizer state drifts the *weights* into a
        # data-sharded layout too (ZeRO-3-like), forcing an all-gather
        # in every forward pass
        ns_shard = jax.tree_util.tree_map(lambda _: self._repl,
                                          self.net_state)
        acc_shard = self._p_shard if update_period > 1 else None
        out_shardings = (self._p_shard, self._o_shard, ns_shard,
                         acc_shard, self._repl, self._b_shard)
        self._train_step = jax.jit(train_step, donate_argnums=donate,
                                   static_argnames=("do_update",),
                                   out_shardings=out_shardings)

        def multi_step(params, opt_state, net_state, grad_acc, data,
                       labels, mask, extra, hyper_k, epoch_k, do_up_k,
                       step, base_key):
            """n_steps train steps in ONE dispatch (lax.scan over the
            same resident batch) — host dispatch latency amortizes to
            zero. hyper_k is (n_steps, n_updaters, 3): the schedule
            advances per step in-scan; epoch_k/do_up_k carry the exact
            uint32 epochs and the accumulation-window apply flags, so
            ``update_period > 1`` closes its windows in-scan exactly
            like the per-batch dispatch path."""
            def body(carry, xs):
                p, o, s, acc = carry
                hyper_i, epoch_i, do_up, i = xs
                p, o, s, acc, loss, _ = scan_step(
                    p, o, s, acc, data, labels, mask, extra, hyper_i,
                    epoch_i, do_up, step + i, base_key, False)
                return (p, o, s, acc), loss
            n = hyper_k.shape[0]
            # the scan's own ops (slicing each step's rows out of the
            # stacked operands) get a name too; layers lie deeper
            with jax.named_scope("window"):
                carry, losses = jax.lax.scan(
                    body, (params, opt_state, net_state, grad_acc),
                    (hyper_k, epoch_k, do_up_k,
                     jnp.arange(n, dtype=jnp.uint32)))
            params, opt_state, net_state, grad_acc = carry
            return params, opt_state, net_state, grad_acc, losses[-1]

        self._multi_step = jax.jit(
            multi_step, donate_argnums=donate,
            out_shardings=(self._p_shard, self._o_shard, ns_shard,
                           acc_shard, self._repl))

        # K-batch window sharding: leading axis = scan step, batch rows
        # sharded on 'data' as usual
        self._kb_shard = NamedSharding(mesh, P(None, "data"))
        self._stack_k = jax.jit(lambda *xs: jnp.stack(xs),
                                out_shardings=self._kb_shard)

        def many_step(params, opt_state, net_state, grad_acc,
                      data_k, labels_k, mask_k, extra_k, hyper_k,
                      epoch_k, do_up_k, step, base_key, collect):
            """K REAL batches in one dispatch: scan over the stacked
            window. Schedule-correct (per-step hyper rows + exact
            uint32 epochs) and update_period-correct (traced apply
            flags)."""
            def body(carry, xs):
                p, o, s, acc = carry
                (data, labels, mask, extra, hyper_i, epoch_i, do_up,
                 i) = xs
                p, o, s, acc, loss, preds = scan_step(
                    p, o, s, acc, data, labels, mask, extra, hyper_i,
                    epoch_i, do_up, step + i, base_key, collect)
                return (p, o, s, acc), (loss, preds)
            K = hyper_k.shape[0]
            with jax.named_scope("window"):
                carry, (losses, preds_k) = jax.lax.scan(
                    body, (params, opt_state, net_state, grad_acc),
                    (data_k, labels_k, mask_k, extra_k, hyper_k, epoch_k,
                     do_up_k, jnp.arange(K, dtype=jnp.uint32)))
            params, opt_state, net_state, grad_acc = carry
            return (params, opt_state, net_state, grad_acc, losses[-1],
                    preds_k)

        self._many_step = jax.jit(
            many_step, donate_argnums=donate,
            static_argnames=("collect",),
            out_shardings=(self._p_shard, self._o_shard, ns_shard,
                           acc_shard, self._repl, self._kb_shard))

        def pred_step(params, net_state, data, mask, extra,
                      nodes_wanted):
            node_vals, _, _ = net.forward(params, net_state, data,
                                          extra=extra,
                                          is_train=False, rng=None,
                                          mask=mask)
            # metrics/extraction read f32 LOGICAL tensors regardless of
            # compute dtype / channel padding
            return [net.depad_node(i, node_vals[i]).astype(jnp.float32)
                    for i in nodes_wanted]

        self._pred_step = jax.jit(pred_step,
                                  static_argnames=("nodes_wanted",))
        # the serve-ladder variant donates the batch data/mask buffers
        # (consumed exactly once per dispatch) so XLA may reuse them
        # for outputs; compiled only by precompile_pred(donate=True) —
        # results are identical, so the two variants are interchangeable
        self._pred_step_donate = jax.jit(pred_step,
                                         static_argnames=("nodes_wanted",),
                                         donate_argnums=(2, 3))
        self._build_resident_prep()

    # -- device-side input normalisation ---------------------------------

    def set_input_norm(self, mean, scale=1.0) -> None:
        """Adopt an iterator chain's normalisation (``IIterator.
        defer_normalize``): from now on a NON-FLOATING batch is raw
        pixels and every program of this trainer starts with
        ``(float32(x) - mean) * scale`` (``Net.forward``, scope
        ``input_norm``); a floating batch lowers to the program it
        always did. ``mean`` is None, ``(C,)`` or ``(H, W, C)``. The
        identity spec (no mean, scale 1) adopts too: it says the
        chain's batches are uint8, which ``precompile`` lowers for."""
        assert self._initialized, "call init_model/load_model first"
        spec = (None if mean is None else np.asarray(mean, np.float32),
                np.float32(scale))
        if self.input_norm is not None and self._same_norm(spec):
            return
        self.input_norm = spec
        self._bind_input_norm()
        # a trace of a non-floating signature made before this call
        # has the old constants baked in; registry keys carry the
        # spec's digest (_dtype_tag), the jit fallback forgets
        for fn in (self._train_step, self._multi_step, self._many_step,
                   self._pred_step, self._pred_step_donate):
            fn.clear_cache()

    def adopt_input_norm(self, spec) -> bool:
        """The ``accept`` of ``IIterator.defer_normalize``: the first
        spec offered is adopted, a later chain is taken over only when
        it offers the same one (one trainer, one normalisation; a
        chain turned down keeps normalising on the host)."""
        if self.input_norm is None:
            self.set_input_norm(*spec)
        return self._same_norm(spec)

    def _same_norm(self, spec) -> bool:
        (m0, s0), (m1, s1) = self.input_norm, spec
        return bool(s0 == s1 and (
            m1 is None if m0 is None
            else m1 is not None and np.array_equal(m0, m1)))

    def _bind_input_norm(self) -> None:
        mean, scale = self.input_norm or (None, 1.0)
        identity = mean is None and scale == 1
        self.net.input_norm = None if identity else self.input_norm
        self._norm_digest = ""
        if not identity:
            import hashlib
            h = hashlib.sha1(scale.tobytes())
            if mean is not None:
                h.update(repr(mean.shape).encode() + mean.tobytes())
            self._norm_digest = h.hexdigest()[:12]

    def _dtype_tag(self, dtype) -> str:
        """The dtype element of a dispatch signature. A non-floating
        input under an adopted normalisation runs a program with that
        mean and scale baked in, so its key carries their digest: a
        sealed program of another spec can never answer for it."""
        if not self._norm_digest or jnp.issubdtype(dtype, jnp.floating):
            return str(dtype)
        return "%s/norm:%s" % (dtype, self._norm_digest)

    def input_norm_record(self) -> Dict[str, Any]:
        """The adopted spec for ``run_start``: ``mean`` the per-channel
        values, or the mean image's shape."""
        if self.input_norm is None:
            return {"adopted": False, "mean": None, "scale": 1.0}
        mean, scale = self.input_norm
        if mean is not None:
            mean = [float(v) for v in mean] if mean.ndim == 1 \
                else "image%r" % (tuple(mean.shape),)
        return {"adopted": True, "mean": mean, "scale": float(scale)}

    def _probe_input_layout(self) -> None:
        """input_layout = rowmajor: decide ONCE whether the batch
        input's device layout is pinned (channels minor, so the
        compiler cannot pick the batch-minor cliff layout —
        doc/perf_profile.md: batch 160 put the batch on the 128-lane
        minor dim, 5,082 -> 3,088 img/s), by placing a tiny array with
        the explicit major-to-minor format. ``input_layout_effective``
        is what took hold and what the ``layout`` record reports. A
        single-process run that asked for the pin and cannot have it
        raises: a knob that silently does nothing makes every number
        taken under it a number about something else."""
        self.input_layout_effective = "none"
        if self.input_layout != "rowmajor":
            return
        if jax.process_count() > 1:
            # multi-process batches come through
            # make_array_from_process_local_data, which takes no layout
            # — an AOT program lowered with a pinned input layout would
            # then mismatch every dispatched array. Pin single-process
            # only.
            from ..monitor import warn_once
            warn_once("input_layout_multiprocess",
                      "input_layout=rowmajor is single-process only; "
                      "inputs stay unpinned under multi-process dp")
            return
        try:
            # one row per data shard: the probe must split like a batch
            jax.block_until_ready(put_with_layout(
                np.zeros((self.mesh.shape["data"], 2, 2, 2), np.float32),
                self._rowmajor(self._b_shard, 4)))
        except Exception as e:
            raise RuntimeError(
                "input_layout = rowmajor was asked for, but the %s "
                "backend cannot place an array with a pinned "
                "major-to-minor layout: %s"
                % (jax.default_backend(), e)) from e
        self.input_layout_effective = "rowmajor"

    @staticmethod
    def _rowmajor(sharding, ndim: int):
        from jax.experimental.layout import Format, Layout
        return Format(Layout(major_to_minor=tuple(range(ndim))),
                      sharding)

    def _pin_layout(self, sharding, ndim: int):
        """Row-major (channels-minor) format for a spatial batch input
        under an effective ``input_layout = rowmajor``, the plain
        sharding otherwise."""
        if self.input_layout_effective != "rowmajor" or ndim < 4:
            return sharding
        return self._rowmajor(sharding, ndim)

    # -- device-resident serve weights (doc/serving.md) ------------------

    def _resident_plan(self) -> List[Dict[str, Any]]:
        """Static per-layer plan of the eval-graph weight work that can
        hoist out of the per-dispatch traced graph into a one-time
        freeze: ``bn_fold_eval`` weight folds, int8/fp8 weight
        quantization, bf16 weight casts, and the per-channel epilogue
        vectors. Channel-alignment-annotated layers keep the legacy
        in-graph path (channel_pad is a training-bench knob; serving
        graphs run unpadded). Empty plan = the serve tree IS the master
        tree (nothing to hoist, nothing extra resident)."""
        net, g = self.net, self.graph
        shared_primaries = set(info.primary_layer_index
                               for info in g.layers
                               if info.type == "share")
        plan: List[Dict[str, Any]] = []
        for li, info in enumerate(g.layers):
            if info.type not in ("conv", "fullc") \
                    or li in shared_primaries:
                continue
            lkey = g.layer_key(li)
            if lkey not in self.params \
                    or "wmat" not in self.params[lkey]:
                continue
            layer = net.layer_objs[li]
            if (getattr(layer, "_in_layout", None) is not None
                    or getattr(layer, "_out_pad", 0)
                    or getattr(layer, "_layout", None) is not None):
                continue
            q = getattr(layer, "_quant", None)
            quant = q is not None and q.is_affine
            bf16 = (layer.param.compute_dtype == "bfloat16"
                    or (q is not None and q.dtype == "bfloat16"))
            fold = (info.type == "conv" and net._bn_fold_eval
                    and li in net._fold_pairs)
            # with conv_pallas_epilogue the fold factor applies to the
            # conv OUTPUT (no per-dispatch weight work exists): only
            # the scale/shift vectors precompute, the weight stays raw
            epifold = (fold and not quant
                       and bool(layer.param.conv_pallas_epilogue))
            prefold = fold and not epifold
            if not (quant or bf16 or prefold or epifold):
                continue
            relu = False
            if fold:
                relu = bool(net.layer_objs[net._fold_pairs[li]]
                            .fuse_relu)
            plan.append({"li": li, "lkey": lkey, "kind": info.type,
                         "q": q, "quant": quant, "bf16": bf16,
                         "prefold": prefold, "epifold": epifold,
                         "relu": relu,
                         "has_bias": layer.param.no_bias == 0})
        return plan

    def _build_resident_prep(self) -> None:
        """The ONE-time serve-weight transformation program: folds,
        quantizes and casts the eval weight tree on device at freeze
        (registered in ``lint/config.py PROGRAM_BUILDERS``). Returns
        only the NEW leaves — untransformed weights alias the masters
        so they are never duplicated on device."""
        self._serve_plan = self._resident_plan()
        self._serve_prep = None
        if not self._serve_plan:
            return
        net = self.net
        plan = self._serve_plan

        def prep(params, net_state):
            out: Dict[str, Dict[str, Any]] = {}
            for item in plan:
                p = params[item["lkey"]]
                new: Dict[str, Any] = {}
                w = p["wmat"]
                b = p.get("bias") if item["has_bias"] else None
                eff = None
                if item["prefold"] or item["epifold"]:
                    fe = net._fold_entries(params, net_state,
                                           item["li"])
                    scale, shift = fe["_fold_scale"], fe["_fold_shift"]
                    if item["prefold"]:
                        w = w * scale
                        eff = shift if b is None else shift + b * scale
                    else:
                        new["_fold_scale"] = scale
                        new["_fold_shift"] = shift
                        if item["relu"]:
                            # value never read — key presence is the
                            # (static) relu flag, as on the legacy path
                            new["_fold_relu"] = jnp.ones((),
                                                         jnp.float32)
                if item["quant"]:
                    q = item["q"]
                    w = q.quantize_w(w)
                    dq = q.dequant_vec()
                    new["_r_dequant"] = dq
                    if item["kind"] == "conv":
                        shift_vec = eff if eff is not None \
                            else (b if b is not None
                                  else jnp.zeros_like(dq))
                        new["_r_shift_relu" if item["relu"]
                            else "_r_shift"] = shift_vec
                elif item["prefold"]:
                    new["_r_shift_relu" if item["relu"]
                        else "_r_shift"] = eff
                if item["bf16"] and not item["quant"]:
                    w = w.astype(jnp.bfloat16)
                if item["quant"] or item["prefold"] or item["bf16"]:
                    new["wmat"] = w
                out[item["lkey"]] = new
            return out

        self._serve_prep = jax.jit(prep)

    def _predict_resident_extra(self) -> int:
        """Bytes the serve tree will add beyond the masters, computed
        from the plan WITHOUT touching the device — so a budget breach
        rejects before the upload, not as an OOM during it."""
        extra = 0
        for item in self._serve_plan:
            w = self.params[item["lkey"]]["wmat"]
            n = int(np.prod(w.shape))
            if item["quant"]:
                extra += n if item["q"].native else 4 * n
            elif item["bf16"]:
                extra += 2 * n
            elif item["prefold"]:
                extra += 4 * n
            # per-channel vectors are noise next to the weight tensors
        return extra

    def freeze_serve_weights(self, force: bool = False):
        """Build (or return) the device-resident serve weight tree:
        eval folds applied, int8/fp8 weights quantized, bf16 weights
        cast — exactly once — and install it in the program registry
        with honest byte accounting against
        ``serve_device_mem_budget``. Every subsequent pred dispatch
        passes the tree as arguments, so all bucket executables share
        one copy per model. Returns the
        :class:`~cxxnet_tpu.artifact.registry.WeightResidency` (None
        when ``serve_weight_residency = 0``). Any weight mutation
        (update/set_weight/copy_model_from/program rebuild) invalidates
        the tree; the next pred dispatch re-freezes against the same
        executables (identical avals — no recompile)."""
        assert self._initialized, "call init_model/load_model first"
        if not self.serve_weight_residency:
            return None
        reg = self.programs
        if reg.residency is not None and not force:
            return reg.residency
        budget = int(self.serve_device_mem_budget * 1e6)

        def tree_bytes(pytrees, seen):
            tot = 0
            for tr in pytrees:
                for pt in tr.values():
                    for v in pt.values():
                        if id(v) in seen:
                            continue
                        seen.add(id(v))
                        tot += int(getattr(v, "nbytes", 0) or 0)
            return tot

        seen: set = set()
        master = tree_bytes((self.params, self.net_state), seen)
        extra = self._predict_resident_extra()
        if budget and master + extra > budget:
            raise _areg.ResidencyBudgetError(
                "model needs ~%d resident bytes (masters %d + serve "
                "tree extra %d) but serve_device_mem_budget allows %d"
                % (master + extra, master, extra, budget))
        t0 = time.perf_counter()
        if self._serve_prep is not None:
            new = self._serve_prep(self.params, self.net_state)
            jax.block_until_ready(new)
            tree = {lk: ({**pt, **new[lk]} if lk in new else pt)
                    for lk, pt in self.params.items()}
        else:
            tree = self.params
        quantize_ms = (time.perf_counter() - t0) * 1e3
        tb = tree_bytes((tree,), set())
        # ``seen`` already holds every master buffer: only the leaves
        # the prep program materialized add to the deduped total
        total = master + tree_bytes((tree,), seen)
        res = _areg.WeightResidency(
            tree, tb, master, total, quantize_ms,
            len(self._serve_plan), self.serve_dtype,
            bool(self._serve_plan))
        reg.install_weights(res, budget)
        if self._mon_on():
            self._mon.emit("weight_residency", **res.record())
        return res

    def _pred_operands(self):
        """The (params, net_state) every eval/pred dispatch passes:
        the device-resident serve tree under weight residency (frozen
        lazily), the raw masters otherwise. One definition so
        precompile keys and dispatch operands can never disagree on
        the calling convention."""
        if self.serve_weight_residency:
            res = self.programs.residency or self.freeze_serve_weights()
            if res is not None:
                return res.tree, self.net_state
        return self.params, self.net_state

    @property
    def _aot(self) -> Dict[tuple, Any]:
        """The registry's executable map — kept as a read surface for
        the serve engine's aot-hit accounting and tests; mutation goes
        through ``self.programs``."""
        return self.programs.aot

    @property
    def _seen_sigs(self) -> set:
        """Dispatch signatures seen (compile/recompile detection) —
        registry-owned so precompile seeding and bundle installs share
        one set with the dispatch-time accounting."""
        return self.programs.seen

    def _call_step(self, kind, sig, jit_fn, args, **static_kw):
        """Dispatch one program: the registry executable when this
        exact signature was precompiled (or installed from a sealed
        artifact — static args baked in either way), the jit function
        otherwise. One code path so a key-scheme change cannot
        silently strand a dispatch site on jit fallback."""
        key = (kind,) + sig
        aot = self.programs.get(key)
        if aot is None:
            return jit_fn(*args, **static_kw)
        out = aot(*args)
        if (kind != "pred" and key not in self._scoped
                and self._mon_on()):
            # after the enqueue: the device works while the host reads
            self._scoped.add(key)
            self._emit_program_scopes(kind, aot)
        return out

    def _emit_program_scopes(self, kind: str, executable) -> None:
        """One ``program_scopes`` record at a registry program's first
        dispatch: {HLO instruction -> scope path}, read from the text
        of the executable that was LOADED (jax's persistent-cache key
        ignores metadata, so an executable read from a cache written
        by an earlier build carries that build's scopes, or none: the
        record says what the chip is running, not what was traced).
        Programs on the jit fallback path give no record, and neither
        do ``pred`` programs: serving gets its spans and scopes with
        its benchmark cell (PERF.md)."""
        with self._span("setup.program_scopes") as span:
            try:
                module, scopes, fusions, mapped = scope_map(
                    executable.as_text(),
                    self.net.scope_names + STEP_SCOPES)
            except Exception as e:  # an executable that cannot print
                self._mon.warn_once(
                    "program_scopes_failed",
                    "no scope map for program %r: %s" % (kind, e))
                return
        self._mon.emit("program_scopes", program=kind, module=module,
                       scopes=scopes, fusions=fusions,
                       fusions_mapped=mapped, wall_ms=span.dur_ns / 1e6)

    # the pred dispatch signature (sans the leading "pred" kind): the
    # single definition — cxxnet_tpu.artifact.registry.pred_sig —
    # shared by `_call_pred`, `precompile_pred`, the serve engine's
    # compile-event accounting, and the sealed-bundle key encoding; a
    # key-scheme change cannot strand one of them on a stale scheme
    pred_sig = staticmethod(_areg.pred_sig)

    def _call_pred(self, data, mask, extra, nodes_wanted):
        params, net_state = self._pred_operands()
        sig = self.pred_sig(data.shape, self._dtype_tag(data.dtype),
                            mask is None, len(extra), nodes_wanted)
        return self._call_step(
            "pred", sig, self._pred_step,
            (params, net_state, data, mask, extra),
            nodes_wanted=nodes_wanted)

    # -- AOT precompile --------------------------------------------------

    def _enable_persistent_cache(self) -> None:
        """Point jax at a persistent on-disk compilation cache
        (``compile_cache_dir``): recompiles across RUNS become cache
        deserializations — the first-round compile cost is paid once
        per (program, jaxlib, flags) per machine. Where the directory
        comes from is one rule shared with benchmarks/run.py and
        chip_smoke.py (utils/compile_cache.py):
        ``JAX_COMPILATION_CACHE_DIR`` in the environment wins over the
        key."""
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache(self.compile_cache_dir)

    def precompile(self, window: int = 1, n_steps: int = 0,
                   per_batch: bool = True) -> int:
        """AOT-compile the dispatch programs for the shapes this run
        will use, before round 0 touches the device.

        ``.lower().compile()``s the per-batch train step, the K-window
        ``update_many`` step (``window`` > 1 — pass the CLI loop's
        dispatch_period), the eval/pred forward, and (``n_steps`` > 0)
        the ``run_steps`` scan, each for every mask variant the run can
        dispatch. The compiled executables are kept and dispatched
        directly (no jit-cache round trip), so the steady-state loop
        never sees a compile: the recompile stalls PR 1's telemetry
        records in round 0 move to a single accounted precompile window
        — and with ``compile_cache_dir`` set they amortize across runs.

        Shapes must be fully known: batch_size from the config, the
        instance shape from ``input_shape``, input dtype from what the
        run's iterator handed over: uint8 once a normalisation was
        adopted (``set_input_norm``, before this call), else float32;
        int32 where the net's first layer looks up ids (``embed``).
        Nets with
        ``extra_data`` inputs and eval iterators with a different
        batch_size fall back to the jit path for those dispatches —
        precompile never changes results, only when compilation
        happens. ``per_batch=False`` compiles ONLY the ``run_steps``
        program (a resident-batch job — no wasted minutes on update/
        pred variants it never dispatches). With
        ``input_layout = rowmajor`` the lowered programs pin the batch
        input's device layout channels-minor. Returns the number of
        programs compiled."""
        assert self._initialized, "call init_model/load_model first"
        with self._span("setup.precompile") as span:
            from ..io.data import inst_array_shape
            self._enable_persistent_cache()
            dtype = np.dtype(np.int32 if self.net.ids_input
                             else np.float32 if self.input_norm is None
                             else np.uint8)
            tag = self._dtype_tag(dtype)
            # GLOBAL batch shapes: multi-process dispatch arrays come out of
            # make_array_from_process_local_data with the global leading dim
            # (each rank contributes batch_size/world rows), and the runtime
            # signature keys use those global shapes
            n = self.batch_size
            data_shape = (n,) + inst_array_shape(
                tuple(self.graph.input_shape))
            lw = max((b for _, _a, b in self._label_slices), default=1)
            label_shape = (n, lw)

            def sds(shape, dt, sharding=None):
                if sharding is None:
                    return jax.ShapeDtypeStruct(shape, dt)
                return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

            data_s = sds(data_shape, dtype,
                         self._pin_layout(self._b_shard, len(data_shape)))
            labels_s = sds(label_shape, np.float32, self._b_shard)
            hyper_s = sds((len(self._hyper_index), 3), np.float32)
            step_s = sds((), np.uint32)
            epoch_s = sds((), np.uint32)
            # the None-mask specialization only exists single-process
            # (multi-process dp always materializes the mask — see _mask)
            mask_variants = [None, sds((n,), np.float32, self._b_shard)]
            if jax.process_count() > 1:
                mask_variants = [sds((n,), np.float32, self._b_shard)]
            do_up_variants = [True] if self.update_period == 1 \
                else [True, False]
            programs = []                    # (key, lower_thunk)

            for mask_v in (mask_variants if per_batch else []):
                for du in do_up_variants:
                    key = ("update",) + _areg.update_sig(
                        data_shape, tag, label_shape, mask_v is None, 0,
                        bool(du))
                    programs.append((key, lambda m=mask_v, d=du:
                                     self._train_step.lower(
                                         self.params, self.opt_state,
                                         self.net_state, self.grad_acc,
                                         data_s, labels_s, m, (), hyper_s,
                                         epoch_s, step_s, self._base_key,
                                         do_update=d)))
                if window > 1:
                    K = int(window)
                    data_k_s = sds((K,) + data_shape, dtype, self._kb_shard)
                    labels_k_s = sds((K,) + label_shape, np.float32,
                                     self._kb_shard)
                    mask_k = None if mask_v is None \
                        else sds((K, n), np.float32, self._kb_shard)
                    hyper_k_s = sds((K, len(self._hyper_index), 3),
                                    np.float32)
                    epoch_k_s = sds((K,), np.uint32)
                    do_up_s = sds((K,), np.bool_)
                    collect = bool(self.eval_train and self._metrics.evals)
                    key = ("update_many",) + _areg.update_many_sig(
                        (K,) + data_shape, tag, (K,) + label_shape,
                        mask_k is None, 0, K, collect)
                    programs.append((key, lambda mk=mask_k, c=collect,
                                     ds=data_k_s, ls=labels_k_s,
                                     hs=hyper_k_s, es=epoch_k_s,
                                     us=do_up_s:
                                     self._many_step.lower(
                                         self.params, self.opt_state,
                                         self.net_state, self.grad_acc,
                                         ds, ls, mk, (), hs, es, us,
                                         step_s, self._base_key,
                                         collect=c)))
                if self._metric_nodes:
                    nodes = tuple(self._metric_nodes)
                    key = ("pred",) + self.pred_sig(
                        data_shape, tag, mask_v is None, 0, nodes)
                    # operands resolved at lower time: under weight
                    # residency the eval dispatches pass the frozen serve
                    # tree, so the precompiled program must take the same
                    # pytree (one calling convention per trainer)
                    programs.append((key, lambda m=mask_v, nw=nodes:
                                     self._pred_step.lower(
                                         *self._pred_operands(),
                                         data_s, m, (),
                                         nodes_wanted=nw)))

            if n_steps > 0:
                # run_steps is the bench/test_skipread mode: its mask
                # variant is known up front (None single-process, the
                # materialized mask under multi-process dp), so exactly ONE
                # program compiles — no wasted minutes on the other variant
                mask_rs = None if jax.process_count() == 1 \
                    else mask_variants[0]
                ns = int(n_steps)
                hyper_k_s = sds((ns, len(self._hyper_index), 3),
                                np.float32)
                epoch_k_s = sds((ns,), np.uint32)
                do_up_k_s = sds((ns,), np.bool_)
                key = ("run_steps",) + _areg.run_steps_sig(
                    data_shape, tag, label_shape, mask_rs is None, 0, ns)
                programs.append((key, lambda m=mask_rs, hs=hyper_k_s,
                                 es=epoch_k_s, us=do_up_k_s:
                                 self._multi_step.lower(
                                     self.params, self.opt_state,
                                     self.net_state, self.grad_acc,
                                     data_s, labels_s, m, (), hs, es,
                                     us, step_s, self._base_key)))

            compiled = self._compile_programs(programs,
                                              "precompile_failed")
        # the span's duration: 0.0 without an enabled monitor
        self.precompile_wall_s = span.dur_ns / 1e9
        self.precompile_programs = compiled
        if self._mon_on():
            self._mon.emit("precompile",
                           wall_ms=self.precompile_wall_s * 1e3,
                           programs=compiled)
        return compiled

    def _compile_programs(self, programs, warn_code: str) -> int:
        """AOT-compile ``(key, lower-thunk)`` pairs into the program
        registry, skipping keys already present (precompiled earlier,
        or installed from a sealed artifact bundle). The registry's
        ``compile`` is the one loop behind ``precompile`` and
        ``precompile_pred`` — failure fallback, signature seeding and
        per-program telemetry cannot drift between the training and
        serving warmup paths."""
        return self.programs.compile(
            programs, warn_code,
            monitor=self._mon if self._mon_on() else None)

    def precompile_pred(self, batch_sizes: Sequence[int],
                        nodes_wanted: Optional[Sequence[int]] = None,
                        dtype=None, donate: bool = False) -> int:
        """AOT-compile the eval/pred forward at a set of batch-size
        buckets — the serve-engine warmup path (doc/serving.md).

        One executable per reachable (bucket, mask-variant): the
        exactly-full variant (mask None — the mask-free specialization
        every perfectly filled micro-batch dispatches) always, plus
        the padded variant (rows rounded up to the bucket ride a zero
        mask tail, the ``num_batch_padd`` machinery) for buckets a
        partial batch can actually land in — the smallest row count
        rounding up to bucket ``b`` is ``prev_bucket + 1``, so when
        that equals ``b`` the masked program is dead and is skipped.
        After this returns, a dispatch at any compiled bucket goes
        straight to its executable — steady-state serving records zero
        XLA compile events.

        ``nodes_wanted`` are node indices (default: the top node, the
        ``predict`` output); compile one call per distinct node set you
        will serve. Failures fall back to the jit path with a one-time
        warning — warmup must never take a server down. Returns the
        number of programs compiled."""
        assert self._initialized, "call init_model/load_model first"
        from ..io.data import inst_array_shape
        t_start = time.perf_counter()
        self._enable_persistent_cache()
        nodes = (self.graph.num_nodes - 1,) if nodes_wanted is None \
            else tuple(nodes_wanted)
        dt = np.dtype(np.float32 if dtype is None else dtype)
        inst = inst_array_shape(tuple(self.graph.input_shape))
        from ..serve.bucketing import reachable_variants
        # one resolve up front: freezes the serve weight tree (weight
        # residency on) so every bucket executable below is lowered
        # against the SAME shared device tree — and a
        # serve_device_mem_budget breach rejects here, at warmup, with
        # the typed error instead of an OOM mid-request
        params_t, state_t = self._pred_operands()
        pred_jit = self._pred_step_donate \
            if donate and self.serve_donate else self._pred_step
        programs = []
        data_structs = {}
        for n, rows in reachable_variants(batch_sizes):
            data_shape = (n,) + inst
            if n not in data_structs:
                data_structs[n] = jax.ShapeDtypeStruct(
                    data_shape, dt,
                    sharding=self._pin_layout(self._b_shard,
                                              len(data_shape)))
            mask_s = None if rows == n else jax.ShapeDtypeStruct(
                (n,), np.float32, sharding=self._b_shard)
            key = ("pred",) + self.pred_sig(
                data_shape, self._dtype_tag(dt), mask_s is None, 0, nodes)
            programs.append((key, lambda ds=data_structs[n], m=mask_s,
                             pj=pred_jit:
                             pj.lower(params_t, state_t, ds,
                                      m, (), nodes_wanted=nodes)))
        compiled = self._compile_programs(programs,
                                          "precompile_pred_failed")
        if self._mon_on():
            self._mon.emit("precompile",
                           wall_ms=(time.perf_counter() - t_start) * 1e3,
                           programs=compiled)
        return compiled

    # -- hyper-params per step ------------------------------------------

    def _hyper(self, epoch: Optional[int] = None) -> np.ndarray:
        """Packed (n_updaters, 3) array: lr, momentum, wd. The epoch is
        NOT packed here — a float32 slot rounds integers past 2^24, so
        it rides separately as an exact uint32 (see _epoch_u32)."""
        if epoch is None:
            epoch = self.update_counter
        arr = np.zeros((len(self._hyper_index), 3), np.float32)
        for i, (lk, tag) in enumerate(self._hyper_index):
            upd = self.updaters[lk][tag]
            upd.param.schedule_epoch(epoch)
            arr[i] = (upd.param.learning_rate, upd.param.momentum,
                      upd.param.wd)
        return arr

    def _epoch_u32(self, epoch: Optional[int] = None) -> np.uint32:
        """Exact device-side epoch (applied-update counter) for Adam's
        bias correction — uint32, the same fix pattern as the RNG
        ``step`` scalar."""
        if epoch is None:
            epoch = self.update_counter
        return np.uint32(epoch)

    def _step_scalar(self) -> np.uint32:
        """Global sample-step counter for RNG folding (exact uint32; a
        float32 slot loses integer precision past 2^24)."""
        return np.uint32(self.update_counter * self.update_period
                         + self.sample_counter)

    # -- batch plumbing --------------------------------------------------

    def _local_batch_size(self, batch: DataBatch) -> int:
        """Rows this process contributes. For an already-global array
        (placed by the prefetch transform) that is 1/world_size of its
        leading dim; for host arrays it is the array's own size."""
        n = batch.batch_size
        if (jax.process_count() > 1 and isinstance(batch.data, jax.Array)
                and batch.data.sharding == self._b_shard):
            n //= jax.process_count()
        return n

    def _mask(self, batch: DataBatch):
        """Row-validity mask, or None when every row is real — the
        None specialization lets BN stats and the loss skip the
        broadcast-mask multiplies on full-size activations (the
        no-padding case is every steady-state batch; only epoch-tail
        batches compile the masked variant).

        Multi-process dp always materializes the mask: the None/array
        choice selects between two compiled programs, and per-RANK
        padding can differ on the epoch tail — ranks dispatching
        structurally different SPMD programs would deadlock the
        gradient collectives."""
        if not batch.num_batch_padd and jax.process_count() == 1:
            return None
        n = self._local_batch_size(batch)
        m = np.ones((n,), np.float32)
        if batch.num_batch_padd:
            m[n - batch.num_batch_padd:] = 0.0
        return m

    def _label_fields(self, label: np.ndarray, nvalid: int):
        return {name: label[:nvalid, a:b]
                for name, a, b in self._label_slices}

    def _host_label(self, batch: DataBatch) -> np.ndarray:
        """This process's label rows as float32 numpy (device labels
        placed by the prefetch transform come back via local shards)."""
        if isinstance(batch.label, jax.Array):
            return self._local_rows(batch.label).astype(np.float32)
        return np.asarray(batch.label, np.float32)  # cxxlint: disable=CXL003 -- host ring-buffer labels; no device value involved

    def _ship(self, arr: np.ndarray, sharding) -> jnp.ndarray:
        """Cast-and-transfer policy shared by per-batch and K-window
        placement: u8 pixels ship raw (1/4 bytes, device casts), the
        int32 ids of a net that embeds them ship as they are, all else
        float32; under multi-process dp each rank contributes its
        local shard of the global batch (config batch_size is GLOBAL,
        split across ranks like the reference splits across PS
        workers). bf16 rows also ship raw — a bf16-warmed serve ladder
        staging through here must not silently up-cast (and recompile)
        on the H2D path."""
        ids = self.net.ids_input and arr.dtype == np.int32
        if arr.dtype != np.uint8 and arr.dtype != _BF16 and not ids:
            arr = np.asarray(arr, np.float32)  # cxxlint: disable=CXL003 -- host-side cast before the H2D ship; input is host numpy
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, arr)
        # spatial batches take the row-major layout pin (channels on
        # the minor/lane dim) when input_layout=rowmajor is active
        fmt = self._pin_layout(sharding, arr.ndim)
        if fmt is sharding:
            return jax.device_put(arr, sharding)
        return put_with_layout(arr, fmt)

    def _put_batch_array(self, x) -> jnp.ndarray:
        if isinstance(x, jax.Array) and x.sharding == self._b_shard:
            return x                      # already resident (test_skipread)
        return self._ship(np.asarray(x), self._b_shard)  # cxxlint: disable=CXL003 -- host staging of the input batch (jax.Array case returned above)

    def _put_mask(self, batch: DataBatch):
        m = self._mask(batch)
        return None if m is None else self._put_batch_array(m)

    def _device_batch(self, batch: DataBatch):
        data = self._put_batch_array(batch.data)
        labels = self._put_batch_array(batch.label)
        return (data, labels, self._put_mask(batch),
                self._device_extra(batch))

    def device_put_batch(self, batch: DataBatch) -> DataBatch:
        """Move a batch's arrays to the device with the batch sharding.
        Hand this to PrefetchIterator.set_transform so the transfer
        happens in the prefetch thread, overlapped with compute."""
        return DataBatch(
            data=self._put_batch_array(batch.data),
            label=self._put_batch_array(batch.label),
            # copy: the source may be a ring buffer that is released
            # (and refilled) once the device arrays are ready, while
            # this staged batch lives on until consumed
            inst_index=None if batch.inst_index is None
            else np.array(batch.inst_index),
            num_batch_padd=batch.num_batch_padd,
            extra_data=[self._put_batch_array(e)
                        for e in batch.extra_data])

    def _device_extra(self, batch: DataBatch):
        return tuple(self._put_batch_array(e) for e in batch.extra_data)

    def _put_window(self, arrs) -> jnp.ndarray:
        """Place a K-batch window as ONE (K, batch, ...) array sharded
        (None, 'data'). Host arrays stack host-side and ship in a
        single transfer (K separate device_puts cost K dispatch round
        trips); device-resident arrays (prefetch-transform batches,
        test_skipread) stack device-side."""
        if any(isinstance(a, jax.Array) for a in arrs):
            return self._stack_k(*[self._put_batch_array(a)
                                   for a in arrs])
        return self._ship(np.stack([np.asarray(a) for a in arrs]),  # cxxlint: disable=CXL003 -- host-side window stack; device arrays took the _stack_k branch above
                          self._kb_shard)

    def _local_rows(self, arr, flatten: bool = True,
                    axis: int = 0) -> np.ndarray:
        """Fetch this process's rows of a batch-sharded output.

        Single-process: the whole array. Multi-process dp: concatenate
        the addressable shards in global row order along the batch
        ``axis`` (0 for per-batch outputs, 1 for K-window outputs whose
        leading axis is the scan step), which is exactly the order of
        this rank's local input rows (make_array_from_process_local_data
        splits the local batch over local devices in ascending mesh
        position). Shards are deduped by row range: with a model axis
        >1, batch-sharded outputs are replicated across 'model', so
        each row slice appears once per model-axis device. ``flatten``
        collapses the trailing dims to the as_mat 2-D view."""
        if jax.process_count() == 1:
            out = np.asarray(arr)  # cxxlint: disable=CXL003 -- intentional D2H: _local_rows exists to fetch rows for host metrics/output
        else:
            uniq = {}
            for s in arr.addressable_shards:
                uniq.setdefault(s.index[axis].start or 0, s)
            out = np.concatenate(
                [np.asarray(uniq[k].data) for k in sorted(uniq)],  # cxxlint: disable=CXL003 -- intentional D2H of local shards (see above)
                axis=axis)
        if not flatten:
            return out
        lead = out.shape[:axis + 1]
        return out.reshape(lead + (-1,))

    # -- observability ---------------------------------------------------

    def set_monitor(self, mon) -> None:
        """Attach a monitor (cxxnet_tpu.monitor.Monitor). With an
        enabled sink, each dispatch is timed wall-clock INCLUDING a
        block on the loss scalar — an honest device-step time at the
        cost of losing dispatch/compute overlap (the observer effect;
        documented in doc/observability.md). A None/disabled monitor
        leaves the step path untouched."""
        self._mon = mon
        self._scoped = set()             # a new stream gets the maps
        if self._initialized:
            self._emit_model_records()

    def _emit_model_records(self) -> None:
        """Static per-model telemetry: analytic FLOPs (the MFU
        denominator) and the layout/fusion pass decisions — schema-
        validated so BENCH records and monitor streams carry the same
        machine-readable perf context."""
        if not self._mon_on():
            return
        net = self.net
        n_params = sum(int(np.prod(w.shape))
                       for pt in self.params.values()
                       for w in pt.values())
        fwd = net.analytic_flops_per_example()
        self._mon.emit("model_info",
                       flops_per_example=fwd,
                       train_flops_per_example=3.0 * fwd,
                       tokens_per_example=net.tokens_per_example,
                       train_flops_per_token=3.0 * fwd
                       / net.tokens_per_example,
                       params=n_params,
                       layers=len(net.graph.layers))
        # attention layers, and those whose causal core is the fused
        # kernel (layers/sequence.py: the shapes decide)
        attn = [layer for layer in net.layer_objs
                if hasattr(layer, "fused_core")]
        cores = [layer.fused_core for layer in attn]
        # expert layers, and those whose experts run as the grouped
        # kernels while the routing fits their buffers (the same)
        grouped = [layer.grouped for layer in net.layer_objs
                   if hasattr(layer, "grouped")]
        # linear-attention layers (gated_delta: a state along time, in
        # chunks), the largest chunk among them, and those whose scan
        # is the fused kernels (the shapes decide)
        linear = [layer for layer in net.layer_objs
                  if hasattr(layer, "fused_scan")]
        chunks = [layer.chunk for layer in linear]
        self._mon.emit("layout",
                       # what took hold, not what was asked for
                       input_layout=self.input_layout_effective,
                       bn_fuse_relu=len(net._identity_layers),
                       bn_fold_eval_pairs=len(net._fold_pairs),
                       pool_concat_fused=len(net._pool_concat),
                       # how any Pallas kernel of this process is built
                       # (layers/pallas_kernels.interpret)
                       pallas_interpret=_pallas.interpret(),
                       attention_layers=len(cores),
                       attention_fused_layers=sum(cores),
                       # those whose core's o and row log-sum-exp a
                       # remat = block segment keeps, so that the
                       # core's forward kernel runs once a step
                       # (layers/base.py: BLOCK_REMAT_KEEPS)
                       attention_saved_layers=(
                           sum(cores) if self.remat == "block" else 0),
                       # those that see a window of keys, not every
                       # earlier one (gqa_attention's window key)
                       attention_window_layers=sum(
                           1 for layer in attn
                           if getattr(layer, "window", 0) > 0),
                       moe_layers=len(grouped),
                       moe_grouped_layers=sum(grouped),
                       linear_attention_layers=len(chunks),
                       linear_attention_chunk=max(chunks, default=0),
                       linear_attention_fused_layers=sum(
                           layer.fused_scan for layer in linear),
                       # gated short-convolution mixers (gated_conv), and
                       # whether the head is the embedding's own matrix
                       # (an embed layer applied to a sequence node)
                       short_conv_layers=sum(
                           info.type == "gated_conv"
                           for info in net.graph.layers),
                       head_tied=any(getattr(layer, "tied_head", False)
                                     for layer in net.layer_objs),
                       **net.layout_summary)
        if self.quant_report.get("active"):
            r = self.quant_report
            self._mon.emit("quantized_model", dtype=r["dtype"],
                           layers=r["layers"],
                           fallback_layers=r["fallback_layers"],
                           native=r["native"])

    def _mon_on(self) -> bool:
        return self._mon is not None and self._mon.enabled

    def _span(self, name: str, **attrs: int):
        """``Monitor.span`` of the attached monitor; the shared no-op
        without one (monitor/spans.py)."""
        if self._mon is None:
            return NULL_SPAN
        return self._mon.span(name, **attrs)

    def note_data_wait(self, seconds: float) -> None:
        """The drive loop reports time it spent blocked on the data
        iterator since the last dispatch; the next step record carries
        it as data_wait_ms (the data-wait vs device-step split)."""
        self._pending_data_wait += seconds

    def _note_signature(self, kind: str, sig: tuple,
                        wall: float) -> bool:
        """First sighting of a dispatch signature means this wall time
        included an XLA compile (first-step) or recompile (a shape /
        static-arg change). Returns True when so, and emits the
        compile record."""
        key = (kind,) + sig
        if key in self._seen_sigs:
            return False
        first = not self._seen_sigs
        self._seen_sigs.add(key)
        self._mon.emit("compile",
                       kind="first" if first else "recompile",
                       wall_ms=wall * 1e3, signature=repr(key))
        return True

    def _loss_wait(self, loss, staged, sid: int) -> float:
        """Block on a dispatch's loss (monitored runs only) and return
        the dispatch's wall time in seconds: the extent of its three
        spans, ``trainer.stage`` start to ``trainer.loss_wait`` end."""
        with self._span("trainer.loss_wait", step=sid) as waited:
            jax.block_until_ready(loss)  # cxxlint: disable=CXL003 -- monitor-gated: wall_ms must cover device compute; unmonitored runs never sync
        return (waited.t1_ns - staged.t0_ns) / 1e9

    def _emit_step(self, kind: str, n_batches: int, examples: int,
                   wall: float, sig: tuple, lr: float) -> None:
        """One ``step`` record per dispatch. ``loss`` is the
        dispatch's last training loss — the caller already blocked on
        it for ``wall_ms``, so fetching the scalar adds no sync."""
        compiled = self._note_signature(kind, sig, wall)
        wait, self._pending_data_wait = self._pending_data_wait, 0.0
        self._mon.emit(
            "step", step=self._steps_total, round=self.round,
            dispatch=kind, n_batches=n_batches, examples=examples,
            tokens=examples * self.net.tokens_per_example,
            wall_ms=wall * 1e3, data_wait_ms=wait * 1e3,
            examples_per_sec=examples / wall if wall > 0 else 0.0,
            update_counter=self.update_counter, lr=lr,
            loss=float(self._last_loss),
            compile=compiled)
        self._emit_moe(examples // n_batches, n_batches)

    def _emit_moe(self, rows: int, n_batches: int) -> None:
        """One ``moe`` record a dispatch of a net with expert layers:
        what each layer's held experts got in the dispatch's LAST step,
        and the share of the dispatch's ``n_batches`` passes a layer in
        which the experts ran as the grouped kernels, from the counters
        the layers leave in their state (the loss this dispatch was
        closed by is already on the host, so the state is ready: no
        further wait)."""
        layers = {}
        took = 0
        for lkey in self._moe_keys:
            st = self.net_state[lkey]
            layer = self.net.layer_objs[self._layer_index[lkey]]
            load = np.asarray(st["load"], np.float64)  # cxxlint: disable=CXL003 -- monitor-gated fetch of a few counters after the loss
            picks = rows * layer.in_shapes[0].y * layer.topk
            layers[lkey] = {
                "load_min": float(load.min()), "load_mean": float(load.mean()),
                "load_max": float(load.max()),
                "held_share": float(st["picks_held"]) / picks,
                "dropped": int(st["dropped"])}
            passes = int(st["grouped"])
            took += passes - self._moe_grouped[lkey]
            self._moe_grouped[lkey] = passes
        if layers:
            self._mon.emit(
                "moe", step=self._steps_total, layers=layers,
                dropped=sum(v["dropped"] for v in layers.values()),
                held_share=float(np.mean([v["held_share"]
                                          for v in layers.values()])),
                load_max_over_mean=max(
                    v["load_max"] / max(v["load_mean"], 1e-9)
                    for v in layers.values()),
                grouped_share=took / float(n_batches * len(layers)))

    def end_round(self) -> None:
        """Close the current round's counter window (idempotent):
        computes last_round_examples_per_sec for the wrapper poll
        surface and the round_end record."""
        if self._round_t0 is None:
            return
        dt = time.perf_counter() - self._round_t0
        if dt > 0:
            self.last_round_examples_per_sec = self._round_examples / dt
        self.last_round_examples = self._round_examples
        self.last_round_wall_s = dt
        self._round_t0 = None

    def counters_snapshot(self) -> Dict[str, float]:
        """Cheap progress snapshot (no device sync): total dispatches,
        total real examples consumed, and the throughput of the last
        completed round — the wrapper/C-ABI polling surface."""
        return {"steps": self._steps_total,
                "examples": self._examples_total,
                "last_round_examples_per_sec":
                    self.last_round_examples_per_sec}

    def _count_examples(self, examples: int) -> None:
        """One dispatch = one step id, however many batches it fused;
        ``examples`` counts the real (non-padded) LOCAL rows consumed
        (per-process under multi-process dp — run_start carries
        process_count for consumers that want global throughput)."""
        self._steps_total += 1
        self._examples_total += examples
        self._round_examples += examples

    # -- public API ------------------------------------------------------

    def start_round(self, r: int) -> None:
        self.end_round()                 # close the previous window
        self.round = r
        self._round_t0 = time.perf_counter()
        self._round_examples = 0

    def update(self, batch: DataBatch) -> None:
        assert self._initialized, "call init_model/load_model first"
        sid = self._steps_total + 1      # this dispatch's step id
        with self._span("trainer.stage", step=sid) as staged:
            data, labels, mask, extra = self._device_batch(batch)
            hyper = self._hyper()
            # step BEFORE the counter bump: batch i of the run folds
            # RNG with step U*period+S (0-based), the same index
            # scan_step uses as step0+i — so dropout/insanity masks are
            # identical whether batches go through update(),
            # update_many, or run_steps
            step = self._step_scalar()
            self.sample_counter += 1
            do_update = self.sample_counter >= self.update_period
            sig = _areg.update_sig(data.shape,
                                   self._dtype_tag(data.dtype),
                                   labels.shape, mask is None,
                                   len(extra), bool(do_update))
        with self._span("trainer.enqueue", step=sid):
            out = self._call_step(
                "update", sig, self._train_step,
                (self.params, self.opt_state, self.net_state,
                 self.grad_acc, data, labels, mask, extra, hyper,
                 self._epoch_u32(), step, self._base_key),
                do_update=bool(do_update))
        (self.params, self.opt_state, self.net_state,
         self.grad_acc, loss, preds) = out
        self.programs.residency = None   # weights moved: the frozen
        #                                  serve tree is stale
        self._last_loss = loss
        ex = self._local_batch_size(batch) - batch.num_batch_padd
        self._count_examples(ex)
        if self._mon_on():
            self._emit_step("update", 1, ex,
                            self._loss_wait(loss, staged, sid), sig,
                            float(hyper[0, 0]) if len(hyper) else 0.0)
        if do_update:
            self.sample_counter = 0
            self.update_counter += 1
        if self.eval_train and self._metrics.evals:
            # the train metrics on the host, the chip idle meanwhile:
            # the predictions' fetch, then the metric in Python
            nvalid = self._local_batch_size(batch) - batch.num_batch_padd
            with self._span("trainer.metrics", step=sid):
                with self._span("trainer.metrics_fetch", step=sid):
                    pred_np = [self._local_rows(p)[:nvalid] for p in preds]
                self._train_metrics.add_eval(
                    pred_np, self._label_fields(self._host_label(batch),
                                                nvalid))

    def run_steps(self, batch: DataBatch, n_steps: int) -> None:
        """Run n_steps train steps on one resident batch in a single
        dispatch (steady-state throughput measurement — the
        test_skipread mode, iter_batch_proc-inl.hpp:21). The LR/momentum
        schedule advances per step in-scan via a per-step hyper array
        (reference applies ScheduleEpoch every update, updater/param.h:
        96-117), and ``update_period > 1`` accumulation windows close
        in-scan via traced apply flags — the reference's canonical
        update_period=2 configs benchmark in this fused mode, equality-
        tested against the per-batch dispatch path."""
        assert self._initialized, "call init_model/load_model first"
        sid = self._steps_total + 1      # this dispatch's step id
        with self._span("trainer.stage", step=sid) as staged:
            data, labels, mask, extra = self._device_batch(batch)
            n = int(n_steps)
            period = self.update_period
            S, U = self.sample_counter, self.update_counter
            epochs = [U + (S + i) // period for i in range(n)]
            hyper_k = np.stack([self._hyper(e) for e in epochs])
            epoch_k = np.asarray(epochs, np.uint32)  # cxxlint: disable=CXL003 -- host python list of schedule epochs
            do_up_k = np.asarray([((S + i + 1) % period) == 0  # cxxlint: disable=CXL003 -- host python list of apply flags
                                  for i in range(n)])
            sig = _areg.run_steps_sig(data.shape,
                                      self._dtype_tag(data.dtype),
                                      labels.shape, mask is None,
                                      len(extra), n)
        with self._span("trainer.enqueue", step=sid):
            out = self._call_step(
                "run_steps", sig, self._multi_step,
                (self.params, self.opt_state, self.net_state,
                 self.grad_acc, data, labels, mask, extra, hyper_k,
                 epoch_k, do_up_k, self._step_scalar(), self._base_key))
        (self.params, self.opt_state, self.net_state, self.grad_acc,
         loss) = out
        self.programs.residency = None
        self._last_loss = loss
        ex = (self._local_batch_size(batch) - batch.num_batch_padd) * n
        self._count_examples(ex)
        if self._mon_on():
            self._emit_step("run_steps", n, ex,
                            self._loss_wait(loss, staged, sid), sig,
                            float(hyper_k[0, 0, 0]) if hyper_k.size
                            else 0.0)
        self.update_counter = U + (S + n) // period
        self.sample_counter = (S + n) % period

    def update_many(self, batches: Sequence[DataBatch]) -> None:
        """Train on K real batches in ONE jitted dispatch: host dispatch
        latency amortizes across the window while the schedule stays
        per-update correct (hyper rows advance in-scan) and
        update_period accumulation windows close in-scan (traced apply
        flags). Observable semantics are identical to K ``update()``
        calls — proven by an equality test across an LR-schedule
        boundary.

        The throughput intent of the reference's threadbuffer overlap
        (iter_batch_proc-inl.hpp:132-220) at the per-batch ScheduleEpoch
        semantics of updater/param.h:96-117."""
        assert self._initialized, "call init_model/load_model first"
        K = len(batches)
        if K == 1:
            return self.update(batches[0])
        sid = self._steps_total + 1      # this dispatch's step id
        with self._span("trainer.stage", step=sid) as staged:
            period = self.update_period
            S, U = self.sample_counter, self.update_counter
            epochs = [U + (S + i) // period for i in range(K)]
            hyper_k = np.stack([self._hyper(e) for e in epochs])
            epoch_k = np.asarray(epochs, np.uint32)  # cxxlint: disable=CXL003 -- host python list of schedule epochs
            do_up = np.asarray([((S + i + 1) % period) == 0  # cxxlint: disable=CXL003 -- host python list of apply flags
                                for i in range(K)])
            step0 = self._step_scalar()
            data_k = self._put_window([b.data for b in batches])
            labels_k = self._put_window([b.label for b in batches])
            masks = [self._mask(b) for b in batches]
            if all(m is None for m in masks):
                mask_k = None
            else:   # mixed window: materialize ones for unpadded rows
                mask_k = self._put_window(
                    [np.ones((self._local_batch_size(b),), np.float32)
                     if m is None else m
                     for m, b in zip(masks, batches)])
            n_extra = len(batches[0].extra_data)
            extra_k = tuple(
                self._put_window([b.extra_data[j] for b in batches])
                for j in range(n_extra))
            collect = bool(self.eval_train and self._metrics.evals)
            sig = _areg.update_many_sig(data_k.shape,
                                        self._dtype_tag(data_k.dtype),
                                        labels_k.shape, mask_k is None,
                                        n_extra, K, collect)
        with self._span("trainer.enqueue", step=sid):
            out = self._call_step(
                "update_many", sig, self._many_step,
                (self.params, self.opt_state, self.net_state,
                 self.grad_acc, data_k, labels_k, mask_k, extra_k,
                 hyper_k, epoch_k, do_up, step0, self._base_key),
                collect=collect)
        (self.params, self.opt_state, self.net_state, self.grad_acc,
         loss, preds_k) = out
        self.programs.residency = None
        self._last_loss = loss
        ex = sum(self._local_batch_size(b) - b.num_batch_padd
                 for b in batches)
        self._count_examples(ex)
        if self._mon_on():
            self._emit_step("update_many", K, ex,
                            self._loss_wait(loss, staged, sid), sig,
                            float(hyper_k[0, 0, 0]) if hyper_k.size
                            else 0.0)
        self.update_counter = U + (S + K) // period
        self.sample_counter = (S + K) % period
        if collect:
            with self._span("trainer.metrics", step=sid):
                with self._span("trainer.metrics_fetch", step=sid):
                    preds_np = [self._local_rows(p, axis=1)
                                for p in preds_k]
                for i, b in enumerate(batches):
                    nvalid = self._local_batch_size(b) - b.num_batch_padd
                    self._train_metrics.add_eval(
                        [p[i][:nvalid] for p in preds_np],
                        self._label_fields(self._host_label(b), nvalid))

    def train_metric_str(self, name: str = "train") -> str:
        res = self._train_metrics.results()
        self._train_metrics.clear()
        if self._mon_on() and res:
            self._mon.emit("eval", round=self.round, name=name,
                           metrics={t: float(v) for t, v in res})
        return MetricSet.format_line(name, res)

    def evaluate(self, data_iter, name: str) -> str:
        """Run a full eval pass; returns '\\t<name>-<metric>:<value>'."""
        return self.evaluate_metrics(data_iter, name)[0]

    def evaluate_metrics(self, data_iter, name: str
                         ) -> Tuple[str, Dict[str, float]]:
        """One eval pass returning BOTH the parity line and the
        ``{tag: value}`` dict — one reduction per metric serves the
        line, the structured ``eval`` record, and machine consumers
        (the continual loop's eval gate reads the dict; re-running
        ``results()`` would double the collective count under
        multi-process runs)."""
        if not self._metrics.evals:
            return "", {}
        self._metrics.clear()
        nodes_wanted = tuple(self._metric_nodes)
        from ..parallel import synced_batches
        # same lockstep window as the CLI train loop (dispatch_period),
        # not a private constant — multi-process ranks must agree on it
        for batch in synced_batches(data_iter,
                                    window=self.dispatch_period):
            # same input path as training: uint8 pixels ship raw (1/4
            # the H2D bytes) and pre-placed prefetch batches pass
            # through (reference evaluates through the training pipeline,
            # nnet_impl-inl.hpp:241-276)
            vals = self._call_pred(self._put_batch_array(batch.data),
                                   self._put_mask(batch),
                                   self._device_extra(batch),
                                   nodes_wanted)
            nvalid = self._local_batch_size(batch) - batch.num_batch_padd
            pred_np = [self._local_rows(v)[:nvalid] for v in vals]
            self._metrics.add_eval(
                pred_np, self._label_fields(self._host_label(batch),
                                            nvalid))
        res = self._metrics.results()
        vals = {t: float(v) for t, v in res}
        if self._mon_on() and res:
            # structured record beside the parity line; ONE reduction
            # per metric serves both (results() is collective under
            # multi-process runs)
            self._mon.emit("eval", round=self.round, name=name,
                           metrics=vals)
        return MetricSet.format_line(name, res), vals

    @staticmethod
    def rows_to_prediction(m: np.ndarray) -> np.ndarray:
        """Output rows -> per-row prediction: the single raw column, or
        the argmax class as float32 (nnet_impl-inl.hpp:317-330). The
        one definition of the predict convention — the serve engine and
        ``predict`` below must agree row for row."""
        m = m.reshape(m.shape[0], -1)
        if m.shape[1] == 1:
            return m[:, 0]
        return np.argmax(m, axis=1).astype(np.float32)

    def predict(self, batch: DataBatch) -> np.ndarray:
        """argmax class (or raw scalar) per row of the top node
        (nnet_impl-inl.hpp:317-330)."""
        top = self.graph.num_nodes - 1
        (val,) = self._call_pred(self._put_batch_array(batch.data),
                                 self._put_mask(batch),
                                 self._device_extra(batch), (top,))
        nvalid = self._local_batch_size(batch) - batch.num_batch_padd
        return self.rows_to_prediction(self._local_rows(val)[:nvalid])

    def extract_feature(self, batch: DataBatch, node: str) -> np.ndarray:
        ni = self.net.node_index_by_name(node)
        (val,) = self._call_pred(self._put_batch_array(batch.data),
                                 self._put_mask(batch),
                                 self._device_extra(batch), (ni,))
        nvalid = self._local_batch_size(batch) - batch.num_batch_padd
        return self._local_rows(val, flatten=False)[:nvalid]

    def check_weight_consistency(self, atol: float = 0.0) -> None:
        """Assert every device replica holds identical weights — the
        ``test_on_server=1`` audit (reference CheckWeight_,
        async_updater-inl.hpp:149-154). With SPMD + pinned replicated
        out-shardings this should hold bitwise; a mismatch means a
        sharding or donation bug. Partially-sharded weights (e.g.
        model-axis fullc) are compared within each replica group;
        identical NaNs count as equal (a numerical blow-up is not a
        replication bug). Under multi-process dp, fully-replicated
        weights are also cross-checked between ranks."""
        from collections import defaultdict

        def _differs(a, b):
            return not np.allclose(a, b, rtol=0.0, atol=atol,
                                   equal_nan=True)

        for lk, pt in self.params.items():
            for tag, w in pt.items():
                if not isinstance(w, jax.Array):
                    continue
                groups = defaultdict(list)
                for s in w.addressable_shards:
                    # slices are unhashable before py3.12; key on their
                    # fields
                    key = tuple((sl.start, sl.stop, sl.step)
                                for sl in s.index)
                    groups[key].append(s)
                for shards in groups.values():
                    ref = np.asarray(shards[0].data)
                    for s in shards[1:]:
                        if _differs(ref, np.asarray(s.data)):
                            raise AssertionError(
                                "weight %s:%s diverged between device "
                                "replicas %s and %s"
                                % (lk, tag, shards[0].device, s.device))
                if jax.process_count() > 1 and len(groups) == 1:
                    # fully replicated: audit across ranks too
                    from jax.experimental import multihost_utils
                    ref = np.asarray(w.addressable_shards[0].data)
                    allv = np.asarray(
                        multihost_utils.process_allgather(ref))
                    for r in range(allv.shape[0]):
                        if _differs(ref, allv[r]):
                            raise AssertionError(
                                "weight %s:%s diverged between process "
                                "ranks (rank %d vs %d)"
                                % (lk, tag, jax.process_index(), r))

    # -- weights ---------------------------------------------------------

    def get_weight(self, layer_name: str, tag: str) -> np.ndarray:
        """Weight in reference convention: fullc (out,in); conv
        (out_ch, in_pg*kh*kw); vectors 1-D (visitor.h:26-165)."""
        w = np.asarray(self.params[layer_name][tag])
        return self._to_ref_layout(w)

    def set_weight(self, layer_name: str, tag: str,
                   value: np.ndarray) -> None:
        cur = self.params[layer_name][tag]
        new = self._from_ref_layout(np.asarray(value, np.float32),
                                    cur.shape)
        p = dict(self.params)
        lp = dict(p[layer_name])
        lp[tag] = jax.device_put(new, self._repl) if cur.ndim == 1 \
            else jax.device_put(new,
                                self._p_shard[layer_name][tag])
        p[layer_name] = lp
        self.params = p
        self.programs.residency = None   # frozen serve tree is stale

    @staticmethod
    def _to_ref_layout(w: np.ndarray) -> np.ndarray:
        if w.ndim == 2:                      # fullc (in,out) -> (out,in)
            return w.T.copy()
        if w.ndim == 4:                      # HWIO -> (out, in*kh*kw)
            kh, kw, ipg, out = w.shape
            return w.transpose(3, 2, 0, 1).reshape(out, ipg * kh * kw)
        return w.copy()

    @staticmethod
    def _from_ref_layout(w: np.ndarray,
                         target_shape: Tuple[int, ...]) -> np.ndarray:
        if len(target_shape) == 2:
            return np.ascontiguousarray(w.T)
        if len(target_shape) == 4:
            kh, kw, ipg, out = target_shape
            return np.ascontiguousarray(
                w.reshape(out, ipg, kh, kw).transpose(2, 3, 1, 0))
        return w.reshape(target_shape)

    # -- checkpoint ------------------------------------------------------

    def gather_snapshot(self) -> Tuple[Dict[str, np.ndarray], Dict]:
        """Device->host gather of everything a snapshot holds, plus its
        metadata — the only checkpoint phase that must run on the
        training thread at an update boundary. Serialization and the
        atomic commit live in :mod:`.checkpoint` and can run on a
        background writer (CheckpointManager). Multi-process: the
        optimizer-state gathers are collective — call on ALL ranks."""
        arrays: Dict[str, np.ndarray] = {}
        for lk, pt in self.params.items():
            for tag, w in pt.items():
                arrays["param/%s/%s" % (lk, tag)] = np.asarray(w)
        for lk, st in self.net_state.items():
            for k, v in st.items():
                arrays["state/%s/%s" % (lk, k)] = np.asarray(v)
        if self.save_optimizer:
            # seamless-resume extension (the reference never checkpoints
            # momentum, nnet_impl-inl.hpp:98-116; off by default for
            # snapshot-format parity)
            def fetch(v):
                # ZeRO-1 leaves span processes under multi-host dp;
                # gather the global value before saving
                if isinstance(v, jax.Array) and \
                        not v.is_fully_addressable:
                    from jax.experimental import multihost_utils
                    v = multihost_utils.process_allgather(v, tiled=True)
                a = np.asarray(v)
                # npz can't represent bfloat16 (stored as opaque V2 and
                # unreadable on load); momentum_dtype=bfloat16 buffers
                # ship as f32 (exact) and load_model casts back per the
                # resuming config
                return a.astype(np.float32) if a.dtype == jnp.bfloat16 \
                    else a
            for lk, tags in self.opt_state.items():
                for tag, st in tags.items():
                    for k, v in st.items():
                        arrays["opt/%s/%s/%s" % (lk, tag, k)] = fetch(v)
        # calibration range tables ride as ordinary arrays so the
        # content digest covers them (a quantized snapshot is a
        # first-class verified artifact; nnet/quantize.py)
        for lkey, tab in self.quant_tables.items():
            for field, v in tab.items():
                arrays["quant/%s/%s" % (lkey, field)] = np.asarray(v)
        meta = {
            "update_counter": self.update_counter,
            "structure": self.graph.to_dict(),
            "cfg": self.cfg,
            # the topology this run trained under, sealed beside the
            # weights: resume compares it against the runtime so a
            # silently different mesh / world size cannot slip past
            # (dist_topology_check, doc/distributed.md) and the
            # elastic handoff can re-derive the reader shard map from
            # update_counter at the new world size
            "topology": self._topology_meta(),
        }
        if self.quant_meta:
            meta["quantized"] = dict(self.quant_meta)
        return arrays, meta

    def _topology_meta(self) -> Dict[str, Any]:
        """The topology dict sealed into snapshot meta: input topology
        (hosts/local devices, faked under the dryrun), mesh axis
        sizes, and the global batch the shard map partitions."""
        from ..parallel import current_topology
        topo = current_topology().describe()
        topo["mesh"] = {str(k): int(v)
                        for k, v in dict(self.mesh.shape).items()} \
            if self.mesh is not None else None
        topo["global_batch"] = int(self.batch_size)
        return topo

    def _check_loaded_topology(self, meta: Dict[str, Any],
                               path: str) -> None:
        """Compare a snapshot's sealed topology against this runtime
        (dist_topology_check): a changed mesh or world size is the
        elastic-resume path when intentional and a data-duplication /
        deadlock hazard when not — so it is never silent. ``warn``
        (default) warns once and lets the resume machinery re-derive
        the shard map; ``strict`` refuses the load."""
        saved = meta.get("topology")
        self.resumed_topology = saved
        self.topology_changed = False
        if not saved or self.dist_topology_check == "off":
            return
        cur = self._topology_meta()
        # a single-host mesh resize (train on 8 devices, serve on 1)
        # is routine and stays silent; mesh/local-device drift only
        # matters once hosts are (or were) in play — the world-size
        # axis itself is always compared
        keys = ("hosts",) if saved.get("hosts", 1) <= 1 \
            and cur.get("hosts", 1) <= 1 else \
            ("hosts", "local_devices", "mesh")
        diffs = [k for k in keys if saved.get(k) != cur.get(k)]
        if not diffs:
            return
        self.topology_changed = True
        desc = ", ".join("%s %r -> %r" % (k, saved.get(k), cur.get(k))
                         for k in diffs)
        if self.dist_topology_check == "strict":
            raise ValueError(
                "snapshot %s was written under a different topology "
                "(%s) and dist_topology_check=strict refuses the "
                "silent change; resume with dist_topology_check=warn "
                "to accept the elastic handoff" % (path, desc))
        from ..monitor import warn_once
        warn_once("dist_topology_changed",
                  "snapshot %s was written under a different topology "
                  "(%s); the reader shard map re-derives from the "
                  "resumed update counter at the new world size "
                  "(doc/distributed.md)" % (path, desc))

    def save_model(self, path: str) -> None:
        """Synchronous verified snapshot: gather, then atomically
        commit with a content digest (checkpoint.write_snapshot). The
        direct API raises on write failure; the train loop's managed
        path (CheckpointManager) downgrades failures to warnings."""
        from .checkpoint import write_snapshot
        arrays, meta = self.gather_snapshot()
        # multi-process: every rank participates in the gathers above
        # (call save_model on ALL ranks); only root touches the file
        if jax.process_index() != 0:
            return
        write_snapshot(path, arrays, meta)

    def load_model(self, path: str) -> None:
        # verified read: digest + format_version checked before any
        # array is trusted (checkpoint.read_snapshot). A sealed
        # artifact bundle (doc/artifacts.md) loads as its inner
        # snapshot, then installs its serialized executables once the
        # programs are rebuilt (_attach_bundle at the end).
        from .checkpoint import read_snapshot
        bundle = None
        from ..artifact import bundle as _ab
        if _ab.is_bundle(path):
            bundle = _ab.load_bundle(path)
            path = bundle.snapshot_uri
        # raw bytes ride from the bundle's verification pass so the
        # snapshot is read once; the content digest still re-verifies
        blob, meta = read_snapshot(
            path, raw=bundle.snapshot_raw if bundle else None)
        saved_graph = NetGraph.from_dict(meta["structure"])
        self._absorb_globals()
        # re-parse config against saved structure (Configure equality
        # check, nnet_config.h:263-267)
        self.graph = saved_graph
        self.graph.configure(self.cfg)
        if self.batch_size == 0:
            self.batch_size = self.graph.batch_size
        self.net = FuncNet(self.graph, self.batch_size)
        params, net_state = self.net.init(
            jax.random.PRNGKey(self.seed))
        for lk, pt in params.items():
            for tag in pt:
                k = "param/%s/%s" % (lk, tag)
                if k in blob:
                    pt[tag] = jnp.asarray(blob[k])
        for lk, st in net_state.items():
            for kk in st:
                k = "state/%s/%s" % (lk, kk)
                if k in blob:
                    st[kk] = jnp.asarray(blob[k])
        self.params, self.net_state = params, net_state
        self.update_counter = int(meta.get("update_counter", 0))
        # calibration ranges (task=quantize snapshots) load before
        # _post_init so serve_dtype activation sees them
        from .quantize import tables_from_blob
        self.quant_tables = tables_from_blob(blob)
        self.quant_meta = dict(meta.get("quantized", {}))
        self._post_init()
        # topology comparison AFTER _post_init: the check needs the
        # mesh this runtime actually built (dist_topology_check)
        self._check_loaded_topology(meta, path)
        # restore optimizer state when the snapshot carries it
        if any(k.startswith("opt/") for k in blob):
            for lk, tags in self.opt_state.items():
                for tag, st in tags.items():
                    new = dict(st)
                    for k in st:
                        key = "opt/%s/%s/%s" % (lk, tag, k)
                        if key in blob:
                            # cast to the dtype the CURRENT config
                            # initialized (snapshots store f32; the
                            # momentum_dtype of the resuming run wins)
                            new[k] = jnp.asarray(blob[key],
                                                 dtype=st[k].dtype)
                    self.opt_state[lk][tag] = new
            self.opt_state = jax.device_put(self.opt_state,
                                            self._o_shard)
        if bundle is not None:
            self._attach_bundle(bundle)

    def _attach_bundle(self, bundle) -> None:
        """Install a sealed bundle's serialized executables into the
        program registry — AFTER ``_post_init`` rebuilt the dispatch
        programs, so the installs land in the final registry. The
        fingerprint gate is exact dict equality: platform, jax/jaxlib
        versions, device kind+count, process count and mesh must all
        match what the bundle was sealed on, or every key falls back
        to re-lower+compile with one warning; on a match the programs
        load onto this trainer's mesh devices and a blob that fails
        raises (registry.ArtifactLoadError). Emits the honest
        ``artifact_load`` accounting (hits + rebuilds == programs)."""
        from ..artifact.bundle import runtime_fingerprint
        fp_ok = bundle.manifest.get("fingerprint") \
            == runtime_fingerprint(self.mesh)
        # the sealed executables' weight calling convention must match
        # this trainer's: a residency-sealed pred takes the frozen
        # serve tree as arguments, a legacy one the raw masters — a
        # mismatch would call an executable with the wrong pytree, so
        # it downgrades to the per-key re-lower fallback instead
        if int(bundle.manifest.get("weight_residency", 0)) \
                != int(bool(self.serve_weight_residency)):
            fp_ok = False
        rep = self.programs.install_serialized(
            bundle.programs, bundle.path, fp_ok,
            list(self.mesh.devices.flat), monitor=self._mon)
        if self._mon_on():
            self._mon.emit("artifact_load", **rep)

    @staticmethod
    def _read_source_blob(path: str):
        """Digest-verified (arrays, meta) of a finetune/reload source:
        a plain snapshot, or a sealed artifact bundle resolved to its
        inner snapshot (the bundle's member verification runs first,
        then the snapshot's own content digest — doc/artifacts.md)."""
        from ..artifact import bundle as _ab
        from .checkpoint import read_snapshot
        if _ab.is_bundle(path):
            b = _ab.load_bundle(path)
            return read_snapshot(b.snapshot_uri, raw=b.snapshot_raw)
        return read_snapshot(path)

    def finetune_from(self, path: str, remap: Sequence[str] = (),
                      strict: bool = True) -> Dict[str, Any]:
        """The ``task = finetune`` bootstrap (doc/tasks.md): carry
        weights over from a verified snapshot or sealed bundle into a
        freshly initialized net, remapping the layers named in
        ``remap`` (fresh init — the new-label-count output head) and
        digest-verifying everything carried (``read_snapshot`` refuses
        a source whose content digest fails).

        Call after ``init_model``. Carry-over is by layer *name* with
        exact shape equality (nnet_impl-inl.hpp:117-150); a layer whose
        saved shape no longer matches and is NOT in ``remap`` raises
        :class:`FinetuneShapeError` naming it (``strict=False``
        restores the reference's silent skip-and-reinit). Returns (and
        emits as the ``finetune`` record) the carry accounting."""
        assert self._initialized, "call init_model first"
        blob, meta = self._read_source_blob(path)
        remap_set = set(remap)
        unknown = remap_set - set(self.params.keys())
        if unknown:
            raise ValueError(
                "finetune_remap names unknown param layer(s) %s; "
                "known: %s" % (sorted(unknown), sorted(self.params)))
        carried = self._carry_from_blob(blob, remap_set, strict)
        fresh = sorted(remap_set)
        frozen = sorted(set(
            lk for lk, tags in self.updaters.items()
            for tag, upd in tags.items() if upd.param.lr_mult == 0.0))
        rec = {
            "source": path,
            "source_digest": str(meta.get("content_digest", "")),
            "carried": len(carried), "remapped": len(fresh),
            "fresh": sorted(set(self.params) - set(carried)
                            - remap_set),
            "carried_layers": carried, "remapped_layers": fresh,
            "frozen_groups": frozen,
        }
        if self.silent == 0:
            print("finetune_from %s: carried %s; remapped %s%s"
                  % (path, ", ".join(carried) or "<none>",
                     ", ".join(fresh) or "<none>",
                     ("; frozen %s" % ", ".join(frozen)) if frozen
                     else ""))
        if self._mon_on():
            self._mon.emit("finetune", **rec)
        return rec

    def _carry_from_blob(self, blob, remap_set, strict: bool):
        """The ONE name+shape carry loop behind ``finetune_from`` and
        ``copy_model_from`` (params + net_state, ``_put_all``,
        residency invalidation) — a fix to the carry semantics cannot
        silently miss one of them. Returns the carried layer keys."""
        carried = []
        for lk, pt in self.params.items():
            if lk in remap_set:
                continue                 # declared remap: fresh init
            hit = {}
            for tag in pt:
                k = "param/%s/%s" % (lk, tag)
                if k not in blob:
                    continue
                if blob[k].shape != tuple(pt[tag].shape):
                    if strict:
                        raise FinetuneShapeError(
                            lk, tag, blob[k].shape, pt[tag].shape)
                    continue             # legacy: skip, keep fresh init
                hit[tag] = jnp.asarray(blob[k])
            if hit:
                newp = dict(self.params[lk])
                newp.update(hit)
                self.params[lk] = newp
                carried.append(lk)
        for lk, st in self.net_state.items():
            if lk in remap_set:
                continue                 # remapped layers keep fresh state
            for kk in st:
                k = "state/%s/%s" % (lk, kk)
                if k in blob and blob[k].shape == tuple(st[kk].shape):
                    st[kk] = jnp.asarray(blob[k])
        self._put_all()
        self.programs.residency = None   # frozen serve tree is stale
        return carried

    def load_weights_inplace(self, path: str) -> None:
        """Refresh params/net_state/update_counter from a verified
        snapshot (or bundle) WITHOUT rebuilding the graph or the
        dispatch programs — every array must match an existing leaf's
        shape exactly. The continual exporter's per-generation reload:
        the bucket-ladder executables (weight-agnostic; weights are
        arguments) stay valid, so generation exports after the first
        compile zero new programs (doc/continual.md)."""
        assert self._initialized, "call init_model/load_model first"
        blob, meta = self._read_source_blob(path)
        for lk, pt in self.params.items():
            newp = dict(pt)
            for tag in pt:
                k = "param/%s/%s" % (lk, tag)
                if k not in blob:
                    continue
                if blob[k].shape != tuple(pt[tag].shape):
                    raise ValueError(
                        "load_weights_inplace: %s:%s shape %s does not "
                        "match the live net's %s — in-place reload "
                        "requires an identical structure (use "
                        "load_model for a structural change)"
                        % (lk, tag, blob[k].shape,
                           tuple(pt[tag].shape)))
                newp[tag] = jnp.asarray(blob[k])
            self.params[lk] = newp
        for lk, st in self.net_state.items():
            for kk in st:
                k = "state/%s/%s" % (lk, kk)
                if k in blob and blob[k].shape == tuple(st[kk].shape):
                    st[kk] = jnp.asarray(blob[k])
        self.update_counter = int(meta.get("update_counter",
                                           self.update_counter))
        self._put_all()
        self.programs.residency = None   # frozen serve tree is stale

    def copy_model_from(self, path: str) -> None:
        """Finetune: copy weights for layers whose *names* match with
        identical shapes, silently skipping the rest
        (nnet_impl-inl.hpp:117-150). Call after init_model. The
        remap-aware, typed-error front end over the same carry loop
        is :meth:`finetune_from` (the ``task = finetune`` path)."""
        from .checkpoint import read_snapshot
        assert self._initialized
        blob, _ = read_snapshot(path)
        copied = self._carry_from_blob(blob, set(), strict=False)
        if self.silent == 0 and copied:
            print("copy_model_from: copied layers %s" % ", ".join(copied))

    @property
    def last_loss(self) -> float:
        return float(self._last_loss)

"""CLI task driver.

Parity with ``/root/reference/src/cxxnet_main.cpp:26-575``: a config file
plus ``key=value`` CLI overrides drives tasks ``train`` / ``finetune`` /
``pred`` / ``extract_feature`` / ``get_weight`` (plus the TPU-port tasks
``serve`` / ``serve_fleet`` / ``fleet`` / ``quantize`` / ``export`` /
``continual``); snapshots are written as
``<model_dir>/<round:04d>.model.npz``; ``continue=1`` resumes from the
latest snapshot (SyncLastestModel, :180-202); ``test_io=1`` exercises the
data pipeline without the net (:455-468); only the root process saves
and logs in distributed runs (:424-435, 501-503).

Usage: python -m cxxnet_tpu.main config.conf [key=value ...]
"""

from __future__ import annotations

import os
import re
import signal
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .io import create_iterator
from .io.iter_batch import (attach_chain_spans, enable_chain_wait_stats,
                            pipeline_snapshot)
from .monitor import (Monitor, create_monitor, device_memory_snapshot,
                      run_metadata, set_global)
from .nnet.checkpoint import CheckpointManager, find_latest_valid
from .nnet.trainer import NetTrainer
from .parallel import (allreduce_host_sum, clear_dryrun_topology,
                       current_topology, init_distributed, is_root,
                       set_allreduce_retry, set_dryrun_topology,
                       synced_batches, world_size)
from .parallel.topology import DryrunFeed, build_dryrun_feed
from .utils.config import (parse_cli_overrides, parse_config_file,
                           split_sections)
from .utils.stream import open_stream, set_stream_retry, uri_scheme

_MODEL_RE = re.compile(r"^(\d{4})\.model\.npz$")

# exit code of a preempted run: SIGTERM/SIGINT arrived, the emergency
# snapshot committed, telemetry flushed. EX_TEMPFAIL — schedulers and
# wrapper scripts treat it as "re-queue me" (doc/checkpointing.md)
EXIT_PREEMPTED = 75

# tasks that read data through the pred iterator (or its fallback);
# quantize rides here too — calibration wants the deterministic eval
# transform, not the shuffled/augmented training stream
_PRED_TASKS = ("pred", "extract_feature", "extract", "pred_raw", "serve",
               "quantize", "build_index")

# randomized-pipeline knobs neutralized when a pred-like task falls
# back to the train data block: evaluation order must be the file
# order and every example must go through the deterministic eval
# transform (center crop / mean / scale stay — they define the input
# distribution; the stochastic knobs do not)
_PRED_NEUTRAL = (
    ("shuffle", "0"), ("shuffle_chunk", "0"),
    ("rand_crop", "0"), ("rand_mirror", "0"),
    ("max_random_contrast", "0"), ("max_random_illumination", "0"),
    ("max_rotate_angle", "0"), ("max_shear_ratio", "0"),
    ("max_aspect_ratio", "0"),
    ("min_random_scale", "1"), ("max_random_scale", "1"),
    ("min_crop_size", "-1"), ("max_crop_size", "-1"),
    ("rotate", "-1"), ("rotate_list", ""),
)


class LearnTask:
    def __init__(self) -> None:
        self.task = "train"
        self.net_type = "feedforward"
        self.num_round = 10
        self.start_counter = 1
        self.save_period = 1
        self.model_dir = "./models"
        self.model_in = ""
        self.continue_training = 0
        self.print_step = 100
        self.silent = 0
        self.task_eval_train = 1
        self.test_on_server = 0
        self.name_pred = "pred.txt"
        self.output_format = "txt"
        self.extract_node_name = ""
        self.weight_filename = "weight.txt"
        self.weight_layer = ""
        self.weight_tag = "wmat"
        self.test_io = 0
        self.device = ""
        # batches per jitted dispatch in the train loop (update_many):
        # amortizes host dispatch latency; schedule stays per-update
        # correct. 1 = per-batch update().
        self.dispatch_period = 8
        # precompile = 1: AOT-compile the dispatch programs for the
        # run's static shapes before round 0 (trainer.precompile);
        # combined with compile_cache_dir the compiles amortize across
        # runs (doc/observability.md)
        self.precompile = 0
        # crash-safe checkpointing (doc/checkpointing.md): background
        # commit thread, retention GC, durable fsync, remote-read
        # retries. checkpoint_async=1 keeps the training thread's
        # share of a snapshot to the device->host gather.
        self.checkpoint_async = 1
        self.checkpoint_fsync = 1
        self.keep_snapshots = 0          # 0 = keep every snapshot
        self.stream_retry = 0            # remote read retries (opt-in)
        # post-training quantization (task = quantize,
        # doc/perf_profile.md "Low-precision inference"): target dtype,
        # calibration stream length, the f32 parity gate, output path
        self.quantize_dtype = "int8"
        self.quantize_batches = 8
        self.quantize_parity_eps = 0.05
        self.quantize_out = ""
        # sealed artifact export (task = export, doc/artifacts.md):
        # output bundle directory; "" derives NNNN.model.bundle beside
        # model_in so a watched model_dir picks the bundle up
        self.export_out = ""
        # embedding index build (task = build_index, doc/retrieval.md):
        # similarity metric sealed into the index, and a corpus-size
        # cap (0 = embed the whole iterator)
        self.index_metric = "dot"
        self.index_rows = 0
        # finetune remap contract (doc/tasks.md "finetune"): layers
        # named here re-initialize fresh (the new-label-count head);
        # any OTHER shape mismatch is a typed FinetuneShapeError
        # naming the layer unless finetune_strict = 0 restores the
        # reference's silent skip-and-reinit
        self.finetune_remap: Tuple[str, ...] = ()
        self.finetune_strict = 1
        # multi-host SPMD launch (doc/distributed.md): coordinator
        # address + world shape driving jax.distributed.initialize.
        # Env vars (CXXNET_COORDINATOR et al.) and managed-runtime
        # autodetect keep working; config keys win when set.
        self.dist_coordinator = ""
        self.dist_num_hosts = 0          # 0 = env / runtime autodetect
        self.dist_host_rank = -1         # -1 = env / runtime autodetect
        # single-process multi-host dryrun: fake N input hosts over
        # this process's devices — full shard math (mesh build,
        # per-host batch assembly, re-derivation), zero DCN
        self.dist_dryrun_hosts = 0
        # bounded retries for the process-group metric allreduce
        # (transient DCN hiccups re-enter the collective; 0 fails fast)
        self.dist_allreduce_retry = 2
        # observability (doc/observability.md); a null monitor until
        # run() builds the configured one, so task methods are safe to
        # call directly in tests
        self._mon = Monitor()
        self._cfg_stream = []
        self._resume_report = None
        self._resume_found = False
        # preemption flag set from the SIGTERM/SIGINT handler; holds
        # the signal number until the train loop's next update boundary
        self._preempt_signum: Optional[int] = None

    # -- config ----------------------------------------------------------

    def _set(self, name: str, val: str) -> None:
        if name == "task":
            self.task = val
        if name == "net_type":
            self.net_type = val
        if name in ("num_round", "max_round"):
            self.num_round = int(val)
        if name == "start_counter":
            self.start_counter = int(val)
        if name == "save_model":
            self.save_period = 0 if val == "0" else int(val)
        if name == "model_dir":
            self.model_dir = val
        if name == "model_in":
            self.model_in = val
        if name == "continue":
            self.continue_training = int(val)
        if name == "print_step":
            self.print_step = int(val)
        if name == "silent":
            self.silent = int(val)
        if name in ("eval_train", "train_eval"):
            self.task_eval_train = int(val)
        if name == "test_on_server":
            self.test_on_server = int(val)
        if name == "extract_node_name":
            self.extract_node_name = val
        if name == "extract_layer_name":
            # reference semantics: the get_weight layer selector
            # (cxxnet_main.cpp:339), NOT an extract_feature trigger
            self.weight_layer = val
        if name == "output_format":
            if val not in ("txt", "bin"):
                raise ValueError(
                    "output_format must be 'txt' or 'bin', got %r" % val)
            self.output_format = val
        if name == "weight_filename":
            self.weight_filename = val
        if name == "weight_layer":
            self.weight_layer = val
        if name == "weight_tag":
            self.weight_tag = val
        if name == "test_io":
            self.test_io = int(val)
        if name == "dev":
            self.device = val
        if name == "dispatch_period":
            self.dispatch_period = max(1, int(val))
        if name == "precompile":
            self.precompile = int(val)
        if name == "checkpoint_async":
            self.checkpoint_async = int(val)
        if name == "checkpoint_fsync":
            self.checkpoint_fsync = int(val)
        if name == "keep_snapshots":
            self.keep_snapshots = int(val)
        if name == "stream_retry":
            self.stream_retry = int(val)
        if name == "quantize_dtype":
            self.quantize_dtype = val
        if name == "quantize_batches":
            self.quantize_batches = int(val)
        if name == "quantize_parity_eps":
            self.quantize_parity_eps = float(val)
        if name == "quantize_out":
            self.quantize_out = val
        if name == "export_out":
            self.export_out = val
        if name == "index_metric":
            self.index_metric = val
        if name == "index_rows":
            self.index_rows = int(val)
        if name == "finetune_remap":
            self.finetune_remap = tuple(
                t.strip() for t in val.split(",") if t.strip())
        if name == "finetune_strict":
            self.finetune_strict = int(val)
        if name == "dist_coordinator":
            self.dist_coordinator = val
        if name == "dist_num_hosts":
            self.dist_num_hosts = int(val)
        if name == "dist_host_rank":
            self.dist_host_rank = int(val)
        if name == "dist_dryrun_hosts":
            self.dist_dryrun_hosts = int(val)
        if name == "dist_allreduce_retry":
            self.dist_allreduce_retry = int(val)

    # -- model files -----------------------------------------------------

    def _model_path(self, counter: int) -> str:
        if uri_scheme(self.model_dir):
            return "%s/%04d.model.npz" % (self.model_dir.rstrip("/"),
                                          counter)
        return os.path.join(self.model_dir, "%04d.model.npz" % counter)

    def _sync_latest_model(self) -> Optional[str]:
        """Find the newest *valid* snapshot in model_dir
        (cxxnet_main:180-202, hardened): every candidate is
        digest/structure verified newest-first, corrupt ones are
        quarantined with a warning, and only a snapshot that actually
        loads is handed to load_model. Works for remote model_dir URIs
        via the stream layer."""
        rep = find_latest_valid(self.model_dir, monitor=self._mon)
        self._resume_report = rep
        if rep.path is None:
            if rep.quarantined:
                self._mon.warn_once(
                    "resume_no_valid_snapshot",
                    "continue=1: model_dir %r holds %d snapshot(s) but "
                    "none verifies — quarantined %s and starting from "
                    "round 0" % (self.model_dir, rep.scanned,
                                 ", ".join(rep.quarantined)))
            return None
        self.start_counter = rep.counter + 1
        return rep.path

    # -- run -------------------------------------------------------------

    def run(self, argv: List[str]) -> int:
        if len(argv) < 1:
            print("Usage: python -m cxxnet_tpu.main config.conf "
                  "[key=value ...]")
            return 1
        # CPU-only local mode (example/multi-machine/launch.py): N
        # virtual CPU devices, set through jax.config before the
        # backend initializes
        ndev = os.environ.get("CXXNET_NUM_CPU_DEVICES")
        if ndev:
            from .parallel import force_virtual_cpu
            force_virtual_cpu(int(ndev))
        # config parses BEFORE distributed bring-up (pure text, no jax
        # touched) so the dist_* launch keys can drive
        # jax.distributed.initialize — env vars stay as fallback
        cfg = parse_config_file(argv[0])
        cfg += parse_cli_overrides(argv[1:])
        blocks, global_cfg = split_sections(cfg)
        for name, val in global_cfg:
            self._set(name, val)
        init_distributed(
            coordinator=self.dist_coordinator or None,
            num_processes=self.dist_num_hosts or None,
            process_id=None if self.dist_host_rank < 0
            else self.dist_host_rank)
        set_allreduce_retry(self.dist_allreduce_retry)
        # 'pred = <outfile>' doubles as the pred-block marker
        # (cxxnet_main.cpp:281-282), so read it from the raw stream
        for name, val in cfg:
            if name == "pred":
                self.name_pred = val

        # structured telemetry (monitor = none|stdout|jsonl); non-root
        # ranks get a null sink inside create_monitor. Installed as the
        # process-global so deep call sites (metric fallback warnings)
        # reach the same stream.
        self._cfg_stream = cfg
        self._mon = create_monitor(global_cfg)
        set_global(self._mon)
        # opt-in retry for transient remote-stream reads (flaky object
        # stores on preemptible capacity); 0 = fail fast, the default
        set_stream_retry(self.stream_retry)

        # iterators (closed on exit: prefetch threads / decode pools);
        # hoisted above the try so the finally can always iterate it
        all_iters: List[object] = []
        try:
            if self.dist_dryrun_hosts > 1:
                # fake the input topology for THIS run; cleared in the
                # finally so library callers never inherit a stale fake
                set_dryrun_topology(self.dist_dryrun_hosts)
            # model_in via filename convention infers start counter when
            # continuing training (cxxnet_main.cpp:204-215); finetune starts
            # a fresh model numbering
            if self.model_in and self.task == "train":
                m = _MODEL_RE.match(os.path.basename(self.model_in))
                if m:
                    self.start_counter = int(m.group(1)) + 1

            if self.continue_training:
                latest = self._sync_latest_model()
                self._resume_found = latest is not None
                if latest is not None:
                    self.model_in = latest
                rep = self._resume_report
                if self._mon.enabled and rep is not None:
                    self._mon.emit(
                        "resume",
                        source=latest or "",
                        counter=-1 if rep.counter is None
                        else rep.counter,
                        scanned=rep.scanned,
                        quarantined=len(rep.quarantined))

            itr_train = None
            eval_iters: List[Tuple[str, object]] = []
            pred_iter = None
            batch_cfg = [(k, v) for k, v in global_cfg
                         if k in ("batch_size", "input_shape", "label_width")]
            # multi-process dp: config batch_size is GLOBAL (doc/global.md);
            # each rank's iterator produces its 1/world_size local shard,
            # which the trainer assembles into the global batch
            # (make_array_from_process_local_data). Rank-disjoint DATA comes
            # from the iterators' own part_index/num_parts sharding.
            nproc = world_size()

            def _local_bs(v: str) -> str:
                assert int(v) % nproc == 0, \
                    "batch_size %s must divide evenly across %d " \
                    "processes" % (v, nproc)
                return str(int(v) // nproc)

            def _localize(pairs):
                """Divide every batch_size by world_size — both the global
                section AND iterator-block overrides (a block-level
                batch_size applied after the divided global one would feed
                world_size-times-too-many rows into the global assembly)."""
                if nproc == 1:
                    return pairs
                return [(k, _local_bs(v) if k == "batch_size" else v)
                        for k, v in pairs]

            batch_cfg = _localize(batch_cfg)
            if self.task == "serve_fleet":
                # the fleet front end serves network traffic, not an
                # iterator — skip data-block construction entirely (a
                # deployment config's train blocks may point at paths
                # the serving host does not mount)
                return self._task_serve_fleet(cfg)
            if self.task == "fleet":
                # the horizontal tier: balancer + autoscaler + canary
                # over N replica processes, each a task=serve_fleet
                # child spawned from this same config file
                return self._task_fleet(cfg, argv[0], argv[1:])
            if self.task == "fleet_balancer":
                # one door of a sharded front tier: a standalone
                # balancer process learning replicas and peers from
                # the endpoint registry (spawned by task=fleet when
                # fleet_balancers > 1, or run standalone)
                return self._task_fleet_balancer(cfg)
            if self.task == "export":
                # sealing a snapshot into a bundle needs no data
                # either — only the net config and the serve contract
                assert self.model_in, "task export requires model_in"
                return self._task_export(cfg)
            if (self.task in _PRED_TASKS and not self.test_io
                    and not any(b["kind"] == "pred" for b in blocks)):
                # no 'pred =' block: these tasks fall back to the train
                # data block, which is configured for training (shuffled,
                # randomly augmented) — say so once, and neutralize the
                # stochastic knobs so the output is deterministic and
                # row-aligned with the source files
                for b in blocks:
                    if b["kind"] != "data":
                        continue
                    b["cfg"] = list(b["cfg"]) + list(_PRED_NEUTRAL)
                    self._mon.warn_once(
                        "pred_fallback_train_iter",
                        "task=%s has no 'pred =' iterator block; "
                        "falling back to the train data block %r with "
                        "shuffle/augmentation disabled" %
                        (self.task, b["name"]))
            for b in blocks:
                if (self.dist_dryrun_hosts > 1 and b["kind"] == "data"
                        and (self.test_io
                             or self.task in ("train", "finetune"))):
                    # multi-host dryrun (doc/distributed.md): one
                    # batch-block-sharded chain per virtual host,
                    # assembled into the exact single-host global
                    # batch in host-rank order. Eval blocks stay
                    # unsharded — the shard math under test is the
                    # training input path
                    gbs = 0
                    for k, v in list(batch_cfg) + list(b["cfg"]):
                        if k == "batch_size":
                            gbs = int(v)
                    assert gbs > 0, "dryrun requires batch_size"
                    self._mon.warn_once(
                        "dryrun_neutralized_knobs",
                        "dist_dryrun_hosts=%d: shuffle off and "
                        "round_batch=0 on every per-host chain (the "
                        "bit-identity and exactly-once invariants "
                        "need deterministic record order)"
                        % self.dist_dryrun_hosts)
                    it = build_dryrun_feed(b["cfg"], batch_cfg,
                                           self.dist_dryrun_hosts, gbs)
                    it.init()
                    all_iters.append(it)
                    itr_train = it
                    continue
                with self._mon.span("setup.iterator"):
                    it = create_iterator(_localize(b["cfg"]), batch_cfg)
                    it.init()
                all_iters.append(it)
                if b["kind"] == "data":
                    itr_train = it
                elif b["kind"] == "eval":
                    eval_iters.append((b["name"], it))
                elif b["kind"] == "pred":
                    pred_iter = it

            if self.test_io:
                return self._task_test_io(itr_train)

            if self.task == "serve":
                assert self.model_in, "task serve requires model_in"
                return self._task_serve(cfg, pred_iter or itr_train)

            if self.task == "quantize":
                assert self.model_in, "task quantize requires model_in"
                return self._task_quantize(cfg, pred_iter or itr_train)

            if self.task == "build_index":
                assert self.model_in, \
                    "task build_index requires model_in"
                return self._task_build_index(cfg,
                                              pred_iter or itr_train)

            trainer = NetTrainer(cfg)
            if self.task in ("train", "finetune", "continual"):
                # monitor BEFORE init/load: the finetune carry record
                # and a bundle model_in's artifact_load accounting are
                # emitted during the bootstrap below
                trainer.set_monitor(self._mon)
                mode = self.task
                if self.task == "continual":
                    # the loop's training mode (continual_task):
                    # train = fresh init / warm-start model_in;
                    # finetune = remap-aware bootstrap
                    from .continual import ContinualConfig
                    mode = ContinualConfig(cfg).task
                with self._mon.span("setup.init_model"):
                    if self.model_in and (mode == "train"
                                          or self._resume_found):
                        # plain verified load — including a resumed
                        # (continue = 1) finetune/continual run: its
                        # own snapshots already carry the remapped
                        # structure, so resume must NOT re-remap a
                        # freshly initialized head over the trained one
                        trainer.load_model(self.model_in)
                    else:
                        trainer.init_model()
                        if mode == "finetune":
                            assert self.model_in, \
                                "finetune requires model_in"
                            trainer.finetune_from(
                                self.model_in, remap=self.finetune_remap,
                                strict=bool(self.finetune_strict))
                self._defer_normalize(
                    trainer, [itr_train] + [it for _, it in eval_iters])
                if self.task == "continual":
                    return self._task_continual(cfg, trainer,
                                                itr_train, eval_iters)
                return self._task_train(trainer, itr_train, eval_iters)

            assert self.model_in, "task %s requires model_in" % self.task
            # monitor before load: a bundle model_in emits its
            # artifact_load accounting during load_model
            trainer.set_monitor(self._mon)
            trainer.load_model(self.model_in)
            self._defer_normalize(trainer, [pred_iter or itr_train])
            if self.task == "pred":
                return self._task_predict(trainer, pred_iter or itr_train)
            if self.task in ("extract_feature", "extract",
                             "pred_raw"):
                # "extract" is the reference task name
                # (cxxnet_main.cpp:115); "pred_raw" appears in the
                # reference kaggle_bowl pred.conf meaning a raw
                # probability dump = extract of the top node
                if self.task == "pred_raw" and \
                        not self.extract_node_name:
                    self.extract_node_name = "top"
                return self._task_extract(trainer, pred_iter or itr_train)
            if self.task == "get_weight":
                return self._task_get_weight(trainer)
            print("unknown task %r" % self.task)
            return 1
        finally:
            # iterator construction and the task bodies share one
            # cleanup scope: a config error must still close prefetch
            # threads, release the jsonl sink, and clear the global
            # monitor (a stale global would swallow later warn_once
            # calls in long-lived library processes). The nested
            # finally flushes the sink even when an iterator close
            # raises (a wedged prefetch thread must not lose the
            # buffered tail of the record stream).
            try:
                for it in all_iters:
                    it.close()
            finally:
                clear_dryrun_topology()
                set_global(None)
                self._mon.close()

    def _task_test_io(self, itr) -> int:
        assert itr is not None, "test_io requires a data block"
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("test_io", self._cfg_stream))
        start = time.time()
        n = 0
        for r in range(self.num_round):
            for batch in itr:
                n += batch.batch_size - batch.num_batch_padd
        dt = time.time() - start
        ips = n / max(dt, 1e-9)
        mon.line("test_io: %d instances in %.2fs (%.1f/sec)"
                 % (n, dt, ips))
        if mon.enabled:
            mon.emit("test_io", instances=n, wall_s=dt,
                     instances_per_sec=ips)
        return 0

    # -- preemption ------------------------------------------------------

    def _install_preempt_handlers(self):
        """Catch SIGTERM/SIGINT (the preemption notice) and convert
        them into a flag the train loop honors at the next update
        boundary — an emergency snapshot beats dying mid-write. Only
        the main thread can own signal handlers; library callers on
        other threads keep their process defaults."""
        if threading.current_thread() is not threading.main_thread():
            return []
        installed = []

        def _on_signal(signum, frame):
            self._preempt_signum = signum

        for s in (signal.SIGTERM, signal.SIGINT):
            try:
                installed.append((s, signal.signal(s, _on_signal)))
            except (ValueError, OSError) as e:
                # without the handler a preemption kills the process
                # mid-round instead of snapshotting — worth a warning
                self._mon.warn_once(
                    "preempt_handler_unavailable",
                    "cannot install handler for signal %s (%s); "
                    "preemption will not trigger an emergency "
                    "snapshot" % (s, e))
        return installed

    @staticmethod
    def _restore_handlers(installed) -> None:
        for s, old in installed:
            try:
                signal.signal(s, old)
            except (ValueError, OSError, TypeError):
                pass  # cxxlint: disable=CXL006 -- best-effort restore on the exit path; install already warned when signals are unavailable

    def _preempt_now(self) -> bool:
        """True when any rank has a pending preemption signal. Multi-
        process: a host allreduce so every rank takes the emergency
        exit at the same update boundary (a lone rank breaking out of
        the SPMD loop would deadlock the others) — call at identical
        points on all ranks."""
        flagged = self._preempt_signum is not None
        if world_size() > 1:
            total = allreduce_host_sum(
                np.asarray([1 if flagged else 0], np.int32))
            return int(np.asarray(total)[0]) > 0
        return flagged

    def _preempt_exit(self, ckpt, round_idx: int, mon) -> int:
        """Emergency snapshot at the current update boundary, clean
        telemetry, distinct exit code. ``round_idx`` rounds completed
        fully, so the snapshot commits under counter ``round_idx`` —
        resume re-runs the interrupted round from its start with the
        mid-round weights (never loses a completed round)."""
        signum = int(self._preempt_signum or 0)
        if self.silent == 0 and is_root():
            mon.line("preempted by signal %d: emergency snapshot "
                     "%04d.model.npz" % (signum, round_idx))
        ckpt.save(round_idx, emergency=True)
        ckpt.close()
        if mon.enabled:
            mon.emit("preempt", signal=signum, round=round_idx,
                     exit_code=EXIT_PREEMPTED)
        return EXIT_PREEMPTED

    @staticmethod
    def _defer_normalize(trainer, iters) -> None:
        """The task runner hands a trainer its iterators, so it asks
        each chain whether the trainer may run the normalisation
        (``IIterator.defer_normalize``): a plain crop / mirror image
        chain then delivers uint8 pixels and ``(x - mean) * scale``
        runs as the first ops of the step. The first chain in the
        list (the training data) sets the trainer's spec; a chain that
        offers another keeps the host path. Before ``precompile``,
        which lowers for the dtype the chains will deliver."""
        for it in iters:
            if it is not None:
                it.defer_normalize(trainer.adopt_input_norm)

    def _task_train(self, trainer, itr_train, eval_iters) -> int:
        assert itr_train is not None, "train requires a data block"
        mon = self._mon
        if trainer._mon is not mon:      # run() may have attached it
            trainer.set_monitor(mon)     # already (no duplicate
            #                              model_info records)
        if hasattr(itr_train, "set_transform"):
            # threadbuffer chains overlap host->device transfer with
            # device compute by device_put-ing in the prefetch thread
            itr_train.set_transform(trainer.device_put_batch)
        monitored = mon.enabled
        io_hist = None
        if monitored:
            mon.emit("run_start", **run_metadata(
                self.task, self._cfg_stream, trainer.mesh),
                input_norm=trainer.input_norm_record())
            topo = current_topology()
            if topo.num_hosts > 1:
                # the input/mesh topology this dist (or dryrun) run
                # trains under (doc/distributed.md)
                mon.emit("dist_topology", **topo.describe(),
                         mesh=dict(trainer.mesh.shape),
                         global_batch=trainer.batch_size)
            if trainer.topology_changed:
                # elastic handoff: the loaded snapshot was written
                # under a different world size/mesh; the reader shard
                # map re-derives at the round boundary (resume
                # re-runs the interrupted round from its start, so
                # the handoff record offset is 0 within the round)
                old = trainer.resumed_topology or {}
                mon.emit("dist_resize",
                         old_hosts=int(old.get("hosts", 0)),
                         new_hosts=topo.num_hosts,
                         counter=trainer.update_counter,
                         start_record=0)
            # batch-fetch latency histogram on the prefetch chain
            # (found anywhere in the chain, not only outermost);
            # attached only under an active monitor so the default
            # path never pays the per-batch clock reads
            io_hist = enable_chain_wait_stats(itr_train)
            attach_chain_spans(itr_train, mon.span)
        k = self.dispatch_period
        # checkpoints go through the manager: atomic commit + digest,
        # background writer (checkpoint_async), retention GC
        # (keep_snapshots), telemetry (doc/checkpointing.md)
        ckpt = CheckpointManager(
            trainer, self._model_path, model_dir=self.model_dir,
            monitor=mon, async_=bool(self.checkpoint_async),
            fsync=bool(self.checkpoint_fsync),
            keep=self.keep_snapshots)
        if self.precompile:
            # AOT-compile every dispatch signature of the steady-state
            # loop (per-batch tail, K-batch window, eval forward) before
            # round 0: the round-0 recompile stalls collapse into one
            # accounted precompile window, and the stream records zero
            # compile events afterwards
            trainer.precompile(window=k)
        start = time.time()

        def _progress(r, nbatch):
            if (self.print_step and nbatch % self.print_step < k
                    and self.silent == 0 and is_root()):
                mon.line("round %8d:[%8d] %ld sec elapsed"
                         % (r, nbatch, int(time.time() - start)))

        # installed inside the try so every exit path restores the
        # process handlers (a long-lived library caller must get its
        # Ctrl-C back even when the loop below raises)
        handlers = []
        self._ndisp = 0
        try:
            handlers = self._install_preempt_handlers()
            for r in range(self.start_counter - 1, self.num_round):
                # update-boundary preemption check (collective when
                # multi-process): r rounds have fully completed
                if self._preempt_now():
                    return self._preempt_exit(ckpt, r, mon)
                with mon.span("train.round", round=r):
                    rc = self._train_round(trainer, itr_train, eval_iters,
                                           ckpt, r, io_hist, _progress)
                if rc is not None:
                    return rc
            # drain the writer before run_end: every checkpoint record
            # lands in the stream, and the last commit is durable
            # before the exit code says success
            ckpt.close()
        finally:
            ckpt.close()
            self._restore_handlers(handlers)
        if self.silent == 0 and is_root():
            mon.line("updating end, %ld sec in all"
                     % int(time.time() - start))
        if monitored:
            c = trainer.counters_snapshot()
            mon.emit("run_end", wall_s=time.time() - start,
                     steps=int(c["steps"]), examples=int(c["examples"]))
        return 0

    def _train_round(self, trainer, itr_train, eval_iters, ckpt, r: int,
                     io_hist, progress) -> Optional[int]:
        """One round of ``task = train``: the dispatch loop, then the
        round's evals, records and snapshot. Returns the preemption
        exit code when a signal ended the round, else None. The loop's
        three phases are spans (wait for a batch, dispatch, end of
        round): ``step.data_wait_ms`` IS the ``train.data_wait`` spans'
        time since the last dispatch, one measurement."""
        mon = self._mon
        monitored = mon.enabled
        k = self.dispatch_period
        trainer.start_round(r)
        if monitored:
            mon.emit("round_start", round=r)
        # trace hooks are NOT gated on an enabled sink: a profiler
        # trace is one config line (monitor_trace_dir) away even with
        # monitor = none (doc/debug_perf.md)
        mon.maybe_start_trace(r)
        nbatch = 0
        window = []
        # lockstep across ranks: unequal per-rank batch counts would
        # deadlock the SPMD collectives (see parallel.synced_batches)
        batches = synced_batches(itr_train, window=k)
        while True:
            # data-wait half of the step-time split: time this loop
            # spends blocked on the iterator (its restart included)
            with mon.span("train.data_wait", round=r) as wait:
                batch = next(batches, None)
            if monitored:
                trainer.note_data_wait(wait.dur_ns / 1e9)
            if batch is None:
                break
            if k > 1:
                window.append(batch)
                if len(window) < k:
                    continue
            with mon.span("train.dispatch", round=r):
                if k == 1:
                    trainer.update(batch)
                    nbatch += 1
                else:
                    trainer.update_many(window)
                    nbatch += len(window)
                    window = []
            progress(r, nbatch)
            # every rank reaches each dispatch boundary the same
            # number of times (synced_batches), so the collective
            # preemption check stays in lockstep. Multi-process, the
            # check is a blocking host allgather — throttle it to
            # every 8th dispatch (the shared counter keeps ranks
            # agreeing on WHICH dispatches check) so the hot path does
            # not grow a second per-dispatch host collective
            self._ndisp += 1
            if (world_size() == 1 or self._ndisp % 8 == 0) \
                    and self._preempt_now():
                return self._preempt_exit(ckpt, r, mon)
        if window:
            with mon.span("train.dispatch", round=r, n=len(window)):
                for batch in window:    # round tail: per-batch (a short
                    trainer.update(batch)  # window would recompile)
        with mon.span("train.round_end", round=r):
            trainer.end_round()         # close the throughput window
            #                             before evals start
            line = "[%d]" % (r + 1)
            if self.task_eval_train:
                line += trainer.train_metric_str("train")
            for name, it in eval_iters:
                line += trainer.evaluate(it, name)
            if self.silent == 0 and is_root():
                mon.line(line)
            mon.maybe_stop_trace(r)
            if monitored:
                self._emit_round_records(trainer, itr_train, r, io_hist)
            if self.test_on_server:
                # per-round weight consistency audit (the reference's
                # test_on_server CheckWeight_,
                # async_updater-inl.hpp:149-154): every device replica
                # must hold identical weights
                trainer.check_weight_consistency()
            if self.save_period and (r + 1) % self.save_period == 0:
                # all ranks call (ZeRO-state gathers are collective);
                # only root commits, on the background writer when
                # checkpoint_async
                ckpt.save(r + 1)
        return None

    def _emit_round_records(self, trainer, itr_train, r: int,
                            io_hist) -> None:
        mon = self._mon
        mon.emit("round_end", round=r,
                 examples=trainer.last_round_examples,
                 wall_s=trainer.last_round_wall_s,
                 examples_per_sec=trainer.last_round_examples_per_sec)
        mon.emit("memory", round=r, **device_memory_snapshot())
        if io_hist is not None:
            mon.emit("io_wait", round=r, **io_hist.snapshot())
            io_hist.reset()
        ps = pipeline_snapshot(itr_train)
        if ps is not None:
            # per-round input-pipeline health: buffer-reuse rate of the
            # zero-copy assembly, H2D staging time of the prefetch
            # (doc/observability.md)
            mon.emit("pipeline", round=r, **ps)
        if isinstance(itr_train, DryrunFeed):
            # per-round per-host input-shard accounting: rows_per_host
            # sums exactly to the round's real rows (the exactly-once
            # invariant, counted per round)
            mon.emit("dist_shard", round=r, **itr_train.accounting())
            itr_train.reset_accounting()

    def _task_continual(self, cfg, trainer, itr_train,
                        eval_iters) -> int:
        """Continual train-while-serve (doc/continual.md): one
        long-lived process trains on a looping iterator while the
        fleet front end serves live traffic from ``model_dir``; every
        ``continual_export_every`` updates the generation pipeline
        runs (eval gate -> verified snapshot -> sealed bundle ->
        watcher ``notify()`` -> zero-downtime flip), for
        ``continual_generations`` generations. SIGTERM/SIGINT takes
        the emergency-snapshot exit (code 75) like ``task = train``."""
        assert itr_train is not None, "continual requires a data block"
        assert world_size() == 1, \
            "task=continual must run single-process"
        from .continual import ContinualLoop
        mon = self._mon
        if hasattr(itr_train, "set_transform"):
            # same prefetch-thread H2D overlap as _task_train: the
            # long-lived trainer must not pay serialized transfers
            itr_train.set_transform(trainer.device_put_batch)
        if mon.enabled:
            mon.emit("run_start", **run_metadata(
                "continual", self._cfg_stream, trainer.mesh),
                input_norm=trainer.input_norm_record())
        handlers = []
        try:
            handlers = self._install_preempt_handlers()
            loop = ContinualLoop(
                cfg, trainer, itr_train, eval_iters,
                model_dir=self.model_dir,
                path_for=self._model_path,
                monitor=mon,
                should_stop=lambda: self._preempt_signum is not None,
                checkpoint_async=bool(self.checkpoint_async),
                checkpoint_fsync=bool(self.checkpoint_fsync),
                keep_snapshots=self.keep_snapshots,
                start_counter=self.start_counter,
                dispatch_period=self.dispatch_period)
            summary = loop.run()
        finally:
            self._restore_handlers(handlers)
        if summary["preempted"]:
            signum = int(self._preempt_signum or 0)
            if self.silent == 0 and is_root():
                mon.line("continual: preempted by signal %d after %d "
                         "generation(s); emergency snapshot committed"
                         % (signum, summary["deployed"]))
            if mon.enabled:
                mon.emit("preempt", signal=signum,
                         round=trainer.round,
                         exit_code=EXIT_PREEMPTED)
            return EXIT_PREEMPTED
        if mon.enabled:
            mon.emit("task_end", task="continual",
                     generations=summary["deployed"],
                     requests=summary["requests"])
        return 0

    def _task_serve(self, cfg, itr) -> int:
        """Long-lived concurrent predictor (doc/serving.md): load the
        snapshot into a frozen bucketed engine behind the dynamic
        batcher, then drive ``serve_clients`` threaded closed-loop
        clients over the iterator's examples — a self-contained soak
        that exercises the full concurrent path and emits the
        ``serve_*`` telemetry records."""
        assert itr is not None, "serve requires an iterator block"
        assert world_size() == 1, "task=serve must run single-process"
        from .serve import ServeSession, run_closed_loop
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("serve", self._cfg_stream))
        session = ServeSession(cfg, model_path=self.model_in,
                               monitor=mon)
        try:
            c = session.cfg
            # example pool for the clients: enough valid rows that
            # wrapping reuse stays fair, forced to a private float32
            # copy (iterator ring buffers recycle their arrays)
            want = max(256, c.clients * c.request_rows)
            pool_parts, got = [], 0
            for batch in itr:
                n = batch.batch_size - batch.num_batch_padd
                pool_parts.append(np.array(batch.data[:n], np.float32))
                got += n
                if got >= want:
                    break
            assert pool_parts, "serve: iterator produced no examples"
            pool = np.concatenate(pool_parts, axis=0)
            agg = run_closed_loop(session, pool, c.clients, c.requests,
                                  c.request_rows)
            summary = session.close()
        finally:
            # a failure between warmup and close must not leave the
            # worker threads emitting into a sink run() is about to
            # close (close is idempotent; no-op on the success path)
            session.close(drain=False)
        mon.line(
            "serve: %d ok / %d busy / %d timeout / %d error requests "
            "(%d rows) in %.2fs, p50 %.1f ms p99 %.1f ms, fill %.2f, "
            "compiles after warmup %d"
            % (agg["ok"], agg["busy"], agg["timeout"], agg["error"],
               summary["rows"], agg["wall_s"],
               summary["latency_p50_ms"], summary["latency_p99_ms"],
               summary["fill_rate"], summary["compile_events"]))
        if mon.enabled:
            mon.emit("task_end", task="serve", requests=agg["ok"],
                     rows=summary["rows"])
        return 0

    def _task_quantize(self, cfg, itr) -> int:
        """Post-training calibration (doc/perf_profile.md
        "Low-precision inference"): stream the iterator through the
        frozen eval net collecting per-channel activation/weight
        ranges, parity-gate the quantized graph against the f32 eval
        outputs over the same batches, and commit a digest-verified
        snapshot whose ``quant/`` arrays carry the ranges — the
        artifact ``serve_dtype = int8|fp8`` loads."""
        assert itr is not None, "quantize requires an iterator block"
        assert world_size() == 1, "task=quantize must run single-process"
        from .io.data import DataBatch
        from .nnet.checkpoint import write_snapshot
        from .nnet.quantize import Calibrator, normalize_serve_dtype
        mon = self._mon
        t_start = time.time()
        qdtype = normalize_serve_dtype(self.quantize_dtype)
        if qdtype not in ("int8", "fp8"):
            raise ValueError(
                "quantize_dtype must be int8 or fp8, got %r"
                % self.quantize_dtype)
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("quantize", self._cfg_stream))
        # calibration runs the f32 graph whatever the config's
        # serve_dtype says (a deployment conf carries serve_dtype=int8
        # for the serve replicas; the override appends last, so it wins)
        trainer = NetTrainer(list(cfg) + [("serve_dtype", "float32")])
        trainer.load_model(self.model_in)
        top = (trainer.graph.num_nodes - 1,)
        calib = Calibrator(trainer)
        if not calib.targets:
            raise ValueError(
                "task=quantize: this net has no quantizable layers "
                "(conv/fullc owning their params, no channel-alignment "
                "annotations) — nothing to calibrate")
        batches, refs = [], []
        for batch in itr:
            # private copies: iterator ring buffers recycle their arrays
            nb = DataBatch(data=np.array(batch.data),
                           label=np.array(batch.label),
                           num_batch_padd=batch.num_batch_padd)
            nvalid = nb.batch_size - nb.num_batch_padd
            (val,) = trainer._call_pred(
                trainer._put_batch_array(nb.data),
                trainer._put_mask(nb), (), top)
            refs.append(np.array(trainer._local_rows(val)[:nvalid]))
            calib.observe(nb)
            batches.append(nb)
            if len(batches) >= self.quantize_batches:
                break
        assert batches, "quantize: iterator produced no batches"
        tables = calib.finish()
        qmeta = {"dtype": qdtype, "batches": len(batches),
                 "source": self.model_in,
                 "bn_fold_eval": trainer.net._bn_fold_eval,
                 "parity_eps": self.quantize_parity_eps}
        # activate the quantized graph on THIS trainer (fresh programs)
        # and measure parity against the stored f32 outputs
        trainer.set_quantization(tables, qmeta, dtype=qdtype)
        max_abs = mean_sum = agree = nrow = nelt = 0
        for nb, ref in zip(batches, refs):
            nvalid = nb.batch_size - nb.num_batch_padd
            (val,) = trainer._call_pred(
                trainer._put_batch_array(nb.data),
                trainer._put_mask(nb), (), top)
            got = trainer._local_rows(val)[:nvalid]
            diff = np.abs(got.astype(np.float64) - ref)
            max_abs = max(max_abs, float(diff.max()))
            mean_sum += float(diff.sum())
            nelt += diff.size
            agree += int(np.sum(trainer.rows_to_prediction(got)
                                == trainer.rows_to_prediction(ref)))
            nrow += nvalid
        mean_abs = mean_sum / max(nelt, 1)
        agree_rate = agree / max(nrow, 1)
        rep = trainer.quant_report
        out = self.quantize_out or re.sub(
            r"\.npz$", "", self.model_in) + ".%s.npz" % qdtype
        ok = mean_abs <= self.quantize_parity_eps
        if ok:
            arrays, meta = trainer.gather_snapshot()
            write_snapshot(out, arrays, meta,
                           fsync=bool(self.checkpoint_fsync))
        wall = time.time() - t_start
        if mon.enabled:
            mon.emit("quantize", dtype=rep.get("dtype", qdtype),
                     batches=len(batches), layers=rep.get("layers", 0),
                     fallback_layers=rep.get("fallback_layers", 0),
                     parity_max_abs=max_abs, parity_mean_abs=mean_abs,
                     agree_rate=agree_rate, out=out if ok else "",
                     wall_ms=wall * 1e3)
        mon.line(
            "quantize[%s]: %d layers (%d fallback) over %d batches, "
            "parity mean|Δ| %.2g max|Δ| %.2g agree %.3f — %s"
            % (rep.get("dtype", qdtype), rep.get("layers", 0),
               rep.get("fallback_layers", 0), len(batches), mean_abs,
               max_abs, agree_rate,
               ("wrote %s" % out) if ok else
               "PARITY GATE FAILED (eps %g), no snapshot written"
               % self.quantize_parity_eps))
        if mon.enabled:
            mon.emit("task_end", task="quantize", outfile=out if ok
                     else "", rows=nrow)
        return 0 if ok else 1

    def _task_serve_fleet(self, cfg) -> int:
        """Fleet serving (doc/serving.md "Fleet serving"): N routed
        engines with per-tenant quotas and checkpoint-driven hot-swap
        behind the HTTP/JSON + binary protocol listeners. Runs for
        ``serve_fleet_duration_s`` seconds (0 = until SIGTERM/SIGINT —
        the deployment mode), then drains every engine cleanly."""
        assert world_size() == 1, \
            "task=serve_fleet must run single-process"
        from .serve import FleetServer
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("serve_fleet", self._cfg_stream))
        fleet = FleetServer(cfg, monitor=mon)
        handlers = []
        try:
            fleet.start()
            mon.line("serve_fleet: listening http=%s binary=%s, "
                     "models: %s"
                     % (fleet.http_port, fleet.binary_port,
                        ", ".join("%s@%04d" % (d["model"], d["counter"])
                                  for d in fleet.describe())))
            handlers = self._install_preempt_handlers()
            dur = fleet.fleet_cfg.duration_s
            deadline = time.monotonic() + dur if dur > 0 else None
            while self._preempt_signum is None:
                if deadline is not None \
                        and time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
            summary = fleet.close()
        finally:
            # a failure between start and close must still stop the
            # listener/watcher threads and drain the engines (close is
            # idempotent; no-op on the success path)
            fleet.close(drain=False)
            self._restore_handlers(handlers)
        c = summary["requests"]
        mon.line("serve_fleet: %d requests (%d ok / %d over_quota / "
                 "%d busy / %d timeout / %d error), %d hot-swaps"
                 % (c["requests"], c["ok"], c["over_quota"], c["busy"],
                    c["timeout"], c["error"], summary["swaps"]))
        if mon.enabled:
            mon.emit("task_end", task="serve_fleet",
                     requests=c["requests"], swaps=summary["swaps"])
        return 0

    def _task_fleet(self, cfg, conf_path: str,
                    cli_overrides: List[str]) -> int:
        """Horizontal fleet (doc/serving.md "Horizontal fleet"): a
        front-of-fleet balancer + autoscale controller (+ optional
        canary rollout) over N shared-nothing ``serve_fleet`` replica
        processes spawned from this same config file. Runs for
        ``fleet_duration_s`` seconds (0 = until SIGTERM/SIGINT), then
        drains every replica cleanly — scale-in order on every exit
        path: deroute, wait in-flight, SIGTERM."""
        assert world_size() == 1, "task=fleet must run single-process"
        from .fleet import FleetController
        mon = self._mon
        if mon.enabled:
            # device=False: the parent must stay off the jax backend —
            # a chip belongs to one process, and the replicas need it
            mon.emit("run_start",
                     **run_metadata("fleet", self._cfg_stream,
                                    device=False))
        controller = FleetController(cfg, conf_path, monitor=mon,
                                     extra_overrides=cli_overrides)
        handlers = []
        summary = {}
        try:
            controller.start()
            bal = controller.balancer
            mon.line("fleet: balancer http=%s binary=%s, %d replicas "
                     "serving %s%s"
                     % (bal.http_port, bal.binary_port,
                        controller.ready_count(),
                        controller.current_version(),
                        ", canary %s armed"
                        % controller.canary.canary_version
                        if controller.canary else ""))
            handlers = self._install_preempt_handlers()
            dur = controller.tier.duration_s
            deadline = time.monotonic() + dur if dur > 0 else None
            while self._preempt_signum is None:
                if deadline is not None \
                        and time.monotonic() >= deadline:
                    break
                time.sleep(0.05)
        finally:
            # a failure between start and the wait loop must still
            # stop the scale thread, drain replicas, and close the
            # listeners (close is idempotent per component)
            summary = controller.close()
            self._restore_handlers(handlers)
        mon.line("fleet: %d requests (%d ok / %d shed / %d error, "
                 "%d retries recovered)%s"
                 % (summary.get("requests", 0), summary.get("ok", 0),
                    summary.get("shed", 0), summary.get("errors", 0),
                    summary.get("retries", 0),
                    ", canary %s" % summary["canary"]
                    if "canary" in summary else ""))
        if mon.enabled:
            mon.emit("task_end", task="fleet",
                     requests=summary.get("requests", 0))
        return 0

    def _task_fleet_balancer(self, cfg) -> int:
        """One door of the sharded front tier (doc/serving.md
        "Sharded front tier"): a standalone :class:`FleetBalancer`
        that publishes its ports through ``fleet_port_file`` and
        reconciles replicas / tier peers from the shared endpoint
        registry on every sync tick — the same spawn-through-CLI +
        port-file discipline replicas use. Runs for
        ``fleet_duration_s`` seconds (0 = until SIGTERM/SIGINT)."""
        assert world_size() == 1, \
            "task=fleet_balancer must run single-process"
        from .fleet import FleetBalancer, FleetTierConfig
        from .fleet.placement import (EndpointRegistry,
                                      sync_from_registry,
                                      write_endpoint_file)
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("fleet_balancer",
                                    self._cfg_stream, device=False))
        tier = FleetTierConfig(cfg)
        bal = FleetBalancer(tier, cfg, monitor=mon)
        registry = EndpointRegistry(tier.registry_path)
        handlers = []
        summary = {}
        try:
            bal.start()
            sync_from_registry(bal, registry, tier.balancer_id)
            if tier.port_file:
                write_endpoint_file(
                    tier.port_file,
                    {"pid": os.getpid(), "http_port": bal.http_port,
                     "binary_port": bal.binary_port})
            mon.line("fleet_balancer: %s http=%s binary=%s, "
                     "registry %s"
                     % (tier.balancer_id, bal.http_port,
                        bal.binary_port, tier.registry_path))
            handlers = self._install_preempt_handlers()
            dur = tier.duration_s
            deadline = time.monotonic() + dur if dur > 0 else None
            # the sync cadence bounds how fast this door sees a drain
            # or a new replica — well under the controller's drain
            # wait, and cheap (an mtime stat when nothing changed)
            sync_s = min(0.2, tier.gossip_s)
            while self._preempt_signum is None:
                if deadline is not None \
                        and time.monotonic() >= deadline:
                    break
                sync_from_registry(bal, registry, tier.balancer_id)
                time.sleep(sync_s)
        finally:
            summary = bal.close()
            self._restore_handlers(handlers)
        mon.line("fleet_balancer: %s served %d requests (%d ok / "
                 "%d shed / %d error, %d retries recovered)"
                 % (tier.balancer_id, summary.get("requests", 0),
                    summary.get("ok", 0), summary.get("shed", 0),
                    summary.get("errors", 0),
                    summary.get("retries", 0)))
        if mon.enabled:
            mon.emit("task_end", task="fleet_balancer",
                     requests=summary.get("requests", 0))
        return 0

    def _task_export(self, cfg) -> int:
        """Seal ``model_in`` into a deployable artifact bundle
        (doc/artifacts.md): load the verified snapshot into a frozen
        bucket-ladder engine, AOT-compile every (bucket, mask-variant)
        executable the serve contract can dispatch, and commit
        snapshot + serialized executables + fingerprint + manifest as
        one two-phase bundle at ``export_out`` (default: the
        ``NNNN.model.bundle`` sibling of ``model_in``). A serve
        replica booting from the bundle on a matching runtime
        deserializes instead of compiling — near-zero cold start."""
        assert world_size() == 1, "task=export must run single-process"
        from .artifact.bundle import default_bundle_path, export_bundle
        from .serve import ServeConfig, build_engine
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("export", self._cfg_stream))
        sc = ServeConfig(cfg)
        engine = build_engine(cfg, self.model_in, buckets=sc.buckets,
                              max_batch=sc.max_batch, node=sc.node,
                              monitor=mon)
        # warm_run off: export needs the executables, not the
        # first-request latency of a live server
        compiled = engine.warmup(warm_run=False)
        out = self.export_out or default_bundle_path(self.model_in)
        stats = export_bundle(engine, out, node=sc.node, monitor=mon)
        if mon.enabled:
            mon.emit("export", **stats)
        mon.line("export: sealed %s -> %s (%d programs compiled, %d "
                 "serialized, %d bytes)"
                 % (self.model_in, out, compiled, stats["programs"],
                    stats["bytes"]))
        if mon.enabled:
            mon.emit("task_end", task="export", outfile=out)
        return 0

    def _task_build_index(self, cfg, itr) -> int:
        """Embed the iterator's corpus through the frozen serve net
        and seal model + index as ONE deployable bundle
        (doc/retrieval.md): stream valid rows through the bucketed
        engine (the exact dispatch ``/v1/embed`` serves), build the
        exact top-k index over the embeddings, AOT-compile the search
        program family into the same registry, and commit everything
        as a digest-verified artifact. A replica booting from the
        bundle serves ``/v1/embed`` and ``/v1/search`` with zero
        compiles, and a hot-swap flips model and index atomically."""
        assert itr is not None, "build_index requires an iterator block"
        assert world_size() == 1, \
            "task=build_index must run single-process"
        from .artifact.bundle import default_bundle_path, export_bundle
        from .retrieval import (EmbeddingIndex, RetrievalEngine,
                                self_recall)
        from .serve import ServeConfig, build_engine
        mon = self._mon
        t_start = time.time()
        if mon.enabled:
            mon.emit("run_start",
                     **run_metadata("build_index", self._cfg_stream))
        sc = ServeConfig(cfg)
        engine = build_engine(cfg, self.model_in, buckets=sc.buckets,
                              max_batch=sc.max_batch, node=sc.node,
                              monitor=mon)
        compiled = engine.warmup(warm_run=False)
        # corpus pass: valid rows only, private copies (iterator ring
        # buffers recycle their arrays), capped by index_rows
        parts, got, cap = [], 0, self.index_rows
        for batch in itr:
            n = batch.batch_size - batch.num_batch_padd
            if cap and got + n > cap:
                n = cap - got
            if n > 0:
                parts.append(np.array(batch.data[:n], np.float32))
                got += n
            if cap and got >= cap:
                break
        assert parts, "build_index: iterator produced no examples"
        rows = np.concatenate(parts, axis=0)
        vecs = np.asarray(engine.run(rows), np.float32)
        index = EmbeddingIndex.build(
            ids=np.arange(rows.shape[0], dtype=np.int64),
            vectors=vecs.reshape(rows.shape[0], -1),
            metric=self.index_metric, node=sc.node)
        spec = sc.search_buckets
        buckets = tuple(sorted({int(t) for t in spec.split(",")
                                if t.strip()})) \
            if spec and spec != "auto" else None
        rengine = RetrievalEngine(index, engine.trainer.programs,
                                  k=sc.search_k or 10,
                                  buckets=buckets, monitor=mon)
        budget = int(engine.trainer.serve_device_mem_budget * 1e6)
        rengine.warmup(warm_run=False, budget_bytes=budget)
        t_rec = time.time()
        rec = self_recall(rengine)
        if mon.enabled:
            mon.emit("retrieval", queries=min(8, index.rows), k=1,
                     metric=index.metric, recall=rec,
                     wall_ms=(time.time() - t_rec) * 1e3)
        out = self.export_out or default_bundle_path(self.model_in)
        stats = export_bundle(engine, out, node=sc.node, monitor=mon,
                              retrieval=rengine)
        if mon.enabled:
            mon.emit("index_build", out=out, rows=index.rows,
                     dim=index.dim, metric=index.metric, node=sc.node,
                     bytes=index.nbytes,
                     wall_ms=(time.time() - t_start) * 1e3)
            mon.emit("export", **stats)
        mon.line(
            "build_index: %d rows x %d dims (%s) sealed with %s -> %s "
            "(self-recall@1 %.3f, %d+%d programs, %d index bytes)"
            % (index.rows, index.dim, index.metric, self.model_in,
               out, rec, compiled, len(rengine.buckets), index.nbytes))
        if mon.enabled:
            mon.emit("task_end", task="build_index", outfile=out,
                     rows=index.rows)
        return 0

    def _task_predict(self, trainer, itr) -> int:
        assert itr is not None, "pred requires an iterator"
        # pred/extract are single-process tasks (as in the reference
        # CLI): under multi-process dp each rank would see only its
        # data shard and they would race on the output file
        assert world_size() == 1, \
            "task=pred must run single-process (launch without " \
            "CXXNET_COORDINATOR)"
        mon = self._mon
        if mon.enabled:
            mon.emit("run_start", **run_metadata(
                "pred", self._cfg_stream, trainer.mesh))
        nrow = 0
        with open_stream(self.name_pred, "w") as f:
            for batch in itr:
                for v in trainer.predict(batch):
                    f.write("%g\n" % v)
                    nrow += 1
        mon.line("finished prediction, write into %s" % self.name_pred)
        if mon.enabled:
            mon.emit("task_end", task="pred", outfile=self.name_pred,
                     rows=nrow)
        return 0

    def _task_extract(self, trainer, itr) -> int:
        assert itr is not None, "extract requires an iterator"
        assert world_size() == 1, \
            "task=extract_feature must run single-process"
        if self._mon.enabled:
            self._mon.emit("run_start", **run_metadata(
                "extract", self._cfg_stream, trainer.mesh))
        node = self.extract_node_name
        txt = self.output_format == "txt"
        nrow, shape3 = 0, (0, 0, 0)
        mode = "w" if txt else "wb"
        with open_stream(self.name_pred, mode) as f:
            for batch in itr:
                feats = trainer.extract_feature(batch, node)
                if feats.ndim == 4:      # NHWC -> reference (ch, y, x)
                    feats = feats.transpose(0, 3, 1, 2)
                    shape3 = feats.shape[1:]
                else:
                    feats = feats.reshape(feats.shape[0], -1)
                    shape3 = (1, 1, feats.shape[1])
                nrow += feats.shape[0]
                if txt:
                    flat = feats.reshape(feats.shape[0], -1)
                    for row in flat:
                        f.write(" ".join("%g" % x for x in row) + "\n")
                else:
                    f.write(np.ascontiguousarray(
                        feats, dtype="<f4").tobytes())
        # shape sidecar: "nrow,ch,y,x" (cxxnet_main.cpp:418)
        with open_stream(self.name_pred + ".meta", "w") as fm:
            fm.write("%d,%d,%d,%d\n" % ((nrow,) + tuple(shape3)))
        self._mon.line("finished feature extraction, write into %s"
                       % self.name_pred)
        if self._mon.enabled:
            self._mon.emit("task_end", task="extract",
                           outfile=self.name_pred, rows=nrow)
        return 0

    def _task_get_weight(self, trainer) -> int:
        assert self.weight_layer, "get_weight requires weight_layer"
        if self._mon.enabled:
            self._mon.emit("run_start", **run_metadata(
                "get_weight", self._cfg_stream, trainer.mesh))
        w = trainer.get_weight(self.weight_layer, self.weight_tag)
        rows = w.reshape(w.shape[0], -1) if w.ndim > 1 else w[None, :]
        if self.output_format == "txt":
            with open_stream(self.weight_filename, "w") as f:
                np.savetxt(f, rows, fmt="%g")
        else:                            # raw float32 (cxxnet_main:350)
            with open_stream(self.weight_filename, "wb") as f:
                f.write(np.ascontiguousarray(rows, "<f4").tobytes())
        self._mon.line("weight %s:%s %s written to %s"
                       % (self.weight_layer, self.weight_tag, w.shape,
                          self.weight_filename))
        if self._mon.enabled:
            self._mon.emit("task_end", task="get_weight",
                           outfile=self.weight_filename)
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    return LearnTask().run(sys.argv[1:] if argv is None else argv)


if __name__ == "__main__":
    sys.exit(main())

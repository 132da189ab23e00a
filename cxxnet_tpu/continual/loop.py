"""The continual supervisor: train -> gate -> export -> hot-swap, xN.

One process owns both halves of the production loop (doc/continual.md):

- the **trainer** runs on a looping data iterator (epochs stream
  back-to-back; round telemetry keeps its per-epoch shape), driven in
  ``dispatch_period`` windows exactly like the ``task = train`` loop;
- the **fleet front end** (:class:`~cxxnet_tpu.serve.frontend.
  FleetServer`) serves live traffic from ``model_dir`` the whole time,
  hot-swapping through its :class:`~cxxnet_tpu.serve.swap.
  SnapshotWatcher`.

Every ``continual_export_every`` applied updates the loop runs one
**generation attempt**:

1. **eval gate** — a full eval pass; the gated metric must be
   non-worsening against the best deployed generation
   (``continual_gate = min|max``, slack ``continual_gate_eps``). A
   failed gate skips the snapshot AND the export — the fleet keeps
   serving the old generation, training continues, and the attempt is
   recorded (``generation`` record, ``action = "gate_skipped"``).
2. **snapshot** — a digest-verified atomic commit through the
   :class:`~cxxnet_tpu.nnet.checkpoint.CheckpointManager` (the
   background writer is drained before export reads the file back).
3. **export** — the ``task = export`` pipeline sealed in-process by
   :class:`GenerationExporter`: the first generation compiles the
   bucket-ladder executables once, later generations reload weights
   in place (:meth:`~cxxnet_tpu.nnet.trainer.NetTrainer.
   load_weights_inplace` — the executables are weight-agnostic) and
   re-seal with zero new compiles.
4. **flip** — ``FleetServer.notify_watchers()`` wakes the poll thread
   the instant the bundle commits; the watcher shadow-boots the
   bundle (deserialized executables: zero compile events on a
   matching runtime) and flips with zero failed requests. The first
   generation *boots* the fleet instead (there is nothing to swap
   from yet).

The loop honors the CLI's preemption contract: ``should_stop`` is
checked at every dispatch and pipeline boundary, and a preempted run
commits an emergency snapshot, drains the fleet, and reports
``preempted`` so ``main`` can exit 75 (EX_TEMPFAIL).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..artifact.bundle import default_bundle_path, export_bundle
from ..nnet.checkpoint import CheckpointManager
from ..serve import FleetServer, ServeConfig, build_engine

_GATE_MODES = ("min", "max", "off")
_TASKS = ("train", "finetune")


class ContinualConfig:
    """Parsed ``continual_*`` keys (doc/continual.md):

    - ``continual_generations`` — deployed generations to run before a
      clean exit (>= 1).
    - ``continual_export_every`` — applied updates between generation
      attempts (required > 0; boundaries land on dispatch windows, so
      an attempt may run up to ``dispatch_period - 1`` updates late).
    - ``continual_task`` — the loop's training mode: ``train`` (fresh
      init, or resume ``model_in``) or ``finetune`` (remap-aware
      bootstrap from a snapshot/bundle ``model_in``).
    - ``continual_eval`` — eval block name the gate reads (default:
      the first eval block).
    - ``continual_metric`` — metric tag the gate compares (default:
      the first configured metric, e.g. ``error``).
    - ``continual_gate`` — ``min`` (smaller is better: error, logloss
      — the default), ``max`` (larger is better: rec@k), or ``off``
      (every attempt exports).
    - ``continual_gate_eps`` — slack: ``min`` passes while
      ``value <= best + eps`` (``max``: ``value >= best - eps``).
    - ``continual_swap_timeout_s`` — how long to wait for the watcher
      flip before recording ``swap_timeout`` (the bundle stays
      committed; the watcher flips it on a later poll).
    - ``continual_linger_s`` — serve-only window after the last
      generation before the clean drain (lets in-flight client load
      finish against the final generation).
    - ``continual_max_updates`` — safety bound on total applied
      updates (0 = unbounded); a gate that never passes ends the run
      here instead of looping forever.
    - ``continual_index_rows`` — when > 0, capture the first N valid
      training rows as a retrieval corpus and re-embed + rebuild the
      embedding index with every generation's weights, sealed into the
      generation bundle beside them (doc/retrieval.md) — the hot-swap
      flips model and index as one unit. 0 (default) exports
      index-less bundles.
    """

    def __init__(self, cfg: Sequence[Tuple[str, str]]):
        self.generations = 3
        self.export_every = 0
        self.task = "train"
        self.eval_name = ""
        self.metric = ""
        self.gate = "min"
        self.gate_eps = 0.0
        self.swap_timeout_s = 120.0
        self.linger_s = 0.0
        self.max_updates = 0
        self.index_rows = 0
        for name, val in cfg:
            if name == "continual_generations":
                self.generations = int(val)
            if name == "continual_export_every":
                self.export_every = int(val)
            if name == "continual_task":
                if val not in _TASKS:
                    raise ValueError(
                        "continual_task must be train|finetune, got %r"
                        % val)
                self.task = val
            if name == "continual_eval":
                self.eval_name = val
            if name == "continual_metric":
                self.metric = val
            if name == "continual_gate":
                if val not in _GATE_MODES:
                    raise ValueError(
                        "continual_gate must be min|max|off, got %r"
                        % val)
                self.gate = val
            if name == "continual_gate_eps":
                self.gate_eps = float(val)
            if name == "continual_swap_timeout_s":
                self.swap_timeout_s = float(val)
            if name == "continual_linger_s":
                self.linger_s = float(val)
            if name == "continual_max_updates":
                self.max_updates = int(val)
            if name == "continual_index_rows":
                self.index_rows = int(val)
        if self.generations < 1:
            raise ValueError("continual_generations must be >= 1")
        if self.export_every < 1:
            raise ValueError(
                "task=continual requires continual_export_every >= 1 "
                "(applied updates between generation attempts)")

    def passes(self, value: float, best: Optional[float]) -> bool:
        if self.gate == "off" or best is None:
            return True
        if self.gate == "min":
            return value <= best + self.gate_eps
        return value >= best - self.gate_eps

    def ratchet(self, value: float, best: Optional[float]) -> float:
        """The new best after a deploy: the BEST value ever deployed,
        not the last — with eps slack, comparing against the last
        value would let the metric drift one eps per generation
        without ever failing the gate."""
        if best is None or self.gate == "off":
            return value
        return min(best, value) if self.gate == "min" \
            else max(best, value)


class GenerationExporter:
    """Per-generation ``task = export`` without per-generation
    compiles: the first :meth:`export` builds and warms a bucket
    engine from the snapshot (the one compile window of the whole
    loop); later calls reload weights in place — the AOT executables
    take weights as *arguments*, so identical avals mean the sealed
    programs stay valid — and re-seal a fresh bundle. The engine and
    the training trainer never share device state: serving contracts
    (bucket mesh, frozen serve tree) stay isolated from the live
    update path."""

    def __init__(self, cfg: Sequence[Tuple[str, str]], monitor=None):
        self.cfg = list(cfg)
        self.sc = ServeConfig(self.cfg)
        self._mon = monitor
        self.engine = None
        self.compiled_programs = 0       # gen-1 warmup compiles
        self.index_metric = "dot"
        for name, val in self.cfg:
            if name == "index_metric":
                self.index_metric = val

    def export(self, snapshot: str, out: str,
               corpus: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Seal ``snapshot`` into a committed bundle at ``out``;
        returns the ``export`` record fields. With a ``corpus`` (raw
        host rows), re-embed it through THIS generation's weights and
        seal the rebuilt index into the same bundle — the search
        programs land in the shared registry under the same
        ``search_sig`` keys, so generation 1 pays their compiles once
        and every later rebuild re-seals the family with zero new
        compiles."""
        if self.engine is None:
            engine = build_engine(
                self.cfg, snapshot, buckets=self.sc.buckets,
                max_batch=self.sc.max_batch, node=self.sc.node,
                monitor=self._mon)
            # warm_run off: export needs the executables, not the
            # first-request latency of a live server. The engine is
            # kept only once warmup succeeds — a failed warmup must
            # not leave a half-initialized engine that every later
            # generation would reuse to seal unwarmed bundles
            self.compiled_programs = engine.warmup(warm_run=False)
            self.engine = engine
        else:
            self.engine.trainer.load_weights_inplace(snapshot)
        retrieval = None
        if corpus is not None and corpus.shape[0] > 0:
            retrieval = self._build_retrieval(corpus, out)
        return export_bundle(self.engine, out, node=self.sc.node,
                             monitor=self._mon, retrieval=retrieval)

    def _build_retrieval(self, corpus: np.ndarray, out: str):
        from ..retrieval import EmbeddingIndex, RetrievalEngine
        t0 = time.time()
        vecs = np.asarray(self.engine.run(corpus), np.float32)
        index = EmbeddingIndex.build(
            ids=np.arange(corpus.shape[0], dtype=np.int64),
            vectors=vecs.reshape(corpus.shape[0], -1),
            metric=self.index_metric, node=self.sc.node)
        spec = self.sc.search_buckets
        buckets = tuple(sorted({int(t) for t in spec.split(",")
                                if t.strip()})) \
            if spec and spec != "auto" else None
        r = RetrievalEngine(index, self.engine.trainer.programs,
                            k=self.sc.search_k or 10,
                            buckets=buckets, monitor=self._mon)
        budget = int(
            self.engine.trainer.serve_device_mem_budget * 1e6)
        r.warmup(warm_run=False, budget_bytes=budget)
        if self._mon is not None and self._mon.enabled:
            self._mon.emit(
                "index_build", out=out, rows=index.rows,
                dim=index.dim, metric=index.metric,
                node=self.sc.node, bytes=index.nbytes,
                wall_ms=(time.time() - t0) * 1e3)
        return r


class ContinualLoop:
    """The supervisor. Construct with an initialized trainer and live
    iterators (the CLI's ``_task_continual`` wires these from the
    ordinary config path), then :meth:`run`.

    ``should_stop`` is polled at every boundary (the CLI passes its
    SIGTERM/SIGINT flag); ``on_generation(record)`` fires after every
    generation attempt's record is emitted — the soak test
    (tests/test_continual.py) uses it to coordinate client traffic
    with the loop's lifecycle.
    """

    def __init__(self, cfg: Sequence[Tuple[str, str]], trainer,
                 itr_train, eval_iters: Sequence[Tuple[str, Any]],
                 model_dir: str,
                 path_for: Callable[[int], str],
                 monitor=None,
                 should_stop: Optional[Callable[[], bool]] = None,
                 on_generation: Optional[Callable[[Dict], None]] = None,
                 checkpoint_async: bool = True,
                 checkpoint_fsync: bool = True,
                 keep_snapshots: int = 0,
                 start_counter: int = 1,
                 dispatch_period: int = 8):
        self.cfg = list(cfg)
        self.cc = ContinualConfig(self.cfg)
        self.trainer = trainer
        self.itr_train = itr_train
        self.eval_iters = list(eval_iters)
        self.model_dir = model_dir
        self.path_for = path_for
        self._mon = monitor
        self._should_stop = should_stop or (lambda: False)
        self._on_generation = on_generation
        self._ckpt_kw = dict(async_=bool(checkpoint_async),
                             fsync=bool(checkpoint_fsync),
                             keep=int(keep_snapshots))
        self.next_counter = max(1, int(start_counter))
        self.dispatch_period = max(1, int(dispatch_period))
        self.fleet: Optional[FleetServer] = None
        self.exporter = GenerationExporter(self.cfg, monitor=monitor)
        self._round = 0
        # retrieval corpus capture (continual_index_rows): RAW rows,
        # not embeddings — every generation re-embeds them through its
        # own weights so the sealed index always matches the bundle
        self._corpus_parts: List[np.ndarray] = []
        self._corpus_got = 0
        # (model_id, router generation) -> last observed post-warmup
        # compile count of that engine: each engine contributes its
        # LAST observation exactly once to the loop total, however
        # many attempts observe it (a swap_timeout leaves the same
        # engine current across attempts)
        self._compile_counts: Dict[Tuple[str, int], int] = {}
        if self.cc.gate != "off" and not self.eval_iters:
            raise ValueError(
                "task=continual with continual_gate=%s needs an eval "
                "iterator block (or continual_gate = off)"
                % self.cc.gate)
        if self.cc.eval_name:
            names = [n for n, _ in self.eval_iters]
            if self.cc.eval_name not in names:
                raise ValueError(
                    "continual_eval %r names no eval block (have %s)"
                    % (self.cc.eval_name, names))

    # -- telemetry helpers -----------------------------------------------

    def _mon_on(self) -> bool:
        return self._mon is not None and self._mon.enabled

    def _emit(self, event: str, **fields) -> None:
        if self._mon_on():
            self._mon.emit(event, **fields)

    def _line(self, text: str) -> None:
        if self._mon is not None:
            self._mon.line(text)
        else:
            print(text)

    # -- training drive --------------------------------------------------

    def _stream(self):
        """Infinite batch stream with per-epoch round bookkeeping —
        the 'looping iterator' half of the loop. Epoch boundaries keep
        the round telemetry shape of ``task = train`` (round_start /
        round_end with examples/sec), and the monotone-round invariant
        of the step records holds across generations."""
        t = self.trainer
        while True:
            t.start_round(self._round)
            self._emit("round_start", round=self._round)
            n = 0
            for batch in self.itr_train:
                n += 1
                yield batch
            if n == 0:
                # an empty pass would spin this loop at full speed
                # (unbounded round records, next() never returning)
                raise ValueError(
                    "task=continual: the training iterator produced "
                    "no batches in a full pass — check the data "
                    "block (round_batch may be dropping the only "
                    "partial batch)")
            t.end_round()
            self._emit("round_end", round=self._round,
                       examples=t.last_round_examples,
                       wall_s=t.last_round_wall_s,
                       examples_per_sec=t.last_round_examples_per_sec)
            self._round += 1

    def _capture_corpus(self, stream):
        """Tee the first ``continual_index_rows`` valid training rows
        off the batch stream as the retrieval corpus (host copies —
        the iterator/transform may hand back recycled or device
        arrays)."""
        want = self.cc.index_rows
        for batch in stream:
            if self._corpus_got < want:
                n = min(batch.batch_size - batch.num_batch_padd,
                        want - self._corpus_got)
                if n > 0:
                    self._corpus_parts.append(np.array(
                        np.asarray(batch.data)[:n], np.float32))
                    self._corpus_got += n
            yield batch

    def _corpus_rows(self) -> Optional[np.ndarray]:
        if not self._corpus_parts:
            return None
        if len(self._corpus_parts) > 1:
            self._corpus_parts = [
                np.concatenate(self._corpus_parts, axis=0)]
        return self._corpus_parts[0]

    def _train_until(self, stream, target_updates: int) -> bool:
        """Advance the trainer to ``target_updates`` applied updates in
        dispatch windows; False when preempted mid-way. Boundaries
        land on window edges, so the attempt may overshoot by up to
        ``dispatch_period - 1`` updates — never undershoot."""
        t = self.trainer
        k = self.dispatch_period
        while t.update_counter < target_updates:
            if self._should_stop():
                return False
            window = [next(stream) for _ in range(k)]
            if k == 1:
                t.update(window[0])
            else:
                t.update_many(window)
        return True

    # -- the generation pipeline -----------------------------------------

    def _gate_value(self) -> Tuple[str, str, float]:
        """(eval block name, metric tag, value) of the gated metric
        for this attempt — one full eval pass (the same pass also
        lands in the stream as an ``eval`` record)."""
        if not self.eval_iters:
            return "", "", -1.0
        name, itr = self.eval_iters[0]
        if self.cc.eval_name:
            name, itr = next((n, it) for n, it in self.eval_iters
                             if n == self.cc.eval_name)
        line, vals = self.trainer.evaluate_metrics(itr, name)
        if not vals:
            if self.cc.gate == "off":
                return name, "", -1.0    # ungated, nothing to record
            raise ValueError(
                "task=continual: no metrics configured — the eval "
                "gate needs at least one metric[...] key "
                "(or continual_gate = off)")
        tag = self.cc.metric or next(iter(vals))
        if tag not in vals:
            raise ValueError(
                "continual_metric %r is not among the configured "
                "metrics %s" % (tag, sorted(vals)))
        self._line("[gen %d]%s" % (self.next_counter, line))
        return name, tag, vals[tag]

    def _note_engine_compiles(self) -> None:
        """Record the CURRENT engines' post-warmup compile counters —
        called before each swap retires an engine and again at close.
        Keyed by (model, router generation), so the same engine
        observed across attempts (a swap_timeout keeps it current)
        just updates its entry instead of double-counting."""
        if self.fleet is None:
            return
        for mid in self.fleet.router.ids():
            e = self.fleet.router.resolve(mid)
            snap = e.session.engine.counters_snapshot()
            self._compile_counts[(e.model_id, e.generation)] = \
                int(snap["compile_events"])

    def _serve_compile_total(self) -> int:
        return sum(self._compile_counts.values())

    def _start_fleet(self) -> None:
        cfg = list(self.cfg)
        if not any(k == "serve_models" for k, _ in cfg):
            # default the fleet onto the loop's own model_dir (the
            # cfg's model_in — the finetune source — must NOT become
            # a pinned serve source)
            cfg.append(("serve_models", "default=%s" % self.model_dir))
        self.fleet = FleetServer(cfg, monitor=self._mon)
        self.fleet.start()
        self._line(
            "continual: fleet listening http=%s binary=%s, models: %s"
            % (self.fleet.http_port, self.fleet.binary_port,
               ", ".join("%s@%04d" % (d["model"], d["counter"])
                         for d in self.fleet.describe())))

    def _await_swap(self, counter: int) -> Tuple[bool, float]:
        """Wait for the watcher flip to ``counter`` (kicked via
        ``notify_watchers``); (flipped, wall_s)."""
        mid = self.fleet.router.default_id
        t0 = time.monotonic()
        deadline = t0 + self.cc.swap_timeout_s
        while time.monotonic() < deadline:
            if self.fleet.router.resolve(mid).counter >= counter:
                return True, time.monotonic() - t0
            if self._should_stop():
                break
            time.sleep(0.02)
        return False, time.monotonic() - t0

    def _attempt(self, stream, best: Optional[float]
                 ) -> Tuple[str, Optional[float], Dict[str, Any]]:
        """One generation attempt after its training window:
        gate -> snapshot -> export -> flip. Returns (action, new best,
        record)."""
        t0 = time.perf_counter()
        counter = self.next_counter
        eval_name, tag, value = self._gate_value()
        rec: Dict[str, Any] = {
            "generation": counter, "counter": counter,
            "metric": tag, "value": value, "eval": eval_name,
            "train_updates": int(self.trainer.update_counter),
            "path": "",
        }
        if not self.cc.passes(value, best):
            # failed gate: no snapshot, no export — the fleet keeps
            # serving the old generation and training continues
            rec.update(action="gate_skipped", gate_best=best,
                       wall_ms=(time.perf_counter() - t0) * 1e3)
            self._line(
                "continual: generation %d gate FAILED (%s %g vs best "
                "%g + eps %g) — keeping generation %d serving"
                % (counter, tag, value, best, self.cc.gate_eps,
                   counter - 1))
            return "gate_skipped", best, rec
        ckpt = self._ckpt
        ckpt.save(counter)
        ckpt.wait()                      # export reads the file back
        snap = self.path_for(counter)
        out = default_bundle_path(snap)
        try:
            stats = self.exporter.export(snap, out,
                                         corpus=self._corpus_rows())
        except Exception as e:
            # failing to *upgrade* must never take down what works:
            # warn, keep serving, keep training (the committed
            # snapshot is still a valid swap target for the watcher,
            # at shadow-build compile cost instead of zero)
            if self._mon is not None:
                self._mon.warn_once(
                    "continual_export_failed:%04d" % counter,
                    "generation %d export failed (%s); the fleet "
                    "keeps serving the previous generation" %
                    (counter, e))
            rec.update(action="export_failed", gate_best=best,
                       wall_ms=(time.perf_counter() - t0) * 1e3)
            # advance past the committed-but-unexported snapshot: the
            # watcher may flip to it meanwhile (at shadow-build
            # compile cost), and a retry at the SAME counter would
            # make _await_swap see "already flipped" and record a
            # deployment whose bundle is not actually serving
            self.next_counter += 1
            return "export_failed", best, rec
        self._emit("export", **stats)
        rec["path"] = out
        if self.fleet is None:
            self._start_fleet()
            rec.update(boot=True, swapped=False, swap_wall_s=0.0)
        else:
            self._note_engine_compiles()  # last look at the retiring
            #                               engine's counters
            self.fleet.notify_watchers()
            flipped, swap_wall = self._await_swap(counter)
            rec.update(boot=False, swapped=flipped,
                       swap_wall_s=round(swap_wall, 3))
            if not flipped:
                rec.update(action="swap_timeout", gate_best=best,
                           wall_ms=(time.perf_counter() - t0) * 1e3)
                self._line(
                    "continual: generation %d exported but the swap "
                    "did not land within %gs (the watcher flips it "
                    "on a later poll)"
                    % (counter, self.cc.swap_timeout_s))
                # the artifact IS deployed-pending; counters advance
                # so the next generation does not collide
                self.next_counter += 1
                return "swap_timeout", self.cc.ratchet(value, best), rec
        # the swapped-in engine's compile counter right after the
        # flip: the zero-compile acceptance surface of the soak
        mid = self.fleet.router.default_id
        snap_c = self.fleet.router.resolve(mid) \
            .session.engine.counters_snapshot()
        rec.update(action="deployed", gate_best=best,
                   swap_compile_events=int(snap_c["compile_events"]),
                   export_programs=int(stats.get("programs", 0)),
                   wall_ms=(time.perf_counter() - t0) * 1e3)
        self._line(
            "continual: generation %d deployed (%s, %s) in %.1fs"
            % (counter,
               "%s %g" % (tag, value) if tag else "ungated",
               "fleet boot" if rec.get("boot") else
               "hot-swap %.2fs" % rec["swap_wall_s"],
               rec["wall_ms"] / 1e3))
        self.next_counter += 1
        # ratchet against the BEST deployed value, not the last —
        # consecutive comparison would drift one eps per generation
        return "deployed", self.cc.ratchet(value, best), rec

    # -- run ---------------------------------------------------------------

    def run(self) -> Dict[str, Any]:
        cc = self.cc
        t_start = time.time()
        updates0 = int(self.trainer.update_counter)
        deployed = skipped = failed = 0
        best: Optional[float] = None
        preempted = False
        stream = self._stream()
        if cc.index_rows > 0:
            stream = self._capture_corpus(stream)
        self._ckpt = CheckpointManager(
            self.trainer, self.path_for, model_dir=self.model_dir,
            monitor=self._mon, **self._ckpt_kw)
        try:
            while deployed < cc.generations:
                if cc.max_updates and (self.trainer.update_counter
                                       - updates0) >= cc.max_updates:
                    self._line(
                        "continual: continual_max_updates=%d reached "
                        "with %d/%d generations deployed — stopping"
                        % (cc.max_updates, deployed, cc.generations))
                    break
                target = self.trainer.update_counter + cc.export_every
                if not self._train_until(stream, target):
                    preempted = True
                    break
                if self._should_stop():
                    preempted = True
                    break
                action, best, rec = self._attempt(stream, best)
                self._emit("generation", **rec)
                if self._on_generation is not None:
                    self._on_generation(rec)
                if action == "deployed":
                    deployed += 1
                elif action == "gate_skipped":
                    skipped += 1
                else:
                    failed += 1
            if preempted:
                # emergency snapshot at the boundary we stopped on —
                # resume (continue = 1) picks it up; it never gated,
                # so it deliberately carries NO bundle (the watcher
                # only flips artifacts a generation attempt sealed)
                self._ckpt.save(self.next_counter, emergency=True)
            elif cc.linger_s > 0:
                # serve-only tail: in-flight client load finishes
                # against the final generation before the drain
                deadline = time.monotonic() + cc.linger_s
                while time.monotonic() < deadline \
                        and not self._should_stop():
                    time.sleep(0.05)
        finally:
            self._ckpt.close()
            self._note_engine_compiles()  # the final engines
            fleet_summary: Dict[str, Any] = {}
            if self.fleet is not None:
                fleet_summary = self.fleet.close()
        updates = int(self.trainer.update_counter) - updates0
        wall = time.time() - t_start
        req = fleet_summary.get("requests", {})
        swaps = int(fleet_summary.get("swaps", 0))
        summary = {
            "generations": deployed + skipped + failed,
            "deployed": deployed, "gate_skipped": skipped,
            "export_failed": failed, "updates": updates,
            "swaps": swaps, "wall_s": round(wall, 3),
            "serve_compile_events": self._serve_compile_total(),
            "requests": int(req.get("requests", 0)),
            "request_errors": int(req.get("error", 0)
                                  + req.get("closed", 0)),
            "preempted": preempted,
        }
        self._emit("continual", **summary)
        self._line(
            "continual: %d generation(s) deployed (%d gate-skipped, "
            "%d failed), %d updates, %d hot-swaps, %d serve requests "
            "(%d errors), %d post-warmup serve compiles, %ld sec"
            % (deployed, skipped, failed, updates, swaps,
               summary["requests"], summary["request_errors"],
               summary["serve_compile_events"], int(wall)))
        return summary

"""The program registry: ONE owner for every AOT executable.

Before this module, the (signature, bucket, mask-variant, dtype,
layout) key scheme and the lower+compile loop lived inside
``NetTrainer`` (``precompile`` / ``precompile_pred`` /
``_compile_programs``) and were *consumed* from four places — trainer
precompile, serve engine warmup, bench, and ``_call_pred`` — each
re-deriving dispatch signatures inline. The registry is the extraction
of that state into one object:

- **key scheme** — the module-level ``*_sig`` functions are the single
  definition of every dispatch signature. The trainer builds its
  precompile keys AND its per-dispatch lookup keys through them, so a
  scheme change cannot strand one call site on a stale scheme (the
  bug class PR 4's ``pred_sig`` unification closed for pred, now
  closed for update/update_many/run_steps too).
- **compile loop** — :meth:`ProgramRegistry.compile` is the one place
  ``(key, lower-thunk)`` pairs become executables: failure fallback,
  signature seeding and per-program compile telemetry cannot drift
  between the training and serving warmup paths.
- **serialization** — a compiled executable round-trips through
  ``jax.experimental.serialize_executable`` into the sealed artifact
  bundle (:mod:`cxxnet_tpu.artifact.bundle`), and
  :meth:`ProgramRegistry.install_serialized` loads them back at boot:
  a key satisfied from a bundle never re-lowers, and the per-key
  hit/rebuild accounting feeds the ``artifact_load`` telemetry record
  so the zero-compile cold-start claim is counted, not asserted.

Keys are tuples of primitives (strings, ints, bools, nested tuples):
``repr(key)`` is the bundle manifest's key encoding and
``ast.literal_eval`` recovers it exactly.
"""

from __future__ import annotations

import ast
import pickle
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple


class ResidencyBudgetError(RuntimeError):
    """Loading (or hot-swapping in) a model would exceed the explicit
    ``serve_device_mem_budget`` — the memory-honest alternative to
    discovering the overcommit as a device OOM mid-request. The load
    is rejected whole; whatever was serving keeps serving."""


class ArtifactLoadError(RuntimeError):
    """A sealed executable failed to deserialize where it had to
    succeed: at export (the round-trip check on the sealing runtime)
    or at boot on a runtime whose fingerprint MATCHED the bundle's.
    That is a fault in the bundle or the runtime, not a portability
    miss: it surfaces as this error instead of a warning and a silent
    recompile, so "zero-compile boot" cannot degrade unseen. (A
    mismatched fingerprint stays the documented path: one warning,
    every key re-lowers.)"""


class WeightResidency:
    """The device-resident serve weight tree and its accounting.

    One per model: the eval-transformed parameter tree every bucket
    executable of that model consumes as *arguments* (so N buckets
    share ONE device copy — the closure-constant alternative would
    bake the transformed weights into every executable). Built once at
    load/freeze by ``NetTrainer.freeze_serve_weights``:

    - ``bn_fold_eval`` weight folds applied once (no per-dispatch
      ``w * fold_scale`` pass),
    - int8/fp8 weights quantized once (no per-dispatch round/clip/cast
      of the weight tensor in the traced graph),
    - bf16 serve weights pre-cast (half the resident weight bytes),
    - per-channel dequant/shift epilogue vectors materialized as tree
      leaves instead of closure constants.

    ``tree_bytes`` is the footprint of the tree the executables see;
    ``total_bytes`` additionally counts the retained f32 masters,
    deduplicated by buffer identity (untransformed leaves alias the
    masters and are counted once) — the number budget enforcement and
    the ``weight_residency`` telemetry record report.
    """

    __slots__ = ("tree", "tree_bytes", "master_bytes", "total_bytes",
                 "quantize_ms", "layers", "dtype", "active")

    def __init__(self, tree, tree_bytes: int, master_bytes: int,
                 total_bytes: int, quantize_ms: float, layers: int,
                 dtype: str, active: bool):
        self.tree = tree
        self.tree_bytes = int(tree_bytes)
        self.master_bytes = int(master_bytes)
        self.total_bytes = int(total_bytes)
        self.quantize_ms = float(quantize_ms)
        self.layers = int(layers)
        self.dtype = dtype
        self.active = bool(active)

    def record(self) -> Dict[str, Any]:
        """The ``weight_residency`` telemetry record fields."""
        return {"bytes": self.total_bytes,
                "tree_bytes": self.tree_bytes,
                "master_bytes": self.master_bytes,
                "quantize_ms": self.quantize_ms,
                "layers": self.layers,
                "dtype": self.dtype,
                "active": self.active}

# -- the dispatch-signature scheme ----------------------------------------
#
# Every function returns the signature WITHOUT the leading kind tag;
# a full registry key is ("update",) + update_sig(...), etc. The
# trainer's per-dispatch lookups and its precompile key construction
# both call these — the single source the registry exists for.


def pred_sig(shape, dtype, mask_is_none: bool, n_extra: int,
             nodes_wanted) -> tuple:
    """The eval/pred forward signature: (batch shape, input dtype,
    mask variant, extra-input count, served node set)."""
    return (tuple(shape), str(dtype), bool(mask_is_none), int(n_extra),
            tuple(nodes_wanted))


def update_sig(data_shape, dtype, label_shape, mask_is_none: bool,
               n_extra: int, do_update: bool) -> tuple:
    """The per-batch train-step signature (static apply flag baked)."""
    return (tuple(data_shape), str(dtype), tuple(label_shape),
            bool(mask_is_none), int(n_extra), bool(do_update))


def update_many_sig(data_k_shape, dtype, labels_k_shape,
                    mask_is_none: bool, n_extra: int, window: int,
                    collect: bool) -> tuple:
    """The K-batch window signature (leading axis = scan step)."""
    return (tuple(data_k_shape), str(dtype), tuple(labels_k_shape),
            bool(mask_is_none), int(n_extra), int(window),
            bool(collect))


def run_steps_sig(data_shape, dtype, label_shape, mask_is_none: bool,
                  n_extra: int, n_steps: int) -> tuple:
    """The resident-batch scan signature (bench/test_skipread mode)."""
    return (tuple(data_shape), str(dtype), tuple(label_shape),
            bool(mask_is_none), int(n_extra), int(n_steps))


def search_sig(q_rows: int, dim: int, corpus_rows: int, k: int,
               metric: str, dtype) -> tuple:
    """The retrieval top-k signature: (query bucket, embedding dim,
    corpus rows, k, similarity metric, query dtype). The corpus matrix
    is a program *argument* (not a closure constant), so the executable
    serializes into the bundle and a generation's index swap reuses the
    same compiled program family."""
    return (int(q_rows), int(dim), int(corpus_rows), int(k),
            str(metric), str(dtype))


def parse_key(text: str) -> tuple:
    """Recover a registry key from its ``repr`` (the bundle manifest
    encoding). Keys are tuples of primitives, so ``literal_eval`` is
    exact; anything else raises ValueError."""
    key = ast.literal_eval(text)
    if not isinstance(key, tuple) or not key \
            or not isinstance(key[0], str):
        raise ValueError("not a registry key: %r" % text)
    return key


class ProgramRegistry:
    """Compiled-executable store keyed by (kind,) + signature.

    Owned by one trainer; the serve engine and bench consume it
    through the trainer. ``seen`` is the compile-event detection set
    (a dispatch whose key is not in ``seen`` paid a compile) — it
    deliberately survives :meth:`reset` the way the trainer's
    signature set always did, so a program rebuild does not erase the
    run's compile accounting.
    """

    def __init__(self):
        self.aot: Dict[tuple, Any] = {}
        self.seen: set = set()
        # sealed-artifact accounting (install_serialized)
        self.bundle_path = ""
        self.fingerprint_match = True
        self.art_hits = 0
        self.art_rebuilds = 0
        # keys whose executable was DESERIALIZED from a bundle: a
        # Loaded executable does not re-serialize faithfully (the
        # payload comes back without its compiled symbols), so
        # re-export must copy these keys' original blobs from the
        # source bundle instead of serializing the live object
        self.installed: set = set()
        # the device-resident serve weight tree (None until the owning
        # trainer freezes its serve weights); every pred executable of
        # this registry consumes it as arguments, so the tree is shared
        # across the whole bucket ladder
        self.residency: Optional[WeightResidency] = None

    # -- lookup ----------------------------------------------------------

    def get(self, key: tuple):
        """The executable for ``key``, or None (jit fallback)."""
        return self.aot.get(key)

    def __contains__(self, key: tuple) -> bool:
        return key in self.aot

    def __len__(self) -> int:
        return len(self.aot)

    def reset(self) -> None:
        """Orphan every executable (a program rebuild: new graph, new
        shardings). Bundle-installed programs go too — they were
        compiled against the replaced graph."""
        self.aot = {}
        self.bundle_path = ""
        self.fingerprint_match = True
        self.art_hits = 0
        self.art_rebuilds = 0
        self.installed = set()
        self.residency = None            # tree built for the old graph

    def install_weights(self, residency: WeightResidency,
                        budget_bytes: int = 0) -> WeightResidency:
        """Adopt a frozen serve weight tree, enforcing the explicit
        device-memory budget (0 = unlimited). Raises
        :class:`ResidencyBudgetError` — a typed rejection, not an OOM —
        when the model's resident bytes exceed the budget; nothing is
        installed in that case."""
        if budget_bytes and residency.total_bytes > budget_bytes:
            raise ResidencyBudgetError(
                "model weight tree needs %d resident bytes but "
                "serve_device_mem_budget allows %d"
                % (residency.total_bytes, budget_bytes))
        self.residency = residency
        return residency

    # -- the one compile loop --------------------------------------------

    def compile(self, programs: Sequence[Tuple[tuple, Callable]],
                warn_code: str, monitor=None) -> int:
        """AOT-compile ``(key, lower-thunk)`` pairs, skipping keys
        already present (including keys a bundle install satisfied —
        that skip IS the near-zero cold start). On the CPU backend a
        failed compile warns once and leaves that key on the jit
        fallback path. On an accelerator it propagates: there a
        refusal is the compiler saying the program does not fit
        (VMEM, tiling, HBM), and a run that asked for precompile must
        not turn that into a slower run that still exits 0.
        Per-program telemetry rides on ``monitor`` when one is
        attached. Returns the number of programs newly compiled."""
        import warnings

        import jax
        compiled = 0
        for key, thunk in programs:
            if key in self.aot:
                continue
            t0 = time.perf_counter()
            try:
                with warnings.catch_warnings():
                    # donated pred buffers that XLA cannot alias into
                    # the (differently shaped) outputs warn per
                    # compile; donation is best-effort by design
                    warnings.filterwarnings(
                        "ignore", message=".*[Dd]onat")
                    self.aot[key] = thunk().compile()
            except Exception as e:
                if jax.default_backend() != "cpu":
                    raise
                from ..monitor import warn_once
                warn_once(warn_code,
                          "precompile of %r failed (falling back to "
                          "jit): %s" % (key[0], e))
                continue
            compiled += 1
            # seed the signature set: the run's first dispatch of this
            # signature is NOT a compile — it happened here, and the
            # stream records it with its own wall time
            self.seen.add(key)
            if monitor is not None and monitor.enabled:
                monitor.emit("compile", kind="precompile",
                             wall_ms=(time.perf_counter() - t0) * 1e3,
                             signature=repr(key))
        return compiled

    # -- sealed-artifact serialization -----------------------------------

    def serialize_programs(self, devices) -> List[Tuple[tuple, bytes]]:
        """Serialize every freshly COMPILED executable into portable
        blobs (``jax.experimental.serialize_executable`` payload +
        arg pytrees, pickled together), round-trip-checked: each blob
        is deserialized once right here onto ``devices`` — the devices
        of the mesh the programs were compiled for, which is where a
        boot will load them — because a blob that only fails at boot
        would degrade zero-compile to rebuild-everything (observed
        with re-serialized *Loaded* executables: the payload comes
        back without its compiled symbols). A blob that does not load
        back raises :class:`ArtifactLoadError`: an export that cannot
        seal its programs has not made the artifact it was asked for.
        Keys in ``installed`` are excluded — the exporter copies their
        original bundle blobs byte-for-byte instead."""
        from jax.experimental import serialize_executable as se
        out: List[Tuple[tuple, bytes]] = []
        for key in sorted(self.aot, key=repr):
            if key in self.installed:
                continue
            payload, in_tree, out_tree = se.serialize(self.aot[key])
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
            try:
                _load_executable(blob, devices)
            except Exception as e:
                raise ArtifactLoadError(
                    "executable %r does not load back from its own "
                    "serialization: %s" % (key[0], e)) from e
            out.append((key, blob))
        return out

    def install_serialized(self, programs: Sequence[Tuple[tuple, bytes]],
                           path: str, fingerprint_ok: bool, devices,
                           monitor=None) -> Dict[str, Any]:
        """Deserialize bundle executables into the store, onto
        ``devices`` (the owning trainer's mesh devices — jax would
        otherwise load every program for ALL devices of the backend,
        and a one-device serve program then refuses its arguments on
        any host with more than one).

        With a matching runtime fingerprint every program becomes a
        resident executable (a *hit*: that key will never lower or
        compile this boot), and a blob that fails to load raises
        :class:`ArtifactLoadError`. A mismatched fingerprint installs
        NOTHING — one warning, and every key re-lowers on demand (a
        *rebuild*). Returns the ``artifact_load`` record fields;
        honesty rule: ``hits + rebuilds == len(programs)``, always.
        """
        t0 = time.perf_counter()
        hits = rebuilds = 0
        self.bundle_path = path
        self.fingerprint_match = bool(fingerprint_ok)
        if not fingerprint_ok:
            rebuilds = len(programs)
            _warn(monitor, "artifact_fingerprint_mismatch",
                  "artifact bundle %s was sealed on a different "
                  "platform/jaxlib/topology; its %d executable(s) are "
                  "unusable here — every program re-lowers and "
                  "recompiles (results are unaffected)"
                  % (path, len(programs)))
        else:
            for key, blob in programs:
                try:
                    exe = _load_executable(blob, devices)
                except Exception as e:
                    raise ArtifactLoadError(
                        "bundle %s matches this runtime's fingerprint "
                        "but its executable %r failed to load: %s"
                        % (path, key[0], e)) from e
                self.aot[key] = exe
                # a bundle-installed program is not a compile event:
                # the first dispatch of this signature runs a sealed
                # executable
                self.seen.add(key)
                self.installed.add(key)
                hits += 1
        self.art_hits, self.art_rebuilds = hits, rebuilds
        return {"path": path,
                "fingerprint_match": bool(fingerprint_ok),
                "hits": hits, "rebuilds": rebuilds,
                "wall_ms": (time.perf_counter() - t0) * 1e3}


def _load_executable(blob: bytes, devices: Sequence):
    """One sealed blob -> a loaded executable bound to ``devices``."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = pickle.loads(blob)
    return se.deserialize_and_load(payload, in_tree, out_tree,
                                   execution_devices=devices)


def _warn(monitor, code: str, message: str) -> None:
    if monitor is not None:
        monitor.warn_once(code, message)
    else:
        from ..monitor import warn_once
        warn_once(code, message)

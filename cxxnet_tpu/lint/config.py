"""Repo-specific knowledge the checks key off.

These maps are the single place where "which functions may build XLA
programs" and "which functions are the serving/training hot path" are
declared. A new AOT call site or hot-path root is a one-line diff here
— reviewed as such — instead of an invisible new compile hazard.
Paths are matched as ``/``-separated suffixes of the scanned file
path, so the maps work from any checkout root.
"""

# -- CXL001: the program-construction registry ----------------------------
# The ONLY code allowed to call jax.jit / pjit / .lower(...): the
# trainer's single-sourced program builders (PR 4 collapsed four
# duplicated AOT sites into these) and the Pallas kernel module's
# module-level decorators. Everything else must route through
# NetTrainer.precompile / precompile_pred / the engine, which share the
# pred_sig key scheme — a fifth duplicate program-build site fails the
# gate instead of shipping a silent recompile hazard.
PROGRAM_BUILDERS = {
    "cxxnet_tpu/nnet/trainer.py": (
        "NetTrainer._build_steps",
        "NetTrainer.precompile",
        "NetTrainer.precompile_pred",
        "NetTrainer._compile_programs",
        # the one-time serve weight-residency upload: folds/quantizes/
        # casts the eval weight tree on device at freeze
        # (doc/serving.md "Device memory accounting") — never
        # dispatched per request
        "NetTrainer._build_resident_prep",
    ),
    # the program registry (doc/artifacts.md): the one compile loop
    # every (key, lower-thunk) pair goes through, and the sealed-
    # artifact deserializer that installs bundle executables in place
    # of compilation
    "cxxnet_tpu/artifact/registry.py": (
        "ProgramRegistry.compile",
        "ProgramRegistry.install_serialized",
    ),
    "cxxnet_tpu/layers/pallas_kernels.py": ("<module>",),
    # a net's parameters made on the mesh in one program, once at init
    # (an expert axis's tensors never whole on one chip) — never
    # dispatched per step
    "cxxnet_tpu/nnet/net.py": ("FuncNet.init_on",),
    # the calibration amax program (one jitted forward computing every
    # quantizable layer's activation range per batch) — offline
    # task=quantize path, never dispatched while serving
    "cxxnet_tpu/nnet/quantize.py": (
        "Calibrator._build_amax_program",
    ),
    # the step_breakdown measurement programs (doc/distributed.md
    # "Overlapped gradient sync"): a grad-only program and a group-
    # granular reduce-only program, built once per measurement call by
    # the scaling sweep — never on the training path
    "cxxnet_tpu/parallel/gradsync.py": (
        "measure_step_breakdown",
    ),
    # the retrieval top-k program family (doc/retrieval.md): one lower
    # site per query bucket, keyed by search_sig in the SAME registry
    # as the predict programs — sealed into bundles and installed at
    # boot, so a served /v1/search never reaches this builder
    "cxxnet_tpu/retrieval/engine.py": (
        "RetrievalEngine._lower_search",
    ),
    # the relayout program behind input_layout = rowmajor: what
    # jax.device_put(x, Format) builds internally, rebuilt here with a
    # per-process salt so it is never READ from the persistent cache
    # (jax 0.9.0 loses output layouts there) — one tiny identity per
    # pinned format, never a model program
    "cxxnet_tpu/utils/compile_cache.py": (
        "put_with_layout",
    ),
}

# -- CXL003: hot-path roots -----------------------------------------------
# Functions on the steady-state throughput path: the per-dispatch train
# loop and the serve stage/dispatch pair. Anything reachable from these
# (same-module call graph) that forces a host sync — np.asarray /
# device_get / block_until_ready / .item() / .tolist() — is either a
# measured, justified sync (inline suppression with the reason) or a
# regression.
HOT_PATH_ROOTS = {
    "cxxnet_tpu/nnet/trainer.py": (
        "NetTrainer.update",
        "NetTrainer.update_many",
        "NetTrainer.run_steps",
    ),
    "cxxnet_tpu/serve/engine.py": (
        "InferenceEngine.stage",
        "InferenceEngine.dispatch",
    ),
    "cxxnet_tpu/serve/batcher.py": (
        "DynamicBatcher._collect_loop",
        "DynamicBatcher._dispatch_loop",
    ),
    # the fleet balancer's per-request path (doc/serving.md
    # "Horizontal fleet"): every fleet request funnels through
    # handle -> _route -> _forward, so a host sync added there taxes
    # the whole fleet's latency, not one engine's. The multiplexed
    # data path (doc/serving.md "Fleet data path") adds the channel
    # writer/reader loops (every forward's frames and replies cross
    # them) and the coalescer flush + merged-forward chain — all
    # steady-state per-request code. The same registrations anchor
    # the CXL002 side: the loops are threading.Thread targets, so the
    # lock-discipline closure already covers the state they share
    # with submitting threads.
    "cxxnet_tpu/fleet/balancer.py": (
        "FleetBalancer.handle",
        "FleetBalancer._route",
        "FleetBalancer._forward",
        "FleetBalancer._forward_merged",
        "ReplicaChannel._writer_loop",
        "ReplicaChannel._reader_loop",
        "_Coalescer._flush_loop",
    ),
    # the replica-side v2 frame loop: request decode (zero-copy
    # frombuffer view), async admission, and the out-of-order reply
    # writer — the per-request path of every pipelined fleet forward
    "cxxnet_tpu/serve/frontend.py": (
        "_BinaryHandler.handle",
        "_V2ConnState.complete",
        "FleetServer.handle_async",
    ),
}

# -- CXL004: telemetry schema ---------------------------------------------
# The module holding the REQUIRED validator map, matched by suffix.
SCHEMA_MODULE = "monitor/schema.py"

# -- CXL005: config-key drift ---------------------------------------------
# The stale-doc direction (documented key with no consumer) only runs
# when the scan set includes the primary config consumer below — a
# partial scan (one file + the real doc/ tree) must not call every
# documented key stale. The undocumented direction runs per-file
# regardless.
CONFIG_CONSUMER_ROOT = "cxxnet_tpu/main.py"

# Keys consumed through a pattern the literal scanner cannot see (regex
# or computed-prefix matching). Each entry names its real consumer so
# the allowlist is auditable.
CONFIG_KEYS_PATTERN_CONSUMED = {
    "metric": "nnet/trainer.py _RE_METRIC (metric / metric[field,node])",
    "label_vec": "io/data.py label_vec[a,b) range binding",
    "extra_data_shape": "io/data.py extra_data_shape[i] indexed keys",
    "layer": "graph.py netconfig layer[from->to] section grammar",
}

"""Parallelism: device mesh, shardings, multi-host init.

This module replaces the reference's entire parallel stack — per-GPU
worker threads + semaphores (neural_net-inl.hpp:325-658), the layerwise
async parameter server (mshadow-ps, async_updater-inl.hpp), and the
rabit/ps-lite distributed backends (SURVEY.md §2.7) — with the TPU-native
equivalent: ONE SPMD XLA program over a ``jax.sharding.Mesh``.

Capability mapping (reference -> here):
- multi-GPU batch split + local PS gradient sum  -> batch sharded on the
  'data' mesh axis; XLA inserts the all-reduce over ICI during autodiff
- layerwise async push/pull overlap (priority = -layer_index) -> XLA's
  latency-hiding scheduler overlaps those same collectives with compute
- fullc_gather (ship activations, recompute full grad) -> sharded matmul:
  fullc weights sharded on the 'model' axis, XLA all-gathers activations
- update_on_server (optimizer state on server) -> optimizer state sharded
  across 'data' (ZeRO-style), toggled per config
- rabit eval-metric allreduce -> process-group sum over DCN
- multi-node launch (dmlc tracker/MPI) -> jax.distributed.initialize
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .topology import (HostTopology, clear_dryrun_topology,
                       current_topology, set_dryrun_topology)


def force_virtual_cpu(n_devices: int) -> None:
    """Run this process on ``n_devices`` virtual CPU devices — the
    ps-lite local-mode analogue (SURVEY.md §4.5) used by tests and the
    multichip dry-run to exercise sharding without TPU chips.

    Must be called before the jax backend initializes. Set through
    jax.config so it wins over a conflicting ``JAX_PLATFORMS`` or
    ``--xla_force_host_platform_device_count`` in the environment.
    """
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def make_mesh(n_data: Optional[int] = None, n_model: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a topology-aware (data, model) mesh.

    Default: all devices on the data axis — the TPU analogue of
    ``dev = gpu:0-3`` (nnet_impl-inl.hpp:374-391). Topology rule
    (doc/distributed.md): the **data axis spans hosts x local
    devices** and the **model axis stays within one host** — model
    collectives run every layer and belong on ICI, never on DCN. With
    ``jax.devices()`` returning devices in process-major order (and
    the dryrun partitioning that order into equal virtual-host
    blocks), a model group of ``n_model`` consecutive devices sits
    within one host exactly when ``n_model`` divides the per-host
    local device count — enforced here, so a config cannot silently
    stripe its every-layer collectives across the slow interconnect.
    """
    if devices is None:
        devices = jax.devices()
    total = len(devices)
    if n_data is None:
        n_data = total // n_model
    use = n_data * n_model
    if use > total:
        raise ValueError("mesh wants %d devices, have %d" % (use, total))
    topo = current_topology()
    if n_model > 1 and topo.num_hosts > 1 \
            and topo.local_device_count % n_model != 0:
        raise ValueError(
            "model axis %d does not divide the %d local devices per "
            "host (%d hosts): the model axis must stay within a host "
            "(ICI before DCN) — shrink n_model or repartition"
            % (n_model, topo.local_device_count, topo.num_hosts))
    arr = np.asarray(devices[:use]).reshape(n_data, n_model)
    return Mesh(arr, ("data", "model"))


def default_data_axis(batch_size: int,
                      n_devices: Optional[int] = None) -> int:
    """The trainer's default mesh rule: the largest data-axis size
    that divides the global batch (the reference similarly drops
    devices that would get an empty slice, nnet_impl-inl.hpp:378-387).
    ``NetTrainer._post_init`` is its caller."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return max(d for d in range(1, n_devices + 1)
               if batch_size % d == 0)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch-dim sharding for input arrays."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_sharding(mesh: Mesh, params, model_parallel_min: int = 0,
                   leading=None):
    """Sharding pytree for parameters.

    Weights stay replicated except 2-D fullc weights whose output dim is
    divisible by the 'model' axis and exceeds ``model_parallel_min`` —
    those shard on the output dim (the fullc_gather analogue: XLA
    all-gathers the activations and each shard computes its slice) — and
    the tensors ``leading`` names (``{layer key: {tag: mesh axis}}``,
    ``FuncNet.leading_axes``: an expert layer's experts on its expert
    axis), which shard on their leading axis. Their gradients stay where
    they are (a chip owns its experts); a replicated weight's gradient is
    the all-reduce of the chips' parts.
    """
    msize = mesh.shape["model"]
    leading = leading or {}

    def spec(path, leaf):
        axis = leading and len(path) == 2 and leading.get(
            path[0].key, {}).get(path[1].key)
        if axis:
            return NamedSharding(mesh, P(axis))
        if (msize > 1 and model_parallel_min > 0 and hasattr(leaf, "ndim")
                and leaf.ndim == 2
                and leaf.shape[-1] % msize == 0
                and leaf.shape[-1] >= model_parallel_min):
            return NamedSharding(mesh, P(None, "model"))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec, params)


def opt_state_sharding(leaf_shape, param_spec: P, mesh: Mesh,
                       shard_data: bool) -> NamedSharding:
    """Sharding for one optimizer-state leaf (momentum / adam moments).

    Default: mirror its weight's sharding. With ``shard_data`` (the
    ``update_on_server=1`` capability analogue — optimizer state leaves
    the replicated pool, like it lived on the server in the reference),
    leaves whose first dim divides the 'data' axis are ZeRO-1 sharded
    across it; XLA then keeps the optimizer update sharded and
    all-gathers only the weights.
    """
    if shard_data:
        dsize = mesh.shape["data"]
        if (len(leaf_shape) >= 1 and leaf_shape[0] % dsize == 0
                and leaf_shape[0] >= dsize
                and (len(param_spec) == 0 or param_spec[0] is None)):
            # compose with the weight's own axes (a model-sharded fullc
            # weight's momentum shards on BOTH 'data' and 'model')
            rest = tuple(param_spec)[1:] if len(param_spec) > 1 else ()
            spec = ("data",) + rest + (None,) * (
                len(leaf_shape) - 1 - len(rest))
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P(*param_spec))


def _process_group_up() -> bool:
    """True once ``jax.distributed`` has a client — set up by
    :func:`init_distributed` or by a launcher. Reads jax's distributed
    state only, never the backend: without a process group jax is
    single-process by construction (process_count 1, process_index 0),
    so :func:`rank` / :func:`world_size` / :func:`is_root` need no
    device then. That keeps ``task = fleet`` and ``task =
    fleet_balancer`` parents off the chip their replica children need
    (a chip belongs to one process at a time)."""
    from jax._src import distributed
    return distributed.global_state.client is not None


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bring-up over DCN (the rabit::Init / ps-lite tracker
    equivalent, cxxnet_main.cpp:74-91). No-op when single-process or when
    env vars are absent.

    Must run before ANY backend-initializing jax API — so this function
    deliberately reads only the environment (never jax.process_count(),
    which would initialize the backend single-process and lock out
    jax.distributed.initialize).
    """
    if _process_group_up():
        return      # already up (an earlier call, or a launcher's own)
    coordinator = coordinator or os.environ.get("CXXNET_COORDINATOR")
    if not coordinator:
        return
    if num_processes is None:
        env = os.environ.get("CXXNET_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("CXXNET_PROCESS_ID")
        process_id = int(env) if env else None
    # num_processes/process_id may stay None: managed runtimes (TPU
    # pods) let jax.distributed autodetect them — the "env-autodetected
    # where the runtime provides them" half of the dist_* launch
    # contract (doc/distributed.md)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=None if num_processes is None
        else int(num_processes),
        process_id=None if process_id is None else int(process_id))


# bounded retries for the host-side process-group collectives (the
# eval-metric allreduce): a transient DCN hiccup re-enters the
# collective instead of failing the round. stream_retry-style opt-out:
# set 0 to fail fast (main.py wires `dist_allreduce_retry`, default 2)
_allreduce_retry = 2
_ALLREDUCE_BACKOFF_MS = 50.0


def set_allreduce_retry(n: int) -> None:
    global _allreduce_retry
    _allreduce_retry = max(0, int(n))


def allreduce_host_sum(x: np.ndarray) -> np.ndarray:
    """Sum a small host array across processes (metric reduction — the
    rabit Allreduce in metric.h:60-68) via a process allgather.

    Transient failures (collective timeout, coordination-service
    blips — the DCN failure modes that surface as RuntimeError/OSError
    on every participant) retry up to ``set_allreduce_retry`` times
    with exponential backoff, warn once, and emit a ``dist_retry``
    record on recovery. Retrying a collective is only sound when all
    ranks retry: these transport failures DO surface fleet-wide, and a
    lone rank whose peers somehow advanced times out again, exhausts
    its budget, and raises — the metric layer then falls back to
    process-local values as before (utils/metric.py). Exhaustion
    re-raises; this is a bounded retry, not a swallow."""
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils
    attempts = 0
    while True:
        try:
            out = np.asarray(
                multihost_utils.process_allgather(x).sum(axis=0))
        except (RuntimeError, OSError) as e:
            attempts += 1
            if attempts > _allreduce_retry:
                raise
            from ..monitor import warn_once
            warn_once("allreduce_retry",
                      "process-group allreduce failed transiently "
                      "(%s: %s); retrying up to %d time(s)"
                      % (type(e).__name__, e, _allreduce_retry))
            time.sleep(_ALLREDUCE_BACKOFF_MS * (2 ** (attempts - 1))
                       / 1e3)
            continue
        if attempts:
            from ..monitor import get_global
            mon = get_global()
            if mon is not None and mon.enabled:
                mon.emit("dist_retry", what="allreduce_host_sum",
                         attempts=attempts, recovered=True)
        return out


def synced_batches(it, window: int = 1):
    """Iterate a per-rank data iterator in lockstep across processes.

    Under multi-process dp, rank-strided sharding can leave ranks with
    local row counts differing by one; when that crosses a local-batch
    multiple, ranks would emit different batch counts and the SPMD
    collectives inside the train/eval step would deadlock. Each rank
    buffers up to ``window`` batches, allgathers its available count
    (ONE host collective per window — pass the train loop's
    dispatch_period to amortize), and the loop yields the cross-rank
    minimum, stopping when any rank comes up short; a richer rank drops
    at most its last ``window`` tail batches per round. Single-process:
    passthrough with zero overhead.
    """
    if jax.process_count() == 1:
        yield from it
        return
    from jax.experimental import multihost_utils
    src = iter(it)
    while True:
        buf = []
        while len(buf) < window:
            try:
                buf.append(next(src))
            except StopIteration:
                break
        counts = np.asarray(multihost_utils.process_allgather(
            np.asarray([len(buf)], np.int32)))
        nmin = int(counts.min())
        for b in buf[:nmin]:
            yield b
        if nmin < window:
            return


def rank() -> int:
    return jax.process_index() if _process_group_up() else 0


def world_size() -> int:
    return jax.process_count() if _process_group_up() else 1


def is_root() -> bool:
    """Only rank 0 saves/logs (cxxnet_main.cpp:424-435,501-503)."""
    return rank() == 0

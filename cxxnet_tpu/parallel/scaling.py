"""The dryrun scaling sweep: a test harness for the multi-host input
path (tests/test_multihost_dryrun.py, tests/test_gradsync.py). It runs
on virtual CPU devices and is not a measurement of any chip.

Runs the SAME multi-host input path the CLI trains through
(:func:`cxxnet_tpu.parallel.topology.build_dryrun_feed` — one
batch-block-sharded reader chain per virtual host, assembled in
host-rank order) at a series of faked world sizes, and measures what a
single-process dryrun can honestly measure:

- **throughput** (examples/sec from the trainer's own telemetry
  counters — the same numbers a monitored training run reports),
- **per-host data-wait** (wall time the assembler spent blocked on
  each host's chain) and the data-wait share of step wall time,
- **per-host input-shard accounting** — rows consumed per host, which
  must sum exactly to the dataset's real rows (the exactly-once
  invariant, counted per sweep point),
- **loss parity** — the final loss must be bit-identical across every
  world size (the assembled global batch IS the single-host batch),
- **zero recompiles** after the accounted precompile window.

What it can NOT measure — and says so in the record: cross-host
collective time. A dryrun runs one process with zero DCN traffic, so
the on-chip scaling curve is marked pending a device window.
"""

from __future__ import annotations

import os
import tempfile
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import current_topology, set_dryrun_topology, \
    clear_dryrun_topology
from .topology import build_dryrun_feed

_SCALE_NET = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 64
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = %(classes)d
layer[3->3] = softmax
netconfig = end
input_shape = 1,1,%(features)d
batch_size = %(batch)d
eta = 0.1
seed = 7
eval_train = 0
silent = 1
"""


def _write_csv(path: str, rows: int, features: int,
               classes: int) -> None:
    rng = np.random.RandomState(11)
    X = rng.rand(rows, features).astype(np.float32)
    y = (X @ rng.randn(features, classes)).argmax(1)
    with open(path, "w") as f:
        for i in range(rows):
            f.write(",".join([str(int(y[i]))]
                             + ["%g" % v for v in X[i]]) + "\n")


def dryrun_scaling_sweep(host_counts: Sequence[int], rows: int = 512,
                         features: int = 64, classes: int = 8,
                         global_batch: int = 64, rounds: int = 2,
                         monitor=None,
                         workdir: Optional[str] = None,
                         grad_sync: str = "fused",
                         grad_sync_bucket_mb: float = 0.0,
                         optim_shard: int = 0
                         ) -> Dict[str, Any]:
    """Measure the dryrun input-sharding path at each world size in
    ``host_counts`` (each must divide the device count and the global
    batch). Emits one schema-validated ``scaling_point`` record per
    world size on ``monitor`` (when enabled) and returns the
    MULTICHIP-style record dict. ``grad_sync`` / ``optim_shard`` run
    the sweep trainer under the overlapped-reduction and ZeRO-1 knobs
    (doc/distributed.md, doc/updater.md); each point then carries a
    ``step_breakdown`` sub-record (also emitted on ``monitor``) with
    the backprop/reduce/step walls, the hidden-reduce overlap ratio,
    and the per-host optimizer-state bytes."""
    from . import gradsync
    from ..monitor import MemorySink, Monitor
    from ..monitor.schema import validate_records
    from ..nnet.trainer import NetTrainer
    from ..utils.config import parse_config
    import jax
    import time as _time

    own_dir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="cxxnet_scaling_")
    csv = os.path.join(workdir, "scaling.csv")
    _write_csv(csv, rows, features, classes)
    conf = _SCALE_NET % {"features": features, "classes": classes,
                         "batch": global_batch}
    block_cfg = [("iter", "csv"), ("filename", csv),
                 ("input_shape", "1,1,%d" % features),
                 ("label_width", "1"), ("silent", "1")]
    batch_cfg = [("batch_size", str(global_batch)),
                 ("input_shape", "1,1,%d" % features),
                 ("label_width", "1")]

    points: List[Dict[str, Any]] = []
    losses: List[float] = []
    for hosts in host_counts:
        hosts = int(hosts)
        feed = None
        try:
            if hosts > 1:
                set_dryrun_topology(hosts)
            topo = current_topology()
            feed = build_dryrun_feed(block_cfg, batch_cfg, hosts,
                                     global_batch)
            feed.init()
            sink = MemorySink()
            t = NetTrainer(parse_config(conf) + [
                ("grad_sync", grad_sync),
                ("grad_sync_bucket_mb", str(grad_sync_bucket_mb)),
                ("optim_shard", str(int(optim_shard)))])
            t.init_model()
            t.set_monitor(Monitor(sink))
            t.precompile(window=1)
            last_batch = None
            for r in range(rounds):
                t.start_round(r)
                t_wait = _time.perf_counter()
                for batch in feed:
                    t.note_data_wait(_time.perf_counter() - t_wait)
                    t.update(batch)
                    last_batch = batch
                    t_wait = _time.perf_counter()
                t.end_round()
            validate_records(sink.records)
            steps = [r for r in sink.records if r["event"] == "step"]
            wall = sum(r["wall_ms"] for r in steps)
            wait = sum(r["data_wait_ms"] for r in steps)
            share = wait / (wall + wait) if wall + wait > 0 else 0.0
            acc = feed.accounting()
            point = {
                "hosts": hosts,
                "local_devices": topo.local_device_count,
                "global_batch": global_batch,
                "examples_per_sec": round(
                    t.last_round_examples_per_sec, 1),
                "data_wait_share": round(min(1.0, share), 4),
                "rows_per_host": [n // rounds
                                  for n in acc["rows_per_host"]],
                "wait_ms_per_host": [round(w / rounds, 3)
                                     for w in acc["wait_ms_per_host"]],
                "zero_recompiles": not any(r["compile"]
                                           for r in steps),
            }
            losses.append(float(t.last_loss))
            # breakdown AFTER the loss capture: the measurement drives
            # real update dispatches (documented in gradsync), so the
            # parity loss above must be read first
            bd = gradsync.measure_step_breakdown(t, last_batch)
            point["step_breakdown"] = bd
            points.append(point)
            if monitor is not None and monitor.enabled:
                monitor.emit("scaling_point", **point)
                monitor.emit("step_breakdown", **bd)
        finally:
            if feed is not None:
                feed.close()
            clear_dryrun_topology()

    record = {
        "metric": "dryrun examples/sec vs faked world size "
                  "(single-process multi-host input sharding)",
        "dryrun": True,
        "dataset_rows": rows,
        "rounds": rounds,
        "points": points,
        # bit-identity across world sizes: the assembled global batch
        # is the single-host batch, so the final loss must agree to
        # the last bit at every point
        "loss_parity": bool(losses) and all(
            x == losses[0] for x in losses),
        "final_loss": losses[0] if losses else None,
        # exactly-once, counted: per-host consumed rows sum to the
        # dataset at every world size (every record is a real row;
        # tail padding is synthetic and never counted)
        "exactly_once": all(sum(p["rows_per_host"]) == rows
                            for p in points),
        "on_chip": "pending a device window: a dryrun runs one "
                   "process with zero DCN traffic, so this curve "
                   "measures shard math and per-host input cost, "
                   "never interconnect (doc/distributed.md)",
        "grad_sync": grad_sync,
        "grad_sync_bucket_mb": float(grad_sync_bucket_mb),
        "optim_shard": int(optim_shard),
        "breakdown_caveat":
            "step_breakdown walls come from the same dryrun: its "
            "collectives are shared-memory copies, not DCN, so "
            "overlap_ratio bounds the schedule shape only — device "
            "timings pending a window (doc/distributed.md "
            "'Overlapped gradient sync'). Byte columns are exact.",
    }
    if own_dir:
        try:
            os.remove(csv)
            os.rmdir(workdir)
        except OSError:
            pass  # cxxlint: disable=CXL006 -- best-effort tempdir cleanup after the sweep
    return record

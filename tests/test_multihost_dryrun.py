"""Multi-host SPMD training, exercised through the single-process
dryrun (doc/distributed.md) — the live tier-1 coverage for the code
paths the two-process spawn tests (tests/test_distributed.py) can only
cover when the jaxlib CPU backend supports cross-process collectives
(in this container they skip):

- topology-aware mesh build (model axis within a host, never across),
- per-host batch assembly (batch-block shard map -> rank-order concat
  is BIT-IDENTICAL to the single-host batch),
- shard-map re-derivation at a world-size change (the elastic
  handoff), and the full CLI path: ``dist_dryrun_hosts = H`` trains
  with zero recompiles after precompile and a loss trajectory
  bit-identical to the single-host run on the same global batch,
- SIGTERM mid-round -> emergency snapshot -> resume at a smaller
  world size -> no-dup/no-loss data order -> sealed-bundle executables
  still reload with zero compile events (the physical fingerprint is
  unchanged by an input-topology resize).
"""

import json
import os
import signal
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from cxxnet_tpu.main import EXIT_PREEMPTED, LearnTask
from cxxnet_tpu.monitor import MemorySink, Monitor, set_global
from cxxnet_tpu.monitor.schema import read_jsonl, validate_records
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import (clear_dryrun_topology, current_topology,
                                 make_mesh, set_dryrun_topology)
from cxxnet_tpu.parallel.topology import DryrunFeed, build_dryrun_feed
from cxxnet_tpu.utils.config import parse_config

NET = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 8
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
layer[3->3] = softmax
netconfig = end
input_shape = 1,1,10
batch_size = 8
eta = 0.2
seed = 5
eval_train = 0
silent = 1
"""

CONF = """
data = train
iter = csv
  filename = %(csv)s
  input_shape = 1,1,10
  label_width = 1
  silent = 1
iter = end
eval = val
iter = csv
  filename = %(csv)s
  input_shape = 1,1,10
  label_width = 1
  silent = 1
iter = end
%(net)s
metric = error
num_round = 2
save_model = 1
print_step = 0
dispatch_period = 1
precompile = 1
monitor = jsonl
"""


def _write_csv(path, n=64, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1)
    with open(path, "w") as f:
        for i in range(n):
            f.write(",".join([str(int(y[i]))]
                             + ["%g" % v for v in X[i]]) + "\n")


def _write_conf(tmp_path, n=64):
    csv = str(tmp_path / "d.csv")
    _write_csv(csv, n=n)
    conf = str(tmp_path / "run.conf")
    with open(conf, "w") as f:
        f.write(CONF % {"csv": csv, "net": NET})
    return conf


@pytest.fixture(autouse=True)
def _clean_dryrun():
    """No test may leak a faked topology into the rest of tier-1."""
    yield
    clear_dryrun_topology()
    set_global(None)


# -- topology-aware mesh ---------------------------------------------------


def test_make_mesh_keeps_model_axis_within_host():
    set_dryrun_topology(2)               # 2 virtual hosts x 4 devices
    topo = current_topology()
    assert topo.describe() == {"hosts": 2, "local_devices": 4,
                               "world_devices": 8, "dryrun": True}
    # data axis spans hosts x local devices; model groups of 2 and 4
    # sit within one 4-device host
    assert dict(make_mesh().shape) == {"data": 8, "model": 1}
    assert dict(make_mesh(4, 2).shape) == {"data": 4, "model": 2}
    assert dict(make_mesh(2, 4).shape) == {"data": 2, "model": 4}
    # a model axis of 8 would span both hosts: every-layer collectives
    # on DCN — refused
    with pytest.raises(ValueError, match="within a host"):
        make_mesh(1, 8)
    clear_dryrun_topology()
    assert current_topology().num_hosts == 1
    # single-host: any dividing model axis is fine
    assert dict(make_mesh(1, 8).shape) == {"data": 1, "model": 8}


def test_dryrun_topology_validation():
    with pytest.raises(ValueError, match="divide"):
        set_dryrun_topology(3)           # 3 does not divide 8 devices


# -- per-host batch assembly ----------------------------------------------


def test_dryrun_feed_assembles_bit_identical_global_batches(tmp_path):
    """H per-host chains concatenated in rank order must reproduce the
    single-reader batch stream byte-for-byte — including the padded
    tail (suffix padding, summed mask)."""
    csv = str(tmp_path / "d.csv")
    _write_csv(csv, n=20)                # 20 rows, B=8 -> padded tail
    block = [("iter", "csv"), ("filename", csv),
             ("input_shape", "1,1,10"), ("label_width", "1"),
             ("silent", "1")]
    batch_cfg = [("batch_size", "8"), ("input_shape", "1,1,10"),
                 ("label_width", "1")]
    from cxxnet_tpu.io import create_iterator
    ref = create_iterator(block + [("shuffle", "0"),
                                   ("round_batch", "0")], batch_cfg)
    ref.init()
    feed = build_dryrun_feed(block, batch_cfg, 2, 8)
    feed.init()
    n_batches = 0
    for a, b in zip(ref, feed):
        assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
        assert np.array_equal(np.asarray(a.label),
                              np.asarray(b.label))
        assert a.num_batch_padd == b.num_batch_padd
        n_batches += 1
    assert n_batches == 3                # 20 rows / batch 8, padded
    acc = feed.accounting()
    assert sum(acc["rows_per_host"]) == 20   # exactly-once, counted
    assert acc["batches"] == 3
    ref.close()
    feed.close()


# -- the headline: CLI dryrun bit-identity + zero recompiles ---------------


def test_cli_dryrun_bit_identical_and_zero_recompiles(tmp_path):
    """`dist_dryrun_hosts = H` over 8 virtual devices trains with zero
    recompiles after precompile and bit-identical parameters / eval
    trajectory vs the single-host run on the same global batch — for
    H = 2 and 4 — with schema-valid dist_topology/dist_shard records
    whose per-host rows sum exactly to the dataset every round."""
    conf = _write_conf(tmp_path)
    streams, models = {}, {}
    for H in (1, 2, 4):
        mdir = str(tmp_path / ("m%d" % H))
        mon = str(tmp_path / ("mon%d.jsonl" % H))
        rc = LearnTask().run([conf, "model_dir=%s" % mdir,
                              "monitor_path=%s" % mon,
                              "dist_dryrun_hosts=%d" % H])
        assert rc == 0
        streams[H] = read_jsonl(mon)
        validate_records(streams[H])
        models[H] = dict(np.load(os.path.join(mdir,
                                              "0002.model.npz")))
    for H in (2, 4):
        recs = streams[H]
        steps = [r for r in recs if r["event"] == "step"]
        assert steps and not any(r["compile"] for r in steps), \
            "H=%d dispatched a compile after precompile" % H
        (topo,) = [r for r in recs if r["event"] == "dist_topology"]
        assert topo["hosts"] == H and topo["dryrun"] is True
        assert topo["local_devices"] == 8 // H
        assert topo["mesh"] == {"data": 8, "model": 1}
        shards = [r for r in recs if r["event"] == "dist_shard"]
        assert len(shards) == 2          # one per round
        for s in shards:
            assert len(s["rows_per_host"]) == H
            assert sum(s["rows_per_host"]) == 64
        # eval trajectory identical to the single-host run
        evals = [r["metrics"] for r in recs if r["event"] == "eval"]
        ref = [r["metrics"] for r in streams[1] if r["event"] == "eval"]
        assert evals == ref
        # final parameters bit-identical
        for k in models[1]:
            if k == "__meta__":
                continue
            assert np.array_equal(models[1][k], models[H][k]), \
                "H=%d diverged on %s" % (H, k)


# -- elastic: SIGTERM -> emergency snapshot -> smaller world size ----------


def test_elastic_sigterm_resume_no_dup_no_loss_bundle_reload(
        tmp_path, monkeypatch):
    """SIGTERM one faked host mid-round at H=4: the rank-allreduced
    emergency snapshot commits at the round boundary; the survivors
    resume at H=2 (continue=1 + dist_dryrun_hosts=2), the shard map
    re-derives (dist_resize record), the resumed rounds' data order
    matches a fresh H=2 run from the same weights bit-for-bit (the
    no-dup/no-loss check), and the bundle sealed from the emergency
    snapshot still boots with zero compile events — an input-topology
    resize does not touch the physical fingerprint."""
    conf = _write_conf(tmp_path)
    mdir = str(tmp_path / "models")
    mon_a = str(tmp_path / "a.jsonl")

    calls = {"n": 0}
    orig = NetTrainer.update

    def patched(self, batch):
        out = orig(self, batch)
        calls["n"] += 1
        if calls["n"] == 20:             # mid-round 2 (8 batches/rd)
            signal.raise_signal(signal.SIGTERM)
        return out

    monkeypatch.setattr(NetTrainer, "update", patched)
    rc = LearnTask().run([conf, "model_dir=%s" % mdir,
                          "monitor_path=%s" % mon_a, "num_round=5",
                          "dist_dryrun_hosts=4"])
    monkeypatch.setattr(NetTrainer, "update", orig)
    assert rc == EXIT_PREEMPTED
    recs = read_jsonl(mon_a)
    validate_records(recs)
    (pre,) = [r for r in recs if r["event"] == "preempt"]
    assert pre["round"] == 2
    cps = [r for r in recs if r["event"] == "checkpoint"]
    assert cps[-1]["emergency"] is True
    emergency = os.path.join(mdir, "0002.model.npz")
    assert os.path.exists(emergency)
    # the emergency snapshot sealed the H=4 topology beside the weights
    blob = dict(np.load(emergency, allow_pickle=False))
    meta = json.loads(bytes(blob["__meta__"]).decode())
    assert meta["topology"]["hosts"] == 4
    assert meta["topology"]["dryrun"] is True

    # seal the emergency snapshot into a bundle (the deployed artifact
    # the survivors' serve path boots from)
    assert LearnTask().run([conf, "task=export",
                            "monitor=none",   # no cwd monitor.jsonl
                            "model_in=%s" % emergency]) == 0
    bundle = os.path.join(mdir, "0002.model.bundle")
    assert os.path.isdir(bundle)

    # resume at the smaller world size: rounds 2..4 re-run at H=2
    mon_b = str(tmp_path / "b.jsonl")
    rc = LearnTask().run([conf, "model_dir=%s" % mdir,
                          "monitor_path=%s" % mon_b, "num_round=5",
                          "continue=1", "dist_dryrun_hosts=2"])
    assert rc == 0
    recs = read_jsonl(mon_b)
    validate_records(recs)
    (res,) = [r for r in recs if r["event"] == "resume"]
    assert res["counter"] == 2
    (rez,) = [r for r in recs if r["event"] == "dist_resize"]
    assert rez["old_hosts"] == 4 and rez["new_hosts"] == 2
    shards = [r for r in recs if r["event"] == "dist_shard"]
    assert len(shards) == 3              # rounds 2, 3, 4
    for s in shards:                     # exactly-once at the new size
        assert len(s["rows_per_host"]) == 2
        assert sum(s["rows_per_host"]) == 64

    # no-dup/no-loss data order: a FRESH H=2 run from the same
    # emergency weights must produce bit-identical final parameters —
    # the resumed stream is exactly the fresh stream
    ctrl = str(tmp_path / "ctrl")
    os.makedirs(ctrl)
    import shutil
    shutil.copy(emergency, os.path.join(ctrl, "0002.model.npz"))
    rc = LearnTask().run([conf, "model_dir=%s" % ctrl, "num_round=5",
                          "model_in=%s"
                          % os.path.join(ctrl, "0002.model.npz"),
                          "monitor=none",   # no cwd monitor.jsonl
                          "dist_dryrun_hosts=2"])
    assert rc == 0
    a = dict(np.load(os.path.join(mdir, "0005.model.npz")))
    b = dict(np.load(os.path.join(ctrl, "0005.model.npz")))
    for k in a:
        if k == "__meta__":
            continue
        assert np.array_equal(a[k], b[k]), \
            "resumed run diverged from fresh run on %s" % k

    # the sealed executables still match after the resize: bundle boot
    # with ZERO compile events, every program an artifact hit
    from cxxnet_tpu.serve import ServeSession
    sink = MemorySink()
    cfg = parse_config(open(conf).read())
    sess = ServeSession(cfg, model_path=bundle, monitor=Monitor(sink))
    rows = np.random.RandomState(0).rand(5, 10).astype(np.float32)
    sess.predict(rows)
    summary = sess.close()
    validate_records(sink.records)
    assert [r for r in sink.records if r["event"] == "compile"] == []
    assert summary["compile_events"] == 0
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art["fingerprint_match"] is True
    assert art["rebuilds"] == 0 and art["hits"] > 0


# -- topology sealed into checkpoints --------------------------------------


def test_topology_check_warn_and_strict(tmp_path):
    set_dryrun_topology(2)
    t = NetTrainer(parse_config(NET))
    t.init_model()
    snap = str(tmp_path / "0001.model.npz")
    t.save_model(snap)
    clear_dryrun_topology()
    # warn (default): loads, flags the change for the resume machinery
    t2 = NetTrainer(parse_config(NET))
    t2.load_model(snap)
    assert t2.topology_changed is True
    assert t2.resumed_topology["hosts"] == 2
    # strict: refuses the silent topology change
    t3 = NetTrainer(parse_config(NET)
                    + [("dist_topology_check", "strict")])
    with pytest.raises(ValueError, match="different topology"):
        t3.load_model(snap)
    # same faked topology back in place: clean load, no flag
    set_dryrun_topology(2)
    t4 = NetTrainer(parse_config(NET))
    t4.load_model(snap)
    assert t4.topology_changed is False


# -- metric allreduce bounded retry ---------------------------------------


def test_allreduce_retry_recovers_and_emits_record(monkeypatch):
    import jax
    from jax.experimental import multihost_utils
    from cxxnet_tpu import parallel
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient DCN hiccup")
        return np.stack([np.asarray(x)] * 2)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", flaky)
    monkeypatch.setattr(parallel, "_ALLREDUCE_BACKOFF_MS", 1.0)
    sink = MemorySink()
    set_global(Monitor(sink))
    parallel.set_allreduce_retry(2)
    out = parallel.allreduce_host_sum(np.array([1.5, 2.0]))
    assert out.tolist() == [3.0, 4.0]
    validate_records(sink.records)
    (ret,) = [r for r in sink.records if r["event"] == "dist_retry"]
    assert ret["attempts"] == 1 and ret["recovered"] is True
    # one structured warning, not one per retry storm
    assert len([r for r in sink.records
                if r["event"] == "warning"]) == 1


def test_allreduce_retry_exhaustion_reraises(monkeypatch):
    import jax
    from jax.experimental import multihost_utils
    from cxxnet_tpu import parallel

    def dead(x):
        raise RuntimeError("DCN down")

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", dead)
    monkeypatch.setattr(parallel, "_ALLREDUCE_BACKOFF_MS", 1.0)
    parallel.set_allreduce_retry(1)
    try:
        with pytest.raises(RuntimeError, match="DCN down"):
            parallel.allreduce_host_sum(np.array([1.0]))
    finally:
        parallel.set_allreduce_retry(2)


# -- scaling sweep -----------------------------------------------------------


def test_dryrun_scaling_sweep_invariants():
    from cxxnet_tpu.parallel.scaling import dryrun_scaling_sweep
    sink = MemorySink()
    rec = dryrun_scaling_sweep([1, 2], rows=64, global_batch=16,
                               rounds=1, monitor=Monitor(sink))
    validate_records(sink.records)
    pts = [r for r in sink.records if r["event"] == "scaling_point"]
    assert len(pts) == 2
    assert rec["loss_parity"] is True
    assert rec["exactly_once"] is True
    assert all(p["zero_recompiles"] for p in rec["points"])
    assert rec["points"][1]["rows_per_host"] == [32, 32]
    assert "pending a device window" in rec["on_chip"]

"""Layerwise-overlapped gradient sync + ZeRO-1 optimizer-state
sharding (doc/distributed.md "Overlapped gradient sync",
doc/updater.md "Optimizer-state placement"):

- the reduction-group partitioner: every (layer, tag) tensor lands in
  exactly one group at ANY bucket size, order is reverse-layer
  deterministic (property-tested with seeded trees),
- the custom-vjp group boundary is the numeric identity (bitwise-equal
  jitted gradients),
- ``grad_sync = overlap`` trains bit-identically to ``fused`` through
  the full CLI dryrun at H=2 (tier-1) and H=4 (slow) with zero
  recompiles after precompile,
- ``optim_shard = 1`` drops per-host optimizer-state bytes to 1/H,
  measured by the schema-validated ``step_breakdown`` record,
- frozen (``lr_mult = 0``) groups allocate no optimizer state,
- sharded optimizer state round-trips the snapshot format and
  survives an elastic H=4 -> H=2 resume no-dup/no-loss,
- the dryrun scaling sweep carries overlap ratio and bytes/host per
  point.
"""

import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from cxxnet_tpu.main import EXIT_PREEMPTED, LearnTask
from cxxnet_tpu.monitor import MemorySink, Monitor, set_global
from cxxnet_tpu.monitor.schema import (read_jsonl, validate_record,
                                       validate_records)
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import (clear_dryrun_topology, gradsync,
                                 set_dryrun_topology)
from cxxnet_tpu.io.data import DataBatch
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

# leading dims all divide the 8 virtual devices, so every optimizer
# leaf ZeRO-shards (the bytes-ratio assertions are then exact)
SHARD_NET = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 64
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 8
layer[3->3] = softmax
netconfig = end
input_shape = 1,1,16
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


def _batch(features=10, seed=0, batch=8, classes=4):
    rng = np.random.RandomState(seed)
    return (rng.rand(batch, features).astype(np.float32),
            rng.randint(0, classes, (batch, 1)).astype(np.float32))


def _trainer(net=NET, extra=()):
    t = NetTrainer(parse_config(net) + list(extra))
    t.init_model()
    return t


# -- the partitioner: exactly-once at any bucket size ----------------------


def test_partition_groups_property():
    """Seeded sweep standing in for a hypothesis property test (the
    container has no hypothesis): random param trees x random layer
    indices x bucket sizes from 0 through huge — every (layer, tag)
    lands in exactly one group, flattened order is exactly the
    reverse-layer (then name) sort, group indices are the issue order,
    and byte accounting sums to the tree."""
    rng = np.random.RandomState(17)
    for trial in range(20):
        n_layers = int(rng.randint(1, 9))
        params, layer_index = {}, {}
        for li in range(n_layers):
            lk = "l%02d" % li
            layer_index[lk] = li
            tags = ["wmat", "bias"][:int(rng.randint(1, 3))]
            params[lk] = {
                tag: np.zeros((int(rng.randint(1, 65)),), np.float32)
                for tag in tags}
        all_keys = sorted((lk, tag) for lk, pt in params.items()
                          for tag in pt)
        expect_order = sorted(
            all_keys, key=lambda kt: (-layer_index[kt[0]], kt[0], kt[1]))
        total = sum(params[lk][tag].nbytes for lk, tag in all_keys)
        for bucket_mb in (0.0, 32 / (1 << 20), 128 / (1 << 20), 4.0):
            groups = gradsync.partition_groups(params, layer_index,
                                               bucket_mb=bucket_mb)
            flat = [kt for g in groups for kt in g.keys]
            # exactly once: no tensor dropped, none duplicated
            assert sorted(flat) == all_keys, \
                "trial %d bucket %s" % (trial, bucket_mb)
            # reverse-layer deterministic order
            assert flat == expect_order
            assert [g.index for g in groups] == list(range(len(groups)))
            assert sum(g.nbytes for g in groups) == total
            for g in groups:
                assert g.layer_span[0] >= g.layer_span[1]
            if bucket_mb == 0.0:
                # per-layer mode: one group per distinct layer index
                assert len(groups) == n_layers
                for g in groups:
                    assert len({lk for lk, _ in g.keys}) == 1
        # determinism: same inputs, same partition
        a = gradsync.partition_groups(params, layer_index, 0.0)
        b = gradsync.partition_groups(params, layer_index, 0.0)
        assert [g.keys for g in a] == [g.keys for g in b]


def test_partition_groups_bucketing_never_splits_a_tensor():
    params = {"l0": {"wmat": np.zeros((1024,), np.float32)},
              "l1": {"wmat": np.zeros((4,), np.float32)}}
    li = {"l0": 0, "l1": 1}
    # greedy buckets close AFTER crossing the threshold: the tiny top
    # tensor merges with the big one below it, and the big tensor —
    # larger than the bucket — still lands whole (never split), so
    # the group overshoots the bucket rather than cutting a tensor
    groups = gradsync.partition_groups(params, li,
                                       bucket_mb=512 / (1 << 20))
    assert [g.keys for g in groups] == [(("l1", "wmat"),
                                         ("l0", "wmat"))]
    assert groups[0].nbytes == 4096 + 16 > 512
    # bucket above the whole tree: still one group, same order
    big = gradsync.partition_groups(params, li, bucket_mb=4.0)
    assert [g.keys for g in big] == [g.keys for g in groups]


# -- the boundary: numeric identity ----------------------------------------


def test_group_boundary_grads_bitwise_identical():
    import jax
    import jax.numpy as jnp
    t = _trainer()
    groups = gradsync.partition_groups(t.params, t._layer_index, 0.0)
    X, y = _batch()
    Xd = jnp.asarray(X)

    def loss_plain(p):
        out = Xd
        out = jnp.maximum(out @ p["fc1"]["wmat"] + p["fc1"]["bias"], 0)
        out = out @ p["fc2"]["wmat"] + p["fc2"]["bias"]
        return jnp.sum(out * out)

    def loss_marked(p):
        return loss_plain(gradsync.apply_group_boundaries(p, groups))

    g0 = jax.jit(jax.grad(loss_plain))(t.params)
    g1 = jax.jit(jax.grad(loss_marked))(t.params)
    for lk in t.params:
        for tag in t.params[lk]:
            assert np.array_equal(np.asarray(g0[lk][tag]),
                                  np.asarray(g1[lk][tag]))


def test_trainer_overlap_matches_fused_bitwise():
    """Direct trainer parity: fused vs per-layer overlap vs bucketed
    overlap, five real updates, bit-equal parameters."""
    import jax
    X, y = _batch()

    def run(extra):
        t = _trainer(extra=extra)
        b = DataBatch(data=X, label=y)
        for _ in range(5):
            t.update(b)
        return jax.device_get(t.params)

    pf = run([("grad_sync", "fused")])
    po = run([("grad_sync", "overlap")])
    pb = run([("grad_sync", "overlap"),
              ("grad_sync_bucket_mb", "0.0001")])
    for lk in pf:
        for tag in pf[lk]:
            assert np.array_equal(pf[lk][tag], po[lk][tag])
            assert np.array_equal(pf[lk][tag], pb[lk][tag])


def test_grad_sync_knob_validation():
    with pytest.raises(ValueError, match="fused|overlap"):
        _trainer(extra=[("grad_sync", "async")])
    with pytest.raises(ValueError, match="bucket"):
        _trainer(extra=[("grad_sync_bucket_mb", "-1")])


# -- CLI dryrun: overlap bit-parity vs fused at H=2 (tier-1) and 4 ---------


def _cli_parity_at(tmp_path, H):
    conf = _write_conf(tmp_path)
    models, streams = {}, {}
    for mode in ("fused", "overlap"):
        mdir = str(tmp_path / ("m_%s" % mode))
        mon = str(tmp_path / ("%s.jsonl" % mode))
        rc = LearnTask().run([conf, "model_dir=%s" % mdir,
                              "monitor_path=%s" % mon,
                              "dist_dryrun_hosts=%d" % H,
                              "grad_sync=%s" % mode])
        assert rc == 0
        streams[mode] = read_jsonl(mon)
        validate_records(streams[mode])
        models[mode] = dict(np.load(os.path.join(mdir,
                                                 "0002.model.npz")))
    for mode in ("fused", "overlap"):
        steps = [r for r in streams[mode] if r["event"] == "step"]
        assert steps and not any(r["compile"] for r in steps), \
            "%s dispatched a compile after precompile" % mode
    evals = {m: [r["metrics"] for r in streams[m]
                 if r["event"] == "eval"] for m in streams}
    assert evals["overlap"] == evals["fused"]
    for k in models["fused"]:
        if k == "__meta__":
            continue
        assert np.array_equal(models["fused"][k],
                              models["overlap"][k]), \
            "H=%d overlap diverged from fused on %s" % (H, k)


def test_cli_overlap_bit_parity_h2(tmp_path):
    """grad_sync=overlap through the full CLI dryrun at H=2: zero
    recompiles after precompile, bit-identical parameters and eval
    trajectory vs the fused run — same semantics, different
    schedule."""
    _cli_parity_at(tmp_path, 2)


@pytest.mark.slow
def test_cli_overlap_bit_parity_h4(tmp_path):
    """The H=4 sweep of the same pin (slow: two more full CLI runs on
    top of the H=2 pair keeps tier-1 inside its budget)."""
    _cli_parity_at(tmp_path, 4)


# -- ZeRO-1: bytes drop 1/H, measured --------------------------------------


def test_optim_shard_bytes_per_host_quarter_at_h4():
    """optim_shard=1 at a faked H=4 (8 devices, 2 per host): every
    optimizer leaf of SHARD_NET splits dim 0 across the data axis, so
    distinct per-host bytes are EXACTLY unsharded/4 — and the
    unsharded footprint matches the replicated run's."""
    t0 = _trainer(SHARD_NET)
    replicated = gradsync.tree_logical_bytes(t0.opt_state)
    assert gradsync.host_resident_bytes(t0.opt_state) == replicated
    set_dryrun_topology(4)
    t = _trainer(SHARD_NET, extra=[("optim_shard", "1")])
    unsharded = gradsync.tree_logical_bytes(t.opt_state)
    assert unsharded == replicated
    per_host = gradsync.host_resident_bytes(t.opt_state)
    assert per_host * 4 == unsharded


def test_step_breakdown_record_schema_and_bytes():
    """measure_step_breakdown on an overlap+sharded trainer at H=2:
    schema-valid record, per-host bytes exactly half, group count
    matches the partition, ratios in range."""
    set_dryrun_topology(2)
    t = _trainer(SHARD_NET, extra=[("grad_sync", "overlap"),
                                   ("optim_shard", "1")])
    t.precompile(window=1)
    X, y = _batch(features=16, classes=8)
    b = DataBatch(data=X, label=y)
    t.update(b)
    bd = gradsync.measure_step_breakdown(t, b, repeats=1)
    rec = dict(bd, event="step_breakdown", t=time.time())
    assert validate_record(rec) == []
    assert bd["hosts"] == 2
    assert bd["grad_sync"] == "overlap" and bd["optim_shard"] == 1
    assert bd["groups"] == len(t._sync_groups) == 2
    assert bd["opt_state_bytes_per_host"] * 2 \
        == bd["opt_state_bytes_unsharded"]
    assert 0.0 <= bd["overlap_ratio"] <= 1.0
    assert bd["grad_bytes"] > 0 and bd["frozen_groups"] == 0


# -- frozen groups: no state, still bit-exact ------------------------------


def test_frozen_group_allocates_no_state():
    frozen_net = NET.replace("nhidden = 8",
                             "nhidden = 8\n  lr_mult = 0")
    t = _trainer(frozen_net)
    assert t.opt_state["fc1"] == {"wmat": {}, "bias": {}}
    assert gradsync.frozen_group_count(t.opt_state) == 2
    t_full = _trainer()
    saved = gradsync.tree_logical_bytes(t_full.opt_state) \
        - gradsync.tree_logical_bytes(t.opt_state)
    assert saved == t_full.opt_state["fc1"]["wmat"]["m_w"].nbytes \
        + t_full.opt_state["fc1"]["bias"]["m_w"].nbytes
    # the freeze stays bit-exact with the skipped state
    import jax
    X, y = _batch()
    b = DataBatch(data=X, label=y)
    w0 = jax.device_get(t.params["fc1"]["wmat"])
    for _ in range(4):
        t.update(b)
    assert np.array_equal(w0, jax.device_get(t.params["fc1"]["wmat"]))
    # the head still trains
    assert gradsync.frozen_group_count(t.opt_state) == 2


# -- sharded optimizer state through the snapshot format -------------------


def test_sharded_opt_state_snapshot_round_trip(tmp_path):
    """save_optimizer=1 + optim_shard=1: the snapshot stores gathered
    global arrays, load re-shards onto the mesh, and the resumed run
    steps bit-identically to the uninterrupted one."""
    import jax
    set_dryrun_topology(2)
    extra = [("optim_shard", "1"), ("save_optimizer", "1")]
    t = _trainer(SHARD_NET, extra=extra)
    X, y = _batch(features=16, classes=8)
    b = DataBatch(data=X, label=y)
    for _ in range(3):
        t.update(b)
    snap = str(tmp_path / "0001.model.npz")
    t.save_model(snap)
    blob = dict(np.load(snap, allow_pickle=False))
    opt_keys = [k for k in blob if k.startswith("opt/")]
    assert sorted(opt_keys) == [
        "opt/fc1/bias/m_w", "opt/fc1/wmat/m_w",
        "opt/fc2/bias/m_w", "opt/fc2/wmat/m_w"]
    # gathered: each saved array is the full logical leaf
    assert blob["opt/fc1/wmat/m_w"].shape == (16, 64)
    t2 = _trainer(SHARD_NET, extra=extra)
    t2.load_model(snap)
    assert gradsync.host_resident_bytes(t2.opt_state) * 2 \
        == gradsync.tree_logical_bytes(t2.opt_state)
    t.update(b)
    t2.update(b)
    for lk in t.params:
        for tag in t.params[lk]:
            assert np.array_equal(jax.device_get(t.params[lk][tag]),
                                  jax.device_get(t2.params[lk][tag]))


def test_elastic_resize_resumes_sharded_opt_state(tmp_path,
                                                  monkeypatch):
    """SIGTERM mid-round at H=4 with optim_shard=1 + save_optimizer=1:
    the emergency snapshot carries the gathered optimizer state; the
    H=2 resume re-shards it and finishes bit-identically (params AND
    optimizer state) to a fresh H=2 run from the same emergency
    snapshot — sharded state survives the resize no-dup/no-loss."""
    conf = _write_conf(tmp_path)
    mdir = str(tmp_path / "models")
    extra = ["save_optimizer=1", "optim_shard=1"]

    calls = {"n": 0}
    orig = NetTrainer.update

    def patched(self, batch):
        out = orig(self, batch)
        calls["n"] += 1
        if calls["n"] == 20:             # mid-round 2 (8 batches/rd)
            signal.raise_signal(signal.SIGTERM)
        return out

    monkeypatch.setattr(NetTrainer, "update", patched)
    rc = LearnTask().run([conf, "model_dir=%s" % mdir, "num_round=4",
                          "monitor=none", "dist_dryrun_hosts=4"]
                         + extra)
    monkeypatch.setattr(NetTrainer, "update", orig)
    assert rc == EXIT_PREEMPTED
    emergency = os.path.join(mdir, "0002.model.npz")
    blob = dict(np.load(emergency, allow_pickle=False))
    assert "opt/fc2/wmat/m_w" in blob    # momentum rode the emergency
    assert blob["opt/fc2/wmat/m_w"].shape == (8, 4)

    # resume at H=2 from the emergency snapshot
    rc = LearnTask().run([conf, "model_dir=%s" % mdir, "num_round=4",
                          "monitor=none", "continue=1",
                          "dist_dryrun_hosts=2"] + extra)
    assert rc == 0

    # fresh H=2 control from the same snapshot
    import shutil
    ctrl = str(tmp_path / "ctrl")
    os.makedirs(ctrl)
    shutil.copy(emergency, os.path.join(ctrl, "0002.model.npz"))
    rc = LearnTask().run([conf, "model_dir=%s" % ctrl, "num_round=4",
                          "monitor=none",
                          "model_in=%s"
                          % os.path.join(ctrl, "0002.model.npz"),
                          "dist_dryrun_hosts=2"] + extra)
    assert rc == 0
    a = dict(np.load(os.path.join(mdir, "0004.model.npz")))
    b = dict(np.load(os.path.join(ctrl, "0004.model.npz")))
    assert sorted(a) == sorted(b)
    assert any(k.startswith("opt/") for k in a)
    for k in a:
        if k == "__meta__":
            continue
        assert np.array_equal(a[k], b[k]), \
            "resumed run diverged from fresh run on %s" % k


# -- the scaling sweep carries step_breakdown ------------------------------


def test_scaling_sweep_emits_step_breakdown():
    from cxxnet_tpu.parallel.scaling import dryrun_scaling_sweep
    sink = MemorySink()
    rec = dryrun_scaling_sweep([1, 2], rows=64, global_batch=16,
                               rounds=1, monitor=Monitor(sink),
                               grad_sync="overlap", optim_shard=1)
    validate_records(sink.records)
    assert rec["loss_parity"] is True and rec["exactly_once"] is True
    assert rec["grad_sync"] == "overlap" and rec["optim_shard"] == 1
    assert "pending" in rec["breakdown_caveat"]
    bds = [r for r in sink.records if r["event"] == "step_breakdown"]
    assert len(bds) == 2
    for p, bd in zip(rec["points"], bds):
        assert p["step_breakdown"]["hosts"] == p["hosts"] \
            == bd["hosts"]
        assert bd["grad_sync"] == "overlap" and bd["groups"] >= 2
        # every leaf of the sweep net shards -> exact 1/H per host
        assert bd["opt_state_bytes_per_host"] * bd["hosts"] \
            == bd["opt_state_bytes_unsharded"]
        assert 0.0 <= bd["overlap_ratio"] <= 1.0

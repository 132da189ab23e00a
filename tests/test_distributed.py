"""Multi-process distributed bring-up tests — the ps-lite "local mode"
equivalent (reference example/multi-machine/run.sh:12-18 runs n workers
as processes on one machine; SURVEY.md §4.5).

Spawns 2 real OS processes, each a single-device CPU jax process joined
via ``jax.distributed`` over localhost, and verifies:
- ``init_distributed`` env bring-up (CXXNET_COORDINATOR et al.) works
  when called before any other jax API (the round-1 ordering bug)
- ``allreduce_host_sum`` sums across processes (rabit Allreduce,
  metric.h:60-68)
- metric values are globally reduced in ``Metric.get()``
- only rank 0 is root (root-only save/log, cxxnet_main.cpp:501-503)
- per-rank data sharding: imgrec autodetects process rank and the two
  ranks read disjoint record shards that union to the full set
  (iter_image_recordio-inl.hpp:169-185)
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, sys
import numpy as np

sys.path.insert(0, %(repo)r)

# workers stay on the CPU whatever the environment says (see conftest)
import jax
jax.config.update("jax_platforms", "cpu")

# init_distributed must come before ANY backend-touching jax call
from cxxnet_tpu.parallel import (init_distributed, rank, world_size,
                                 is_root, allreduce_host_sum)
init_distributed()

r = rank()
assert world_size() == 2, "world_size=%%d" %% world_size()
assert r == int(os.environ["CXXNET_PROCESS_ID"])
assert is_root() == (r == 0)

out = allreduce_host_sum(np.array([r + 1.0, 1.0]))
assert out.tolist() == [3.0, 2.0], out.tolist()

# metric reduction: rank 0 contributes 2 wrong of 3, rank 1 contributes
# 0 wrong of 1 -> global error = 2/4 = 0.5 (per-rank values differ)
from cxxnet_tpu.utils.metric import create_metric
m = create_metric("error")
if r == 0:
    m.add_eval(np.array([[0.9, .1], [0.9, .1], [0.9, .1]], np.float32),
               np.array([[1.], [1.], [0.]], np.float32))
else:
    m.add_eval(np.array([[0.9, 0.1]], np.float32),
               np.array([[0.]], np.float32))
assert abs(m.get() - 0.5) < 1e-9, m.get()

# per-rank data sharding through the imgrec iterator rank autodetect
workdir = os.environ["CXXNET_TEST_WORKDIR"]
from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator
it = ImageRecordIterator()
it.set_param("path_imgrec", os.path.join(workdir, "data.rec"))
it.set_param("silent", "1")
it.init()
seen = []
while it.next():
    seen.append(int(it.value().index))
with open(os.path.join(workdir, "shard%%d.txt" %% r), "w") as f:
    f.write(",".join(map(str, sorted(seen))))

# root-only model save (only rank 0 writes)
if is_root():
    with open(os.path.join(workdir, "root.model"), "w") as f:
        f.write("model")
print("WORKER%%d OK" %% r)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# some containers ship a jaxlib whose CPU backend cannot run
# cross-process collectives ("Multiprocess computations aren't
# implemented on the CPU backend") even though jax.distributed
# bring-up itself succeeds — every two-process test here would fail on
# its first allreduce. Probe once with a minimal 2-process allgather
# and skip the spawn tests with that reason instead of failing tier-1.
_PROBE = r"""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["PROBE_COORD"],
    num_processes=2, process_id=int(os.environ["PROBE_RANK"]))
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(np.ones((1,)))
assert np.asarray(out).sum() == 2.0
print("PROBE OK")
"""

_mp_cpu_reason = None


def _multiprocess_cpu_unavailable():
    """Cached probe: empty string when 2-process CPU collectives work,
    else the reason to skip with."""
    global _mp_cpu_reason
    if _mp_cpu_reason is not None:
        return _mp_cpu_reason
    port = _free_port()
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({"JAX_PLATFORMS": "cpu",
                    "PROBE_COORD": "127.0.0.1:%d" % port,
                    "PROBE_RANK": str(r)})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PROBE], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    reason = ""
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            if p.returncode != 0:
                tail = out.decode(errors="replace").strip()
                reason = ("2-process CPU collectives unavailable "
                          "in this container: %s" % tail[-200:])
    except subprocess.TimeoutExpired:
        reason = "2-process CPU collective probe timed out"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    _mp_cpu_reason = reason
    return reason


@pytest.fixture
def multiprocess_cpu():
    reason = _multiprocess_cpu_unavailable()
    if reason:
        pytest.skip(reason)


def _pack_rec(path, n=10):
    cv2 = pytest.importorskip("cv2")
    from cxxnet_tpu.io.recordio import RecordIOWriter, pack_image_record
    rng = np.random.RandomState(0)
    w = RecordIOWriter(path, force_python=True)
    for i in range(n):
        img = rng.randint(0, 255, (8, 8, 3), np.uint8)
        ok, buf = cv2.imencode(".png", img)
        assert ok
        w.write_record(pack_image_record(i, float(i % 3),
                                         bytes(buf.tobytes())))
    w.close()


def test_two_process_bringup(tmp_path, multiprocess_cpu):
    _pack_rec(str(tmp_path / "data.rec"), n=10)
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(WORKER % {"repo": REPO})

    port = _free_port()
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # no virtual 8-device CPU here
        env.update({
            "JAX_PLATFORMS": "cpu",
            "CXXNET_COORDINATOR": "127.0.0.1:%d" % port,
            "CXXNET_NUM_PROCESSES": "2",
            "CXXNET_PROCESS_ID": str(r),
            "CXXNET_TEST_WORKDIR": str(tmp_path),
        })
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            outs.append(out.decode(errors="replace"))
            assert p.returncode == 0, \
                "rank %d failed:\n%s" % (r, outs[-1])
            assert ("WORKER%d OK" % r) in outs[-1], outs[-1]
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()

    # shards are disjoint and union to the full record set
    shards = []
    for r in range(2):
        with open(tmp_path / ("shard%d.txt" % r)) as f:
            txt = f.read().strip()
        shards.append(set(int(t) for t in txt.split(",") if t))
    assert shards[0] and shards[1], "a rank got an empty shard"
    assert not (shards[0] & shards[1]), "shards overlap"
    assert shards[0] | shards[1] == set(range(10))

    # root-only save: the file exists exactly once, written by rank 0
    assert (tmp_path / "root.model").exists()


# ---------------------------------------------------------------------------
# Cross-process TRAINING equivalence: dp spanning 2 OS processes (x2
# virtual devices each) must produce the same parameters as the same
# training on 1 process x 4 devices — the rabit-mode training guarantee
# (example/multi-machine/run.sh:12-18). Includes a mid-run root-only
# snapshot + resume across the process boundary.
# ---------------------------------------------------------------------------

TRAIN_CONF = """
netconfig = start
layer[0->1] = fullc:fc1
  nhidden = 16
layer[1->2] = relu
layer[2->3] = fullc:fc2
  nhidden = 4
layer[3->3] = softmax
netconfig = end
input_shape = 1,1,10
batch_size = 8
eta = 0.2
momentum = 0.9
random_type = gaussian
init_sigma = 0.1
seed = 11
eval_train = 0
"""

TRAIN_BODY = r"""
import numpy as np

def make_data():
    rng = np.random.RandomState(42)
    X = rng.rand(48, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1).astype(np.float32)
    return X, y[:, None]

def train(t, workdir, lo, hi, barrier):
    from cxxnet_tpu.io.data import DataBatch
    X, y = make_data()
    mid = workdir + "/mid.model.npz"
    for step in range(6):
        if step == 3:
            # mid-run snapshot: root writes, everyone resumes from it
            from cxxnet_tpu.parallel import is_root, allreduce_host_sum
            if is_root():
                t.save_model(mid)
            barrier()
            t.load_model(mid)
        gb = slice(step * 8, (step + 1) * 8)
        t.update(DataBatch(data=X[gb][lo:hi], label=y[gb][lo:hi]))
    return {("%s/%s" % (lk, tag)): np.asarray(w)
            for lk, pt in t.params.items() for tag, w in pt.items()}
"""

TRAIN_WORKER = r"""
import os, sys
import numpy as np
sys.path.insert(0, %(repo)r)

from cxxnet_tpu.parallel import force_virtual_cpu
force_virtual_cpu(2)                       # 2 local devices per process
from cxxnet_tpu.parallel import init_distributed
init_distributed()                         # before other jax API

import jax
assert jax.process_count() == 2 and len(jax.devices()) == 4

from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config
from cxxnet_tpu.parallel import rank, is_root, allreduce_host_sum

%(body)s

workdir = os.environ["CXXNET_TEST_WORKDIR"]
with open(workdir + "/train.conf") as f:
    t = NetTrainer(parse_config(f.read()))
t.init_model()
r = rank()
barrier = lambda: allreduce_host_sum(np.zeros(1))
# rank's half of each global batch of 8
params = train(t, workdir, r * 4, (r + 1) * 4, barrier)
if is_root():
    np.savez(workdir + "/mp_final.npz", **params)
print("TRAINWORKER%%d OK loss=%%.6f" %% (r, t.last_loss))
"""

TRAIN_SINGLE = r"""
import os, sys
import numpy as np
sys.path.insert(0, %(repo)r)

from cxxnet_tpu.parallel import force_virtual_cpu
force_virtual_cpu(4)                       # same 4-device topology

import jax
assert len(jax.devices()) == 4

from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.utils.config import parse_config

%(body)s

workdir = os.environ["CXXNET_TEST_WORKDIR"]
with open(workdir + "/train.conf") as f:
    t = NetTrainer(parse_config(f.read()))
t.init_model()
params = train(t, workdir, 0, 8, lambda: None)
np.savez(workdir + "/sp_final.npz", **params)
print("SINGLE OK loss=%%.6f" %% t.last_loss)
"""


def test_cross_process_training_equivalence(tmp_path, multiprocess_cpu):
    (tmp_path / "train.conf").write_text(TRAIN_CONF)

    # --- 2 processes x 2 devices, with mid-run snapshot + resume
    script = str(tmp_path / "train_worker.py")
    with open(script, "w") as f:
        f.write(TRAIN_WORKER % {"repo": REPO, "body": TRAIN_BODY})
    port = _free_port()
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "CXXNET_COORDINATOR": "127.0.0.1:%d" % port,
            "CXXNET_NUM_PROCESSES": "2",
            "CXXNET_PROCESS_ID": str(r),
            "CXXNET_TEST_WORKDIR": str(tmp_path),
        })
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=600)
            txt = out.decode(errors="replace")
            assert p.returncode == 0, "rank %d failed:\n%s" % (r, txt)
            assert ("TRAINWORKER%d OK" % r) in txt, txt
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()

    # the mid-run snapshot was written by root during the 2-process run
    # (checked BEFORE the single-process run, which also snapshots)
    assert (tmp_path / "mid.model.npz").exists()

    # --- 1 process x 4 devices, same data/seed/schedule
    script1 = str(tmp_path / "train_single.py")
    with open(script1, "w") as f:
        f.write(TRAIN_SINGLE % {"repo": REPO, "body": TRAIN_BODY})
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["CXXNET_TEST_WORKDIR"] = str(tmp_path)
    env.pop("CXXNET_COORDINATOR", None)
    out = subprocess.run([sys.executable, script1], env=env,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, timeout=600)
    assert out.returncode == 0, out.stdout.decode(errors="replace")

    # --- final parameters match across the process boundary
    mp = np.load(tmp_path / "mp_final.npz")
    sp = np.load(tmp_path / "sp_final.npz")
    assert set(mp.files) == set(sp.files)
    for k in mp.files:
        np.testing.assert_allclose(
            mp[k], sp[k], rtol=2e-6, atol=1e-7,
            err_msg="param %s diverged across process boundary" % k)


# ---------------------------------------------------------------------------
# Full CLI path under multi-process dp: main.py must split the GLOBAL
# config batch_size across ranks and the csv base iterator must shard
# rows by rank (disjoint strided shards), with no hand-slicing outside
# the framework.
# ---------------------------------------------------------------------------

CLI_WORKER = r"""
import os, sys
import numpy as np
sys.path.insert(0, %(repo)r)

from cxxnet_tpu.parallel import force_virtual_cpu
force_virtual_cpu(2)
from cxxnet_tpu.parallel import init_distributed
init_distributed()

import jax
assert jax.process_count() == 2

from cxxnet_tpu.main import LearnTask

workdir = os.environ["CXXNET_TEST_WORKDIR"]
rc = LearnTask().run([workdir + "/cli.conf"])
assert rc == 0, "CLI train failed rc=%%d" %% rc
print("CLIWORKER%%d OK" %% jax.process_index())
"""

CLI_CONF = """
data = train
iter = csv
  filename = %s/cli.csv
  input_shape = 1,1,10
  label_width = 1
iter = end
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
num_round = 2
max_round = 2
metric = error
model_dir = %s/cli_models
silent = 1
"""


def _run_two_cli_ranks(tmp_path, timeout=600):
    """Launch the CLI worker script on 2 coordinated ranks and assert
    both exit 0 with their OK marker (shared harness for the
    two-process CLI tests; a collective deadlock trips the timeout)."""
    script = str(tmp_path / "cli_worker.py")
    with open(script, "w") as f:
        f.write(CLI_WORKER % {"repo": REPO})
    port = _free_port()
    procs = []
    for r in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "CXXNET_COORDINATOR": "127.0.0.1:%d" % port,
            "CXXNET_NUM_PROCESSES": "2",
            "CXXNET_PROCESS_ID": str(r),
            "CXXNET_TEST_WORKDIR": str(tmp_path),
        })
        procs.append(subprocess.Popen(
            [sys.executable, script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            txt = out.decode(errors="replace")
            assert p.returncode == 0, "rank %d failed:\n%s" % (r, txt)
            assert ("CLIWORKER%d OK" % r) in txt, txt
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()


def test_cli_two_process_training(tmp_path, multiprocess_cpu):
    rng = np.random.RandomState(3)
    X = rng.rand(32, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1)
    with open(tmp_path / "cli.csv", "w") as f:
        for i in range(32):
            f.write(",".join([str(y[i])] + ["%g" % v for v in X[i]])
                    + "\n")
    (tmp_path / "cli.conf").write_text(CLI_CONF
                                       % (tmp_path, tmp_path))
    _run_two_cli_ranks(tmp_path)

    # root-only snapshots exist for both rounds
    assert (tmp_path / "cli_models" / "0001.model.npz").exists()
    assert (tmp_path / "cli_models" / "0002.model.npz").exists()


CLI_CONF_ODD = """
data = train
iter = csv
  filename = %s/odd.csv
  input_shape = 1,1,10
  label_width = 1
  batch_size = 8
iter = end
eval = val
iter = csv
  filename = %s/odd.csv
  input_shape = 1,1,10
  label_width = 1
iter = end
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
num_round = 2
max_round = 2
metric = error
model_dir = %s/odd_models
silent = 1
"""


def test_cli_two_process_unequal_shards(tmp_path, multiprocess_cpu):
    """Regression for the round-3 advisor finding: 33 rows split
    rank-strided give rank0 17 rows / rank1 16; at local batch 4 the
    ranks would emit 5 vs 4 batches per round and the SPMD collectives
    would deadlock. synced_batches must truncate to the common count.
    The conf also sets batch_size INSIDE the iterator block, which must
    be divided across ranks like the global one."""
    rng = np.random.RandomState(7)
    X = rng.rand(33, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1)
    with open(tmp_path / "odd.csv", "w") as f:
        for i in range(33):
            f.write(",".join([str(y[i])] + ["%g" % v for v in X[i]])
                    + "\n")
    (tmp_path / "cli.conf").write_text(
        CLI_CONF_ODD % (tmp_path, tmp_path, tmp_path))
    # a deadlock (the pre-fix behavior) trips the harness timeout
    _run_two_cli_ranks(tmp_path)
    assert (tmp_path / "odd_models" / "0002.model.npz").exists()


def test_csv_rank_sharding():
    """Explicit part_index/num_parts give disjoint strided shards that
    union to the full row set (single process; no distributed init)."""
    import tempfile
    from cxxnet_tpu.io.iter_csv import CSVIterator
    with tempfile.NamedTemporaryFile("w", suffix=".csv",
                                     delete=False) as f:
        for i in range(7):
            f.write("%d,%d,%d\n" % (i % 3, i, i * 10))
        path = f.name
    seen = {}
    for pi in range(2):
        it = CSVIterator()
        it.set_param("filename", path)
        it.set_param("input_shape", "1,1,2")
        it.set_param("silent", "1")
        it.set_param("part_index", str(pi))
        it.set_param("num_parts", "2")
        it.init()
        got = []
        it.before_first()
        while it.next():
            got.append(it.value().index)
        seen[pi] = set(got)
    assert seen[0] == {0, 2, 4, 6}
    assert seen[1] == {1, 3, 5}
    os.unlink(path)


def test_launch_py_two_process(tmp_path, multiprocess_cpu):
    """example/multi-machine/launch.py spawns n CLI workers that join
    one training job (the ps-lite local-mode launcher equivalent)."""
    rng = np.random.RandomState(5)
    X = rng.rand(32, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1)
    with open(tmp_path / "cli.csv", "w") as f:
        for i in range(32):
            f.write(",".join([str(y[i])] + ["%g" % v for v in X[i]])
                    + "\n")
    (tmp_path / "cli.conf").write_text(CLI_CONF
                                       % (tmp_path, tmp_path))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("CXXNET_COORDINATOR", None)
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "example", "multi-machine", "launch.py"),
         "-n", "2", "--devices-per-worker", "1",
         str(tmp_path / "cli.conf")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        timeout=300)
    txt = out.stdout.decode(errors="replace")
    assert out.returncode == 0, txt
    assert (tmp_path / "cli_models" / "0002.model.npz").exists(), txt
    # rank-prefixed streams from both workers
    assert "[0]" in txt and "[1]" in txt, txt


def test_cli_two_process_divergent_padding(tmp_path, multiprocess_cpu):
    """Regression for the round-4 reviewer finding: the maskless
    specialization (mask=None when a rank's batch has no tail padding)
    selects between two COMPILED PROGRAMS; with 15 rows rank-strided,
    rank0 gets 8 rows (2 exact local-batch-4 batches) while rank1 gets
    7 (its second batch padded) — if the None/array choice were made
    per rank, the ranks would dispatch structurally different SPMD
    programs in the same step and the gradient collectives would hang.
    Multi-process mode must always materialize the mask."""
    rng = np.random.RandomState(11)
    X = rng.rand(15, 10).astype(np.float32)
    y = (X @ rng.randn(10, 4)).argmax(1)
    with open(tmp_path / "odd.csv", "w") as f:
        for i in range(15):
            f.write(",".join([str(y[i])] + ["%g" % v for v in X[i]])
                    + "\n")
    (tmp_path / "cli.conf").write_text(
        CLI_CONF_ODD % (tmp_path, tmp_path, tmp_path))
    # a deadlock (per-rank None/array divergence) trips the timeout
    _run_two_cli_ranks(tmp_path)
    assert (tmp_path / "odd_models" / "0002.model.npz").exists()

"""Sealed model artifacts (doc/artifacts.md): program registry,
``task = export`` bundles, near-zero-cold-start serve boot.

The contract under test:

- ``task = export`` writes a two-phase-committed bundle (verified
  snapshot + serialized executables + fingerprinted manifest) that
  ``ckpt_verify`` vouches for, and any tampered byte — including
  inside a serialized executable — fails verification with exit 1.
- Booting serve from a bundle on a matching runtime produces ZERO
  compile events (warmup included) and parity-identical outputs vs a
  snapshot boot; the ``artifact_load`` record counts every program as
  a hit.
- A mismatched fingerprint falls back per-key to re-lower+compile
  with exactly ONE warning — and still serves identical outputs.
- The hot-swap watcher picks up new verified bundles and prefers a
  bundle over a snapshot at the same counter.
"""

import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from cxxnet_tpu.artifact import registry as areg
from cxxnet_tpu.artifact import bundle as ab
from cxxnet_tpu.main import LearnTask
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_records
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import make_mesh
from cxxnet_tpu.utils.config import parse_config
from cxxnet_tpu.utils.faultfs import FaultFS

SYNTH = """
netconfig=start
layer[+1:h] = fullc:fc1
  nhidden = 16
  init_sigma = 0.05
layer[+1] = relu
layer[h->o] = fullc:fc2
  nhidden = 4
  init_sigma = 0.05
layer[+0] = softmax
netconfig=end
input_shape = 1,1,24
batch_size = 8
eta = 0.1
"""

CFG = parse_config(SYNTH)


@pytest.fixture
def faultfs():
    fs = FaultFS("fault").install()
    try:
        yield fs
    finally:
        fs.uninstall()


def _snapshot(tmp_path, name="0001.model.npz"):
    t = NetTrainer(CFG, mesh=make_mesh(1, 1))
    t.init_model()
    path = str(tmp_path / name)
    t.save_model(path)
    return path


def _export(tmp_path, snap, out=""):
    conf = str(tmp_path / "run.conf")
    with open(conf, "w") as f:
        f.write(SYNTH)
    argv = [conf, "task=export", "model_in=%s" % snap]
    if out:
        argv.append("export_out=%s" % out)
    assert LearnTask().run(argv) == 0
    return out or ab.default_bundle_path(snap)


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """One snapshot + committed bundle shared by the read-only tests
    (export costs ~6 program compiles; pay it once)."""
    tmp_path = tmp_path_factory.mktemp("artifact")
    snap = _snapshot(tmp_path)
    bundle = _export(tmp_path, snap)
    return tmp_path, snap, bundle


# -- key scheme -----------------------------------------------------------


def test_registry_keys_roundtrip_via_repr():
    """Bundle manifests encode registry keys as repr; literal_eval
    must recover them exactly — for every kind's sig shape."""
    keys = [
        ("pred",) + areg.pred_sig((8, 24), np.dtype(np.float32), True,
                                  0, (5,)),
        ("update",) + areg.update_sig((8, 24), "float32", (8, 1),
                                      False, 0, True),
        ("update_many",) + areg.update_many_sig(
            (4, 8, 24), "uint8", (4, 8, 1), True, 0, 4, False),
        ("run_steps",) + areg.run_steps_sig((8, 24), "bfloat16",
                                            (8, 1), True, 0, 200),
    ]
    for key in keys:
        assert areg.parse_key(repr(key)) == key
    with pytest.raises(ValueError):
        areg.parse_key("'not-a-key-tuple'")


def test_trainer_dispatch_sigs_match_precompile_keys():
    """The single-sourcing claim, mechanically: a precompile()d
    trainer dispatches every steady-state program as an AOT hit —
    its runtime signatures resolve to the registry keys precompile
    built (a scheme drift would strand dispatch on jit fallback)."""
    from cxxnet_tpu.io.data import DataBatch
    t = NetTrainer(CFG, mesh=make_mesh(1, 1))
    t.init_model()
    n = t.precompile(window=2)
    assert n > 0 and len(t.programs) == n
    rng = np.random.RandomState(0)

    def batch():
        return DataBatch(
            data=rng.rand(8, 24).astype(np.float32),
            label=rng.randint(0, 4, (8, 1)).astype(np.float32))

    sink = MemorySink()
    t.set_monitor(Monitor(sink))
    t.update(batch())
    t.update_many([batch(), batch()])
    steps = [r for r in sink.records if r["event"] == "step"]
    assert steps and not any(r["compile"] for r in steps)


# -- export + verification ------------------------------------------------


def test_export_writes_committed_verified_bundle(exported):
    _, snap, bundle = exported
    rep = ab.verify_bundle(bundle)
    assert rep["ok"], rep["error"]
    assert rep["programs"] > 0
    man = ab.bundle_manifest(bundle)
    assert man["buckets"] == [1, 2, 4, 8]
    assert man["fingerprint"] == ab.runtime_fingerprint(
        make_mesh(1, 1))
    # every member row carries a digest; the commit marker vouches
    # for the manifest bytes themselves
    assert all(m["sha256"] for m in man["members"])
    assert os.path.exists(
        os.path.join(bundle, ab.MANIFEST_NAME + ab.OK_SUFFIX))


def test_default_bundle_path_convention():
    assert ab.default_bundle_path("/m/0042.model.npz") \
        == "/m/0042.model.bundle"
    # a bundle model_in re-exports IN PLACE: .bundle.bundle would be
    # invisible to the watcher's BUNDLE_RE forever
    assert ab.default_bundle_path("/m/0042.model.bundle") \
        == "/m/0042.model.bundle"
    assert ab.default_bundle_path("/m/0042.model.bundle/") \
        == "/m/0042.model.bundle"


def test_commit_marker_sha_is_required(exported, tmp_path_factory):
    """A marker rewritten without file_sha256 (the consistent-rewrite
    tamper class) must fail verification, not pass leniently."""
    import shutil
    _, _, bundle = exported
    clone = str(tmp_path_factory.mktemp("marker") / "0001.model.bundle")
    shutil.copytree(bundle, clone)
    okp = os.path.join(clone, ab.MANIFEST_NAME + ab.OK_SUFFIX)
    marker = json.load(open(okp))
    del marker["file_sha256"]
    with open(okp, "w") as f:
        json.dump(marker, f)
    rep = ab.verify_bundle(clone)
    assert not rep["ok"] and "file_sha256" in rep["error"]


def test_consistently_rewritten_manifest_bad_rows_report(
        exported, tmp_path_factory):
    """A manifest rewritten CONSISTENTLY with its marker but holding
    a non-string member name must come back as a verdict (and be
    skipped by the watcher scan), never a TypeError from the path
    join — the report-don't-raise contract for every tamper shape."""
    import hashlib
    import shutil
    _, _, bundle = exported
    clone = str(tmp_path_factory.mktemp("rows") / "0001.model.bundle")
    shutil.copytree(bundle, clone)
    manp = os.path.join(clone, ab.MANIFEST_NAME)
    man = json.load(open(manp))
    man["members"].append({"name": 5, "bytes": 1, "sha256": "x"})
    man_bytes = json.dumps(man, sort_keys=True, indent=1).encode()
    with open(manp, "wb") as f:
        f.write(man_bytes)
    with open(os.path.join(clone, ab.MANIFEST_NAME + ab.OK_SUFFIX),
              "w") as f:
        json.dump({"format_version": 1, "bytes": len(man_bytes),
                   "file_sha256":
                   hashlib.sha256(man_bytes).hexdigest()}, f)
    rep = ab.verify_bundle(clone)
    assert not rep["ok"] and "row is malformed" in rep["error"]
    with pytest.raises(ab.BundleError):
        ab.load_bundle(clone)


def test_in_place_reexport_preserves_zero_compile_boot(tmp_path):
    """Re-exporting FROM a bundle (the default in-place path) must
    pass the original serialized blobs through: a deserialized Loaded
    executable does not re-serialize faithfully (its payload comes
    back without compiled symbols), and the silent failure mode was a
    bundle that 'exports OK' but rebuilds everything at boot."""
    snap = _snapshot(tmp_path)
    bundle = _export(tmp_path, snap)
    assert _export(tmp_path, bundle) == bundle   # in place
    rep = ab.verify_bundle(bundle)
    assert rep["ok"] and rep["programs"] > 0
    rows = np.random.RandomState(1).rand(3, 24).astype(np.float32)
    sink = MemorySink()
    sess, _, summary = _serve_once(bundle, rows, Monitor(sink))
    assert [r for r in sink.records if r["event"] == "compile"] == []
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art["hits"] == rep["programs"] and art["rebuilds"] == 0
    assert sess.warmup_programs == 0
    snap = _snapshot(tmp_path)
    conf = str(tmp_path / "run.conf")
    with open(conf, "w") as f:
        f.write(SYNTH)
    stream = str(tmp_path / "mon.jsonl")
    assert LearnTask().run([conf, "task=export", "model_in=%s" % snap,
                            "monitor=jsonl",
                            "monitor_path=%s" % stream]) == 0
    recs = [json.loads(l) for l in open(stream) if l.strip()]
    validate_records(recs)
    (exp,) = [r for r in recs if r["event"] == "export"]
    assert exp["programs"] > 0 and exp["bytes"] > 0
    assert exp["out"].endswith("0001.model.bundle")


def test_ckpt_verify_bundle_tamper_matrix(exported, capsys):
    """Any tampered byte in any member — a serialized executable, the
    snapshot, the commit marker — fails ckpt_verify with exit 1."""
    import tools.ckpt_verify as cv
    tmp_path, snap, bundle = exported
    assert cv.main([bundle]) == 0
    assert cv.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "(bundle, format v1" in out
    # tampered executable member
    prog = os.path.join(bundle, "prog-0000.pkl")
    orig = open(prog, "rb").read()
    try:
        with open(prog, "wb") as f:
            f.write(orig[:-32] + b"\0" * 32)  # same size, flipped bytes
        assert cv.main([bundle]) == 1
        assert "sha256" in capsys.readouterr().out
        assert cv.main([str(tmp_path)]) == 1   # dir scan catches it too
        capsys.readouterr()
    finally:
        with open(prog, "wb") as f:
            f.write(orig)
    # tampered snapshot inside the bundle
    sp = os.path.join(bundle, ab.SNAPSHOT_MEMBER)
    sorig = open(sp, "rb").read()
    try:
        with open(sp, "wb") as f:
            f.write(sorig[:-8])
        assert cv.main([bundle]) == 1
        capsys.readouterr()
    finally:
        with open(sp, "wb") as f:
            f.write(sorig)
    # tampered-but-parseable JSON in the commit marker: a verdict
    # (exit 1), never an AttributeError traceback — and the watcher's
    # read-only scan must survive it too (report-don't-raise)
    okp = os.path.join(bundle, ab.MANIFEST_NAME + ab.OK_SUFFIX)
    okorig = open(okp, "rb").read()
    try:
        with open(okp, "wb") as f:
            f.write(b"[]")
        rep = ab.verify_bundle(bundle)
        assert not rep["ok"] and "not a JSON object" in rep["error"]
        assert cv.main([bundle]) == 1
        capsys.readouterr()
        with pytest.raises(ab.BundleError):
            ab.load_bundle(bundle)
        from cxxnet_tpu.serve.swap import latest_verified
        c, _ = latest_verified(str(tmp_path))   # falls back to snapshot
        assert c == 1
    finally:
        with open(okp, "wb") as f:
            f.write(okorig)
    # uncommitted: explicit target fails; a dir scan reports + skips
    os.rename(okp, okp + ".bak")
    try:
        assert cv.main([bundle]) == 1
        assert "uncommitted" in capsys.readouterr().out
        assert cv.main([str(tmp_path)]) == 0
        assert "UNCOMMITTED" in capsys.readouterr().out
    finally:
        os.rename(okp + ".bak", okp)
    assert cv.main([bundle]) == 0


def test_truncated_executable_via_faultfs(tmp_path, faultfs, capsys):
    """The fault-injection path: a bundle exported to a remote store
    whose executable member suffers a torn write (truncated tail)
    must fail ckpt_verify with exit 1."""
    import tools.ckpt_verify as cv
    t = NetTrainer(CFG, mesh=make_mesh(1, 1))
    t.init_model()
    snap = str(tmp_path / "0001.model.npz")
    t.save_model(snap)
    bundle = "fault://store/0001.model.bundle"
    _export(tmp_path, snap, out=bundle)
    assert ab.verify_bundle(bundle)["ok"]
    assert cv.main([bundle]) == 0
    capsys.readouterr()
    # torn re-write of one executable member: the injected truncation
    # drops the tail bytes between write and durability
    victim = "fault://store/0001.model.bundle/prog-0001.pkl"
    data = faultfs.store[victim]
    faultfs.truncate_tail = 64
    from cxxnet_tpu.utils.stream import open_stream
    with open_stream(victim, "wb") as f:
        f.write(data)
    faultfs.clear_faults()
    rep = ab.verify_bundle(bundle)
    assert not rep["ok"] and "prog-0001" in rep["error"]
    assert cv.main([bundle]) == 1
    assert "CORRUPT" in capsys.readouterr().out


# -- the cold-start contract ----------------------------------------------


def _serve_once(model_path, rows, monitor):
    from cxxnet_tpu.serve import ServeSession
    s = ServeSession(CFG, model_path=model_path, monitor=monitor)
    out = s.predict(rows)
    summary = s.close()
    return s, out, summary


def test_bundle_boot_zero_compiles_and_parity(exported):
    """export -> boot serve from the bundle: zero compile events
    end-to-end (warmup included), every program an artifact hit, and
    outputs byte-identical to a snapshot boot."""
    _, snap, bundle = exported
    rows = np.random.RandomState(7).rand(5, 24).astype(np.float32)
    sink = MemorySink()
    sess, out_b, summary = _serve_once(bundle, rows, Monitor(sink))
    validate_records(sink.records)
    assert [r for r in sink.records if r["event"] == "compile"] == []
    assert sess.warmup_programs == 0     # nothing needed compiling
    assert summary["compile_events"] == 0
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art["fingerprint_match"] is True
    assert art["rebuilds"] == 0
    assert art["hits"] == len(ab.bundle_manifest(bundle)["programs"]) \
        and art["hits"] > 0
    _, out_s, _ = _serve_once(snap, rows, Monitor(MemorySink()))
    assert np.array_equal(out_b, out_s)


def test_fingerprint_mismatch_rebuilds_with_one_warning(
        exported, monkeypatch):
    """A bundle sealed on a 'different' runtime: every key re-lowers
    (honest rebuild accounting), exactly ONE warning fires, and the
    served outputs are still identical — the fallback changes where
    compile time is paid, never the results."""
    _, snap, bundle = exported
    real = ab.runtime_fingerprint
    monkeypatch.setattr(
        ab, "runtime_fingerprint",
        lambda mesh=None: dict(real(mesh), jaxlib="0.0.0-elsewhere"))
    rows = np.random.RandomState(7).rand(5, 24).astype(np.float32)
    sink = MemorySink()
    sess, out_m, summary = _serve_once(bundle, rows, Monitor(sink))
    validate_records(sink.records)
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    nprog = len(ab.bundle_manifest(bundle)["programs"])
    assert art["fingerprint_match"] is False
    assert art["hits"] == 0 and art["rebuilds"] == nprog
    warns = [r for r in sink.records if r["event"] == "warning"
             and r["code"] == "artifact_fingerprint_mismatch"]
    assert len(warns) == 1
    # warmup re-lowered+compiled every reachable program
    compiles = [r for r in sink.records if r["event"] == "compile"]
    assert len(compiles) == nprog and sess.warmup_programs == nprog
    # post-warmup steady state is still compile-free
    assert summary["compile_events"] == 0
    monkeypatch.setattr(ab, "runtime_fingerprint", real)
    _, out_s, _ = _serve_once(snap, rows, Monitor(MemorySink()))
    assert np.array_equal(out_m, out_s)


def test_pred_boots_from_bundle(exported):
    """``model_in = <bundle>`` on the trainer path (task=pred):
    loads the inner snapshot, installs the sealed pred executables,
    and predicts identically to the snapshot."""
    from cxxnet_tpu.io.data import DataBatch
    _, snap, bundle = exported
    rows = np.random.RandomState(3).rand(8, 24).astype(np.float32)
    batch = DataBatch(data=rows,
                      label=np.zeros((8, 1), np.float32))
    tb = NetTrainer(CFG, mesh=make_mesh(1, 1))
    tb.load_model(bundle)
    assert len(tb.programs) > 0          # sealed executables resident
    ts = NetTrainer(CFG, mesh=make_mesh(1, 1))
    ts.load_model(snap)
    assert np.array_equal(tb.predict(batch), ts.predict(batch))
    # the full-bucket pred dispatch runs a bundle-installed program
    key = ("pred",) + areg.pred_sig((8, 24), np.dtype(np.float32),
                                    True, 0,
                                    (tb.graph.num_nodes - 1,))
    assert key in tb.programs


# -- hot-swap -------------------------------------------------------------


def test_watcher_flips_to_new_bundle_without_compiles(tmp_path):
    """The fleet watcher treats a newly committed bundle as a
    verified upgrade — and the shadow 'build' deserializes instead of
    compiling, so the flip skips the shadow-build compile time."""
    from cxxnet_tpu.serve import ServeSession
    from cxxnet_tpu.serve.router import ModelRouter
    from cxxnet_tpu.serve.swap import SnapshotWatcher, latest_verified
    mdir = tmp_path / "models"
    mdir.mkdir()
    snap1 = _snapshot(mdir, "0001.model.npz")
    sink = MemorySink()
    mon = Monitor(sink)
    router = ModelRouter()
    router.register("m", ServeSession(CFG, model_path=snap1,
                                      monitor=mon), 1, snap1)
    watcher = SnapshotWatcher(
        router, "m", str(mdir),
        builder=lambda p: ServeSession(CFG, model_path=p, monitor=mon),
        monitor=mon)
    assert watcher.check_once() is None  # nothing newer yet
    snap2 = _snapshot(mdir, "0002.model.npz")
    bundle2 = _export(tmp_path, snap2)
    # same counter, both verified: the bundle wins the scan
    c, path = latest_verified(str(mdir))
    assert c == 2 and path == bundle2
    sink.clear()
    rec = watcher.check_once()
    assert rec is not None and rec["new_counter"] == 2
    assert rec["path"] == bundle2
    # the shadow build paid zero compiles: every program deserialized
    assert [r for r in sink.records if r["event"] == "compile"] == []
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art["hits"] > 0 and art["rebuilds"] == 0
    assert rec["warmup_programs"] == 0
    router.close_all(drain=True)


def test_watcher_same_counter_snapshot_to_bundle_upgrade(tmp_path):
    """The headline deploy loop: the fleet serves NNNN.model.npz and
    an export seals NNNN.model.bundle beside it. The watcher must
    upgrade to the bundle at the SAME counter (and not flap back and
    forth afterwards)."""
    from cxxnet_tpu.serve import ServeSession
    from cxxnet_tpu.serve.router import ModelRouter
    from cxxnet_tpu.serve.swap import SnapshotWatcher
    mdir = tmp_path / "models"
    mdir.mkdir()
    snap1 = _snapshot(mdir, "0001.model.npz")
    mon = Monitor(MemorySink())
    router = ModelRouter()
    router.register("m", ServeSession(CFG, model_path=snap1,
                                      monitor=mon), 1, snap1)
    watcher = SnapshotWatcher(
        router, "m", str(mdir),
        builder=lambda p: ServeSession(CFG, model_path=p, monitor=mon),
        monitor=mon)
    assert watcher.check_once() is None
    bundle1 = _export(tmp_path, snap1)
    rec = watcher.check_once()
    assert rec is not None and rec["new_counter"] == 1
    assert rec["path"] == bundle1 and rec["warmup_programs"] == 0
    # stable afterwards: already on the bundle, no repeat swap
    assert watcher.check_once() is None
    assert router.resolve("m").path == bundle1
    router.close_all(drain=True)


# -- conf-less cold start ------------------------------------------------


def test_confless_bundle_boot_first_reply_and_closed_loop(exported):
    """A boot that knows only the bundle (the serve contract read from
    its manifest, ``serve_cfg_from_bundle``): the first reply and a
    closed-loop client after it come with every program an artifact
    hit, none rebuilt, a matching fingerprint and no compile event."""
    from cxxnet_tpu.serve import ServeSession, run_closed_loop
    _, _, bundle = exported
    sink = MemorySink()
    sess = ServeSession(ab.serve_cfg_from_bundle(bundle),
                        model_path=bundle, monitor=Monitor(sink))
    try:
        first = sess.predict(np.zeros((1, 24), np.float32))
        agg = run_closed_loop(
            sess, np.random.RandomState(0).rand(16, 24)
            .astype(np.float32), 1, 4)
    finally:
        summary = sess.close()
    assert first.shape[0] == 1 and agg["ok"] == 4
    assert validate_records(sink.records) == []
    assert [r for r in sink.records if r["event"] == "compile"] == []
    assert sess.warmup_programs == 0
    assert summary["compile_events"] == 0
    (art,) = [r for r in sink.records if r["event"] == "artifact_load"]
    assert art["hits"] > 0 and art["rebuilds"] == 0
    assert art["fingerprint_match"] is True

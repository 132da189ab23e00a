"""cxxlint: the analyzer's own test suite plus the tier-1 gate.

Three layers:

1. fixture corpus (tests/fixtures/lint/): one positive and one
   negative mini-tree per check — every check is pinned both firing
   and passing, independent of the real tree's state;
2. machinery: suppressions (reason required, unused flagged), the
   baseline round trip, CLI exit codes (0 clean / 1 findings /
   2 usage);
3. the gate: ``run_lint`` over the real ``cxxnet_tpu/`` + ``tools/``
   asserts ZERO unsuppressed findings, which is what makes cxxlint a
   permanent regression fence rather than a one-shot audit.

Plus targeted regression tests for the real bugs this PR's lint run
surfaced and fixed (watcher swap race, checkpoint counter race,
frontend emit latch).
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from cxxnet_tpu.lint import all_checks, run_lint
from cxxnet_tpu.lint.core import write_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIX = os.path.join(REPO, "tests", "fixtures", "lint")


def lint(subdir, **kw):
    root = os.path.join(FIX, subdir)
    assert os.path.isdir(root), root
    return run_lint([root], **kw)


def codes(result):
    return sorted({f.code for f in result.findings})


def keys(result, code):
    return sorted(f.key for f in result.findings if f.code == code)


# -- fixture corpus: each check fires and passes -------------------------


def test_recompile_fires_on_unregistered_jit_and_lower():
    res = lint("recompile_bad")
    assert codes(res) == ["CXL001"]
    ks = keys(res, "CXL001")
    assert any("jax.jit" in k for k in ks)
    assert any(".lower(...)" in k for k in ks)


def test_recompile_passes_registered_builders_and_str_lower():
    res = lint("recompile_good")
    assert res.findings == []


def test_locks_fires_on_unlocked_cross_thread_write():
    res = lint("locks_bad")
    assert codes(res) == ["CXL002"]
    assert keys(res, "CXL002") == ["Watcher.count"]


def test_locks_passes_when_write_is_under_declared_lock():
    res = lint("locks_good")
    assert res.findings == []


def test_hotpath_fires_reachable_and_locked_variants_only():
    res = lint("hotpath_bad")
    assert codes(res) == ["CXL003"]
    ks = keys(res, "CXL003")
    assert any(k.startswith("NetTrainer._fetch:np.asarray") for k in ks)
    assert any(k.startswith("locked:NetTrainer.update_many") for k in ks)
    # the sync in the function NOT reachable from a root is silent
    assert not any("offpath" in k for k in ks)


def test_hotpath_passes_off_path_host_work():
    res = lint("hotpath_good")
    assert res.findings == []


def test_schema_fires_both_directions():
    res = lint("schema_bad")
    assert codes(res) == ["CXL004"]
    assert keys(res, "CXL004") == ["orphan-validator:orphan_kind",
                                   "unvalidated:mystery_kind"]


def test_schema_passes_and_sees_wrapper_emitters():
    # the _emit wrapper call is an emit site (the grep guard's blind
    # spot): good_kind has an emitter, so no orphan-validator fires
    res = lint("schema_good")
    assert res.findings == []


def test_config_drift_fires_both_directions_and_deprecated_escape():
    root = os.path.join(FIX, "config_bad")
    res = run_lint([root], doc_dir=os.path.join(root, "doc"))
    assert codes(res) == ["CXL005"]
    assert keys(res, "CXL005") == ["stale-doc:stale_key",
                                   "undocumented:mystery_key"]


def test_config_drift_passes_with_prose_mentions():
    root = os.path.join(FIX, "config_good")
    res = run_lint([root], doc_dir=os.path.join(root, "doc"))
    assert res.findings == []


def test_config_drift_stale_direction_skips_partial_scans(tmp_path):
    """Verify-drive regression: a one-file scan against the real doc/
    tree must not call every documented key stale — the stale
    direction requires the primary config consumer in the scan set."""
    p = _write(tmp_path, "one.py",
               "def set_param(self, name, val):\n"
               "    if name == 'batch_size':\n        pass\n")
    res = run_lint([p], doc_dir=os.path.join(REPO, "doc"))
    assert not any(f.key.startswith("stale-doc:")
                   for f in res.findings), codes(res)


def test_swallow_fires_on_pass_bodies():
    res = lint("swallow_bad")
    assert codes(res) == ["CXL006"]
    assert len(res.findings) == 2          # typed and bare handlers


def test_swallow_passes_handled_and_suppressed():
    res = lint("swallow_good")
    assert res.findings == []
    assert len(res.suppressed) == 1
    f, reason = res.suppressed[0]
    assert f.code == "CXL006" and "sentinel" in reason


# -- machinery: suppressions, baseline, CLI ------------------------------


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return str(p)


def test_suppression_requires_reason(tmp_path):
    p = _write(tmp_path, "a.py",
               "try:\n    x = 1\nexcept Exception:\n"
               "    pass  # cxxlint: disable=CXL006\n")
    res = run_lint([p])
    cs = codes(res)
    assert "CXL000" in cs       # reasonless directive is itself flagged
    assert "CXL006" in cs       # and does NOT suppress the finding


def test_unused_suppression_and_unknown_code_flagged(tmp_path):
    p = _write(tmp_path, "a.py",
               "x = 1  # cxxlint: disable=CXL006 -- nothing here\n"
               "y = 2  # cxxlint: disable=CXL999 -- no such check\n")
    res = run_lint([p])
    ks = keys(res, "CXL000")
    assert any(k.startswith("unused:") for k in ks)
    assert any(k.startswith("unknown-code:CXL999") for k in ks)


def test_markdown_reasonless_suppression_is_flagged(tmp_path):
    """Review fix: '<!-- cxxlint: disable=CXL005 -->' must not parse
    the '-->' close as reason '>' — a reasonless markdown directive
    does not suppress and is itself a CXL000 finding, exactly like the
    Python form."""
    import cxxnet_tpu.lint.core as core
    bad = core.SourceFile(
        "x.md", "<!-- cxxlint: disable=CXL005 -->\n| `k` | row |\n")
    (sup,) = bad.suppressions.values()
    assert sup.reason == "" and sup.codes == ["CXL005"]
    good = core.SourceFile(
        "y.md", "| `k` | <!-- cxxlint: disable=CXL005 -- migration note -->\n")
    (sup,) = good.suppressions.values()
    assert sup.reason == "migration note"


def test_malformed_baseline_entry_is_usage_error(tmp_path):
    """Review fix: a baseline entry missing code/path/key must exit 2
    (usage), not die with a KeyError traceback that make/CI reads as
    exit 1 'findings present'."""
    bl = tmp_path / "baseline.json"
    bl.write_text('{"findings": [{"code": "CXL006", "path": "x.py"}]}')
    from cxxnet_tpu.lint.core import LintError
    p = _write(tmp_path, "a.py", "x = 1\n")
    with pytest.raises(LintError, match="missing code/path/key"):
        run_lint([p], baseline_path=str(bl))
    r = _cli([p, "--baseline", str(bl)])
    assert r.returncode == 2, (r.returncode, r.stderr)


def test_standalone_comment_suppresses_next_line(tmp_path):
    p = _write(tmp_path, "a.py",
               "try:\n    x = 1\nexcept Exception:\n"
               "    # cxxlint: disable=CXL006 -- covered by caller\n"
               "    pass\n")
    res = run_lint([p])
    assert res.findings == []
    assert len(res.suppressed) == 1


def test_select_does_not_flag_other_checks_suppressions(tmp_path):
    # a CXL006 suppression must not read as 'unused' when only CXL001
    # ran — the directive's check never had the chance to fire
    p = _write(tmp_path, "a.py",
               "try:\n    x = 1\nexcept Exception:\n"
               "    pass  # cxxlint: disable=CXL006 -- fine\n")
    res = run_lint([p], select=["CXL001"])
    assert res.findings == []


def test_baseline_round_trip(tmp_path):
    src = ("try:\n    x = 1\nexcept Exception:\n    pass\n")
    p = _write(tmp_path, "a.py", src)
    res = run_lint([p])
    assert codes(res) == ["CXL006"]
    bl = str(tmp_path / "baseline.json")
    write_baseline(bl, res.findings)
    res2 = run_lint([p], baseline_path=bl)
    assert res2.findings == [] and len(res2.baselined) == 1
    # a NEW instance of the same problem still fails the gate
    p2 = _write(tmp_path, "b.py", src)
    res3 = run_lint([p, p2], baseline_path=bl)
    assert [f.path for f in res3.findings] == [p2]


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    p = _write(tmp_path, "a.py", "def broken(:\n")
    res = run_lint([p])
    assert keys(res, "CXL000") == ["parse-error"]


def _cli(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "cxxnet_tpu.lint"] + args,
        capture_output=True, text=True, cwd=cwd,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_cli_exit_codes_and_json():
    # fixture scans pass a nonexistent --doc-dir: the stale-doc
    # direction of CXL005 is only meaningful over the full tree
    nodoc = ["--doc-dir", os.path.join(FIX, "no-such-doc-dir")]
    clean = _cli([os.path.join(FIX, "swallow_good"), "--format", "json",
                  "--no-baseline"] + nodoc)
    assert clean.returncode == 0, clean.stderr
    data = json.loads(clean.stdout)
    assert data["counts"]["findings"] == 0
    assert data["counts"]["suppressed"] == 1
    dirty = _cli([os.path.join(FIX, "swallow_bad"), "--format", "json",
                  "--no-baseline"] + nodoc)
    assert dirty.returncode == 1
    data = json.loads(dirty.stdout)
    assert {f["code"] for f in data["findings"]} == {"CXL006"}
    assert all(f["path"] and f["line"] > 0 and f["message"]
               for f in data["findings"])
    usage = _cli(["/no/such/path"])
    assert usage.returncode == 2
    badflag = _cli(["--no-such-flag"])
    assert badflag.returncode == 2
    badsel = _cli([os.path.join(FIX, "swallow_bad"),
                   "--select", "CXL999"])
    assert badsel.returncode == 2


def test_at_least_five_checks_registered():
    cs = [c.code for c in all_checks()]
    assert len(cs) >= 5
    for code in ("CXL001", "CXL002", "CXL003", "CXL004", "CXL005",
                 "CXL006"):
        assert code in cs


# -- THE GATE: the real tree stays clean ---------------------------------


def test_tree_is_lint_clean():
    """Tier-1 regression fence: zero unsuppressed findings over
    cxxnet_tpu/ + tools/ with the committed (empty) baseline. A new
    recompile site, unlocked cross-thread write, hot-path sync, schema
    or config drift, or silent swallow fails this test."""
    res = run_lint(
        [os.path.join(REPO, "cxxnet_tpu"), os.path.join(REPO, "tools")],
        doc_dir=os.path.join(REPO, "doc"),
        baseline_path=os.path.join(REPO, "cxxnet_tpu", "lint",
                                   "baseline.json"))
    assert res.findings == [], "\n".join(f.render()
                                         for f in res.findings)
    # the committed baseline stays EMPTY: new debt must be fixed or
    # suppressed-with-reason, not grandfathered silently
    with open(os.path.join(REPO, "cxxnet_tpu", "lint",
                           "baseline.json")) as f:
        assert json.load(f)["findings"] == []


def test_gate_catches_lock_discipline_in_fixed_modules():
    """Satellite pin: the three modules whose CXL002 findings were
    FIXED (not baselined) stay clean under the lock-discipline check
    alone — the fix cannot quietly regress."""
    res = run_lint(
        [os.path.join(REPO, "cxxnet_tpu", "serve", "swap.py"),
         os.path.join(REPO, "cxxnet_tpu", "serve", "router.py"),
         os.path.join(REPO, "cxxnet_tpu", "nnet", "checkpoint.py"),
         os.path.join(REPO, "cxxnet_tpu", "serve", "batcher.py")],
        select=["CXL002"])
    assert res.findings == [], "\n".join(f.render()
                                         for f in res.findings)


# -- regression pins for the real bugs the lint run surfaced -------------


def test_watcher_concurrent_check_once_single_swap(tmp_path, monkeypatch):
    """The race CXL002 flagged in swap.py: two concurrent check_once
    calls (poll thread + direct caller) both saw the same new snapshot
    and would both shadow-build and swap. Serialized now: exactly one
    build, one swap; the second call sees the bumped counter."""
    from cxxnet_tpu.serve import swap as swap_mod
    from cxxnet_tpu.serve.router import ModelRouter

    class FakeSession:
        def __init__(self):
            self.warmup_programs = 0

        def close(self, drain=True):
            return {"requests": 0, "compile_events": 0}

    router = ModelRouter()
    router.register("m", FakeSession(), counter=1, path="old")

    monkeypatch.setattr(swap_mod, "latest_verified",
                        lambda d, min_counter=-1: (2, "snap-2"))
    started = threading.Event()
    release = threading.Event()
    builds = []

    def builder(path):
        builds.append(path)
        started.set()
        assert release.wait(5)
        return FakeSession()

    w = swap_mod.SnapshotWatcher(router, "m", str(tmp_path), builder)
    t1 = threading.Thread(target=w.check_once)
    t1.start()
    assert started.wait(5)              # first call is mid-build
    t2 = threading.Thread(target=w.check_once)
    t2.start()
    release.set()
    t1.join(5)
    t2.join(5)
    assert builds == ["snap-2"]         # ONE build, not two
    assert w.swaps == 1
    assert router.resolve("m").counter == 2


def test_checkpoint_counters_exact_under_async_commits(tmp_path):
    """The CXL002 finding in checkpoint.py: commits/failures are
    written on the writer thread and read from the training thread —
    now lock-guarded; N async saves == N commits, no lost updates."""
    import numpy as np
    from cxxnet_tpu.nnet.checkpoint import CheckpointManager

    class FakeTrainer:
        def gather_snapshot(self):
            return {"param/x/wmat": np.zeros((2, 2), np.float32)}, \
                {"counter": 0}

    mgr = CheckpointManager(
        FakeTrainer(), lambda c: str(tmp_path / ("%04d.model.npz" % c)),
        model_dir=str(tmp_path), async_=True)
    for i in range(1, 9):
        mgr.save(i)
    mgr.close()
    with mgr._lock:
        assert mgr.commits == 8 and mgr.failures == 0


def test_emit_latch_warns_once_across_threads(capsys):
    """The telemetry-failure latch (the frontend/batcher CXL006 +
    CXL002 findings): SafeEmitter is the single shared implementation,
    it never raises, and N concurrent failures print exactly one
    stderr line."""
    from cxxnet_tpu.monitor import SafeEmitter
    from cxxnet_tpu.serve.frontend import FleetServer

    class BoomMon:
        enabled = True

        def emit(self, kind, **fields):
            raise IOError("disk full")

    emit = SafeEmitter(BoomMon(), "test-emitter")
    threads = [threading.Thread(target=lambda: emit("serve_http",
                                                    status="ok"))
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    err = capsys.readouterr().err
    assert err.count("telemetry emit failed") == 1
    # and the frontend routes through it (the fix cannot quietly
    # revert to a hand-rolled latch)
    srv = FleetServer.__new__(FleetServer)   # no engines needed
    srv._safe_emit = SafeEmitter(BoomMon(), "cxxnet_tpu serve frontend")
    for _ in range(3):
        srv._emit("serve_http", status="ok")
    assert capsys.readouterr().err.count("telemetry emit failed") == 1


def test_warn_once_never_raises_on_dead_sink():
    """Review fix: warn_once is called from fallback paths that were
    infallible before they warned (shard autodetect, the checkpoint
    writer's dir-fsync warning) — a dead sink must not turn the
    warning into a crash or flip a successful commit to failed."""
    from cxxnet_tpu.monitor import Monitor

    class BoomSink:
        enabled = True

        def write(self, record):
            raise IOError("disk full")

    mon = Monitor(BoomSink())
    mon.warn_once("test_code", "message")       # must not raise
    mon.warn_once("test_code", "message")       # latch still dedupes


def test_schema_check_fails_loudly_without_schema_module(tmp_path):
    """Anti-rot (the old grep guard's 'pattern rotted' assert): emit
    sites with no schema module in the scan set is a finding, not a
    silent no-op — a moved schema.py cannot disable the gate."""
    p = _write(tmp_path, "app.py",
               "def run(mon):\n    mon.emit(\"some_kind\", a=1)\n")
    res = run_lint([p], select=["CXL004"])
    assert keys(res, "CXL004") == ["no-schema-module"]

"""Horizontal fleet tier (cxxnet_tpu/fleet/): balancer routing +
retry-on-replica-loss, fleet-wide quotas, autoscale decisions, canary
promote/rollback, enriched /healthz + port file, and the replica
PROCESS path (spawn / kill / self-heal) — the first live multi-process
coverage in tier-1 (shared-nothing OS processes need no cross-process
collectives, so this runs on the CPU backend where the jax two-process
spawn tests must skip)."""

import http.client
import json
import os
import signal
import threading
import time
import types

import numpy as np
import pytest

from cxxnet_tpu.fleet import (CanaryRollout, FleetBalancer,
                              FleetController, FleetTierConfig,
                              ReplicaManager, SpawnError,
                              canary_decision, classify_load,
                              models_spec, version_of)
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_record, validate_records
from cxxnet_tpu.serve import FleetServer
from cxxnet_tpu.serve.frontend import BinaryClient
from cxxnet_tpu.utils.config import parse_config

from test_fleet import FLEET_MLP_CONF, _save_mlp_snapshot


# -- pure: config grammar --------------------------------------------------


def test_fleet_tier_config_parse_and_defaults():
    c = FleetTierConfig([
        ("model_in", "snap.npz"), ("fleet_replicas", "2"),
        ("fleet_max_replicas", "6"), ("fleet_slo_p99_ms", "100"),
        ("canary_fraction", "0.25")])
    assert c.models == [("default", "snap.npz", "")]
    assert c.min_replicas == 2 and c.max_replicas == 6
    assert c.slo_p99_ms == 100.0 and c.canary_fraction == 0.25
    # serve_models passes through, canary_model defaults to the first
    c = FleetTierConfig([
        ("serve_models", "main=./m1;alt=./m2|1,8"),
        ("canary_source", "./m1b")])
    assert c.models == [("main", "./m1", ""), ("alt", "./m2", "1,8")]
    assert c.canary_model == "main"
    assert c.models_with_source("./new") == \
        [("main", "./new", ""), ("alt", "./m2", "1,8")]
    assert c.target_version(c.models_with_source("./new")) == "new"


def test_fleet_tier_config_errors():
    with pytest.raises(ValueError):
        FleetTierConfig([])                      # no model source
    with pytest.raises(ValueError):
        FleetTierConfig([("model_in", "x"), ("fleet_replicas", "0")])
    with pytest.raises(ValueError):              # initial > max
        FleetTierConfig([("model_in", "x"), ("fleet_replicas", "5"),
                         ("fleet_max_replicas", "2")])
    with pytest.raises(ValueError):
        FleetTierConfig([("model_in", "x"),
                         ("canary_fraction", "1.5")])
    with pytest.raises(ValueError):              # unknown canary model
        FleetTierConfig([("serve_models", "a=./x"),
                         ("canary_source", "./y"),
                         ("canary_model", "ghost")])
    with pytest.raises(ValueError):              # both listeners off
        FleetTierConfig([("model_in", "x"), ("fleet_http_port", "-1"),
                         ("fleet_binary_port", "-1")])


def test_models_spec_roundtrip_and_version_of():
    from cxxnet_tpu.serve import FleetConfig
    entries = [("a", "./x", ""), ("b", "./y", "1,8")]
    assert FleetConfig._parse_models(models_spec(entries)) == entries
    plain = [("a", "./x", ""), ("b", "./y", "")]
    assert FleetConfig._parse_models(models_spec(plain)) == plain
    assert version_of("/m/0002.model.bundle") == "0002.model.bundle"
    assert version_of("/m/dir/") == "dir"


# -- pure: autoscale classification ---------------------------------------


def _tier(**over):
    pairs = [("model_in", "x")] + [(k, str(v)) for k, v in
                                   over.items()]
    return FleetTierConfig(pairs)


def test_classify_load_overload_signals():
    t = _tier(fleet_slo_p99_ms=100)
    # queues present but under the hi watermark: the steady band
    base = {"requests": 100, "ok": 100, "shed": 0, "errors": 0,
            "p99_ms": 10.0, "queue_rows": 8, "max_batch": 16,
            "ready": 2}
    assert classify_load(base, t)[0] == "steady"
    # queued rows beyond fleet dispatch capacity
    assert classify_load(dict(base, queue_rows=40), t)[0] \
        == "overload"
    # shed rate over threshold
    assert classify_load(dict(base, shed=10), t)[0] == "overload"
    # p99 over the SLO even with short queues
    assert classify_load(dict(base, p99_ms=150.0), t)[0] == "overload"
    # no SLO configured: p99 alone never triggers
    assert classify_load(dict(base, p99_ms=150.0),
                         _tier())[0] != "overload"


def test_classify_load_idle_and_steady():
    t = _tier(fleet_slo_p99_ms=100)
    assert classify_load({"requests": 0, "queue_rows": 0, "ready": 1,
                          "max_batch": 16}, t)[0] == "idle"
    # traffic but queues near-empty and p99 well under SLO
    low = {"requests": 50, "ok": 50, "shed": 0, "p99_ms": 20.0,
           "queue_rows": 0, "max_batch": 16, "ready": 2}
    assert classify_load(low, t)[0] == "idle"
    # p99 above half the SLO: not idle (don't flap around the SLO)
    assert classify_load(dict(low, p99_ms=80.0), t)[0] == "steady"
    # queue present but under hi threshold: steady
    assert classify_load(dict(low, queue_rows=8), t)[0] == "steady"


def test_take_window_carries_datapath_health(balancer_pair):
    """The autoscaler's window gained the data-path signals
    (channel_depth, forwards, coalesce_fill) — present, sane, and
    transparent to classify_load."""
    bal, reps, _, _ = balancer_pair
    rows = np.zeros((1, 64), np.float32)
    bal.take_window()                      # reset
    for _ in range(3):
        code, _ = _http_predict(bal.http_port, "gold", rows)
        assert code == 200
    w = bal.take_window()
    assert w["requests"] == 3 and w["forwards"] == 3
    assert w["coalesce_fill"] == 1.0       # coalescing off by default
    assert w["channel_depth"] >= 0
    t = _tier()
    assert classify_load(w, t)[0] in ("idle", "steady")


def test_canary_decision_matrix():
    t = _tier(canary_min_requests=20, canary_max_error_rate=0.05,
              canary_p99_ratio=2.0)
    base = {"ok": 500, "errors": 0, "requests": 500, "p99_ms": 10.0}
    good = {"ok": 100, "errors": 0, "requests": 100, "p99_ms": 12.0}
    assert canary_decision(base, good, t)[0] == "promote"
    # not enough samples -> wait
    assert canary_decision(base, {"ok": 5, "errors": 0,
                                  "requests": 5, "p99_ms": 1.0},
                           t)[0] == "wait"
    # error rate beyond baseline + allowance -> rollback
    bad = {"ok": 80, "errors": 20, "requests": 100, "p99_ms": 10.0}
    assert canary_decision(base, bad, t)[0] == "rollback"
    # latency blowup -> rollback
    slow = {"ok": 100, "errors": 0, "requests": 100, "p99_ms": 25.0}
    assert canary_decision(base, slow, t)[0] == "rollback"
    # baseline itself erroring: canary only needs to not be WORSE
    flaky_base = {"ok": 90, "errors": 10, "requests": 100,
                  "p99_ms": 10.0}
    ok_ish = {"ok": 93, "errors": 7, "requests": 100, "p99_ms": 11.0}
    assert canary_decision(flaky_base, ok_ish, t)[0] == "promote"


# -- serve-layer hooks: port file + enriched healthz ----------------------


def test_fleet_server_port_file_and_health_snapshot(tmp_path):
    snap = tmp_path / "0001.model.npz"
    _save_mlp_snapshot(snap)
    pf = tmp_path / "ports.json"
    cfg = parse_config(FLEET_MLP_CONF) + [
        ("serve_models", "main=%s" % snap),
        ("serve_http_port", "0"), ("serve_binary_port", "0"),
        ("serve_swap_poll_s", "0"),
        ("serve_port_file", str(pf))]
    server = FleetServer(cfg)
    try:
        server.start()
        ports = json.loads(pf.read_text())
        assert ports["pid"] == os.getpid()
        assert ports["http_port"] == server.http_port > 0
        assert ports["binary_port"] == server.binary_port > 0
        # enriched health: the balancer's routing/autoscale signals
        h = server.health_snapshot()
        assert h["ok"] and h["models"] == ["main"]
        assert h["queue_rows"] == 0 and h["requests"] == 0
        assert h["p99_ms"] >= 0 and "resident_bytes" in h
        m = h["model_health"][0]
        assert m["model"] == "main" and m["counter"] == 1
        assert m["compile_events"] == 0 and m["max_batch"] == 16
        # /v1/models identity satellite: version + fingerprint hash
        d = server.describe()[0]
        assert d["counter"] == 1 and d["bundle"] is False
        assert len(d["fingerprint_sha256"]) == 16
    finally:
        server.close()


# -- balancer over in-process replicas ------------------------------------


def _mk_replica_server(snap, seed_extra=()):
    cfg = parse_config(FLEET_MLP_CONF) + [
        ("serve_models", "default=%s" % snap),
        ("serve_http_port", "0"), ("serve_binary_port", "0"),
        ("serve_swap_poll_s", "0"), ("serve_max_delay_ms", "1"),
        ("serve_queue_rows", "4096"),
    ] + list(seed_extra)
    server = FleetServer(cfg)
    server.start()
    return server


def _http_predict(port, tenant, rows, model=""):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", "/v1/predict",
                     json.dumps({"model": model, "tenant": tenant,
                                 "rows": rows.tolist()}))
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


@pytest.fixture(scope="module")
def balancer_pair(tmp_path_factory):
    """A live balancer over two in-process replica FleetServers, with
    a fleet-wide quota for the shed tests."""
    tmp = tmp_path_factory.mktemp("fleet_tier")
    snap = tmp / "0001.model.npz"
    _save_mlp_snapshot(snap)
    reps = [_mk_replica_server(snap) for _ in range(2)]
    sink = MemorySink()
    mon = Monitor(sink)
    pairs = [("model_in", str(snap)), ("fleet_http_port", "0"),
             ("fleet_binary_port", "0"),
             ("fleet_health_poll_s", "0.1"),
             ("serve_quota", "free:5:2")]
    bal = FleetBalancer(FleetTierConfig(pairs), pairs, monitor=mon)
    bal.start()
    for i, r in enumerate(reps):
        bal.add_replica("r%d" % i, "127.0.0.1", r.http_port,
                        r.binary_port, "v1")
    yield bal, reps, sink, snap
    bal.close()
    for r in reps:
        r.close()


def test_balancer_routes_both_protocols_and_sheds_at_front(
        balancer_pair):
    bal, reps, sink, _ = balancer_pair
    rows = np.random.RandomState(0).rand(3, 64).astype(np.float32)
    code, body = _http_predict(bal.http_port, "gold", rows)
    assert code == 200 and body["rows"] == 3
    assert len(body["result"][0]) == 4
    bc = BinaryClient("127.0.0.1", bal.binary_port)
    try:
        status, out = bc.predict(rows, tenant="gold")
        assert status == "ok" and out.shape == (3, 4)
        np.testing.assert_allclose(out, np.asarray(body["result"]),
                                   rtol=1e-5, atol=1e-6)
        # fleet-wide quota sheds AT THE BALANCER: replicas never see
        # the over-quota rows (their request counters stay flat)
        before = sum(r.counters["requests"] for r in reps)
        shed = 0
        for _ in range(6):
            status, msg = bc.predict(rows[:1], tenant="free")
            if status == "over_quota":
                shed += 1
        assert shed >= 4
        after_ok = sum(r.counters["requests"] for r in reps)
        assert after_ok - before == 6 - shed
    finally:
        bc.close()
    sheds = [r for r in sink.records if r["event"] == "tenant_shed"]
    assert sheds and all(r["tenant"] == "free" for r in sheds)
    routes = [r for r in sink.records if r["event"] == "fleet_route"]
    assert {r["protocol"] for r in routes} == {"http", "binary"}
    assert all(r["replica"].startswith("r")
               for r in routes if r["status"] == "ok")
    assert validate_records(sink.records, strict=False) == []


def test_balancer_introspection_endpoints(balancer_pair):
    bal, reps, _, _ = balancer_pair
    conn = http.client.HTTPConnection("127.0.0.1", bal.http_port,
                                      timeout=30)
    try:
        # wait for at least one health poll to land
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            conn.request("GET", "/healthz")
            h = json.loads(conn.getresponse().read())
            if all(r["p99_ms"] is not None for r in h["replicas"]) \
                    and h["ready"] == 2:
                break
            time.sleep(0.1)
        assert h["ok"] and h["ready"] == 2
        assert {r["replica"] for r in h["replicas"]} == {"r0", "r1"}
        conn.request("GET", "/v1/models")
        m = json.loads(conn.getresponse().read())
        assert m["replica_versions"] == {"v1": 2}
        assert m["models"][0]["counter"] == 1
        assert len(m["models"][0]["fingerprint_sha256"]) == 16
        conn.request("GET", "/nope")
        r = conn.getresponse()
        assert r.status == 404 and r.read()
    finally:
        conn.close()


def test_balancer_drain_stops_routing(balancer_pair):
    bal, reps, _, _ = balancer_pair
    rows = np.zeros((1, 64), np.float32)
    assert bal.drain_replica("r1")
    before = reps[1].counters["requests"]
    for _ in range(8):
        code, _ = _http_predict(bal.http_port, "gold", rows)
        assert code == 200
    assert reps[1].counters["requests"] == before
    # undrain for the following tests
    with bal._lock:
        bal._reps["r1"].draining = False


def test_balancer_canary_pin_splits_deterministically(balancer_pair):
    bal, reps, sink, _ = balancer_pair
    with bal._lock:
        bal._reps["r1"].version = "v2"
    bal.pin_canary("v2", 0.25)
    rows = np.zeros((1, 64), np.float32)
    try:
        for _ in range(40):
            code, _ = _http_predict(bal.http_port, "gold", rows)
            assert code == 200
        stats = bal.version_stats()
        # deterministic interleave: floor(40 * 0.25) = 10 canary picks
        assert stats["v2"]["ok"] == 10
        assert stats["v1"]["ok"] == 30
        assert stats["v2"]["p99_ms"] > 0
    finally:
        bal.unpin_canary()
        with bal._lock:
            bal._reps["r1"].version = "v1"


def test_balancer_zero_failures_across_replica_loss(balancer_pair):
    """Hard-stop one replica under concurrent two-protocol traffic:
    idempotent retry + health marking must keep EVERY request
    answered ok."""
    bal, reps, sink, snap = balancer_pair
    rows = np.random.RandomState(1).rand(2, 64).astype(np.float32)
    stop = threading.Event()
    fails, oks = [], [0] * 4
    lock = threading.Lock()

    def bin_client(ci):
        bc = BinaryClient("127.0.0.1", bal.binary_port)
        try:
            while not stop.is_set():
                status, out = bc.predict(rows, tenant="gold")
                with lock:
                    if status == "ok":
                        oks[ci] += 1
                    else:
                        fails.append(status)
        finally:
            bc.close()

    def http_client(ci):
        while not stop.is_set():
            code, body = _http_predict(bal.http_port, "gold", rows)
            with lock:
                if code == 200:
                    oks[ci] += 1
                else:
                    fails.append((code, body))

    threads = [threading.Thread(target=bin_client, args=(i,))
               for i in range(2)]
    threads += [threading.Thread(target=http_client, args=(i,))
                for i in range(2, 4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.4)
        reps[0].close(drain=False)     # the replica "dies"
        time.sleep(0.8)                # traffic must keep flowing
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert fails == [], fails[:5]
    assert sum(oks) > 50
    # rebuild the lost replica for any later module tests
    bal.remove_replica("r0")
    reps[0] = _mk_replica_server(snap)
    bal.add_replica("r0", "127.0.0.1", reps[0].http_port,
                    reps[0].binary_port, "v1")


# -- controller + canary over a fake (in-process) replica manager ---------


class _FakeReplica:
    def __init__(self, rid, server, models, version, kind):
        self.replica_id = rid
        self.server = server
        self.models = list(models)
        self.version = version
        self.kind = kind
        self.http_port = server.http_port
        self.binary_port = server.binary_port
        self.stopped = False
        self.dead = False
        self.proc = types.SimpleNamespace(returncode=None)

    @property
    def pid(self):
        return 0

    def alive(self):
        return not self.dead


class _FakeManager:
    """ReplicaManager surface over in-process FleetServers — the
    controller/canary logic is identical; only process spawning is
    faked (the real path is covered by the process tests below)."""

    def __init__(self, fail_sources=()):
        self.fail_sources = set(fail_sources)
        self._seq = 0
        self._reps = {}
        self.spawn_log = []

    def spawn(self, models, version, kind="baseline"):
        for _, src, _ in models:
            if src in self.fail_sources:
                raise SpawnError("injected bad bundle: %s" % src)
        self._seq += 1
        rid = "f%03d" % self._seq
        server = _mk_replica_server(models[0][1])
        rep = _FakeReplica(rid, server, models, version, kind)
        self._reps[rid] = rep
        self.spawn_log.append((rid, version, kind))
        return rep

    def stop(self, rep, timeout_s=30.0):
        rep.stopped = True
        self._reps.pop(rep.replica_id, None)
        rep.server.close()
        return 0

    def poll_dead(self):
        dead = [r for r in self._reps.values()
                if r.dead and not r.stopped]
        for r in dead:
            del self._reps[r.replica_id]
        return dead

    def replicas(self):
        return list(self._reps.values())

    def close(self):
        for rep in list(self._reps.values()):
            self.stop(rep)


def _overload_stats(**over):
    base = {"requests": 200, "ok": 100, "shed": 50, "errors": 0,
            "p99_ms": 50.0, "queue_rows": 64, "max_batch": 16,
            "ready": 1, "replicas": 1, "window_s": 1.0}
    base.update(over)
    return base


def _idle_stats(**over):
    base = {"requests": 0, "ok": 0, "shed": 0, "errors": 0,
            "p99_ms": 0.0, "queue_rows": 0, "max_batch": 16,
            "ready": 2, "replicas": 2, "window_s": 1.0}
    base.update(over)
    return base


def test_controller_scales_out_in_and_self_heals(tmp_path):
    snap = tmp_path / "0001.model.npz"
    _save_mlp_snapshot(snap)
    sink = MemorySink()
    mon = Monitor(sink)
    pairs = [("model_in", str(snap)), ("fleet_replicas", "1"),
             ("fleet_min_replicas", "1"), ("fleet_max_replicas", "2"),
             ("fleet_http_port", "0"), ("fleet_binary_port", "-1"),
             ("fleet_scale_up_after_s", "0"),
             ("fleet_scale_down_after_s", "0"),
             ("fleet_health_poll_s", "0.1")]
    mgr = _FakeManager()
    ctl = FleetController(pairs, monitor=mon, manager=mgr)
    ctl.balancer.start()
    try:
        ctl.spawn_replica()
        assert ctl.ready_count() == 1
        # sustained overload -> scale out to max
        ctl._tick(stats=_overload_stats())
        ctl._tick(stats=_overload_stats(ready=2))
        assert ctl.ready_count() == 2
        # at max: a further overload tick must NOT spawn
        ctl._tick(stats=_overload_stats(ready=2))
        assert ctl.ready_count() == 2
        # sustained idle -> drain back to min, zero requests dropped
        ctl._tick(stats=_idle_stats())
        ctl._tick(stats=_idle_stats(ready=1))
        assert ctl.ready_count() == 1
        # at min: idle must not go below
        ctl._tick(stats=_idle_stats(ready=1))
        assert ctl.ready_count() == 1
        # a crashed replica is derouted and replaced (self-heal)
        victim = mgr.replicas()[0]
        victim.dead = True
        victim.server.close()
        ctl._tick(stats=_idle_stats(ready=0))
        assert ctl.ready_count() == 1
        assert mgr.replicas()[0].replica_id != victim.replica_id
        actions = [r["action"] for r in sink.records
                   if r["event"] == "fleet_scale"]
        assert "scale_out" in actions and "scale_in" in actions
        assert "replica_lost" in actions
        assert actions.count("replica_ready") >= 3
        assert validate_records(sink.records, strict=False) == []
    finally:
        ctl.close()


def test_canary_promotes_and_rolls_fleet(tmp_path):
    snap1 = tmp_path / "0001.model.npz"
    snap2 = tmp_path / "0002.model.npz"
    _save_mlp_snapshot(snap1, seed=0)
    _save_mlp_snapshot(snap2, seed=7)
    sink = MemorySink()
    mon = Monitor(sink)
    out = tmp_path / "decision.json"
    pairs = [("model_in", str(snap1)), ("fleet_replicas", "1"),
             ("fleet_http_port", "0"), ("fleet_binary_port", "-1"),
             ("fleet_health_poll_s", "0.1"),
             ("canary_source", str(snap2)),
             ("canary_fraction", "0.5"),
             ("canary_window_s", "0.2"),
             ("canary_min_requests", "5"),
             # some fifteen requests a version on a host six test
             # workers share: their p99s are noise (one stall reads
             # as a slow canary and rolls back); the latency rule is
             # test_canary_decision_matrix's
             ("canary_p99_ratio", "1000"),
             ("canary_out", str(out))]
    mgr = _FakeManager()
    ctl = FleetController(pairs, monitor=mon, manager=mgr)
    assert ctl.canary is not None and ctl.canary.state == "armed"
    ctl.balancer.start()
    try:
        ctl.spawn_replica()
        ctl.canary.arm()
        assert ctl.canary.state == "observing"
        assert ctl.ready_count(kind="canary") == 1
        rows = np.zeros((1, 64), np.float32)
        for _ in range(30):
            code, _ = _http_predict(ctl.balancer.http_port, "t", rows)
            assert code == 200
        time.sleep(0.25)               # let the window elapse
        ctl.canary.step()
        assert ctl.canary.state == "promoted"
        # the whole fleet now serves the new version; pin removed
        assert ctl.current_version() == "0002.model.npz"
        assert all(r.version == "0002.model.npz"
                   for r in mgr.replicas())
        assert ctl.balancer._pin_version is None
        assert ctl.ready_count(kind="canary") == 0
        # new-version replicas actually answer
        code, _ = _http_predict(ctl.balancer.http_port, "t", rows)
        assert code == 200
        # the decision record: emitted, schema-valid, and on disk
        rec = json.loads(out.read_text())
        assert rec["phase"] == "promote"
        assert rec["baseline_version"] == "0001.model.npz"
        assert rec["canary_version"] == "0002.model.npz"
        assert rec["canary"]["requests"] >= 5
        assert validate_record(rec) == []
        assert any(r["event"] == "canary" and r["phase"] == "start"
                   for r in sink.records)
        assert validate_records(sink.records, strict=False) == []
    finally:
        ctl.close()


def test_canary_bad_bundle_rolls_back_and_baseline_survives(tmp_path):
    """The injected-bad-bundle acceptance path: the canary replica
    fails to boot, the rollout rolls back automatically, and the good
    version keeps serving."""
    snap1 = tmp_path / "0001.model.npz"
    _save_mlp_snapshot(snap1)
    bad = str(tmp_path / "0002.model.npz")   # never written: bad source
    sink = MemorySink()
    mon = Monitor(sink)
    out = tmp_path / "decision.json"
    pairs = [("model_in", str(snap1)), ("fleet_replicas", "1"),
             ("fleet_http_port", "0"), ("fleet_binary_port", "-1"),
             ("canary_source", bad), ("canary_out", str(out))]
    mgr = _FakeManager(fail_sources={bad})
    ctl = FleetController(pairs, monitor=mon, manager=mgr)
    ctl.balancer.start()
    try:
        ctl.spawn_replica()
        ctl.canary.arm()
        assert ctl.canary.state == "rolled_back"
        rec = json.loads(out.read_text())
        assert rec["phase"] == "rollback"
        assert "failed to boot" in rec["reason"]
        assert validate_record(rec) == []
        # the good version keeps serving, unpinned
        assert ctl.balancer._pin_version is None
        assert ctl.ready_count() == 1
        rows = np.zeros((1, 64), np.float32)
        code, _ = _http_predict(ctl.balancer.http_port, "t", rows)
        assert code == 200
    finally:
        ctl.close()


def test_canary_insufficient_traffic_rolls_back(tmp_path):
    """No traffic, no evidence: an unobserved version must not be
    promoted — after 3 windows without canary_min_requests the
    rollout rolls back."""
    snap1 = tmp_path / "0001.model.npz"
    snap2 = tmp_path / "0002.model.npz"
    _save_mlp_snapshot(snap1, seed=0)
    _save_mlp_snapshot(snap2, seed=7)
    pairs = [("model_in", str(snap1)), ("fleet_replicas", "1"),
             ("fleet_http_port", "-1"), ("fleet_binary_port", "0"),
             ("canary_source", str(snap2)),
             ("canary_window_s", "0.05"),
             ("canary_out", str(tmp_path / "d.json"))]
    mgr = _FakeManager()
    ctl = FleetController(pairs, manager=mgr)
    ctl.balancer.start()
    try:
        ctl.spawn_replica()
        ctl.canary.arm()
        time.sleep(0.06)
        ctl.canary.step()              # window elapsed: still waiting
        assert ctl.canary.state == "observing"
        time.sleep(0.12)               # past 3 windows
        ctl.canary.step()
        assert ctl.canary.state == "rolled_back"
        assert "insufficient" in ctl.canary.decision["reason"]
    finally:
        ctl.close()


def test_controller_reaps_wedged_replica(tmp_path):
    """A replica whose PROCESS is alive but whose /healthz is dead
    (deadlock) must be force-stopped and replaced — poll_dead alone
    would never see it."""
    snap = tmp_path / "0001.model.npz"
    _save_mlp_snapshot(snap)
    sink = MemorySink()
    mon = Monitor(sink)
    pairs = [("model_in", str(snap)), ("fleet_replicas", "1"),
             ("fleet_http_port", "-1"), ("fleet_binary_port", "0"),
             ("fleet_health_poll_s", "0.1"),
             ("fleet_wedged_after_s", "0.2")]
    mgr = _FakeManager()
    ctl = FleetController(pairs, monitor=mon, manager=mgr)
    ctl.balancer.start()
    try:
        ctl.spawn_replica()
        wedged = mgr.replicas()[0]
        # wedge it: the process stays "alive" but health dies (the
        # server closes its listeners; poll_dead still returns [])
        wedged.server.close(drain=False)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            ctl._tick(stats=_idle_stats(ready=1))
            live = mgr.replicas()
            if live and live[0].replica_id != wedged.replica_id:
                break
            time.sleep(0.1)
        live = mgr.replicas()
        assert live and live[0].replica_id != wedged.replica_id
        assert wedged.stopped                  # force-stopped, not leaked
        lost = [r for r in sink.records
                if r["event"] == "fleet_scale"
                and r["action"] == "replica_lost"]
        assert lost and "wedged" in lost[0]["reason"]
    finally:
        ctl.close()


def test_replica_manager_refuses_post_close_registration(tmp_path,
                                                         monkeypatch):
    """A spawn that completes after close() must stop the fresh
    process instead of leaking it (close raced a scale-out)."""
    from cxxnet_tpu.fleet.config import FleetTierConfig
    tier = FleetTierConfig([("model_in", str(tmp_path / "x.npz")),
                            ("fleet_dir", str(tmp_path / "run"))])
    mgr = ReplicaManager(str(tmp_path / "f.conf"), tier)
    mgr.close()

    class _Proc:
        pid = 4242
        returncode = None
        terminated = False

        def poll(self):
            return None

        def terminate(self):
            self.terminated = True

        def wait(self, timeout=None):
            return 0

        def kill(self):
            self.terminated = True

    proc = _Proc()
    pf = tmp_path / "run" / "r001.ports.json"

    def fake_popen(*a, **k):
        # the "replica" publishes its ports the moment it "boots"
        pf.write_text(json.dumps({"pid": 4242, "http_port": 1,
                                  "binary_port": 2}))
        return proc

    monkeypatch.setattr(
        "cxxnet_tpu.fleet.replica.subprocess.Popen", fake_popen)
    with pytest.raises(SpawnError, match="after the manager closed"):
        mgr.spawn(tier.models, "v1")
    assert proc.terminated                     # the orphan was stopped
    assert mgr.replicas() == []


# -- the real thing: replica OS processes ---------------------------------


@pytest.fixture(scope="module")
def process_fleet(tmp_path_factory):
    """A FleetController over two REAL replica processes spawned from
    a config file through the standard CLI — shared by the process
    tests; its sink carries the full stream."""
    tmp = tmp_path_factory.mktemp("fleet_proc")
    snap = tmp / "models" / "0001.model.npz"
    snap.parent.mkdir()
    _save_mlp_snapshot(snap)
    conf = tmp / "fleet.conf"
    conf.write_text(FLEET_MLP_CONF + """
serve_max_delay_ms = 1
serve_queue_rows = 4096
""")
    sink = MemorySink()
    mon = Monitor(sink)
    pairs = parse_config(FLEET_MLP_CONF) + [
        ("model_in", str(snap)), ("fleet_replicas", "2"),
        ("fleet_min_replicas", "2"), ("fleet_max_replicas", "3"),
        ("fleet_http_port", "0"), ("fleet_binary_port", "0"),
        ("fleet_health_poll_s", "0.2"),
        ("fleet_scale_interval_s", "0.2"),
        ("fleet_dir", str(tmp / "run")),
        ("serve_quota", "free:5:2")]
    ctl = FleetController(pairs, conf_path=str(conf), monitor=mon)
    ctl.start()
    yield ctl, sink
    ctl.close()


def test_replica_processes_serve_both_protocols(process_fleet):
    ctl, sink = process_fleet
    assert ctl.ready_count() == 2
    reps = ctl.manager.replicas()
    assert all(r.alive() and r.pid > 0 for r in reps)
    assert len({r.pid for r in reps}) == 2       # distinct processes
    rows = np.random.RandomState(0).rand(2, 64).astype(np.float32)
    code, body = _http_predict(ctl.balancer.http_port, "gold", rows)
    assert code == 200 and body["rows"] == 2
    bc = BinaryClient("127.0.0.1", ctl.balancer.binary_port)
    try:
        status, out = bc.predict(rows, tenant="gold")
        assert status == "ok"
        np.testing.assert_allclose(out, np.asarray(body["result"]),
                                   rtol=1e-5, atol=1e-6)
    finally:
        bc.close()


def test_replica_process_kill_mid_traffic_zero_failures_and_heal(
        process_fleet):
    """The acceptance bar: SIGKILL a replica process under concurrent
    HTTP+binary load — zero failed requests (idempotent retry), the
    loss is derouted, and the controller self-heals back to
    fleet_min_replicas; zero post-warmup compiles on every surviving
    replica (healthz accounting)."""
    ctl, sink = process_fleet
    rows = np.random.RandomState(1).rand(2, 64).astype(np.float32)
    stop = threading.Event()
    fails, oks = [], [0] * 4
    lock = threading.Lock()

    def bin_client(ci):
        bc = BinaryClient("127.0.0.1", ctl.balancer.binary_port)
        try:
            while not stop.is_set():
                status, out = bc.predict(rows, tenant="gold")
                with lock:
                    if status == "ok":
                        oks[ci] += 1
                    else:
                        fails.append(status)
        finally:
            bc.close()

    def http_client(ci):
        while not stop.is_set():
            code, body = _http_predict(ctl.balancer.http_port,
                                       "gold", rows)
            with lock:
                if code == 200:
                    oks[ci] += 1
                else:
                    fails.append((code, body))

    threads = [threading.Thread(target=bin_client, args=(i,))
               for i in range(2)]
    threads += [threading.Thread(target=http_client, args=(i,))
                for i in range(2, 4)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.5)
        victim = ctl.manager.replicas()[0]
        os.kill(victim.pid, signal.SIGKILL)      # hard loss, no drain
        # traffic must keep flowing while the controller reaps the
        # corpse and spawns a replacement (jax boot takes seconds)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            live = [r for r in ctl.manager.replicas() if r.alive()]
            if len(live) >= 2 and victim.replica_id not in \
                    {r.replica_id for r in live}:
                break
            time.sleep(0.2)
        time.sleep(0.5)                # post-heal traffic window
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert fails == [], fails[:5]
    assert sum(oks) > 20
    # self-healed to min_replicas with a NEW process
    live = [r for r in ctl.manager.replicas() if r.alive()]
    assert len(live) == 2
    assert victim.replica_id not in {r.replica_id for r in live}
    actions = [r["action"] for r in sink.records
               if r["event"] == "fleet_scale"]
    assert "replica_lost" in actions
    # the retry machinery actually recovered requests off the corpse
    routes = [r for r in sink.records if r["event"] == "fleet_route"]
    assert all(r["status"] == "ok" for r in routes
               if r["tenant"] == "gold")
    # zero post-warmup compiles on every live replica (healthz)
    for rep in live:
        conn = http.client.HTTPConnection("127.0.0.1", rep.http_port,
                                          timeout=30)
        try:
            conn.request("GET", "/healthz")
            h = json.loads(conn.getresponse().read())
        finally:
            conn.close()
        assert all(m["compile_events"] == 0
                   for m in h["model_health"])
    assert validate_records(sink.records, strict=False) == []


def test_main_task_fleet_runs_and_drains(tmp_path, monkeypatch):
    """task = fleet end-to-end through the CLI: boots a one-replica
    fleet from a config file, serves for the duration, drains
    cleanly, and leaves a schema-valid stream."""
    from cxxnet_tpu.main import main
    snap = tmp_path / "models" / "0001.model.npz"
    snap.parent.mkdir()
    _save_mlp_snapshot(snap)
    conf = tmp_path / "fleet.conf"
    conf.write_text(FLEET_MLP_CONF + """
task = fleet
model_in = %s
fleet_replicas = 1
fleet_http_port = 0
fleet_binary_port = -1
fleet_duration_s = 0.5
fleet_dir = %s
monitor = jsonl
monitor_path = %s
""" % (snap, tmp_path / "run", tmp_path / "fleet.jsonl"))
    logs = []
    monkeypatch.setattr("builtins.print",
                        lambda *a, **k: logs.append(
                            " ".join(map(str, a))))
    rc = main([str(conf)])
    monkeypatch.undo()
    assert rc == 0, "\n".join(logs)
    txt = "\n".join(logs)
    assert "fleet: balancer" in txt and "1 replicas" in txt
    from cxxnet_tpu.monitor.schema import read_jsonl
    records = read_jsonl(str(tmp_path / "fleet.jsonl"))
    assert validate_records(records, strict=False) == []
    events = [r["event"] for r in records]
    assert "run_start" in events and "task_end" in events
    assert "fleet_scale" in events     # replica_ready at least


# -- the fleet parents never take a device ---------------------------------

_PARENT_PROBE = """
import json, sys
import jax
from jax._src import xla_bridge
from cxxnet_tpu.main import main
rc = main([sys.argv[1]])
print("PROBE " + json.dumps(
    {"rc": rc, "backends": xla_bridge.backends_are_initialized()}))
"""


@pytest.mark.parametrize("task", ["fleet", "fleet_balancer"])
def test_fleet_parent_never_initializes_a_backend(tmp_path, task):
    """A chip belongs to one process at a time, and the replica
    children need it: the ``task = fleet`` / ``task = fleet_balancer``
    parent must run its whole path — config, monitor, run_start,
    spawn, serve, drain — without ever initializing a jax backend
    (root/world come from the process-group state, not from
    ``jax.process_index()``). A fresh interpreter, because this test
    process initialized its backend in conftest."""
    import subprocess
    import sys
    snap = tmp_path / "models" / "0001.model.npz"
    snap.parent.mkdir()
    _save_mlp_snapshot(snap)
    conf = tmp_path / "parent.conf"
    conf.write_text(FLEET_MLP_CONF + """
task = %s
model_in = %s
fleet_replicas = 1
fleet_http_port = 0
fleet_binary_port = -1
fleet_duration_s = 0.3
fleet_dir = %s
fleet_registry = %s
monitor = jsonl
monitor_path = %s
""" % (task, snap, tmp_path / "run", tmp_path / "registry.json",
       tmp_path / "parent.jsonl"))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _PARENT_PROBE, str(conf)], cwd=repo,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("PROBE ")]
    probe = json.loads(line[len("PROBE "):])
    assert probe == {"rc": 0, "backends": False}, proc.stdout
    from cxxnet_tpu.monitor.schema import read_jsonl
    records = read_jsonl(str(tmp_path / "parent.jsonl"))
    assert validate_records(records, strict=False) == []
    (start,) = [r for r in records if r["event"] == "run_start"]
    assert start["platform"] == "none" and start["device_count"] == 0

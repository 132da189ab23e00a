"""Device-resident embedding index + the served /v1/embed + /v1/search
product (doc/retrieval.md).

The contract under test:

- :class:`EmbeddingIndex` validates, canonicalizes (cosine rows
  L2-normalized at BUILD time), and round-trips through a pickle-free
  ``.npz`` payload; a malformed payload is a typed ``IndexError_``.
- :class:`RetrievalEngine` answers EXACT top-k, id-for-id equal to the
  ``oracle_topk`` NumPy reference (tie-break: lowest corpus row), with
  zero post-warmup compiles and index bytes on the residency books.
- ``task = build_index`` seals ids + embeddings + metric + search
  programs into the model bundle; a fleet booting from it serves
  ``/v1/embed`` and ``/v1/search`` (both protocols, ``fan_out=1``
  composition) with ZERO compile events anywhere in the stream.
- A mid-traffic hot-swap flips model and index atomically: zero failed
  requests, zero post-warmup compiles on both engines, and no torn
  model/index pair observable through the composed fsearch path.
- ``ckpt_verify`` reports a bundle whose index member is missing or
  torn as CORRUPT (exit 1) — locally and through the fault-injection
  filesystem.
- A ``multi_logistic`` head serves per-label sigmoid scores (list per
  row, not an argmax) identically on both protocols.
"""

import json
import os
import shutil
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

from cxxnet_tpu.artifact import bundle as ab
from cxxnet_tpu.artifact.registry import (ProgramRegistry,
                                          ResidencyBudgetError,
                                          parse_key, search_sig)
from cxxnet_tpu.main import LearnTask
from cxxnet_tpu.monitor import MemorySink, Monitor
from cxxnet_tpu.monitor.schema import validate_records
from cxxnet_tpu.nnet.trainer import NetTrainer
from cxxnet_tpu.parallel import make_mesh
from cxxnet_tpu.retrieval import (INDEX_MEMBER, EmbeddingIndex,
                                  IndexError_, RetrievalEngine,
                                  l2_normalize, oracle_topk,
                                  self_recall)
from cxxnet_tpu.serve import FleetServer, ServeSession
from cxxnet_tpu.serve.frontend import (BinaryClient, parse_model_op,
                                       pack_search_result)
from cxxnet_tpu.utils.config import parse_config
from cxxnet_tpu.utils.faultfs import FaultFS
from tests.test_trainer import synth_idx

RETR_CONF = """
netconfig=start
layer[+1:h] = fullc:fc1
  nhidden = 8
  init_sigma = 0.1
layer[+1] = relu
layer[h->o] = fullc:fc2
  nhidden = 4
  init_sigma = 0.1
layer[+0] = softmax
netconfig=end
input_shape = 1,1,64
batch_size = 16
eta = 0.1
"""


@pytest.fixture
def faultfs():
    fs = FaultFS("fault").install()
    try:
        yield fs
    finally:
        fs.uninstall()


# -- the index artifact (pure numpy) -------------------------------------


def _rand_index(rows=12, dim=6, metric="dot", seed=0):
    rng = np.random.RandomState(seed)
    return EmbeddingIndex.build(
        ids=np.arange(100, 100 + rows), metric=metric,
        vectors=rng.randn(rows, dim).astype(np.float32))


def test_index_build_validates():
    ok = _rand_index()
    assert ok.rows == 12 and ok.dim == 6
    assert ok.nbytes == 12 * 6 * 4          # ids stay host-side
    with pytest.raises(IndexError_, match="index_metric"):
        _rand_index(metric="l2")
    with pytest.raises(IndexError_, match="non-empty"):
        EmbeddingIndex.build([], np.zeros((0, 4), np.float32))
    with pytest.raises(IndexError_, match="3 ids for 2"):
        EmbeddingIndex.build([1, 2, 3], np.zeros((2, 4), np.float32))
    bad = np.ones((2, 2), np.float32)
    bad[0, 0] = np.nan
    with pytest.raises(IndexError_, match="non-finite"):
        EmbeddingIndex.build([1, 2], bad)


def test_index_cosine_normalizes_at_build_not_load():
    idx = _rand_index(metric="cosine")
    np.testing.assert_allclose(
        np.linalg.norm(idx.vectors, axis=1), 1.0, atol=1e-6)
    # round trip preserves the bytes exactly: no re-normalization
    back = EmbeddingIndex.deserialize(idx.serialize())
    np.testing.assert_array_equal(back.vectors, idx.vectors)
    np.testing.assert_array_equal(back.ids, idx.ids)
    assert back.metric == "cosine"


def test_index_serialize_roundtrip_and_manifest_entry():
    idx = EmbeddingIndex.build(
        ids=[7, 3, 9], vectors=np.eye(3, 5, dtype=np.float32),
        metric="dot", node="fc2", meta={"source": "unit"})
    back = EmbeddingIndex.deserialize(idx.serialize())
    assert back.node == "fc2" and back.meta == {"source": "unit"}
    np.testing.assert_array_equal(back.ids, [7, 3, 9])
    entry = idx.manifest_entry()
    assert entry == {"member": INDEX_MEMBER, "metric": "dot",
                     "node": "fc2", "rows": 3, "dim": 5}


def test_index_deserialize_rejects_garbage_and_tampered_meta():
    with pytest.raises(IndexError_, match="unreadable"):
        EmbeddingIndex.deserialize(b"not an npz payload")
    idx = _rand_index()
    blob = idx.serialize()
    # tamper the metadata record so it disagrees with the arrays
    import io as _io
    z = np.load(_io.BytesIO(blob))
    rec = json.loads(bytes(z["meta"]).decode())
    rec["rows"] = 999
    buf = _io.BytesIO()
    np.savez(buf, ids=z["ids"], vectors=z["vectors"],
             meta=np.frombuffer(json.dumps(rec).encode(), np.uint8))
    with pytest.raises(IndexError_, match="disagrees"):
        EmbeddingIndex.deserialize(buf.getvalue())


def test_oracle_topk_ties_break_by_lowest_row():
    vec = np.zeros((4, 2), np.float32)
    vec[:, 0] = [1.0, 2.0, 2.0, 0.5]       # rows 1 and 2 tie
    idx = EmbeddingIndex.build(ids=[10, 11, 12, 13], vectors=vec)
    ids, scores = oracle_topk(idx, np.array([1.0, 0.0]), 3)
    np.testing.assert_array_equal(ids, [[11, 12, 10]])
    np.testing.assert_allclose(scores, [[2.0, 2.0, 1.0]])
    # k > corpus clips
    ids, _ = oracle_topk(idx, np.array([1.0, 0.0]), 99)
    assert ids.shape == (1, 4)


def test_search_sig_roundtrips_via_manifest_repr():
    key = ("search",) + search_sig(8, 16, 100, 10, "cosine", "float32")
    assert parse_key(repr(key)) == key


# -- the search engine (jax cpu, standalone registry) --------------------


@pytest.mark.parametrize("metric", ["dot", "cosine"])
def test_engine_exact_parity_and_zero_postwarmup_compiles(metric):
    rng = np.random.RandomState(1)
    idx = _rand_index(rows=20, dim=5, metric=metric, seed=2)
    eng = RetrievalEngine(idx, ProgramRegistry(), k=4,
                          buckets=(2, 4))
    compiled = eng.warmup(warm_run=True)
    assert compiled == 2
    assert eng.counters_snapshot()["compile_events"] == 0
    q = rng.randn(5, 5).astype(np.float32)   # chunks 4 + 1(pad->2)
    ids, scores = eng.search(q)
    oids, oscores = oracle_topk(idx, q, 4)
    np.testing.assert_array_equal(ids, oids)
    np.testing.assert_allclose(scores, oscores, atol=1e-5)
    snap = eng.counters_snapshot()
    assert snap["compile_events"] == 0 and snap["aot_hits"] == 2
    assert snap["pad_rows"] == 1


def test_engine_duplicate_scores_match_oracle_tie_break():
    vec = np.tile(np.array([[1.0, 0.0]], np.float32), (6, 1))
    idx = EmbeddingIndex.build(ids=np.arange(6), vectors=vec)
    eng = RetrievalEngine(idx, ProgramRegistry(), k=3, buckets=(1,))
    eng.warmup(warm_run=False)
    ids, _ = eng.search(np.array([1.0, 1.0], np.float32))
    oids, _ = oracle_topk(idx, np.array([1.0, 1.0]), 3)
    np.testing.assert_array_equal(ids, [[0, 1, 2]])
    np.testing.assert_array_equal(ids, oids)


def test_engine_k_and_shape_validation():
    idx = _rand_index(rows=6, dim=3)
    eng = RetrievalEngine(idx, ProgramRegistry(), k=3, buckets=(2,))
    eng.warmup(warm_run=False)
    ids, scores = eng.search(idx.vectors[0], k=2)   # 1-D query ok
    assert ids.shape == (1, 2) and scores.shape == (1, 2)
    with pytest.raises(ValueError, match="1..3"):
        eng.search(np.zeros((1, 3), np.float32), k=4)
    with pytest.raises(ValueError, match="1..3"):
        eng.search(np.zeros((1, 3), np.float32), k=0)
    with pytest.raises(ValueError, match="does not match the index"):
        eng.search(np.zeros((1, 7), np.float32))
    # k above the corpus caps at corpus rows (a static program dim)
    assert RetrievalEngine(idx, ProgramRegistry(), k=99).k == 6


def test_engine_budget_counts_index_bytes_typed_rejection():
    idx = _rand_index(rows=16, dim=8)
    eng = RetrievalEngine(idx, ProgramRegistry(), k=2, buckets=(1,))
    with pytest.raises(ResidencyBudgetError, match="embedding index"):
        eng.warmup(budget_bytes=idx.nbytes - 1)
    # exactly-at-budget admits
    assert eng.warmup(warm_run=False, budget_bytes=idx.nbytes) >= 0


def test_self_recall_is_one_on_distinct_corpus():
    idx = _rand_index(rows=10, dim=8, metric="cosine", seed=3)
    eng = RetrievalEngine(idx, ProgramRegistry(), k=1, buckets=(8,))
    eng.warmup(warm_run=False)
    assert self_recall(eng, sample=8) == 1.0


# -- op-suffix grammar (pure) --------------------------------------------


def test_parse_model_op_grammar():
    assert parse_model_op("m") == ("m", "", None)
    assert parse_model_op("") == ("", "", None)
    assert parse_model_op("m#embed") == ("m", "embed", None)
    assert parse_model_op("m#search:5") == ("m", "search", 5)
    assert parse_model_op("#fsearch:1") == ("", "fsearch", 1)


@pytest.mark.parametrize("bad", ["m#predict", "m#search:0",
                                 "m#search:x", "m#"])
def test_parse_model_op_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_model_op(bad)


def test_pack_search_result_wire_form():
    ids = np.array([[5, 2], [9, 5]], np.int64)
    scores = np.array([[0.75, 0.5], [1.0, -0.25]], np.float32)
    payload, extra = pack_search_result(ids, scores)
    assert payload.shape == (2, 4) and payload.dtype == np.float32
    np.testing.assert_array_equal(payload[:, :2].astype(np.int64), ids)
    np.testing.assert_array_equal(payload[:, 2:], scores)
    assert extra["k"] == 2 and extra["ids"] == [[5, 2], [9, 5]]


# -- build_index -> sealed bundle -> served fleet ------------------------


def _write_conf(tmp, n=80):
    # d=8 -> 64-pixel rows, matching input_shape = 1,1,64
    pimg, plab = synth_idx(str(tmp), n=n, d=8, name="retr")
    conf = """
data = train
iter = mnist
  path_img = "%s"
  path_label = "%s"
  silent = 1
iter = end
%s
model_dir = "%s"
print_step = 0
""" % (pimg, plab, RETR_CONF, tmp / "models")
    p = str(tmp / "run.conf")
    with open(p, "w") as f:
        f.write(conf)
    return p


def _snapshot(tmp, name, seed=0):
    t = NetTrainer(parse_config(RETR_CONF) + [("seed", str(seed))],
                   mesh=make_mesh(1, 1))
    t.init_model()
    path = str(tmp / "models" / name)
    t.save_model(path)
    return path


def _build_index(conf, snap, extra=()):
    argv = [conf, "task=build_index", "model_in=%s" % snap,
            "index_metric=cosine", "index_rows=48", "search_k=4",
            "search_buckets=1,4"] + list(extra)
    assert LearnTask().run(argv) == 0
    return ab.default_bundle_path(snap)


@pytest.fixture(scope="module")
def indexed(tmp_path_factory):
    """conf + snapshot + committed indexed bundle, shared by the
    read-only tests (the build pays the compile window once)."""
    tmp = tmp_path_factory.mktemp("retrieval")
    (tmp / "models").mkdir()
    conf = _write_conf(tmp)
    snap = _snapshot(tmp, "0001.model.npz")
    bundle = _build_index(conf, snap)
    return tmp, conf, snap, bundle


def test_build_index_seals_model_and_index_together(indexed):
    tmp, conf, snap, bundle = indexed
    man = ab.bundle_manifest(bundle)
    entry = man["index"]
    assert entry["member"] == INDEX_MEMBER
    assert entry["metric"] == "cosine" and entry["node"] == ""
    assert entry["rows"] == 48 and entry["dim"] == 4
    assert entry["k"] == 4 and entry["buckets"] == [1, 4]
    # the index member rides the members table like every member
    members = {m["name"]: m for m in man["members"]}
    assert INDEX_MEMBER in members
    assert members[INDEX_MEMBER]["bytes"] > 0
    # search programs sealed beside the pred ladder
    keys = [parse_key(p["key"]) for p in man["programs"]]
    searches = [k for k in keys if k[0] == "search"]
    assert len(searches) == 2               # buckets 1 and 4
    assert {k[1] for k in searches} == {1, 4}
    idx = EmbeddingIndex.deserialize(ab.read_index_member(bundle))
    assert idx.rows == 48 and idx.metric == "cosine"
    rep = ab.verify_bundle(bundle)
    assert rep["ok"], rep


def test_read_index_member_absent_and_verified(indexed):
    _, _, snap, bundle = indexed
    # a plain export has no index member: empty payload, no error
    assert ab.read_index_member(bundle) != b""
    man = dict(ab.bundle_manifest(bundle))
    man.pop("index")
    assert ab.read_index_member(bundle, man) == b""


@pytest.fixture(scope="module")
def retrieval_fleet(indexed):
    """One live fleet booted from the sealed indexed bundle, watching
    the model_dir for hot-swaps; sink collects the whole stream."""
    tmp, conf, snap, bundle = indexed
    sink = MemorySink()
    cfg = parse_config(RETR_CONF) + [
        ("serve_models", "main=%s" % (tmp / "models")),
        ("serve_http_port", "0"), ("serve_binary_port", "0"),
        ("serve_swap_poll_s", "0.05"),
        ("serve_max_delay_ms", "1"),
        ("serve_queue_rows", "4096"),
    ]
    server = FleetServer(cfg, monitor=Monitor(sink))
    server.start()
    yield server, sink, tmp, conf
    server.close()


def _post(port, path, body):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read())
    finally:
        conn.close()


def test_fleet_serves_embed_and_search_zero_compiles(retrieval_fleet):
    server, sink, tmp, _ = retrieval_fleet
    rows = np.random.RandomState(7).rand(3, 64).astype(
        np.float32).tolist()
    st, body = _post(server.http_port, "/v1/embed",
                     {"model": "main", "rows": rows})
    assert st == 200 and len(body["result"]) == 3
    assert len(body["result"][0]) == 4
    q = np.asarray(body["result"], np.float32)
    st, sb = _post(server.http_port, "/v1/search",
                   {"model": "main", "rows": q.tolist(), "k": 3})
    assert st == 200 and sb["k"] == 3 and sb["rows"] == 3
    # exact parity vs the NumPy oracle over the sealed index
    bundle = ab.default_bundle_path(
        str(tmp / "models" / "0001.model.npz"))
    idx = EmbeddingIndex.deserialize(ab.read_index_member(bundle))
    oids, oscores = oracle_topk(idx, q, 3)
    np.testing.assert_array_equal(np.asarray(sb["ids"]), oids)
    np.testing.assert_allclose(np.asarray(sb["scores"], np.float32),
                               oscores, atol=1e-5)
    # fan_out=1 composes embed -> search in one request
    st, fb = _post(server.http_port, "/v1/search",
                   {"model": "main", "rows": rows, "fan_out": 1,
                    "k": 3})
    assert st == 200 and fb["ids"] == sb["ids"]
    # binary protocol: same ops through the model#op[:k] suffix
    bc = BinaryClient("127.0.0.1", server.binary_port)
    try:
        status, out = bc.predict(q, model="main#search:3", tenant="t")
        assert status == "ok" and out.shape == (3, 6)
        np.testing.assert_array_equal(out[:, :3].astype(np.int64),
                                      oids)
        np.testing.assert_allclose(out[:, 3:], oscores, atol=1e-5)
        status, out2 = bc.predict(np.asarray(rows, np.float32),
                                  model="main#fsearch:3", tenant="t")
        assert status == "ok"
        np.testing.assert_array_equal(out2[:, :3], out[:, :3])
    finally:
        bc.close()
    # ZERO compile events: engine counters and the whole stream
    h = server.health_snapshot()
    row = h["model_health"][0]
    assert row["compile_events"] == 0
    assert row["search_compile_events"] == 0
    assert row["search_aot_hits"] >= 2
    assert not [r for r in sink.records if r.get("event") == "compile"]
    # introspection carries the search contract + index residency
    d = server.describe()[0]
    assert d["index"]["rows"] == 48 and d["index"]["k"] == 4
    assert d["index"]["metric"] == "cosine"
    assert d["index"]["buckets"] == [1, 4]
    assert d["device_mem_bytes"] >= 48 * 4 * 4


def test_fleet_search_request_errors_are_typed(retrieval_fleet):
    server, _, _, _ = retrieval_fleet
    # wrong query dim
    st, body = _post(server.http_port, "/v1/search",
                     {"model": "main", "rows": [[0.0] * 7]})
    assert st == 400 and body["error"] == "bad_request"
    # k beyond the sealed depth is a request error, not a compile
    st, body = _post(server.http_port, "/v1/search",
                     {"model": "main", "rows": [[0.0] * 4], "k": 9})
    assert st == 400 and "search_k" in body["message"]
    st, body = _post(server.http_port, "/v1/search",
                     {"model": "main", "rows": [[0.0] * 4], "k": 0})
    assert st == 400
    # unknown op suffix through the binary model field
    bc = BinaryClient("127.0.0.1", server.binary_port)
    try:
        status, msg = bc.predict(np.zeros((1, 4), np.float32),
                                 model="main#knn", tenant="t")
        assert status == "bad_request" and "unknown serve op" in msg
    finally:
        bc.close()


def test_fleet_hot_swap_flips_model_and_index_atomically(
        retrieval_fleet, tmp_path):
    """The composed-fan-out acceptance smoke: concurrent fsearch
    clients, a generation-2 indexed bundle committed mid-traffic —
    zero failed requests, zero post-warmup compiles on both engines,
    and every answer matches generation 1 or generation 2 exactly
    (a torn model/index pair would answer with neither)."""
    server, sink, tmp, conf = retrieval_fleet
    probe = np.random.RandomState(11).rand(1, 64).astype(np.float32)

    def fsearch(rows):
        st, body = _post(server.http_port, "/v1/search",
                         {"model": "main", "rows": rows.tolist(),
                          "fan_out": 1, "k": 3})
        return st, body

    st, g1 = fsearch(probe)
    assert st == 200
    # gen-2: different weights -> different embeddings + index,
    # sealed OUTSIDE the model_dir then renamed in atomically
    side = tmp_path / "side" / "models"
    side.mkdir(parents=True)
    conf2 = _write_conf(tmp_path / "side")
    snap2 = _snapshot(tmp_path / "side", "0002.model.npz", seed=9)
    bundle2 = _build_index(conf2, snap2)

    stop = threading.Event()
    results = {"ok": 0, "fail": [], "answers": set()}
    lock = threading.Lock()

    def client(ci):
        while not stop.is_set():
            st, body = fsearch(probe)
            with lock:
                if st == 200:
                    results["ok"] += 1
                    results["answers"].add(
                        tuple(body["ids"][0])
                        + tuple(np.float32(s)
                                for s in body["scores"][0]))
                else:
                    results["fail"].append((st, body))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    # commit the new generation under load: one atomic rename of the
    # committed bundle dir (the .ok marker travels inside it)
    os.rename(bundle2, str(tmp / "models" / "0002.model.bundle"))
    server.notify_watchers()
    deadline = 30.0
    import time as _time
    t0 = _time.monotonic()
    while _time.monotonic() - t0 < deadline:
        if server.router.resolve("main").counter >= 2:
            break
        _time.sleep(0.05)
    _time.sleep(0.3)                 # traffic on the new generation
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert server.router.resolve("main").counter == 2
    st, g2 = fsearch(probe)
    assert st == 200
    assert results["fail"] == []
    assert results["ok"] > 0
    # no torn pair: every answer under load is exactly gen-1's or
    # gen-2's (ids AND scores)
    def key(body):
        return tuple(body["ids"][0]) + tuple(
            np.float32(s) for s in body["scores"][0])
    assert results["answers"] <= {key(g1), key(g2)}
    # both generations' engines: zero post-warmup compiles (search
    # included), and the stream holds no compile event at all
    row = server.health_snapshot()["model_health"][0]
    assert row["compile_events"] == 0
    assert row["search_compile_events"] == 0
    assert row["generation"] == 1
    assert not [r for r in sink.records if r.get("event") == "compile"]
    errs = validate_records([r for r in sink.records])
    assert not errs, errs[:5]


def test_session_budget_accounts_index_bytes(indexed):
    """The typed residency rejection covers weights + index as one
    book: a budget that fits the weights but not weights + index
    refuses the boot with ResidencyBudgetError naming the index."""
    tmp, conf, snap, bundle = indexed
    cfg = parse_config(RETR_CONF)
    session = ServeSession(cfg, model_path=bundle)
    try:
        idx_bytes = session.index_bytes
        weight_bytes = \
            session.engine.trainer.programs.residency.total_bytes
        assert idx_bytes == 48 * 4 * 4
        from cxxnet_tpu.serve.router import session_resident_bytes
        assert session_resident_bytes(session) == \
            weight_bytes + idx_bytes
    finally:
        session.close(drain=False)
    # between weights and weights+index: the index breaches it
    budget_mb = (weight_bytes + idx_bytes / 2) / 1e6
    with pytest.raises(ResidencyBudgetError, match="embedding index"):
        ServeSession(cfg + [("serve_device_mem_budget",
                             "%.9f" % budget_mb)], model_path=bundle)


def test_ckpt_verify_flags_missing_and_torn_index(indexed, capsys):
    """A bundle whose manifest lists an index member with missing or
    torn bytes is CORRUPT (exit 1) — the small-fix satellite."""
    import tools.ckpt_verify as cv
    _, _, snap, bundle = indexed
    assert cv.main([bundle]) == 0
    capsys.readouterr()
    member = os.path.join(bundle, INDEX_MEMBER)
    orig = open(member, "rb").read()
    # torn bytes (same member, truncated tail)
    try:
        with open(member, "wb") as f:
            f.write(orig[:-32])
        assert cv.main([bundle]) == 1
        assert "CORRUPT" in capsys.readouterr().out
        with pytest.raises(ab.BundleError):
            ab.read_index_member(bundle)
    finally:
        with open(member, "wb") as f:
            f.write(orig)
    # missing bytes entirely
    try:
        os.remove(member)
        assert cv.main([bundle]) == 1
        capsys.readouterr()
    finally:
        with open(member, "wb") as f:
            f.write(orig)
    # manifest names an index member absent from the members table
    man_path = os.path.join(bundle, ab.MANIFEST_NAME)
    man_orig = open(man_path, "rb").read()
    man = json.loads(man_orig)
    try:
        man["index"]["member"] = "ghost.npz"
        with open(man_path, "w") as f:
            json.dump(man, f)
        rep = ab.verify_bundle(bundle)
        assert not rep["ok"]
        assert cv.main([bundle]) == 1
        capsys.readouterr()
    finally:
        with open(man_path, "wb") as f:
            f.write(man_orig)
    assert cv.main([bundle]) == 0


def test_ckpt_verify_torn_index_via_faultfs(indexed, faultfs, capsys):
    """Fault-injection twin: an indexed bundle on a remote store whose
    index member suffers a torn write fails ckpt_verify with exit 1."""
    import tools.ckpt_verify as cv
    from cxxnet_tpu.utils.stream import open_stream
    _, _, snap, bundle = indexed
    remote = "fault://store/0001.model.bundle"
    # byte-copy the committed bundle (members first, marker last —
    # the same commit order the exporter uses)
    names = sorted(os.listdir(bundle),
                   key=lambda n: n.endswith(ab.OK_SUFFIX))
    for name in names:
        with open(os.path.join(bundle, name), "rb") as f:
            data = f.read()
        with open_stream("%s/%s" % (remote, name), "wb") as f:
            f.write(data)
    assert ab.verify_bundle(remote)["ok"]
    assert cv.main([remote]) == 0
    capsys.readouterr()
    victim = "%s/%s" % (remote, INDEX_MEMBER)
    data = faultfs.store[victim]
    faultfs.truncate_tail = 48
    with open_stream(victim, "wb") as f:
        f.write(data)
    faultfs.clear_faults()
    rep = ab.verify_bundle(remote)
    assert not rep["ok"] and INDEX_MEMBER in rep["error"]
    assert cv.main([remote]) == 1
    assert "CORRUPT" in capsys.readouterr().out


# -- multi-label serve: per-label sigmoid scores, both protocols ---------


MULTI_CONF = """
netconfig=start
layer[+1:h] = fullc:fc1
  nhidden = 8
  init_sigma = 0.3
layer[h->o] = fullc:fc2
  nhidden = 3
  init_sigma = 0.3
layer[+0] = multi_logistic
netconfig=end
input_shape = 1,1,16
batch_size = 8
eta = 0.1
"""


def test_multi_label_predict_roundtrip_both_protocols(tmp_path):
    """/v1/predict on a multi_logistic head answers the per-label
    sigmoid score LIST per row (not an argmax), identically on HTTP
    and the binary protocol."""
    t = NetTrainer(parse_config(MULTI_CONF) + [("seed", "4")],
                   mesh=make_mesh(1, 1))
    t.init_model()
    d = tmp_path / "models"
    d.mkdir()
    snap = str(d / "0001.model.npz")
    t.save_model(snap)
    cfg = parse_config(MULTI_CONF) + [
        ("serve_models", "ml=%s" % snap),
        ("serve_http_port", "0"), ("serve_binary_port", "0")]
    server = FleetServer(cfg)
    server.start()
    try:
        rows = np.random.RandomState(2).rand(4, 16).astype(np.float32)
        st, body = _post(server.http_port, "/v1/predict",
                         {"model": "ml", "rows": rows.tolist()})
        assert st == 200 and body["rows"] == 4
        http_out = np.asarray(body["result"], np.float32)
        # one sigmoid score per label per row — a 3-wide list, every
        # value strictly inside (0, 1), NOT collapsed to a class id
        assert http_out.shape == (4, 3)
        assert np.all((http_out > 0.0) & (http_out < 1.0))
        assert not np.allclose(http_out.sum(axis=1), 1.0)  # no softmax
        bc = BinaryClient("127.0.0.1", server.binary_port)
        try:
            status, bin_out = bc.predict(rows, model="ml", tenant="t")
        finally:
            bc.close()
        assert status == "ok" and bin_out.shape == (4, 3)
        np.testing.assert_allclose(bin_out, http_out, rtol=1e-5,
                                    atol=1e-6)
        # an index-less model bounces /v1/search as a typed 400
        st, body = _post(server.http_port, "/v1/search",
                         {"model": "ml", "rows": [[0.0] * 3]})
        assert st == 400
        assert "no embedding index" in body["message"]
    finally:
        server.close()

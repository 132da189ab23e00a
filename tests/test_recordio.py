"""RecordIO tests: format round-trip, native<->python interop, magic-
word escaping, sharded reads, im2rec tool, imgrec iterator pipeline."""

import os
import struct
import subprocess

import numpy as np
import pytest

from cxxnet_tpu.io.recordio import (KMAGIC, RecordIOReader,
                                    RecordIOWriter, native_available,
                                    pack_image_record,
                                    unpack_image_record)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ensure_built() -> bool:
    """Build the native lib/tools on demand (they are gitignored)."""
    if os.path.exists(os.path.join(REPO, "bin/im2rec")):
        return True
    try:
        subprocess.check_call(["make", "-s", "-C", REPO],
                              stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
    except (OSError, subprocess.CalledProcessError):
        return False
    return os.path.exists(os.path.join(REPO, "bin/im2rec"))


_HAVE_TOOLS = _ensure_built()


def _payloads(n=50, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        size = int(rng.randint(1, 2000))
        out.append(rng.bytes(size))
    # adversarial payloads containing the magic word at aligned offsets
    magic = struct.pack("<I", KMAGIC)
    out.append(magic)
    out.append(magic * 3)
    out.append(b"abcd" + magic + b"efgh")
    out.append(magic + b"xy")
    out.append(b"12" + magic)          # magic at unaligned offset
    out.append(b"")                    # empty record is valid, not EOF
    out.append(b"after-empty")         # records after it must survive
    return out


@pytest.mark.parametrize("wpy,rpy", [(True, True), (True, False),
                                     (False, True), (False, False)])
def test_roundtrip_interop(tmp_path, wpy, rpy):
    if (not wpy or not rpy) and not native_available():
        pytest.skip("native lib not built")
    path = str(tmp_path / "t.rec")
    w = RecordIOWriter(path, force_python=wpy)
    payloads = _payloads()
    for p in payloads:
        w.write_record(p)
    w.close()
    r = RecordIOReader(path, force_python=rpy)
    got = list(r)
    assert len(got) == len(payloads)
    for a, b in zip(got, payloads):
        assert a == b
    r.close()


@pytest.mark.parametrize("nparts", [2, 3, 5])
def test_sharded_read_covers_all(tmp_path, nparts):
    path = str(tmp_path / "s.rec")
    w = RecordIOWriter(path, force_python=True)
    payloads = _payloads(n=200, seed=3)
    for p in payloads:
        w.write_record(p)
    w.close()
    got = []
    for pi in range(nparts):
        r = RecordIOReader(path, pi, nparts, force_python=True)
        got.extend(list(r))
        r.close()
    assert sorted(got) == sorted(payloads), \
        "shard split lost/duplicated records"


@pytest.mark.skipif(not native_available(), reason="native lib not built")
def test_native_sharded_read(tmp_path):
    path = str(tmp_path / "ns.rec")
    w = RecordIOWriter(path, force_python=False)
    payloads = _payloads(n=100, seed=5)
    for p in payloads:
        w.write_record(p)
    w.close()
    got = []
    for pi in range(4):
        r = RecordIOReader(path, pi, 4, force_python=False)
        got.extend(list(r))
        r.close()
    assert sorted(got) == sorted(payloads)


def test_image_record_header():
    rec = pack_image_record(12345, 7.0, b"JPEGDATA")
    assert len(rec) == 24 + 8
    idx, label, payload = unpack_image_record(rec)
    assert (idx, label, payload) == (12345, 7.0, b"JPEGDATA")


def _write_jpegs(tmp_path, n=12, size=32):
    import cv2
    rng = np.random.RandomState(0)
    rows = []
    d = tmp_path / "imgs"
    d.mkdir()
    for i in range(n):
        img = rng.randint(0, 255, (size, size, 3), np.uint8)
        fn = "img%03d.jpg" % i
        cv2.imwrite(str(d / fn), img)
        rows.append("%d\t%d\t%s" % (i, i % 3, fn))
    lst = tmp_path / "img.lst"
    lst.write_text("\n".join(rows) + "\n")
    return str(lst), str(d)


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_im2rec_tool_and_imgrec_iterator(tmp_path):
    lst, root = _write_jpegs(tmp_path)
    rec = str(tmp_path / "data.rec")
    subprocess.check_call([os.path.join(REPO, "bin/im2rec"),
                           lst, root, rec], stdout=subprocess.DEVNULL)
    assert os.path.exists(rec)

    from cxxnet_tpu.io import create_iterator
    cfg = [("iter", "imgrec"), ("path_imgrec", rec), ("silent", "1"),
           ("input_shape", "3,32,32")]
    it = create_iterator(cfg, [("batch_size", "4"),
                               ("input_shape", "3,32,32")])
    it.init()
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data.shape == (4, 32, 32, 3)
    labels = sorted(int(l) for b in batches for l in b.label[:, 0])
    assert labels == sorted([i % 3 for i in range(12)])


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_im2rec_spaced_paths(tmp_path):
    """Image paths containing spaces pack intact: the native tool reads
    the rest of the line as the path (same bounded-split rule commit
    dea129b gave the Python imglist parser), instead of truncating at
    the first whitespace token and silently skipping the row."""
    import cv2
    d = tmp_path / "my imgs"
    d.mkdir()
    rng = np.random.RandomState(0)
    names = ["cat 01.jpg", "dog 02.jpg"]
    for fn in names:
        cv2.imwrite(str(d / fn),
                    rng.randint(0, 255, (16, 16, 3), np.uint8))
    lst = tmp_path / "img.lst"
    lst.write_text("".join("%d\t%d\tmy imgs/%s\n" % (i, i, fn)
                           for i, fn in enumerate(names)))
    rec = str(tmp_path / "sp.rec")
    subprocess.check_call([os.path.join(REPO, "bin/im2rec"),
                           str(lst), str(tmp_path) + "/", rec],
                          stdout=subprocess.DEVNULL)
    r = RecordIOReader(rec)
    seen = []
    while True:
        raw = r.next_record()
        if raw is None:
            break
        idx, label, payload = unpack_image_record(raw)
        assert cv2.imdecode(np.frombuffer(payload, np.uint8),
                            cv2.IMREAD_COLOR) is not None
        seen.append((idx, label))
    assert seen == [(0, 0.0), (1, 1.0)]


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_im2rec_numeric_first_token_spaced_path(tmp_path):
    """A spaced path whose FIRST token is numeric ('2012 photos/x.jpg')
    is ambiguous with an excess-labels row. When the assembled path
    exists on disk it must pack (with a warning), not hard-fail; when
    it does not, the error must mention the spaced-path case so the
    workaround is discoverable."""
    import cv2
    d = tmp_path / "2012 photos"
    d.mkdir()
    rng = np.random.RandomState(0)
    cv2.imwrite(str(d / "a.jpg"),
                rng.randint(0, 255, (16, 16, 3), np.uint8))
    lst = tmp_path / "img.lst"
    lst.write_text("0\t1\t2012 photos/a.jpg\n")
    rec = str(tmp_path / "num.rec")
    p = subprocess.run([os.path.join(REPO, "bin/im2rec"),
                        str(lst), str(tmp_path) + "/", rec],
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
    assert "spaced path" in p.stderr
    r = RecordIOReader(rec)
    idx, label, payload = unpack_image_record(r.next_record())
    assert (idx, label) == (0, 1.0)
    assert cv2.imdecode(np.frombuffer(payload, np.uint8),
                        cv2.IMREAD_COLOR) is not None

    # missing file: still an error, now with the spaced-path hint
    lst.write_text("0\t1\t2012 photos/missing.jpg\n")
    p = subprocess.run([os.path.join(REPO, "bin/im2rec"),
                        str(lst), str(tmp_path) + "/",
                        str(tmp_path / "num2.rec")],
                       capture_output=True, text=True)
    assert p.returncode != 0
    assert "spaced path" in p.stderr


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_im2rec_resize(tmp_path):
    lst, root = _write_jpegs(tmp_path, n=4, size=40)
    rec = str(tmp_path / "r.rec")
    subprocess.check_call([os.path.join(REPO, "bin/im2rec"),
                           lst, root, rec, "resize=20"],
                          stdout=subprocess.DEVNULL)
    import cv2
    r = RecordIOReader(rec)
    rec0 = r.next_record()
    _, _, payload = unpack_image_record(rec0)
    img = cv2.imdecode(np.frombuffer(payload, np.uint8),
                       cv2.IMREAD_COLOR)
    assert min(img.shape[:2]) == 20


def test_imgrec_distributed_parts(tmp_path):
    """part_index/num_parts shard a single archive without loss."""
    lst, root = _write_jpegs(tmp_path, n=20)
    rec = str(tmp_path / "d.rec")
    w = RecordIOWriter(rec, force_python=True)
    import cv2
    for i in range(20):
        img = (np.ones((8, 8, 3)) * (i * 10 % 255)).astype(np.uint8)
        ok, enc = cv2.imencode(".png", img)
        w.write_record(pack_image_record(i, float(i % 4),
                                         enc.tobytes()))
    w.close()
    from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator
    seen = []
    for pi in range(3):
        it = ImageRecordIterator()
        it.set_param("path_imgrec", rec)
        it.set_param("part_index", str(pi))
        it.set_param("num_parts", "3")
        it.set_param("silent", "1")
        it.init()
        while it.next():
            seen.append(it.value().index)
    assert sorted(seen) == list(range(20))


def test_raw_tensor_records(tmp_path):
    """Decode-free raw uint8 tensor records round-trip through the
    writer and the reader (the --pipeline-raw input path)."""
    import numpy as np
    from cxxnet_tpu.io.recordio import (RecordIOWriter,
                                        pack_raw_tensor_record,
                                        unpack_raw_tensor_record)

    rng = np.random.RandomState(0)
    imgs = [rng.randint(0, 255, (8, 6, 3), np.uint8) for _ in range(5)]
    p = str(tmp_path / "raw.rec")
    w = RecordIOWriter(p, force_python=True)
    for i, img in enumerate(imgs):
        w.write_record(pack_raw_tensor_record(i, float(i % 2), img))
    w.close()

    # direct unpack
    from cxxnet_tpu.io.recordio import RecordIOReader
    r = RecordIOReader(p, force_python=True)
    idx, lab, arr = unpack_raw_tensor_record(r.next_record())
    assert idx == 0 and lab == 0.0
    np.testing.assert_array_equal(arr, imgs[0])
    r.close()


@pytest.mark.parametrize("asked", [False, True],
                         ids=["not_asked", "asked"])
def test_raw_tensor_records_through_the_chain(tmp_path, asked):
    """Raw-tensor records through an imgrec chain: float32 pixels when
    nobody asked, uint8 once a consumer took the normalisation over
    (``IIterator.defer_normalize``) — the same pixels either way."""
    import numpy as np
    from cxxnet_tpu.io import create_iterator
    from cxxnet_tpu.io.recordio import (RecordIOWriter,
                                        pack_raw_tensor_record)

    rng = np.random.RandomState(0)
    imgs = [rng.randint(0, 255, (8, 6, 3), np.uint8) for _ in range(5)]
    p = str(tmp_path / "raw.rec")
    w = RecordIOWriter(p, force_python=True)
    for i, img in enumerate(imgs):
        w.write_record(pack_raw_tensor_record(i, float(i % 2), img))
    w.close()

    it = create_iterator(
        [("iter", "imgrec"), ("path_imgrec", p), ("silent", "1"),
         ("round_batch", "0")],
        [("batch_size", "5"), ("input_shape", "3,8,6")])
    it.init()
    if asked:
        mean, scale = it.defer_normalize()
        assert mean is None and scale == 1     # the identity spec
    (b,) = list(it)
    assert b.data.dtype == (np.uint8 if asked else np.float32)
    np.testing.assert_array_equal(np.asarray(b.data, np.uint8),
                                  np.stack(imgs))
    it.close()


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_im2rec_label_width_packs_all_labels(tmp_path):
    """label_width=3: the native tool packs all three list labels into
    the record ('ML' flag + extra f32s; the reference only validates
    them, tools/im2rec.cc:83-87) and the imgrec iterator reads them back
    without any path_imglist."""
    import cv2
    from cxxnet_tpu.io.recordio import unpack_image_labels

    rng = np.random.RandomState(3)
    d = tmp_path / "imgs"
    d.mkdir()
    rows = []
    want = {}
    for i in range(8):
        img = rng.randint(0, 255, (24, 24, 3), np.uint8)
        fn = "img%03d.jpg" % i
        cv2.imwrite(str(d / fn), img)
        labs = [float(i % 2), float((i >> 1) % 2), float((i >> 2) % 2)]
        want[i] = labs
        rows.append("%d\t%g\t%g\t%g\t%s" % (i, labs[0], labs[1],
                                            labs[2], fn))
    lst = tmp_path / "img.lst"
    lst.write_text("\n".join(rows) + "\n")
    rec = str(tmp_path / "ml.rec")
    subprocess.check_call([os.path.join(REPO, "bin/im2rec"), str(lst),
                           str(d), rec, "label_width=3"],
                          stdout=subprocess.DEVNULL)

    # raw record check: 'ML' flag + full vector via unpack_image_labels
    r = RecordIOReader(rec, force_python=True)
    n = 0
    for raw in iter(r.next_record, None):
        idx, lab0, payload = unpack_image_record(raw)
        labs = unpack_image_labels(raw)
        assert labs is not None and labs.shape == (3,)
        np.testing.assert_allclose(labs, want[idx])
        assert lab0 == want[idx][0]
        assert cv2.imdecode(np.frombuffer(payload, np.uint8),
                            cv2.IMREAD_COLOR) is not None
        n += 1
    assert n == 8

    # iterator path: label matrix carries the packed vectors
    from cxxnet_tpu.io import create_iterator
    cfg = [("iter", "imgrec"), ("path_imgrec", rec), ("silent", "1"),
           ("label_width", "3"), ("input_shape", "3,24,24")]
    it = create_iterator(cfg, [("batch_size", "4"),
                               ("input_shape", "3,24,24"),
                               ("label_width", "3")])
    it.init()
    got = {}
    for b in it:
        for k in range(b.data.shape[0]):
            got[int(b.inst_index[k])] = list(b.label[k])
    assert got == want


@pytest.mark.skipif(not _HAVE_TOOLS, reason="im2rec not built")
def test_multilabel_archive_cli_train_eval(tmp_path, monkeypatch):
    """pack(label_width=3) -> train a multi_logistic net with a
    label_vec range through the real CLI -> eval metric comes back:
    the archive-packed multi-label flow end to end."""
    import cv2
    from cxxnet_tpu.main import main

    rng = np.random.RandomState(5)
    d = tmp_path / "imgs"
    d.mkdir()
    rows = []
    for i in range(16):
        img = rng.randint(0, 255, (16, 16, 3), np.uint8)
        fn = "im%02d.jpg" % i
        cv2.imwrite(str(d / fn), img)
        rows.append("%d\t%d\t%d\t%d\t%s" % (i, i % 2, (i >> 1) % 2,
                                            (i >> 2) % 2, fn))
    lst = tmp_path / "img.lst"
    lst.write_text("\n".join(rows) + "\n")
    rec = str(tmp_path / "ml.rec")
    subprocess.check_call([os.path.join(REPO, "bin/im2rec"), str(lst),
                           str(d), rec, "label_width=3"],
                          stdout=subprocess.DEVNULL)

    conf = """
data = train
iter = imgrec
  path_imgrec = %s
  silent = 1
iter = end

eval = test
iter = imgrec
  path_imgrec = %s
  silent = 1
iter = end

label_vec[0,3) = tags
netconfig=start
layer[+1:h] = flatten
layer[h->o] = fullc:fc1
  nhidden = 3
  init_sigma = 0.01
layer[o->o] = multi_logistic
  target = tags
netconfig=end

input_shape = 3,16,16
label_width = 3
batch_size = 8
eta = 0.01
metric[tags,o] = rmse
num_round = 2
save_model = 1
model_dir = %s
print_step = 0
""" % (rec, rec, tmp_path / "models")
    cp = tmp_path / "ml.conf"
    cp.write_text(conf)
    logs = []
    monkeypatch.setattr("builtins.print",
                        lambda *a, **k: logs.append(" ".join(map(str, a))))
    main([str(cp)])
    txt = "\n".join(logs)
    assert "test-rmse[tags]:" in txt
    assert os.path.exists(str(tmp_path / "models" / "0002.model.npz"))


def test_imglist_short_rows_zero_pad(tmp_path):
    """A remap list whose rows carry fewer labels than label_width must
    zero-pad (not crash on the trailing path token)."""
    import cv2
    from cxxnet_tpu.io.iter_imgrec import ImageRecordIterator

    rec = str(tmp_path / "s.rec")
    w = RecordIOWriter(rec, force_python=True)
    img = (np.ones((8, 8, 3)) * 100).astype(np.uint8)
    ok, enc = cv2.imencode(".png", img)
    for i in range(4):
        w.write_record(pack_image_record(i, 0.0, enc.tobytes()))
    w.close()
    lst = tmp_path / "map.lst"
    lst.write_text("0\t1.0\ta.png\n1\t2.0\t5.0\tb.png\n"
                   "2\t3.0\t6.0\t9.0\tc.png\n3\t4.0\td.png\n")
    it = ImageRecordIterator()
    it.set_param("path_imgrec", rec)
    it.set_param("path_imglist", str(lst))
    it.set_param("label_width", "3")
    it.set_param("silent", "1")
    it.init()
    got = {}
    while it.next():
        v = it.value()
        got[v.index] = list(v.label)
    assert got == {0: [1.0, 0.0, 0.0], 1: [2.0, 5.0, 0.0],
                   2: [3.0, 6.0, 9.0], 3: [4.0, 0.0, 0.0]}

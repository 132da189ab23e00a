"""Sealed model artifacts: snapshot + serialized executables, verified.

A *bundle* is the deployable unit ``task = export`` writes and a serve
replica boots from (doc/artifacts.md): one directory holding

- ``snapshot.model.npz`` — a verified snapshot (the PR 5 digest
  machinery, quant/ range arrays included), re-committed under the
  bundle so the bundle is self-contained;
- ``prog-NNNN.pkl`` — one serialized compiled executable per program
  registry key (``jax.experimental.serialize_executable`` payload +
  arg pytrees, pickled), keyed in the manifest by the key's ``repr``;
- ``MANIFEST.json`` — the schema'd manifest: format version, runtime
  fingerprint (platform / jax / jaxlib / device kind+count / mesh),
  the bucket ladder and serve dtype the executables were sealed for,
  and a (name, bytes, sha256) row for EVERY member;
- ``MANIFEST.json.ok`` — the commit marker (manifest bytes +
  file_sha256), written LAST: the existing two-phase protocol. A
  bundle without its ``.ok`` is uncommitted — invisible to the
  hot-swap watcher and reported (not failed) by a model_dir scan,
  exactly like an uncommitted remote snapshot payload.

Everything goes through the stream layer, so bundles work on local
paths, remote URIs, and the ``fault://`` fault-injection scheme the
integrity tests drive.

Naming convention: exporting ``NNNN.model.npz`` defaults to
``NNNN.model.bundle`` beside it, so a watched ``model_dir`` can carry
bundles and snapshots side by side and the watcher prefers the bundle
at equal counters (a bundle flip skips the shadow-build compile time).
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import time
from typing import Any, Dict, List, Tuple

from ..utils.stream import (list_stream_dir, local_path, open_stream,
                            read_stream_bytes, remove_stream,
                            stream_exists, uri_scheme)
from .registry import parse_key

BUNDLE_FORMAT_VERSION = 1
BUNDLE_KIND = "cxxnet_artifact_bundle"
MANIFEST_NAME = "MANIFEST.json"
OK_SUFFIX = ".ok"
SNAPSHOT_MEMBER = "snapshot.model.npz"

BUNDLE_RE = re.compile(r"^(\d{4})\.model\.bundle$")
_PROG_RE = re.compile(r"^prog-\d{4}\.pkl$")

_MANIFEST_REQUIRED = ("format_version", "kind", "fingerprint",
                      "buckets", "serve_dtype", "snapshot", "members",
                      "programs")


class BundleError(IOError):
    """Bundle is unreadable, uncommitted, tampered, or malformed."""


def member_uri(bundle: str, name: str) -> str:
    """URI of one member inside a bundle directory — the same join
    convention as snapshot paths (``checkpoint.snapshot_uri``),
    delegated so the two can never drift."""
    from ..nnet.checkpoint import snapshot_uri
    return snapshot_uri(bundle, name)


def _commit_member(uri: str, data: bytes) -> None:
    """Durably write one bundle member. Local paths take the snapshot
    writer's discipline (tmp-write + fsync + rename) so a power loss
    after the ``.ok`` marker lands can never expose committed-but-torn
    member bytes; remote schemes write through the stream layer (their
    durability is the store's PUT semantics, as with snapshots)."""
    if uri_scheme(uri):
        with open_stream(uri, "wb") as f:
            f.write(data)
        return
    p = local_path(uri)
    d = os.path.dirname(p)
    if d and not os.path.isdir(d):
        os.makedirs(d, exist_ok=True)
    tmp = p + ".tmp"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, p)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass  # cxxlint: disable=CXL006 -- best-effort tmp cleanup; the write failure below is what the caller must see
        raise


def _fsync_dir(bundle: str) -> None:
    """Make the bundle directory's entries durable before (and after)
    the commit marker — the dir-fsync half of the two-phase protocol;
    refusal warns once, exactly like the snapshot writer."""
    if uri_scheme(bundle):
        return
    d = local_path(bundle)
    try:
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError as e:
        from ..monitor import warn_once
        warn_once("dir_fsync_refused",
                  "directory fsync of %r failed (%s); the bundle "
                  "commit is not guaranteed durable across power "
                  "loss on this filesystem" % (d, e))


def is_bundle(path: str) -> bool:
    """True when ``path`` is a bundle directory (committed or not):
    the dispatch test ``model_in`` consumers use to tell a bundle from
    a snapshot file."""
    if not uri_scheme(path) and not os.path.isdir(local_path(path)):
        return False
    return stream_exists(member_uri(path, MANIFEST_NAME))


def default_bundle_path(model_in: str) -> str:
    """`NNNN.model.npz` -> `NNNN.model.bundle` beside it; a bundle
    ``model_in`` re-exports IN PLACE (appending another ``.bundle``
    would produce a name the watcher's ``BUNDLE_RE`` never matches —
    an export that 'succeeds' but deploys nothing); any other name
    gets ``.bundle`` appended after stripping ``.npz``."""
    if model_in.rstrip("/").endswith(".bundle"):
        return model_in.rstrip("/")
    if model_in.endswith(".model.npz"):
        return model_in[:-len(".npz")] + ".bundle"
    return re.sub(r"\.npz$", "", model_in) + ".bundle"


# -- fingerprint ----------------------------------------------------------


def runtime_fingerprint(mesh=None) -> Dict[str, Any]:
    """What a serialized executable is only valid against: backend
    platform, jax/jaxlib versions, device kind and count, process
    count, and (when known) the mesh axis sizes the programs were
    lowered over. Compared by plain dict equality — a bundle either
    matches this runtime exactly or every program rebuilds."""
    import jax
    import jaxlib
    devs = jax.devices()
    # process_count + device_count + the mesh entry below ARE the
    # physical host-topology seal (local devices per host is exactly
    # device_count / process_count): a different world size or mesh
    # shape fails the dict-equality gate and every program rebuilds
    # with one warning. The dryrun's FAKED host count is deliberately
    # absent — the SPMD programs are identical at any faked input
    # partition, so an elastic dryrun resize keeps its zero-compile
    # bundle boot (doc/distributed.md) — and no redundant key means
    # bundles sealed before this convention was written down stay
    # valid
    fp = {
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "process_count": jax.process_count(),
    }
    if mesh is not None:
        fp["mesh"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    return fp


def fingerprint_sha(fp: Dict[str, Any]) -> str:
    """Stable short hash of a runtime fingerprint dict — the identity
    operators and the canary comparator use to tell which runtime an
    engine's executables were built for (``/v1/models``,
    doc/serving.md "Horizontal fleet"). Sorted-key JSON so dict order
    never changes the hash."""
    blob = json.dumps(fp, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


# -- export ---------------------------------------------------------------


def export_bundle(engine, out: str, node: str = "",
                  monitor=None, retrieval=None) -> Dict[str, Any]:
    """Seal a warmed engine into a committed bundle at ``out``.

    ``engine`` is a warmed :class:`~cxxnet_tpu.serve.engine.
    InferenceEngine`: its trainer holds the verified weights and its
    program registry holds the compiled bucket-ladder executables.
    ``retrieval`` (a warmed :class:`~cxxnet_tpu.retrieval.engine.
    RetrievalEngine`, or None) additionally seals its embedding index
    as a digest-verified member beside the snapshot — model and index
    then commit, verify, and hot-swap as ONE artifact, and the search
    executables (which live in the same program registry) serialize
    with the pred ladder.
    Write order is the commit protocol: members first (each durably
    committed — local tmp+fsync+rename, see :func:`_commit_member`),
    manifest second, a directory fsync, then ``MANIFEST.json.ok``
    last — and any stale ``.ok`` (plus orphan program members) from a
    previous export at the same path is dropped FIRST, so a crash at
    any point — power loss included — leaves an *uncommitted* bundle,
    never a committed-but-torn one. Returns the ``export`` telemetry
    record fields."""
    from ..monitor import config_hash
    from ..nnet.checkpoint import _serialize
    t0 = time.perf_counter()
    trainer = engine.trainer
    # bundle-installed executables cannot be re-serialized faithfully
    # (a Loaded object's payload comes back without its compiled
    # symbols) — copy their ORIGINAL blobs from the source bundle,
    # read BEFORE anything below overwrites it (in-place re-export is
    # the default for a bundle model_in)
    passthrough = _source_blobs(trainer.programs, monitor)
    ok_uri = member_uri(out, MANIFEST_NAME + OK_SUFFIX)
    if stream_exists(ok_uri) and not remove_stream(ok_uri):
        # a marker we cannot drop means the commit protocol cannot
        # hold: a crash mid-re-export would leave old-manifest-vouched
        # torn members. Refuse rather than proceed unsafely.
        raise BundleError(
            "cannot drop the stale commit marker %s; refusing to "
            "re-export over a committed bundle" % ok_uri)
    # sweep program members of any previous export at this path: a
    # re-export with fewer programs must not leave orphan executables
    # the new manifest no longer vouches for. The index member sweeps
    # for the same reason — an index-less re-export must not leave an
    # orphan corpus the new manifest never mentions
    from ..retrieval.index import INDEX_MEMBER
    for name in list_stream_dir(out):
        if _PROG_RE.match(name) or name == INDEX_MEMBER:
            remove_stream(member_uri(out, name))
    arrays, meta = trainer.gather_snapshot()
    # serialize once and keep the bytes: the members row needs their
    # sha256, and a multi-GB snapshot must not be re-downloaded right
    # after upload just to hash it
    payload, digest = _serialize(arrays, meta)
    snap_stats = {"digest": digest}
    _commit_member(member_uri(out, SNAPSHOT_MEMBER), payload)
    members: List[Dict[str, Any]] = [{
        "name": SNAPSHOT_MEMBER, "bytes": len(payload),
        "sha256": hashlib.sha256(payload).hexdigest(),
    }]
    programs: List[Dict[str, str]] = []
    total = len(payload)
    blobs = passthrough + trainer.programs.serialize_programs(
        list(trainer.mesh.devices.flat))
    for i, (key, blob) in enumerate(sorted(blobs, key=lambda e:
                                           repr(e[0]))):
        name = "prog-%04d.pkl" % i
        _commit_member(member_uri(out, name), blob)
        members.append({"name": name, "bytes": len(blob),
                        "sha256": hashlib.sha256(blob).hexdigest()})
        programs.append({"name": name, "key": repr(key)})
        total += len(blob)
    index_entry = None
    if retrieval is not None:
        idx_blob = retrieval.index.serialize()
        index_entry = retrieval.index.manifest_entry()
        # the served search contract: result depth + query-bucket
        # ladder, so a boot requests exactly the sealed search keys
        index_entry.update({"k": int(retrieval.k),
                            "buckets": [int(b)
                                        for b in retrieval.buckets]})
        _commit_member(member_uri(out, index_entry["member"]), idx_blob)
        members.append({
            "name": index_entry["member"], "bytes": len(idx_blob),
            "sha256": hashlib.sha256(idx_blob).hexdigest()})
        total += len(idx_blob)
    manifest = {
        "format_version": BUNDLE_FORMAT_VERSION,
        "kind": BUNDLE_KIND,
        "fingerprint": runtime_fingerprint(trainer.mesh),
        "buckets": [int(b) for b in engine.buckets],
        "nodes": [int(n) for n in engine.nodes],
        "node": node,
        "serve_dtype": trainer.serve_dtype,
        "input_dtype": str(engine.input_dtype),
        # the sealed executables' weight calling convention: 1 = pred
        # takes the frozen device-resident serve tree as arguments
        # (trainer.freeze_serve_weights), 0 = the raw master tree. A
        # boot whose trainer uses the other convention re-lowers per
        # key instead of calling with the wrong pytree
        "weight_residency": int(bool(trainer.serve_weight_residency)),
        "config_hash": config_hash(trainer.cfg),
        "content_digest": snap_stats["digest"],
        "snapshot": SNAPSHOT_MEMBER,
        "members": members,
        "programs": programs,
    }
    if index_entry is not None:
        manifest["index"] = index_entry
    man_bytes = json.dumps(manifest, sort_keys=True,
                           indent=1).encode()
    _commit_member(member_uri(out, MANIFEST_NAME), man_bytes)
    # every member durable BEFORE the marker vouches for them, and
    # the marker's own rename durable after — the .ok must never be
    # the only bytes a power loss preserved
    _fsync_dir(out)
    marker = {"format_version": BUNDLE_FORMAT_VERSION,
              "bytes": len(man_bytes),
              "file_sha256": hashlib.sha256(man_bytes).hexdigest()}
    _commit_member(ok_uri, json.dumps(marker).encode())
    _fsync_dir(out)
    return {
        "out": out,
        "snapshot": snap_stats["digest"],
        "programs": len(programs),
        # manifest member rows, the same count verify_bundle reports
        "members": len(members),
        "bytes": total + len(man_bytes),
        "wall_ms": (time.perf_counter() - t0) * 1e3,
    }


def _source_blobs(registry, monitor) -> List[Tuple[tuple, bytes]]:
    """Original serialized blobs for the registry's bundle-installed
    keys, read back from the bundle they were loaded from. A source
    that has since vanished (or lost members) warns and ships
    without those keys — the re-exported bundle still boots, those
    keys just re-lower."""
    if not registry.installed or not registry.bundle_path:
        return []
    out: List[Tuple[tuple, bytes]] = []
    try:
        man = bundle_manifest(registry.bundle_path)
        name_by_key = {p["key"]: p["name"] for p in man["programs"]}
        for key in sorted(registry.installed, key=repr):
            name = name_by_key.get(repr(key))
            if name is None:
                continue
            out.append((key, read_stream_bytes(
                member_uri(registry.bundle_path, name))))
    except (BundleError, IOError, OSError) as e:
        from .registry import _warn
        _warn(monitor, "artifact_source_unreadable",
              "source bundle %s is no longer readable (%s); re-export "
              "ships without its %d installed program(s)"
              % (registry.bundle_path, e, len(registry.installed)))
        return []
    return out


# -- verify ---------------------------------------------------------------


def verify_bundle(path: str) -> Dict[str, Any]:
    """Offline integrity report for one bundle (the
    ``tools/ckpt_verify.py`` core for bundles): commit marker, manifest
    bytes + sha, manifest schema, every member's size + sha256, and
    the snapshot's own content digest. ``ok`` is True only when every
    check passes; the first failure names itself in ``error``."""
    rep: Dict[str, Any] = {"path": path, "ok": False, "error": "",
                           "members": 0, "programs": 0,
                           "format_version": 0, "committed": False}
    rep["committed"] = stream_exists(
        member_uri(path, MANIFEST_NAME + OK_SUFFIX))
    try:
        manifest, _ = _read_manifest(path)
    except BundleError as e:
        # report-don't-raise contract: every malformation — including
        # tampered-but-parseable JSON of the wrong shape — comes back
        # as a verdict, never an exception escaping into ckpt_verify
        # or the watcher's scan
        rep["error"] = str(e)
        return rep
    rep["format_version"] = int(manifest["format_version"])
    rep["programs"] = len(manifest["programs"])
    for m in manifest["members"]:
        rep["members"] += 1
        uri = member_uri(path, m["name"])
        try:
            data = read_stream_bytes(uri)
        except (IOError, OSError) as e:
            rep["error"] = "member %s unreadable: %s" % (m["name"], e)
            return rep
        if len(data) != m.get("bytes"):
            rep["error"] = ("member %s size mismatch: manifest says "
                            "%s bytes, found %d"
                            % (m["name"], m.get("bytes"), len(data)))
            return rep
        if hashlib.sha256(data).hexdigest() != m.get("sha256"):
            rep["error"] = "member %s fails its sha256" % m["name"]
            return rep
    from ..nnet.checkpoint import verify_snapshot
    snap_rep = verify_snapshot(member_uri(path, manifest["snapshot"]))
    if not snap_rep["ok"]:
        rep["error"] = "snapshot member: %s" % snap_rep["error"]
        return rep
    rep["ok"] = True
    return rep


def _manifest_malformed(manifest) -> str:
    """Structural validation of a parsed manifest: the report-don't-
    raise contract means tampered-but-parseable JSON of any shape
    must produce a verdict string, never an attribute/key error. ""
    when well-formed."""
    if not isinstance(manifest, dict):
        return "manifest is not a JSON object"
    if manifest.get("kind") != BUNDLE_KIND:
        return "not a %s manifest" % BUNDLE_KIND
    missing = [k for k in _MANIFEST_REQUIRED if k not in manifest]
    if missing:
        return ("manifest missing required field(s): %s"
                % ", ".join(missing))
    if not isinstance(manifest["format_version"], int):
        return "manifest format_version is not an integer"
    if not isinstance(manifest["snapshot"], str):
        return "manifest snapshot field is not a member name"
    if not isinstance(manifest["fingerprint"], dict):
        return "manifest fingerprint is not an object"
    if not isinstance(manifest["serve_dtype"], str):
        return "manifest serve_dtype is not a string"
    # the serve contract consumers compute over (max(), join, ladder
    # parse) — a malformed shape must be a verdict here, not a bare
    # ValueError escaping from build_engine/serve_cfg_from_bundle
    buckets = manifest["buckets"]
    if not isinstance(buckets, list) or not buckets \
            or any(not isinstance(b, int) or b < 1 for b in buckets):
        return "manifest buckets is not a non-empty list of positive " \
               "ints"
    # per-field types, not a loose (str, int) union: an int member
    # NAME would sail through here and then TypeError inside
    # os.path.join — an exception escaping the report-don't-raise
    # contract
    for field, keys in (("members", (("name", str), ("bytes", int),
                                     ("sha256", str))),
                        ("programs", (("name", str), ("key", str)))):
        rows = manifest[field]
        if not isinstance(rows, list):
            return "manifest %s is not a list" % field
        for m in rows:
            if not isinstance(m, dict) \
                    or any(not isinstance(m.get(k), t)
                           for k, t in keys):
                return "manifest %s row is malformed: %r" % (field, m)
    # cross-field: everything the bundle claims to contain must be
    # digest-covered by a members row — a snapshot or program outside
    # the members list would verify OK and then fail to load
    names = {m["name"] for m in manifest["members"]}
    if manifest["snapshot"] not in names:
        return ("manifest snapshot %r has no members row"
                % manifest["snapshot"])
    for p in manifest["programs"]:
        if p["name"] not in names:
            return "manifest program %r has no members row" % p["name"]
    # a sealed index is optional; when declared it must be a shaped
    # object AND digest-covered by a members row — an index outside
    # the members list would verify OK here and then boot a server
    # whose /v1/search has no (or torn) corpus bytes
    idx = manifest.get("index")
    if idx is not None:
        if not isinstance(idx, dict):
            return "manifest index is not an object"
        for k, t in (("member", str), ("metric", str), ("node", str),
                     ("rows", int), ("dim", int), ("k", int)):
            if not isinstance(idx.get(k), t):
                return "manifest index field %r is malformed" % k
        ibuckets = idx.get("buckets")
        if not isinstance(ibuckets, list) or not ibuckets \
                or any(not isinstance(b, int) or b < 1
                       for b in ibuckets):
            return "manifest index buckets is not a non-empty list " \
                   "of positive ints"
        if idx["member"] not in names:
            return ("manifest index member %r has no members row"
                    % idx["member"])
    return ""


def _read_manifest(path: str) -> Tuple[Dict[str, Any], bytes]:
    """The ONE committed-manifest reader behind ``bundle_manifest``,
    ``verify_bundle`` and ``load_bundle``: commit-marker existence,
    marker shape, manifest bytes + sha cross-check, structural
    validation, format gate. Raises :class:`BundleError`; returns
    (manifest, manifest bytes)."""
    man_uri = member_uri(path, MANIFEST_NAME)
    ok_uri = man_uri + OK_SUFFIX
    if not stream_exists(ok_uri):
        raise BundleError("uncommitted bundle %s (no %s%s commit "
                          "marker)" % (path, MANIFEST_NAME, OK_SUFFIX))
    try:
        marker = json.loads(read_stream_bytes(ok_uri).decode())
        man_bytes = read_stream_bytes(man_uri)
    except (IOError, OSError, ValueError) as e:
        raise BundleError("bundle %s manifest/commit marker "
                          "unreadable: %s" % (path, e)) from e
    if not isinstance(marker, dict):
        raise BundleError("bundle %s commit marker is not a JSON "
                          "object" % path)
    if marker.get("bytes") != len(man_bytes):
        raise BundleError(
            "bundle %s manifest size mismatch: committed %s bytes, "
            "found %d" % (path, marker.get("bytes"), len(man_bytes)))
    # file_sha256 is REQUIRED: export always writes it, and accepting
    # its absence would let a consistently rewritten marker+manifest
    # pass full verification
    if marker.get("file_sha256") \
            != hashlib.sha256(man_bytes).hexdigest():
        raise BundleError("bundle %s manifest file_sha256 missing or "
                          "mismatched" % path)
    try:
        manifest = json.loads(man_bytes.decode())
    except ValueError as e:
        raise BundleError("bundle %s manifest unparseable: %s"
                          % (path, e)) from e
    err = _manifest_malformed(manifest)
    if err:
        raise BundleError("bundle %s: %s" % (path, err))
    if int(manifest["format_version"]) > BUNDLE_FORMAT_VERSION:
        raise BundleError(
            "bundle %s format_version %d is newer than this build "
            "reads (<= %d); upgrade cxxnet_tpu or re-export"
            % (path, manifest["format_version"],
               BUNDLE_FORMAT_VERSION))
    return manifest, man_bytes


# -- load -----------------------------------------------------------------


class Bundle:
    """A verified, parsed bundle ready to attach to a trainer.

    ``snapshot_raw`` carries the inner snapshot's bytes from the
    verification pass so ``load_model`` never re-reads them;
    ``programs`` holds the (already digest-checked) serialized blobs —
    deserialization into live executables is the registry's job
    (:meth:`ProgramRegistry.install_serialized`), so a fingerprint-
    mismatched boot never pays the pickle cost."""

    __slots__ = ("path", "manifest", "snapshot_uri", "snapshot_raw",
                 "programs")

    def __init__(self, path: str, manifest: Dict[str, Any],
                 snapshot_raw: bytes,
                 programs: List[Tuple[tuple, bytes]]):
        self.path = path
        self.manifest = manifest
        self.snapshot_uri = member_uri(path, manifest["snapshot"])
        self.snapshot_raw = snapshot_raw
        self.programs = programs


def bundle_manifest(path: str) -> Dict[str, Any]:
    """Parse a bundle's COMMITTED manifest (marker cross-checked,
    structure validated) WITHOUT the per-member verification — the
    cheap read config derivation uses; loading for real goes through
    :func:`load_bundle`. Raises BundleError on an uncommitted /
    unreadable / malformed manifest."""
    return _read_manifest(path)[0]


def load_bundle(path: str) -> Bundle:
    """Verify and load a bundle in ONE pass over its members: commit
    marker, manifest sha, then each member read exactly once — its
    size + sha256 checked, the snapshot's bytes and the program blobs
    kept (boot verification requires reading every member anyway; the
    inner snapshot's content digest is re-verified from the kept
    bytes by ``read_snapshot`` at load). Raises :class:`BundleError`
    on any integrity failure."""
    manifest, _ = _read_manifest(path)
    blobs: Dict[str, bytes] = {}
    for m in manifest["members"]:
        uri = member_uri(path, m["name"])
        try:
            data = read_stream_bytes(uri)
        except (IOError, OSError) as e:
            raise BundleError("bundle %s member %s unreadable: %s"
                              % (path, m["name"], e)) from e
        if len(data) != m["bytes"] \
                or hashlib.sha256(data).hexdigest() != m["sha256"]:
            raise BundleError(
                "bundle %s member %s fails verification (size/sha256 "
                "mismatch)" % (path, m["name"]))
        blobs[m["name"]] = data
    # snapshot/program membership is guaranteed by _manifest_malformed
    programs: List[Tuple[tuple, bytes]] = []
    for p in manifest["programs"]:
        try:
            key = parse_key(p["key"])
        except (ValueError, SyntaxError) as e:
            raise BundleError(
                "bundle %s program key %r is unparseable: %s"
                % (path, p.get("key"), e)) from e
        programs.append((key, blobs[p["name"]]))
    return Bundle(path, manifest, blobs[manifest["snapshot"]],
                  programs)


def read_index_member(path: str, manifest: Dict[str, Any] = None
                      ) -> bytes:
    """Digest-verified bytes of a bundle's sealed embedding index, or
    ``b""`` when the bundle seals no index. Size and sha256 are checked
    against the members row (the membership itself is guaranteed by
    ``_manifest_malformed``); a missing or torn member raises
    :class:`BundleError` — the boot-time mirror of the verify path, so
    a server can never come up on corpus bytes the manifest does not
    vouch for."""
    man = bundle_manifest(path) if manifest is None else manifest
    idx = man.get("index")
    if idx is None:
        return b""
    row = next(m for m in man["members"] if m["name"] == idx["member"])
    try:
        data = read_stream_bytes(member_uri(path, idx["member"]))
    except (IOError, OSError) as e:
        raise BundleError("bundle %s index member %s unreadable: %s"
                          % (path, idx["member"], e)) from e
    if len(data) != row["bytes"] \
            or hashlib.sha256(data).hexdigest() != row["sha256"]:
        raise BundleError(
            "bundle %s index member %s fails verification (size/"
            "sha256 mismatch)" % (path, idx["member"]))
    return data


def serve_cfg_from_bundle(path: str) -> List[Tuple[str, str]]:
    """Config pairs a conf-less boot derives from the manifest: the
    sealed bucket ladder, serve dtype and node. Appended FIRST so an
    explicit config still wins."""
    man = bundle_manifest(path)
    pairs = [
        ("serve_buckets", ",".join(str(b) for b in man["buckets"])),
        ("serve_max_batch", str(max(man["buckets"]))),
        ("serve_dtype", man["serve_dtype"]),
    ]
    if "weight_residency" in man:
        pairs.append(("serve_weight_residency",
                      str(int(man["weight_residency"]))))
    if man.get("node"):
        pairs.append(("serve_node", man["node"]))
    return pairs


# -- model_dir scan -------------------------------------------------------


def scan_bundles(model_dir: str) -> List[Tuple[int, str]]:
    """Committed bundle candidates in ``model_dir`` as (counter,
    basename), newest first — the bundle analogue of
    ``checkpoint.scan_snapshots``. Uncommitted bundles (no ``.ok``)
    are skipped: the export may still be writing them."""
    out = []
    for n in list_stream_dir(model_dir):
        m = BUNDLE_RE.match(n)
        if not m:
            continue
        b = member_uri(model_dir, n)
        if not stream_exists(member_uri(b, MANIFEST_NAME + OK_SUFFIX)):
            continue                     # uncommitted
        out.append((int(m.group(1)), n))
    out.sort(reverse=True)
    return out

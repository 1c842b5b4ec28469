"""Persistent performance archive: per-scope profile records that
outlive the process.

Everything the observability stack measures today dies at exit — the
PR 2 span rings, the PR 4 per-scope flops/bytes attribution, the bench
headline rows — so ``obs_regression`` can only diff against one
hand-committed snapshot and ROADMAP item 5's deferred autotuner has no
measured data to learn from. This module is the substrate both need
(the TVM learned-cost-model pattern): an append-only, CRC-framed,
per-host archive of (workload signature -> measured cost) records
under ``MXNET_OBS_PROFILE_DIR``.

On-disk form (house MXFLIGHT-style framing, many frames per file):

    MXPROF1 <crc32> <len>\\n{ json record }\\n

Files are ``profiles.<host>.mxp``, opened O_APPEND so concurrent
writers interleave whole frames (writers additionally serialize on a
sidecar ``.lock`` flock so the retention rewrite cannot discard a
concurrent append); the reader re-synchronizes on the
magic and skips torn/corrupt frames with named evidence
(``torn-header`` / ``bad-magic`` / ``torn-payload`` / ``crc-mismatch``
/ ``bad-json``) carrying the file + byte offset — a crash mid-write
costs one record, never the archive.

Records are keyed by a STABLE workload signature: the normalized scope
name (trailing ``_<n>`` rename counters stripped), the normalized
PR 4 registered-executable signature (the leading/batch axis of every
rank>=2 shape wildcarded, so a re-jit with a widened batch keeps the
same key), and a config fingerprint (device kind, mesh/process shape,
and the perf-relevant env knobs in ``FINGERPRINT_ENVS``). Each record
carries the measured span stats (count/total/p50/p99 from the PR 2
rings), attributed flops/HBM bytes, and a run id.

Writers: ``record_run()`` (hooked into ``profiler.dump()``) archives
one record per scope; ``append_bench()`` (benchmark/common.py) archives
headline bench rows. Retention is per signature
(``MXNET_OBS_PROFILE_KEEP`` newest records each, default 32).
Readers: ``load()`` -> (records, evidence), ``merge_by_signature()``
joins runs into one timeline per signature — what
``tools/perf_timeline.py`` renders and ``obs_regression --history``
guards.

Off-path contract (PR 2): with ``MXNET_OBS_PROFILE_DIR`` unset every
entry point is ONE guarded branch (`enabled()` is a ~0.1us _fastenv
read) and no store I/O happens at all.
"""

import contextlib
import hashlib
import json
import os
import re
import socket
import threading
import time
import zlib

try:
    import fcntl
except ImportError:        # non-POSIX: intra-process _lock only
    fcntl = None

from .. import _fastenv

__all__ = ["MAGIC", "SCHEMA", "StoreError", "FINGERPRINT_ENVS",
           "enabled", "store_dir", "keep", "history", "run_id",
           "config_fingerprint", "archived_device_doc", "normalize_scope",
           "normalize_signature", "signature_key", "frame",
           "read_file", "load", "append", "append_bench",
           "record_run", "prune", "merge_by_signature", "runs_in",
           "run_series", "host_file", "list_files", "reset"]

MAGIC = b"MXPROF1"
SCHEMA = 1

ENV_DIR = "MXNET_OBS_PROFILE_DIR"
ENV_KEEP = "MXNET_OBS_PROFILE_KEEP"
ENV_HISTORY = "MXNET_OBS_PROFILE_HISTORY"
ENV_RUN = "MXNET_OBS_PROFILE_RUN"

DEFAULT_KEEP = 32        # newest records kept per signature
DEFAULT_HISTORY = 8      # rolling window obs_regression --history uses

# the perf-relevant knobs baked into the config fingerprint: records
# measured under different kernel/serving configs must never merge
# into one timeline (a block_k A/B is two signatures, not noise)
FINGERPRINT_ENVS = (
    "MXNET_PAGED_DECODE_PALLAS",
    "MXNET_PAGED_BLOCK_K",
    "MXNET_KV_BLOCK_SIZE",
    "MXNET_KV_PAGED",
    "MXNET_SPEC_K",
    "MXNET_FLASH_STAT_LANES",
)

_lock = threading.Lock()
_run = [None]            # per-process generated run id
_device_doc = [None]     # cached device/mesh half of the fingerprint


class StoreError(ValueError):
    """A torn or corrupt frame, with named evidence (the flight
    recorder's BundleError discipline)."""

    def __init__(self, evidence, detail=""):
        self.evidence = evidence
        self.detail = detail
        super(StoreError, self).__init__("%s: %s" % (evidence, detail))


# ------------------------------------------------------- gating/env ---

def enabled():
    """THE off-path guard: one ~0.1us dict read. Every public writer
    returns immediately when this is False."""
    return bool(_fastenv.get(ENV_DIR))


def store_dir(create=False):
    d = _fastenv.get(ENV_DIR)
    if not d:
        return None
    if create and not os.path.isdir(d):
        try:
            os.makedirs(d)
        except OSError:
            pass
    return d


def _int_env(name, default, floor):
    try:
        return max(int(_fastenv.get(name, default)), floor)
    except (TypeError, ValueError):
        return default


def keep():
    """Per-signature retention cap (MXNET_OBS_PROFILE_KEEP)."""
    return _int_env(ENV_KEEP, DEFAULT_KEEP, 1)


def history():
    """Rolling-window size for --history (MXNET_OBS_PROFILE_HISTORY)."""
    return _int_env(ENV_HISTORY, DEFAULT_HISTORY, 1)


def run_id():
    """This process's run id: MXNET_OBS_PROFILE_RUN when set (benches /
    CI name their runs), else a generated ``r<unixtime>-p<pid>`` that
    stays stable for the process lifetime so a workload dumped twice
    still reads as one run."""
    explicit = _fastenv.get(ENV_RUN)
    if explicit:
        return explicit
    with _lock:
        if _run[0] is None:
            _run[0] = "r%d-p%d" % (int(time.time()), os.getpid())
        return _run[0]


def _host():
    try:
        h = socket.gethostname() or "host"
    except Exception:
        h = "host"
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", h)


def host_file(dirpath):
    return os.path.join(dirpath, "profiles.%s.mxp" % _host())


def list_files(dirpath):
    try:
        names = sorted(os.listdir(dirpath))
    except OSError:
        return []
    return [os.path.join(dirpath, n) for n in names
            if n.startswith("profiles.") and n.endswith(".mxp")]


# ------------------------------------------------ workload signature ---

# 'f32[8,128]' / 'bf16[4,16,64]{shard}' shape tokens: wildcard the
# leading (batch) axis of every rank>=2 shape so a re-jit with a
# widened batch keeps the signature; rank-1 shapes (param vectors,
# length tables) stay exact — their size IS the workload.
_SHAPE_RE = re.compile(r"([A-Za-z0-9_]+)\[(\d+)((?:,\d+)+)\]")

# jax/Block naming counters: 'dense_1', 'paged_decode_kernel_2' are
# renames of the same scope, not new workloads
_RENAME_RE = re.compile(r"(?:_\d+)+$")


def normalize_signature(sig):
    """Stable form of a PR 4 registered-executable signature: the
    leading dim of every rank>=2 shape token becomes ``*``."""
    if not sig:
        return ""
    return _SHAPE_RE.sub(lambda m: "%s[*%s]" % (m.group(1), m.group(3)),
                         str(sig))


def normalize_scope(name):
    """Stable form of a scope name: trailing ``_<n>`` rename counters
    and any bracketed shape suffix stripped."""
    if not name:
        return ""
    base = str(name).split("[", 1)[0]
    norm = _RENAME_RE.sub("", base)
    return norm or base


_UNKNOWN_DEVICE_DOC = {"device_kind": "?", "backend": "?",
                       "n_devices": 0, "n_processes": 0}
_DEVICE_DOC_KEYS = tuple(_UNKNOWN_DEVICE_DOC)


def archived_device_doc(dirpath=None):
    """The device half of the fingerprint from the NEWEST archived
    record that carries one — written by a process that actually held
    the device — or None. Never touches a backend."""
    records, _ev = load(dirpath)
    for r in reversed(records):                 # load() sorts by ts
        cfg = r.get("config") or {}
        if cfg.get("device_kind") and cfg.get("device_kind") != "?":
            return {k: cfg.get(k) for k in _DEVICE_DOC_KEYS}
    return None


def config_fingerprint(extra=None, discover=True):
    """(fingerprint-id, doc): device kind + mesh/process shape + the
    FINGERPRINT_ENVS knobs, hashed to a short id. The doc rides in
    every record so a timeline can explain why two signatures differ.
    Device discovery is cached per process and best-effort (the store
    must work before/without a backend).

    ``discover=False`` NEVER initializes a backend: the device doc
    comes from the newest archived record (written by the process that
    measured it), else the unknown-device placeholder. This is for a
    parent that must stay off jax because its child holds the chip (a
    chip belongs to one process at a time). The placeholder is not cached, so the doc
    upgrades to the real one once a leg has archived it."""
    doc = _device_doc[0]
    if doc is None:
        if discover:
            try:
                import jax
                dev = jax.devices()[0]
                doc = {"device_kind": getattr(dev, "device_kind", "?"),
                       "backend": jax.default_backend(),
                       "n_devices": jax.device_count(),
                       "n_processes": jax.process_count()}
            except Exception:
                doc = dict(_UNKNOWN_DEVICE_DOC)
            _device_doc[0] = doc
        else:
            doc = archived_device_doc()
            if doc is not None:
                _device_doc[0] = doc
            else:
                doc = dict(_UNKNOWN_DEVICE_DOC)
    cfg = dict(doc)
    cfg["env"] = {k: os.environ[k] for k in FINGERPRINT_ENVS
                  if os.environ.get(k)}
    if extra:
        cfg["extra"] = extra
    blob = json.dumps(cfg, sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha1(blob).hexdigest()[:12], cfg


def signature_key(scope, signature="", fingerprint=""):
    """The stable archive key: normalized scope | normalized program
    signature | config fingerprint id."""
    return "|".join((normalize_scope(scope),
                     normalize_signature(signature),
                     fingerprint or ""))


# --------------------------------------------------------- framing ---

def frame(doc):
    """CRC-frame one record dict -> bytes (one line-oriented frame; the
    trailing newline keeps the file greppable)."""
    body = json.dumps(doc, sort_keys=True, default=str).encode("utf-8")
    head = b"%s %08x %d\n" % (MAGIC, zlib.crc32(body) & 0xFFFFFFFF,
                              len(body))
    return head + body + b"\n"


def read_file(path):
    """Parse one archive file -> (records, evidence). Torn or corrupt
    frames are SKIPPED, each leaving one evidence dict naming the file,
    byte offset and what was wrong; the reader re-synchronizes on the
    next magic so one bad frame never hides the rest."""
    records, evidence = [], []

    def note(offset, kind, detail):
        evidence.append({"file": path, "offset": int(offset),
                         "evidence": kind, "detail": detail})

    try:
        with open(path, "rb") as f:
            data = f.read()
    except OSError as exc:
        note(0, "unreadable", str(exc))
        return records, evidence
    pos, n = 0, len(data)
    while pos < n:
        idx = data.find(MAGIC, pos)
        if idx < 0:
            if data[pos:].strip():
                note(pos, "bad-magic", repr(data[pos:pos + 32]))
            break
        if idx > pos and data[pos:idx].strip():
            note(pos, "bad-magic", repr(data[pos:idx][:32]))
        nl = data.find(b"\n", idx)
        if nl < 0:
            note(idx, "torn-header", "no newline in %d trailing bytes"
                 % (n - idx))
            break
        parts = data[idx:nl].split()
        want_crc = want_len = None
        if len(parts) == 3:
            try:
                want_crc, want_len = int(parts[1], 16), int(parts[2])
            except ValueError:
                pass
        if want_len is None:
            note(idx, "bad-magic", repr(data[idx:nl][:64]))
            pos = idx + len(MAGIC)
            continue
        body = data[nl + 1:nl + 1 + want_len]
        if len(body) < want_len:
            note(idx, "torn-payload", "expected %d body bytes, found %d"
                 % (want_len, len(body)))
            break
        pos = nl + 1 + want_len
        if (zlib.crc32(body) & 0xFFFFFFFF) != want_crc:
            note(idx, "crc-mismatch", "expected %08x, computed %08x"
                 % (want_crc, zlib.crc32(body) & 0xFFFFFFFF))
            continue
        try:
            records.append(json.loads(body.decode("utf-8")))
        except ValueError as exc:
            note(idx, "bad-json", str(exc))
    return records, evidence


def load(dirpath=None):
    """All records across the archive dir's per-host files ->
    (records sorted by ts, evidence list)."""
    d = dirpath or store_dir()
    records, evidence = [], []
    if not d:
        return records, evidence
    for path in list_files(d):
        recs, ev = read_file(path)
        records.extend(recs)
        evidence.extend(ev)
    records.sort(key=lambda r: r.get("ts", 0))
    return records, evidence


# --------------------------------------------------------- writers ---

@contextlib.contextmanager
def _file_lock(path):
    """Cross-process writer lock: flock on a sidecar ``<file>.lock``
    (never the data file itself — prune's os.replace swaps the data
    inode, which would orphan a lock taken on it). O_APPEND alone makes
    concurrent appends safe, but prune's read-modify-replace is not:
    a frame appended between its read and its replace would be
    silently discarded, so every writer — append AND prune — holds
    this lock. Best-effort: without fcntl (non-POSIX) or on lock
    errors, fall back to the intra-process ``_lock`` the callers
    already hold."""
    if fcntl is None:
        yield
        return
    try:
        fd = os.open(path + ".lock", os.O_CREAT | os.O_RDWR, 0o644)
    except OSError:
        yield
        return
    try:
        try:
            fcntl.flock(fd, fcntl.LOCK_EX)
        except OSError:
            pass
        yield
    finally:
        try:
            fcntl.flock(fd, fcntl.LOCK_UN)
        except OSError:
            pass
        os.close(fd)


def append(doc, dirpath=None):
    """Append one framed record to this host's archive file. Returns
    the path, or None when the store is off (the guarded branch) or
    the write fails — archiving must never break the workload."""
    if dirpath is None:
        if not enabled():
            return None
        dirpath = store_dir(create=True)
    elif not os.path.isdir(dirpath):
        try:
            os.makedirs(dirpath)
        except OSError:
            return None
    if not dirpath:
        return None
    path = host_file(dirpath)
    data = frame(doc)
    try:
        with _lock, _file_lock(path):
            with open(path, "ab") as f:     # O_APPEND: whole frames
                f.write(data)
                f.flush()
    except OSError:
        return None
    return path


def _span_stats(s):
    return {"count": s["count"], "total_ms": s["total_ms"],
            "p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"]}


def record_run(run=None, dirpath=None, ts=None):
    """Archive the current telemetry ring + attribution scopes: one
    record per scope name seen by either, keyed by the stable workload
    signature. Called from ``profiler.dump()`` behind ``enabled()``;
    never raises, returns the number of records written."""
    try:
        if dirpath is None and not enabled():
            return 0
        from . import export as _export
        spans = _export.aggregate()["spans"]
        scopes, progsigs = {}, {}
        try:
            from . import attribution as _attr
            analyses = _attr.analyses()
            for a in analyses:
                for scope in a.get("scopes", {}):
                    progsigs.setdefault(scope, a.get("signature") or "")
            if analyses:
                scopes = _attr.summary().get("scopes", {})
        except Exception:
            scopes, progsigs = {}, {}
        fid, cfg = config_fingerprint()
        run = run or run_id()
        ts = time.time() if ts is None else ts
        wrote = 0
        for name in sorted(set(spans) | set(scopes)):
            a = scopes.get(name, {})
            rec = {"schema": SCHEMA, "kind": "scope", "run": run,
                   "ts": ts, "host": _host(), "scope": name,
                   "sig": signature_key(name, progsigs.get(name, ""),
                                        fid),
                   "signature": normalize_signature(
                       progsigs.get(name, "")),
                   "fingerprint": fid, "config": cfg,
                   "stats": (_span_stats(spans[name])
                             if name in spans else None),
                   "flops": a.get("flops", 0),
                   "hbm_bytes": a.get("hbm_bytes", 0)}
            if append(rec, dirpath=dirpath) is not None:
                wrote += 1
        if wrote:
            prune(dirpath=dirpath)
        return wrote
    except Exception:
        return 0


def append_bench(leg, value=None, unit=None, metric=None, extra=None,
                 dirpath=None, run=None, fingerprint=None, config=None):
    """Archive one bench headline row (benchmark/common.py's hook).
    ``fingerprint``/``config`` let a caller that already computed the
    fingerprint (a parent that must not trigger device discovery)
    pass it through instead of recomputing. Returns
    the path written, or None when the store is off. Never raises — a
    bench must not fail because archiving did."""
    try:
        if dirpath is None and not enabled():
            return None
        if fingerprint is None:
            fid, cfg = config_fingerprint()
        else:
            fid, cfg = fingerprint, (config or {})
        metric = metric or leg
        rec = {"schema": SCHEMA, "kind": "bench", "run": run or run_id(),
               "ts": time.time(), "host": _host(), "leg": leg,
               "metric": metric,
               "sig": "bench.%s|%s" % (metric, fid),
               "fingerprint": fid, "config": cfg,
               "value": value, "unit": unit}
        if extra:
            rec["extra"] = extra
        path = append(rec, dirpath=dirpath)
        if path is not None:
            prune(dirpath=dirpath)
        return path
    except Exception:
        return None


def prune(dirpath=None, keep_n=None):
    """Enforce the per-signature retention cap on this host's file:
    keep the newest ``keep_n`` (default MXNET_OBS_PROFILE_KEEP) records
    per signature, atomically rewriting only when something must go.
    The read AND the rewrite happen under ``_lock`` + the cross-process
    ``_file_lock`` — a frame appended concurrently (other thread or
    other process on this host) lands either before the read (and is
    kept) or after the replace (O_APPEND onto the new file), never in
    the window where the rewrite would discard it. Returns the number
    of records dropped."""
    d = dirpath or store_dir()
    if not d:
        return 0
    path = host_file(d)
    if not os.path.exists(path):
        return 0
    keep_n = keep_n or keep()
    tmp = path + ".tmp.%d" % os.getpid()
    try:
        with _lock, _file_lock(path):
            records, _ev = read_file(path)
            by_sig = {}
            for i, r in enumerate(records):
                by_sig.setdefault(r.get("sig", ""), []).append(i)
            drop = set()
            for idxs in by_sig.values():
                if len(idxs) > keep_n:
                    idxs.sort(key=lambda i: (records[i].get("ts", 0), i))
                    drop.update(idxs[:-keep_n])
            if not drop:
                return 0
            kept = [r for i, r in enumerate(records) if i not in drop]
            with open(tmp, "wb") as f:
                for r in kept:
                    f.write(frame(r))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return 0
    return len(drop)


# --------------------------------------------------------- readers ---

def merge_by_signature(records):
    """Group scope records into one timeline per signature:
    {sig: {"scope", "sig", "records" (ts-sorted), "runs" (ordered)}}.
    The read side that makes two consecutive runs of the same workload
    ONE merged timeline."""
    groups = {}
    for r in records:
        if r.get("kind") != "scope":
            continue
        g = groups.setdefault(r.get("sig", ""), {
            "scope": normalize_scope(r.get("scope", "")),
            "sig": r.get("sig", ""), "records": []})
        g["records"].append(r)
    for g in groups.values():
        g["records"].sort(key=lambda r: r.get("ts", 0))
        runs, seen = [], set()
        for r in g["records"]:
            run = r.get("run")
            if run not in seen:
                seen.add(run)
                runs.append(run)
        g["runs"] = runs
    return groups


def runs_in(records):
    """Distinct run ids ordered by first appearance (ts order)."""
    runs, seen = [], set()
    for r in sorted(records, key=lambda r: r.get("ts", 0)):
        run = r.get("run")
        if run is not None and run not in seen:
            seen.add(run)
            runs.append(run)
    return runs


def run_series(group, metric="p50_ms"):
    """Per-run series for one merged signature group: the newest record
    of each run -> [(run, ts, value)]. ``metric`` reads span stats
    first, then top-level fields (bench ``value``, ``flops``...)."""
    newest = {}
    for r in group["records"]:
        newest[r.get("run")] = r
    out = []
    for run in group["runs"]:
        r = newest[run]
        stats = r.get("stats") or {}
        val = stats.get(metric, r.get(metric))
        if val is None and metric == "p50_ms" and stats.get("count"):
            val = stats.get("total_ms", 0) / stats["count"]
        if val is not None:
            out.append((run, r.get("ts", 0), float(val)))
    return out


def reset():
    """Forget the cached run id + device fingerprint (tests)."""
    with _lock:
        _run[0] = None
        _device_doc[0] = None

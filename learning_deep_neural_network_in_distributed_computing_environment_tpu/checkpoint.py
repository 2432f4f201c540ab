"""Async sharded checkpoint engine (ISSUE 5).

The reference has **no checkpointing** — the model lives only in memory and
nothing but PNGs is ever written (SURVEY.md section 5), so this whole
subsystem is beyond-reference.  The PR-0..4 implementation was a blocking
collective: every host ``process_allgather``-ed the FULL worker-stacked
``TrainState`` and serialized it to one msgpack file inline on the round
loop — O(full-state) wire bytes and a serialize+fsync stall per save, per
host.  This engine is the production-multihost shape instead:

- **Sharded I/O**: each process writes only the addressable shards it
  owns (``replica_id == 0`` dedups replicated leaves globally) into a
  per-epoch directory — no gather, 1/num_hosts payload bytes per host::

      ckpt_dir/
        ckpt_<E>/
          shard_<P>.msgpack    per-process pieces: {leaf key: [(index, array)]}
          MANIFEST.json        commit marker, written LAST (every process)
        ckpt_<E>.msgpack       legacy v1 single-file (restore-only back-compat)

  Restore merges the pieces back into full host arrays and ``device_put``s
  each onto its template leaf's sharding, so the save/restore meshes (and
  the process count, on a shared filesystem) may differ freely.

- **Async commit**: the round loop pays only the device->host snapshot of
  the addressable shards (behind ``jax.block_until_ready`` — the fence
  that keeps the donated-buffer round/sync programs from overwriting
  in-flight state); a background writer thread serializes, checksums
  (crc32), fsyncs, and finally publishes ``MANIFEST.json`` — a crash at
  ANY earlier point leaves an unmanifested directory that
  ``latest_checkpoint`` ignores and the next engine open sweeps.  At most
  one write is in flight (the next save waits — backpressure, and the
  snapshot pool stays bounded at one state).

- **Multi-host commit protocol**: every process fsyncs its shard, then a
  tiny ``process_allgather`` of (bytes, crc32) doubles as the
  all-shards-durable barrier, then every process writes the identical
  manifest (tmp + atomic rename; on a shared filesystem last-writer-wins
  with identical content, without one each host still holds a commit
  marker for its own shards).  Collectives stay on the MAIN thread: in
  async mode the background job only writes the local shard and the
  commit runs at the next ``save()``/``wait()`` call.
"""

from __future__ import annotations

import json
import logging
import os
import re
import shutil
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import jax
import numpy as np
from flax import serialization

from .spans import span

log = logging.getLogger(__name__)

_LEGACY_RE = re.compile(r"ckpt_(\d+)\.msgpack$")
_DIR_RE = re.compile(r"ckpt_(\d+)$")
MANIFEST = "MANIFEST.json"
FORMAT = 2

# Test hook (tools/verify.sh kill-mid-write smoke): crash the process at a
# defined point inside a save so the on-disk state is exactly what a real
# mid-write SIGKILL leaves.  Values: "mid_shard" (partial .tmp written),
# "before_manifest" (shards durable, manifest never published).
_CRASH_ENV = "JAX_GRAFT_CKPT_TEST_CRASH"


def _maybe_crash(point: str) -> None:
    if os.environ.get(_CRASH_ENV) == point:
        os._exit(42)


# ----------------------------------------------------------------------
# Snapshot: device -> host copy of the addressable shards
# ----------------------------------------------------------------------

def _piece_index(index, shape) -> list:
    """A shard's global index as JSON/msgpack-able [[start, stop], ...];
    unsharded dims arrive as ``slice(None)`` and normalize to [0, dim]."""
    out = []
    for sl, dim in zip(index, shape):
        if sl.step not in (None, 1):
            raise ValueError(f"strided shard index unsupported: {index}")
        out.append([int(sl.start or 0),
                    int(sl.stop if sl.stop is not None else dim)])
    return out


def snapshot_addressable(state) -> tuple[dict, dict]:
    """Host snapshot of the shards THIS process must persist.

    Returns ``(pieces, meta)``: ``pieces`` maps each leaf's key-path
    string to a list of ``[index, ndarray]`` entries — one per addressable
    shard with ``replica_id == 0``, so replicated leaves are written by
    exactly one process globally and the union over processes tiles each
    leaf exactly once; ``meta`` maps the same keys to global
    shape/dtype/bytes.  Arrays are COPIED (never views of device buffers):
    once this returns, the engines are free to donate/overwrite the
    source state.  The caller fences first (``jax.block_until_ready``) so
    no in-flight program is still writing the buffers being read.
    """
    pieces: dict[str, list] = {}
    meta: dict[str, dict] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if isinstance(leaf, jax.Array):
            plist = [[_piece_index(s.index, leaf.shape),
                      np.array(s.data, copy=True)]
                     for s in leaf.addressable_shards if s.replica_id == 0]
            shape, dtype = leaf.shape, leaf.dtype
        else:  # host leaf (rare): single full piece, process 0 owns it
            arr = np.asarray(leaf)
            plist = ([[[[0, d] for d in arr.shape], np.array(arr)]]
                     if jax.process_index() == 0 else [])
            shape, dtype = arr.shape, arr.dtype
        if plist:
            pieces[key] = plist
        meta[key] = {"shape": [int(d) for d in shape], "dtype": str(dtype),
                     "bytes": int(np.prod(shape, dtype=np.int64))
                     * np.dtype(dtype).itemsize}
    return pieces, meta


def _merge_pieces(key: str, plist: list, shape, dtype) -> np.ndarray:
    """Reassemble one leaf from its (possibly cross-process) pieces.

    Pieces are disjoint by construction (replica 0 of each index), so a
    filled-element count equal to the leaf size proves full coverage —
    a missing shard file surfaces as an explicit error here, never as
    uninitialized memory."""
    out = np.empty(shape, dtype)
    filled = 0
    for index, arr in plist:
        sl = tuple(slice(a, b) for a, b in index)
        out[sl] = arr
        filled += int(arr.size)
    if filled != out.size:
        raise ValueError(
            f"checkpoint leaf {key} is incomplete: pieces cover {filled} of "
            f"{out.size} elements (missing shard file?)")
    return out


# ----------------------------------------------------------------------
# The engine
# ----------------------------------------------------------------------

class CheckpointEngine:
    """Per-run checkpoint engine: sweeps stale leftovers on open, then
    serves off-critical-path sharded saves and every-process pruning.

    ``async_write=False`` runs the identical write path inline (the
    blocking twin of tests/test_checkpoint.py).  ``timing`` dicts
    passed to ``save`` get ``ckpt_snapshot_ms`` filled synchronously and
    ``ckpt_write_ms`` when the (possibly background) write lands — the
    driver threads its per-round ``round_timings`` entry through so stall
    vs hidden wall is attributed per round."""

    def __init__(self, ckpt_dir: str, keep: int = 3,
                 async_write: bool = True,
                 metadata: dict | None = None):
        self.dir = ckpt_dir
        self.keep = max(1, int(keep))
        self.async_write = bool(async_write)
        # run-provenance / arch facts published into every MANIFEST.json
        # (ISSUE 7 satellite): JSON-able dict, identical on every process
        # (it comes from the shared Config), so the every-process manifest
        # write stays byte-identical.  ``manifest_metadata`` reads it back;
        # serve self-configures the model from it.
        self.metadata = dict(metadata) if metadata else {}
        os.makedirs(ckpt_dir, exist_ok=True)
        self._sweep_stale()
        self._pool = None         # writer thread, spawned at first save
        self._pending = None      # (future, epoch, timing, leaf meta)
        self.stats = {"saves": 0, "payload_bytes_per_save": 0,
                      "snapshot_ms_total": 0.0, "write_ms_total": 0.0}

    # -- open-time sweep (ISSUE 5 satellite) ---------------------------
    def _sweep_stale(self) -> None:
        """Delete unmanifested leftovers a crash mid-save left behind:
        ``*.tmp.*`` files (legacy and in-dir) and ``ckpt_<E>/`` dirs with
        no committed manifest.  Nothing can be in flight at open time, so
        everything unmanifested is garbage by definition."""
        def rm(path):
            # every process sweeps the same shared dir at open; losing
            # the unlink race to a peer is success, not an error
            try:
                os.remove(path)
                return True
            except FileNotFoundError:
                return False

        swept = []
        for name in sorted(os.listdir(self.dir)):
            path = os.path.join(self.dir, name)
            if ".tmp." in name and os.path.isfile(path):
                if rm(path):
                    swept.append(name)
            elif _DIR_RE.match(name) and os.path.isdir(path):
                if not os.path.isfile(os.path.join(path, MANIFEST)):
                    shutil.rmtree(path, ignore_errors=True)
                    swept.append(name + "/")
                else:
                    try:
                        inners = sorted(os.listdir(path))
                    except FileNotFoundError:
                        continue   # a peer pruned the dir mid-listing
                    for inner in inners:
                        if ".tmp." in inner and rm(os.path.join(path,
                                                                inner)):
                            swept.append(f"{name}/{inner}")
        if swept:
            log.info("swept %d stale checkpoint leftover(s) in %s: %s",
                     len(swept), self.dir, ", ".join(swept))

    # -- save ----------------------------------------------------------
    def save(self, state, global_epoch: int, timing: dict | None = None
             ) -> str:
        """Snapshot ``state`` and commit it as epoch ``global_epoch``.

        Blocking portion — ALL of it reported as ``ckpt_snapshot_ms``:
        waiting out any previous in-flight write (backpressure — one
        snapshot buffered, ever; ~0 when saves are further apart than the
        write wall), then the fence + device->host shard copy.  Async
        mode returns here; the serialize/checksum/fsync/manifest wall
        rides the background thread.  EVERY process must call this (the
        multi-host commit barrier is collective)."""
        row = {} if timing is None else timing
        # the save that closes round r is epoch r + 1
        with span("round.ckpt_snapshot", row, "ckpt_snapshot_ms",
                  round=int(global_epoch) - 1):
            self._finalize()
            state = _strip_buddy(state)
            jax.block_until_ready(state)   # the donated-buffer snapshot fence
            pieces, meta = snapshot_addressable(state)
        snapshot_ms = row["ckpt_snapshot_ms"]
        payload = sum(int(a.nbytes) for pl in pieces.values()
                      for _i, a in pl)
        self.stats["saves"] += 1
        self.stats["payload_bytes_per_save"] = payload
        self.stats["snapshot_ms_total"] = round(
            self.stats["snapshot_ms_total"] + snapshot_ms, 3)
        job = lambda: self._write_shard(pieces, meta, int(global_epoch),
                                        timing)
        if self.async_write:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ckpt-writer")
            self._pending = (self._pool.submit(job), int(global_epoch),
                             timing, meta)
        else:
            local = job()   # single-process commits inline in the job
            if jax.process_count() > 1:
                self._commit(int(global_epoch), local, meta, timing)
        return os.path.join(self.dir, f"ckpt_{int(global_epoch)}")

    def wait(self) -> None:
        """Block until the in-flight save (if any) is fully committed.
        Multi-host: collective (the deferred commit barrier runs here)."""
        self._finalize()

    def close(self) -> None:
        """``wait()`` + release the writer thread.  The engine stays
        usable (the pool respawns lazily at the next async save); without
        a close every async engine would pin one non-daemon thread until
        interpreter exit."""
        self._finalize()
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def abort(self) -> None:
        """Exception-unwind twin of ``close()``: join and release the
        writer WITHOUT the (multi-host: collective) deferred commit — a
        collective entered during one process's unwind is one its peers
        may never match, turning a loud crash into a job-wide hang.  The
        epoch stays unmanifested (swept at the next engine open); a
        writer failure is logged, not raised, so the original exception
        keeps propagating."""
        pending, self._pending = self._pending, None
        if pending is not None:
            try:
                pending[0].result()
            except Exception:  # noqa: BLE001 — unwind must not be masked
                log.exception("checkpoint writer failed during abort")
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def _finalize(self) -> None:
        if self._pending is None:
            return
        fut, epoch, timing, meta = self._pending
        self._pending = None
        local = fut.result()   # re-raises background write failures loudly
        if jax.process_count() > 1:
            # the commit is collective; it was deferred off the writer
            # thread so its allgather runs HERE, on the main thread, in
            # the same program order on every process
            self._commit(epoch, local, meta, timing)

    def _write_shard(self, pieces, meta, epoch: int, timing) -> dict:
        """Serialize + checksum + fsync this process's shard file.
        Returns {"bytes", "crc32", "payload_bytes"}.  Single-process runs
        the commit inline (no barrier needed).  The ``round.ckpt_write``
        span is the annotation alone: ``ckpt_write_ms`` is summed below
        and in ``_commit``, across two threads on multi-host."""
        with span("round.ckpt_write", round=epoch - 1):
            t0 = time.perf_counter()
            p = jax.process_index()
            d = os.path.join(self.dir, f"ckpt_{epoch}")
            os.makedirs(d, exist_ok=True)
            raw = serialization.msgpack_serialize(
                {"format": FORMAT, "process": p, "leaves": pieces})
            path = os.path.join(d, f"shard_{p}.msgpack")
            tmp = f"{path}.tmp.{p}"
            with open(tmp, "wb") as f:
                f.write(raw)
                _maybe_crash("mid_shard")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            local = {"bytes": len(raw), "crc32": zlib.crc32(raw),
                     "payload_bytes": sum(int(a.nbytes)
                                          for pl in pieces.values()
                                          for _i, a in pl)}
            _maybe_crash("before_manifest")
            if jax.process_count() == 1:
                self._commit(epoch, local, meta, timing, t_start=t0)
            else:
                # multi-host: the commit wall lands separately (deferred to
                # the main thread, _commit with t_start=None adds it); record
                # the serialize+fsync wall here so write_ms_total covers the
                # whole background cost on every backend
                write_ms = round((time.perf_counter() - t0) * 1e3, 3)
                self.stats["write_ms_total"] = round(
                    self.stats["write_ms_total"] + write_ms, 3)
                if timing is not None:
                    timing["ckpt_write_ms"] = write_ms
            return local

    def _commit(self, epoch: int, local: dict, meta, timing,
                t_start: float | None = None) -> None:
        """Publish MANIFEST.json (the atomic commit marker), then prune.

        Multi-host: allgather the per-shard (bytes, crc) — which doubles
        as the all-shards-durable barrier — so every process writes the
        identical manifest.  A crash anywhere before the ``os.replace``
        leaves the epoch unmanifested: invisible to ``latest_checkpoint``
        and swept at the next engine open."""
        t0 = t_start if t_start is not None else time.perf_counter()
        pc = jax.process_count()
        if pc > 1:
            from jax.experimental import multihost_utils
            gathered = multihost_utils.process_allgather(
                np.array([local["bytes"], local["crc32"],
                          local["payload_bytes"]], np.int64))
            shards = {f"shard_{q}.msgpack":
                      {"bytes": int(gathered[q][0]),
                       "crc32": int(gathered[q][1]),
                       "payload_bytes": int(gathered[q][2])}
                      for q in range(pc)}
        else:
            shards = {"shard_0.msgpack": local}
        d = os.path.join(self.dir, f"ckpt_{epoch}")
        manifest = {"format": FORMAT, "global_epoch": int(epoch),
                    "process_count": pc, "shards": shards, "leaves": meta}
        if self.metadata:
            manifest["metadata"] = self.metadata
        path = os.path.join(d, MANIFEST)
        tmp = f"{path}.tmp.{jax.process_index()}"
        with open(tmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)   # <- the commit point
        self._prune()
        write_ms = round((time.perf_counter() - t0) * 1e3, 3)
        self.stats["write_ms_total"] = round(
            self.stats["write_ms_total"] + write_ms, 3)
        if timing is not None:
            # += : multi-host async splits the wall between the writer
            # thread (shard) and this deferred main-thread commit
            timing["ckpt_write_ms"] = round(
                timing.get("ckpt_write_ms", 0.0) + write_ms, 3)

    # -- prune (ISSUE 5 satellite) -------------------------------------
    def _prune(self) -> None:
        """EVERY process prunes to the ``keep`` newest COMMITTED epochs.

        The old implementation pruned on process 0 only, so hosts on
        non-shared filesystems accumulated every epoch forever.  Each
        process now removes what it can see; concurrent removal on a
        shared filesystem is race-tolerant (``rmtree(ignore_errors)``,
        ENOENT swallowed).  Uncommitted dirs are never touched here (an
        in-flight save must survive); the open-time sweep owns those."""
        # age out MANIFESTED epochs (the commit marker), not merely
        # locally-restorable ones: a non-shared-fs host sees only its own
        # shards, so keying on restorability would never prune there —
        # the exact leak this fixes — and a corrupt-but-manifested epoch
        # must age out too instead of lingering forever
        committed = sorted(set(_manifested_epochs(self.dir))
                           | set(_legacy_epochs(self.dir)))
        for old in committed[:-self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"ckpt_{old}"),
                          ignore_errors=True)
            try:
                os.remove(os.path.join(self.dir, f"ckpt_{old}.msgpack"))
            except FileNotFoundError:
                pass

    # -- queries -------------------------------------------------------
    def latest_checkpoint(self) -> Optional[str]:
        return latest_checkpoint(self.dir)

    def summary(self) -> dict:
        """Run-level telemetry for ``results["checkpoint"]``."""
        return {"enabled": True, "async": self.async_write,
                "layout": "sharded", "keep": self.keep,
                "saves": self.stats["saves"],
                "bytes_per_host": self.stats["payload_bytes_per_save"],
                "stall_ms_total": self.stats["snapshot_ms_total"],
                "write_ms_total": self.stats["write_ms_total"]}


# ----------------------------------------------------------------------
# Listing / validation
# ----------------------------------------------------------------------

def _legacy_epochs(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(m.group(1)) for name in os.listdir(ckpt_dir)
                  if (m := _LEGACY_RE.match(name)))


def _read_manifest(epoch_dir: str) -> Optional[dict]:
    try:
        with open(os.path.join(epoch_dir, MANIFEST)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _valid_sharded(epoch_dir: str) -> bool:
    """A sharded epoch is restorable iff its manifest parses and EVERY
    manifested shard file is present at its manifested size AND crc32
    (ISSUE 8 satellite: size alone let a corrupt-but-right-size shard —
    bit rot, a torn overwrite — reach ``host_tree``, which then RAISED
    instead of falling back like the truncation path).  Restore merges
    the pieces into FULL host arrays, so a missing, truncated, or
    corrupt shard are all equally unrestorable — each must drop the
    epoch so ``latest_checkpoint`` falls back to an intact one.  (This
    also means multi-host restore needs a shared filesystem, the
    layout's documented requirement.)  Cost: one read of each shard of
    each locally-manifested epoch per listing — at most ``ckpt_keep``
    epochs by construction, and listings happen at resume/open, never
    in the round loop."""
    manifest = _read_manifest(epoch_dir)
    if not manifest or "shards" not in manifest:
        return False
    for fname, info in manifest["shards"].items():
        path = os.path.join(epoch_dir, fname)
        if (not os.path.isfile(path)
                or os.path.getsize(path) != int(info["bytes"])):
            return False
        try:
            crc = 0
            with open(path, "rb") as f:
                # chunked: peak RAM stays one buffer, not one shard
                while chunk := f.read(1 << 22):
                    crc = zlib.crc32(chunk, crc)
        except OSError:
            return False
        if crc != int(info["crc32"]):
            log.warning(
                "checkpoint shard %s is corrupt (size matches, crc32 "
                "does not) — dropping epoch from the restorable set",
                path)
            return False
    return True


def _sharded_epochs(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        m = _DIR_RE.match(name)
        if m and _valid_sharded(os.path.join(ckpt_dir, name)):
            out.append(int(m.group(1)))
    return sorted(out)


def _manifested_epochs(ckpt_dir: str) -> list[int]:
    """Epochs whose commit marker exists locally, restorable or not —
    the prune population (see ``CheckpointEngine._prune``)."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(
        int(m.group(1)) for name in os.listdir(ckpt_dir)
        if (m := _DIR_RE.match(name))
        and os.path.isfile(os.path.join(ckpt_dir, name, MANIFEST)))


def committed_epochs(ckpt_dir: str) -> list[int]:
    """Epochs with a restorable checkpoint (committed sharded dirs plus
    legacy single files), ascending.  A truncated shard or missing
    manifest drops its epoch from this list — ``latest_checkpoint`` then
    falls back to the newest epoch that IS intact."""
    return sorted(set(_sharded_epochs(ckpt_dir))
                  | set(_legacy_epochs(ckpt_dir)))


def manifest_metadata(path: str) -> dict:
    """The ``metadata`` block a save's engine published into MANIFEST.json
    (model family + arch Config fields — ISSUE 7 satellite), or ``{}``
    for pre-metadata and legacy checkpoints.

    ``path`` is a committed ``ckpt_<E>`` epoch dir or a checkpoint root
    (resolved to the newest committed sharded epoch).  Read-only and
    local — no multi-host agreement collective, so inspection tools and
    the single-process serve path can call it freely."""
    manifest = _read_manifest(path)
    if manifest is None:
        epochs = _sharded_epochs(path)
        if not epochs:
            return {}
        manifest = _read_manifest(
            os.path.join(path, f"ckpt_{epochs[-1]}"))
    return dict((manifest or {}).get("metadata", {}))


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    """Path of the newest COMMITTED checkpoint, agreed across hosts.

    Multi-host: every process must call this together.  Restore re-shards
    with ``jax.device_put`` onto cross-process shardings — a collective
    all hosts must enter — so the resume decision itself has to be
    identical everywhere.  Process 0's newest committed epoch is
    broadcast; hosts that cannot restore it (e.g. lost local disk) fail
    loudly instead of hanging."""
    epochs = committed_epochs(ckpt_dir)
    local = max(epochs) if epochs else -1
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        agreed = int(multihost_utils.broadcast_one_to_all(np.int32(local)))
        if agreed >= 0 and agreed not in epochs:
            raise FileNotFoundError(
                f"process {jax.process_index()} is missing checkpoint epoch "
                f"{agreed} present on process 0 ({ckpt_dir}); cannot resume "
                "consistently")
        local = agreed
    if local < 0:
        return None
    d = os.path.join(ckpt_dir, f"ckpt_{local}")
    if _valid_sharded(d):
        return d
    return os.path.join(ckpt_dir, f"ckpt_{local}.msgpack")


# ----------------------------------------------------------------------
# Restore
# ----------------------------------------------------------------------

def manifest_worker_axis(epoch_dir: str) -> Optional[int]:
    """The worker-stacked leading-axis size a committed sharded epoch was
    written with — read from MANIFEST leaf shapes alone (no shard I/O).
    Every ``TrainState`` leaf leads with [n_workers], so the value is
    well-defined whenever the leaves agree; None for legacy/unreadable
    layouts or disagreeing shapes (caller falls back to the restore-time
    shape error)."""
    manifest = _read_manifest(epoch_dir)
    if not manifest or not manifest.get("leaves"):
        return None
    heads = {tuple(i["shape"])[0] if i["shape"] else None
             for i in manifest["leaves"].values()}
    if len(heads) != 1 or None in heads:
        return None
    return int(heads.pop())


def host_tree(path: str) -> tuple[dict[str, np.ndarray], int]:
    """Template-free inspection load of a SHARDED checkpoint: merge every
    locally-visible shard into ``{leaf key: full host ndarray}`` and
    return it with the committed epoch.  Verifies crc32 per shard file."""
    manifest = _read_manifest(path)
    if not manifest:
        raise FileNotFoundError(f"no committed manifest under {path}")
    pieces: dict[str, list] = {}
    for fname, info in manifest["shards"].items():
        fp = os.path.join(path, fname)
        if not os.path.isfile(fp):
            continue
        with open(fp, "rb") as f:
            raw = f.read()
        if (len(raw) != int(info["bytes"])
                or zlib.crc32(raw) != int(info["crc32"])):
            raise ValueError(
                f"checkpoint shard {fp} is corrupt (size/crc mismatch vs "
                "manifest)")
        payload = serialization.msgpack_restore(raw)
        for key, plist in payload["leaves"].items():
            pieces.setdefault(key, []).extend(plist)
    out = {}
    for key, info in manifest["leaves"].items():
        if key not in pieces:
            raise ValueError(f"checkpoint leaf {key} has no pieces in any "
                             f"visible shard under {path}")
        plist = pieces[key]
        out[key] = _merge_pieces(key, plist, tuple(info["shape"]),
                                 plist[0][1].dtype)
    return out, int(manifest["global_epoch"])


def _strip_buddy(state):
    """Drop the ISSUE 12 buddy rows from a ``TrainState``-shaped tree.

    The buddy copy is DERIVED state (ring-rolled shard-resident rows,
    ``comms.derive_buddy``): persisting it would couple the checkpoint
    layout to the redundancy flag for zero information.  Both the save
    path and the restore template route through this, so checkpoints
    are buddy-less whichever flag wrote or reads them; the engine
    re-derives the copy after restore (``LocalSGDEngine.refresh_buddy``
    / ``stage_state``)."""
    if getattr(state, "buddy", None) is not None and hasattr(state,
                                                             "replace"):
        return state.replace(buddy=None)
    return state


def restore_checkpoint(path: str, state_template, *,
                       params_template=None, bucket_bytes: int | None = None,
                       num_slices: int = 1):
    """Restore ``(state, global_epoch)`` from a checkpoint path.

    ``path`` is a committed sharded directory (format 2) or a legacy
    single msgpack file (format 1 — back-compat shim).  The template
    provides the pytree structure/shapes AND the target shardings: each
    restored host array is ``device_put`` onto its template leaf's
    sharding, so resuming on a different mesh/host-count re-shards
    cleanly instead of leaving host numpy in the tree.

    Cross-residency restore (ISSUE 11): a checkpoint saved with
    scatter-resident params (``.params_resident`` bucket rows — the PR 5
    shard files ARE the 1/N storage unit, no gather ever ran on the save
    path) restores into a replicated template and vice versa; a
    pre-ISSUE-11 (replicated) checkpoint restores into a resident run
    unchanged.  Both directions are exact re-layouts of the same
    consensus vector.  ``params_template`` (per-worker ShapeDtypeStructs,
    the engine's) is required for the replicated->resident direction —
    bucket rows carry no leaf shapes; ``bucket_bytes`` defaults to the
    manifest's recorded ``sync_bucket_mb`` and then the engine default.

    Cross-SLICE restore (ISSUE 13): ``num_slices`` is the RESTORING
    run's slice count; the manifest records the saving run's.  Resident
    bucket rows re-tile across slice layouts wherever the consensus
    semantics permit — a flat (global-consensus) checkpoint restores
    into any S x W hierarchical layout (every slice adopts the one
    consensus) and W re-tiles at a fixed S; a hierarchical checkpoint's
    PER-SLICE consensuses cannot re-shard to a different slice count
    (whose slice would a new one inherit?) and are refused with the
    real reason.  A missing ``sync_residual_outer`` (pre-ISSUE-13 or
    cross-topology) restores as zero rows — EF correction state is
    sub-quantum mass, safe to reset."""
    state_template = _strip_buddy(state_template)
    if os.path.isdir(path):
        merged, epoch = host_tree(path)
        flat, treedef = jax.tree_util.tree_flatten_with_path(state_template)
        merged = _relayout_params_residency(
            path, merged, flat, params_template=params_template,
            bucket_bytes=bucket_bytes, num_slices=num_slices)
        leaves = []
        for kpath, tmpl in flat:
            key = jax.tree_util.keystr(kpath)
            is_round_opt = key.startswith(".round_opt")
            is_outer_res = key.startswith(".sync_residual_outer")
            if is_outer_res and (
                    key not in merged
                    or tuple(np.shape(merged[key]))
                    != tuple(np.shape(tmpl))):
                # ISSUE 13: absent (pre-hierarchical checkpoint) or
                # re-tiled outer EF rows restore as zeros — the residual
                # is accumulated sub-quantum correction mass and resets
                # safely across topology changes (fresh EF start)
                if key in merged:
                    log.warning(
                        "checkpoint %s outer-residual leaf %s shape %s "
                        "does not match template %s (slice/worker "
                        "re-layout) — restoring zero rows", path, key,
                        np.shape(merged.get(key)), np.shape(tmpl))
                merged.pop(key, None)
                leaves.append(_reshard_leaf(
                    tmpl, np.zeros(np.shape(tmpl), np.dtype(tmpl.dtype))))
                continue
            if key not in merged:
                if is_round_opt:
                    # pre-ISSUE-9 checkpoint (or one saved without the
                    # tracker) restored into a tracker-armed run: fresh
                    # zero moments, exactly like a fresh engine init
                    log.warning(
                        "checkpoint %s has no round-optimizer leaf %s — "
                        "restoring zero moments", path, key)
                    leaves.append(_reshard_leaf(
                        tmpl, np.zeros(np.shape(tmpl),
                                       np.dtype(tmpl.dtype))))
                    continue
                raise ValueError(
                    f"checkpoint {path} has no leaf {key} required by the "
                    "restore template (engine config mismatch?)")
            val = merged[key]
            if (is_round_opt
                    and tuple(val.shape) != tuple(np.shape(tmpl))):
                # cross-placement restore (ISSUE 9 satellite): the saved
                # moment rows are either worker-axis shards of one
                # vector ([N, P/N], --opt_placement sharded) or N
                # identical replicas ([N, P]); both reconstruct the same
                # vector, so the re-layout is exact in either direction
                val = _relayout_round_opt(key, val, np.shape(tmpl))
            if tuple(val.shape) != tuple(np.shape(tmpl)):
                raise ValueError(
                    f"checkpoint leaf {key} shape {val.shape} does not "
                    f"match template {np.shape(tmpl)}")
            tdt = getattr(tmpl, "dtype", None)
            if tdt is not None and np.dtype(tdt) != np.dtype(val.dtype):
                raise ValueError(
                    f"checkpoint leaf {key} dtype {val.dtype} does not "
                    f"match template {tdt} (saved with a different "
                    "--dtype/--compute_dtype config?)")
            leaves.append(_reshard_leaf(tmpl, val))
        return jax.tree_util.tree_unflatten(treedef, leaves), epoch
    # ---- legacy v1 single file ---------------------------------------
    with open(path, "rb") as f:
        data = f.read()
    payload = serialization.from_bytes(
        {"state": state_template, "global_epoch": 0}, data)
    state = jax.tree.map(_reshard_leaf, state_template, payload["state"])
    return state, int(payload["global_epoch"])


def _slice_consensus_vectors(rows: np.ndarray, filled: int,
                             saved_slices: int) -> list[np.ndarray]:
    """Split one saved resident bucket's ``[S*W, row]`` rows into the S
    per-slice FILLED consensus vectors (pad trimmed) — the host form of
    what each slice's entry gather would reconstruct (ISSUE 13)."""
    n_rows = int(rows.shape[0])
    if n_rows % max(1, saved_slices):
        raise ValueError(
            f"resident bucket rows ({n_rows}) not divisible by the "
            f"manifest's slice count ({saved_slices})")
    per = n_rows // max(1, saved_slices)
    return [rows[s * per:(s + 1) * per].reshape(-1)[:filled]
            for s in range(max(1, saved_slices))]


def _relayout_resident_slices(path: str, merged: dict, tmpl_flat, *,
                              params_template, bucket_bytes: int,
                              saved_slices: int, num_slices: int) -> dict:
    """Resident -> resident re-layout across slice/worker layouts
    (ISSUE 13): reconstruct each bucket's per-slice consensus vectors
    under the SAVED tiling and re-pack them under the TEMPLATE's.

    Permitted: a flat (1-slice, global-consensus) checkpoint into any
    S x W layout — every slice adopts the one consensus; and a same-S
    re-tile to a different W.  Refused with the real reason: changing
    the slice COUNT of a genuinely per-slice state (the consensuses are
    distinct — no assignment to a different S is semantically defined),
    unless the slices happen to agree bitwise (then the state IS a
    global consensus and re-tiles like a flat one)."""
    from . import comms

    if params_template is None:
        raise ValueError(
            f"checkpoint {path} resident layout needs a re-layout "
            "across slice/worker tilings: pass params_template= (the "
            "engine's per-worker ShapeDtypeStructs)")
    t_items = [(jax.tree_util.keystr(p), t) for p, t in tmpl_flat
               if jax.tree_util.keystr(p).startswith(".params_resident")]
    leaves = jax.tree_util.tree_leaves(params_template)
    out = dict(merged)
    # template tiling: rows = S_t x W_t, shard width from the plan
    rows_t = int(np.shape(t_items[0][1])[0])
    if rows_t % max(1, num_slices):
        raise ValueError(
            f"restore template resident rows ({rows_t}) not divisible "
            f"by num_slices ({num_slices})")
    w_t = rows_t // max(1, num_slices)
    plan_t = comms.bucket_plan(leaves, w_t, bucket_bytes)
    # saved tiling: infer W from the saved rows of bucket 0
    key0 = f".params_resident['{comms._bucket_name(0)}']"
    if key0 not in merged:
        raise ValueError(
            f"checkpoint {path} resident layout has no bucket leaf "
            f"{key0} (saved with a different sync_bucket_mb?)")
    rows_s = int(np.shape(merged[key0])[0])
    if rows_s % max(1, saved_slices):
        raise ValueError(
            f"checkpoint resident rows ({rows_s}) not divisible by the "
            f"manifest's slice count ({saved_slices})")
    w_s = rows_s // max(1, saved_slices)
    plan_s = comms.bucket_plan(leaves, w_s, bucket_bytes)
    if len(plan_s) != len(plan_t):
        raise ValueError(
            f"checkpoint {path} resident bucket count ({len(plan_s)}) "
            f"differs from the template's ({len(plan_t)}) — different "
            "sync_bucket_mb?")
    for i, (bs, bt) in enumerate(zip(plan_s, plan_t)):
        key = f".params_resident['{comms._bucket_name(i)}']"
        if key not in out:
            raise ValueError(
                f"checkpoint {path} resident layout has no bucket leaf "
                f"{key}")
        arr = np.asarray(out.pop(key))
        if arr.shape != (rows_s, bs.padded // w_s):
            raise ValueError(
                f"checkpoint resident bucket {key} has shape "
                f"{arr.shape}, expected {(rows_s, bs.padded // w_s)} "
                "(different sync_bucket_mb or worker count?)")
        filled = sum(size for (_j, _off, size) in bs.items)
        vecs = _slice_consensus_vectors(arr, filled, saved_slices)
        if saved_slices != num_slices:
            if all(np.array_equal(vecs[0], v) for v in vecs[1:]):
                vecs = [vecs[0]] * max(1, num_slices)
            else:
                raise ValueError(
                    f"checkpoint {path} was saved with "
                    f"{saved_slices} slice(s) whose consensuses "
                    f"DIFFER; it cannot re-shard to {num_slices} "
                    "slice(s) — a per-slice consensus has no defined "
                    "assignment to a different slice count (restore "
                    "into the saved topology, or into a replicated "
                    "layout)")
        pad = bt.padded - filled
        tiles = []
        for vec in vecs:
            if pad:
                vec = np.concatenate([vec, np.zeros(pad, vec.dtype)])
            tiles.append(vec.reshape(w_t, bt.padded // w_t))
        out[key] = np.concatenate(tiles, axis=0)
    return out


def _relayout_params_residency(path: str, merged: dict, tmpl_flat,
                               *, params_template=None,
                               bucket_bytes: int | None = None,
                               num_slices: int = 1) -> dict:
    """Re-lay checkpointed params across residency modes (ISSUE 11).

    ``merged`` is the host-merged leaf dict; ``tmpl_flat`` the restore
    template's ``(path, leaf)`` list.  When the checkpoint and template
    agree on residency this is the identity.  Otherwise the consensus
    vector is reconstructed and re-laid out exactly:

    - resident on disk -> replicated template: concatenate each bucket's
      shard rows (the gather, on host), slice the leaves out by the
      bucket plan over the template's own params shapes, and tile each
      to the worker-stacked consensus rows;
    - replicated on disk (incl. pre-ISSUE-11 checkpoints) -> resident
      template: verify every params leaf's rows are identical (only a
      weights x equal consensus state can become resident), pack row 0
      into the resident bucket layout (``comms.resident_from_tree``).
      Needs ``params_template`` — resident bucket rows carry no leaf
      shapes, and a resident restore template has no params tree.

    The bucket size comes from the direction's authoritative side: the
    manifest's recorded ``sync_bucket_mb`` for interpreting a resident
    checkpoint, the restoring engine's ``bucket_bytes`` for building a
    resident template layout (each falls back to the other, then the
    engine default)."""
    from . import comms

    ckpt_resident = any(k.startswith(".params_resident") for k in merged)
    tmpl_resident = any(
        jax.tree_util.keystr(p).startswith(".params_resident")
        for p, _t in tmpl_flat)
    meta = manifest_metadata(path)
    saved_slices = int(meta.get("num_slices", 1) or 1)
    meta_mb = meta.get("sync_bucket_mb")
    meta_bytes = int(float(meta_mb) * (1 << 20)) if meta_mb else None
    if ckpt_resident and tmpl_resident:
        # same layout kind — identity unless the slice/worker tiling
        # changed (ISSUE 13), in which case the consensus vectors
        # re-pack under the template's tiling
        same = all(
            jax.tree_util.keystr(p) in merged
            and tuple(np.shape(merged[jax.tree_util.keystr(p)]))
            == tuple(np.shape(t))
            for p, t in tmpl_flat
            if jax.tree_util.keystr(p).startswith(".params_resident"))
        if same and saved_slices == max(1, num_slices):
            return merged
        return _relayout_resident_slices(
            path, merged, tmpl_flat, params_template=params_template,
            bucket_bytes=(meta_bytes or bucket_bytes
                          or comms.DEFAULT_BUCKET_BYTES),
            saved_slices=saved_slices, num_slices=max(1, num_slices))
    if ckpt_resident == tmpl_resident:
        return merged
    out = dict(merged)
    if ckpt_resident:
        bb = meta_bytes or bucket_bytes or comms.DEFAULT_BUCKET_BYTES
        p_items = [(jax.tree_util.keystr(p), t) for p, t in tmpl_flat
                   if jax.tree_util.keystr(p).startswith(".params[")]
        if not p_items:
            raise ValueError(
                f"checkpoint {path} carries scatter-resident params but "
                "the restore template has neither a params tree nor a "
                "params_resident layout")
        n = int(np.shape(p_items[0][1])[0])
        if n % max(1, saved_slices):
            raise ValueError(
                f"restore template worker rows ({n}) not divisible by "
                f"the checkpoint's slice count ({saved_slices})")
        w_s = n // max(1, saved_slices)
        leaves = [jax.ShapeDtypeStruct(tuple(np.shape(t)[1:]),
                                       np.dtype(t.dtype))
                  for _k, t in p_items]
        # one slot per (leaf, slice): filled below, assembled after
        slice_rows: list[list] = [[None] * max(1, saved_slices)
                                  for _ in p_items]
        for i, b in enumerate(comms.bucket_plan(leaves, w_s, bb)):
            key = f".params_resident['{comms._bucket_name(i)}']"
            if key not in out:
                raise ValueError(
                    f"checkpoint {path} resident layout has no bucket "
                    f"leaf {key} (saved with a different sync_bucket_mb "
                    "than the manifest records?)")
            arr = np.asarray(out.pop(key))
            if arr.shape != (n, b.padded // w_s):
                raise ValueError(
                    f"checkpoint resident bucket {key} has shape "
                    f"{arr.shape}, expected {(n, b.padded // w_s)} "
                    "(different sync_bucket_mb or worker count?)")
            filled = sum(size for (_j, _off, size) in b.items)
            vecs = _slice_consensus_vectors(arr, filled, saved_slices)
            for (j, off, size) in b.items:
                _k, t = p_items[j]
                for s, vec in enumerate(vecs):
                    slice_rows[j][s] = vec[off:off + size].reshape(
                        np.shape(t)[1:]).astype(np.dtype(t.dtype))
        for j, (k, t) in enumerate(p_items):
            # worker (s, i)'s row is ITS slice's consensus — a flat
            # checkpoint (1 slice) broadcasts the one consensus to
            # every row, exactly as before
            rows = np.stack([slice_rows[j][s]
                             for s in range(max(1, saved_slices))
                             for _i in range(w_s)])
            out[k] = np.ascontiguousarray(rows.astype(np.dtype(t.dtype)))
        return out
    bb = bucket_bytes or meta_bytes or comms.DEFAULT_BUCKET_BYTES
    if params_template is None:
        raise ValueError(
            f"checkpoint {path} stores replicated params but the restore "
            "template is scatter-resident: pass params_template= (the "
            "engine's per-worker ShapeDtypeStructs) so the resident "
            "bucket layout can be rebuilt")
    pt_flat, pt_def = jax.tree_util.tree_flatten_with_path(params_template)
    s_t = max(1, num_slices)
    slice_vals: list[list] = []
    n = None
    for p, _t in pt_flat:
        key = ".params" + jax.tree_util.keystr(p)
        if key not in out:
            raise ValueError(
                f"checkpoint {path} has no params leaf {key} needed to "
                "build the resident layout (engine config mismatch?)")
        arr = np.asarray(out.pop(key))
        n = int(arr.shape[0])
        if n % s_t:
            raise ValueError(
                f"checkpoint worker rows ({n}) not divisible by "
                f"num_slices ({s_t})")
        per = n // s_t
        groups = []
        for s in range(s_t):
            g = arr[s * per:(s + 1) * per]
            if not np.array_equal(g, np.broadcast_to(g[:1], g.shape)):
                raise ValueError(
                    f"checkpoint leaf {key} rows differ within slice "
                    f"{s}: only a consensus state (weights x equal "
                    "aggregation) can restore into the scatter-resident "
                    "layout")
            groups.append(g[0])
        slice_vals.append(groups)
    w_t = n // s_t
    parts = []
    for s in range(s_t):
        tree_s = jax.tree_util.tree_unflatten(
            pt_def, [sv[s] for sv in slice_vals])
        parts.append(comms.resident_from_tree(tree_s, w_t,
                                              bucket_bytes=bb))
    for name in parts[0]:
        out[f".params_resident['{name}']"] = np.concatenate(
            [p[name] for p in parts], axis=0)
    return out


def _relayout_round_opt(key: str, val: np.ndarray,
                        tmpl_shape) -> np.ndarray:
    """Convert one round-optimizer leaf between the sharded ([N, P/N]
    worker-axis shard rows) and replicated ([N, P] identical rows)
    layouts (ISSUE 9).  The tracked vector is worker-invariant, so both
    directions are exact: sharded -> replicated concatenates the shard
    rows back into the vector and replicates it; replicated -> sharded
    row-partitions any replica.  The worker count itself must match (the
    other TrainState leaves enforce that first)."""
    n, p = int(val.shape[0]), int(val.shape[1]) if val.ndim == 2 else -1
    want = tuple(int(d) for d in tmpl_shape)
    if val.ndim != 2 or len(want) != 2 or want[0] != n:
        raise ValueError(
            f"checkpoint round-optimizer leaf {key} shape "
            f"{tuple(val.shape)} cannot re-layout to template {want}")
    if want[1] == n * p:         # sharded on disk -> replicated template
        return np.broadcast_to(val.reshape(-1), want).copy()
    if p == n * want[1]:         # replicated on disk -> sharded template
        return np.ascontiguousarray(val[0].reshape(n, want[1]))
    raise ValueError(
        f"checkpoint round-optimizer leaf {key} shape "
        f"{tuple(val.shape)} matches neither the sharded nor the "
        f"replicated layout of template {want} (different "
        "--sync_bucket_mb or worker count?)")


def _reshard_leaf(tmpl, val):
    if isinstance(tmpl, jax.Array) and hasattr(tmpl, "sharding"):
        # .copy() materializes an XLA-owned buffer: device_put of host
        # numpy on XLA:CPU can ZERO-COPY (the jax.Array aliases
        # numpy-owned malloc memory), and the round program then DONATES
        # that buffer — XLA freeing memory it never allocated corrupts
        # the heap (reproducible segfault: resume + a warm persistent
        # compile cache shifts allocation timing enough to crash every
        # run; without the cache it corrupts silently or not at all).
        return jax.block_until_ready(
            jax.device_put(val, tmpl.sharding)).copy()
    return val


# ----------------------------------------------------------------------
# Back-compat module API (blocking wrappers over the engine)
# ----------------------------------------------------------------------

def save_checkpoint(ckpt_dir: str, state, global_epoch: int,
                    keep: int = 3, metadata: dict | None = None) -> str:
    """Blocking sharded save (module-level convenience; the driver holds a
    long-lived ``CheckpointEngine`` instead).  EVERY process must call
    this — the commit barrier is collective.  Note the transient engine's
    open-time sweep: do not mix with a concurrently-writing async engine
    on the same directory."""
    eng = CheckpointEngine(ckpt_dir, keep=keep, async_write=False,
                           metadata=metadata)
    return eng.save(state, global_epoch)


def save_checkpoint_legacy(ckpt_dir: str, state, global_epoch: int) -> str:
    """The pre-engine blocking save (format 1): gather the FULL state to
    every host, serialize one msgpack inline.  Kept to manufacture legacy
    checkpoints for the back-compat tests (tests/test_checkpoint.py)."""
    state = _strip_buddy(state)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        host_state = multihost_utils.process_allgather(state, tiled=True)
    else:
        host_state = jax.device_get(state)
    path = os.path.join(ckpt_dir, f"ckpt_{global_epoch}.msgpack")
    os.makedirs(ckpt_dir, exist_ok=True)
    payload = {"state": host_state, "global_epoch": global_epoch}
    tmp = f"{path}.tmp.{jax.process_index()}"
    with open(tmp, "wb") as f:
        f.write(serialization.to_bytes(payload))
    os.replace(tmp, path)
    return path

"""Checkpoint save/restore.

≙ the reference's ``tf.train.Saver`` + Supervisor autosave +
restore-if-present (src/distributed_train.py:222,244-252,262,405-408)
and the evaluator's read side (src/nn_eval.py:70-88). Differences:

* msgpack-serialized pytrees (flax.serialization) written atomically
  (tmp + rename) so a reader never sees a torn file — the reference
  relies on Saver's own atomicity over NFS.
* The data-iterator position and config are checkpointed too, so
  *resume is exact* (the reference resumes params but restarts its
  time-seeded data stream from scratch).
* A ``checkpoint.json`` pointer names the latest step — the moral
  equivalent of TF's ``checkpoint`` proto file.
* **Quantized sidecar tiers** (``quant/`` — the serving-precision
  pass): a publish may additionally write
  ``ckpt-{step}.quant.msgpack`` next to the artifact, holding
  ``{"tiers": {tier: state-dict-shaped param tree}, "meta": json}``
  for the configured tiers — ``int8`` leaves are
  ``{"q": int8[..., C], "scale": float32[1, ..., C]}`` per-channel
  pairs (1-D leaves stay float32), ``bf16`` leaves a straight bf16
  cast; ``meta`` records the source params' sha256, the calibration
  stats, and the tier list. The sidecar gets its OWN ``.sha256``
  digest sidecar through the same atomic-write machinery, so a torn
  sidecar is refused exactly like a torn checkpoint (the serving
  replica then falls back to the full-precision artifact). Sidecars
  are ADDITIVE: the full-precision artifact's bytes and digest are
  untouched by publishing them, they never make a step "loadable" on
  their own, and they garbage-collect with their step.
* **Per-host sharded format** (SURVEY §2.3 "per-host array
  serialization", ≙ the Saver-over-NFS multi-worker layout): when the
  state holds arrays whose shards this process cannot fully
  materialize (a model/seq/stage/expert axis crossing process
  boundaries), EVERY process writes
  ``ckpt-{step}.shard{p}-of-{P}.msgpack`` with its addressable shard
  data keyed by global index, and process 0 writes a
  ``ckpt-{step}.manifest.json`` (global shapes/dtypes + the extra
  payload) plus the pointer. Restore reads every shard file and
  reassembles full global arrays — so any layout-compatible consumer
  (a resumed cluster of any process count, the evaluator on its own
  mesh, a single device) can load the checkpoint.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
import time
from pathlib import Path
from typing import Any, Callable

import jax
import numpy as np
from flax import serialization

from ..core.log import get_logger
from . import storage

logger = get_logger("checkpoint")

_POINTER = "checkpoint.json"
_DIGEST_SUFFIX = ".sha256"


class CheckpointCorruptError(ValueError):
    """A checkpoint artifact exists but cannot be trusted: torn write
    (truncated msgpack / unparseable manifest) or checksum mismatch.
    Distinct from FileNotFoundError (an incomplete publish) so callers
    can tell "never finished writing" from "finished then damaged" —
    both fall back to the previous loadable step on restore.

    Subclasses ValueError because that is what the raw failures
    (msgpack unpack errors, json.JSONDecodeError) raised before this
    wrapper existed — long-running consumers like the eval service
    catch ValueError around checkpoint reads and skip-and-retry; this
    type must keep flowing into those handlers, not crash them."""


class WorldSizeMismatchError(ValueError):
    """A checkpoint was written under a different world
    ``(replica count, process count, mesh shape)`` than the consumer
    requires. Deliberately NOT a :class:`CheckpointCorruptError`: a
    world mismatch affects EVERY step of the run equally, so the
    restore must not "fall back" past all of them and silently discard
    the run — it must surface so the caller can branch: the
    supervisor's reconfigure path (and the mesh-portable
    ``parallel.api.restore_for_topology``) reshards the artifact for
    the new world; a strict consumer aborts with both worlds named
    instead of a raw flax structure error."""

    def __init__(self, msg: str, saved_world: dict | None = None,
                 requested_world: dict | None = None):
        super().__init__(msg)
        self.saved_world = saved_world
        self.requested_world = requested_world


class OptimizerStateMismatchError(ValueError):
    """A checkpoint carries a different optimizer-STATE kind
    (none/momentum/lars/lamb — train/optim.opt_state_kind) than the
    restoring run's config. Like :class:`WorldSizeMismatchError`, this
    is deliberately NOT a :class:`CheckpointCorruptError`: the mismatch
    affects every step of the run equally, so the restore must surface
    it — naming both kinds — rather than fall back past the whole run
    or silently graft one optimizer's moments into another's slots
    (momentum and LARS state even share a tree shape, so the structural
    graft would SUCCEED and quietly corrupt the trust-ratio math)."""

    def __init__(self, msg: str, saved_kind: str | None = None,
                 requested_kind: str | None = None):
        super().__init__(msg)
        self.saved_kind = saved_kind
        self.requested_kind = requested_kind


# -- I/O retry wrapper ------------------------------------------------------
#
# Checkpoint reads/writes hit network filesystems in production; a
# transient EIO/ESTALE must not look like corruption (which would
# discard a perfectly good step). FileNotFoundError stays immediate:
# a missing file is a publish-ordering fact, not a flake.

_IO_ATTEMPTS = 3
_IO_BACKOFF_S = 0.05


def _io_retries(fn: Callable[[], Any], what: str) -> Any:
    for attempt in range(1, _IO_ATTEMPTS + 1):
        try:
            return fn()
        except FileNotFoundError:
            raise
        except OSError as e:
            if attempt == _IO_ATTEMPTS:
                raise
            delay = _IO_BACKOFF_S * 2 ** (attempt - 1)
            logger.warning("I/O error on %s (%s) — attempt %d/%d, "
                           "retrying in %.2fs", what, e, attempt,
                           _IO_ATTEMPTS, delay)
            time.sleep(delay)


def _ckpt_path(train_dir: Path, step: int) -> Path:
    return train_dir / f"ckpt-{step:08d}.msgpack"


def _manifest_path(train_dir: Path, step: int) -> Path:
    return train_dir / f"ckpt-{step:08d}.manifest.json"


def _shard_path(train_dir: Path, step: int, p: int, count: int) -> Path:
    return train_dir / f"ckpt-{step:08d}.shard{p:03d}-of-{count:03d}.msgpack"


def _leaf_locally_complete(leaf: Any) -> bool:
    """True when this process can materialize the WHOLE array."""
    if not isinstance(leaf, jax.Array):
        return True
    return bool(leaf.is_fully_addressable or leaf.is_fully_replicated)


def state_needs_sharded_save(state: Any) -> bool:
    """True when some array's shards live only on other processes —
    the single-file writer (a process-0 ``device_get``) cannot
    materialize it and the per-host sharded format must be used."""
    return not all(_leaf_locally_complete(l) for l in jax.tree.leaves(state))


def _flat_state_items(state: Any):
    """state → [("a/b/c", leaf)] over the flax state-dict view."""
    sd = serialization.to_state_dict(state)
    flat, _ = jax.tree_util.tree_flatten_with_path(sd)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out.append((key, leaf))
    return out


def snapshot_for_save(state: Any):
    """Synchronously pull this process's view of ``state`` to host.

    Returns ``("full", host_state_dict)`` when every leaf is locally
    complete (the classic single-file layout, written by process 0), or
    ``("sharded", local_leaves, meta)`` where ``local_leaves`` maps
    leaf keys to either a full ndarray (locally-complete leaves, kept
    by process 0 only) or ``{"indices": [...], "datas": [...]}`` shard
    slabs, and ``meta`` records global shape/dtype per leaf.
    """
    if not state_needs_sharded_save(state):
        return ("full", serialization.to_state_dict(jax.device_get(state)))
    pidx = jax.process_index()
    local: dict = {}
    meta: dict = {}
    for key, leaf in _flat_state_items(state):
        if leaf is None:
            continue
        if _leaf_locally_complete(leaf):
            meta[key] = {"full": True}
            if pidx == 0:
                local[key] = np.asarray(jax.device_get(leaf))
            continue
        meta[key] = {"shape": list(leaf.shape), "dtype": str(leaf.dtype)}
        slabs: dict = {}
        for sh in leaf.addressable_shards:
            idx = tuple(sl.indices(dim)[:2]
                        for sl, dim in zip(sh.index, leaf.shape))
            if idx not in slabs:  # replicas of the same slab: keep one
                slabs[idx] = np.asarray(sh.data)
        local[key] = {
            "indices": [[list(ab) for ab in idx] for idx in slabs],
            "datas": list(slabs.values()),
        }
    return ("sharded", local, meta)


class _LeafViews:
    """Zero-copy per-shard host views of ONE device array — the CPU
    client's donation-safe snapshot primitive. ``np.asarray`` of a
    single-device shard is a host view (no copy), and PJRT's
    copy-on-donate protects any buffer with a live external reference,
    so the views keep their pre-donation values after the next step
    donates the state (pinned by tests/test_async_checkpoint.py).
    Crucially the
    CROSS-SHARD ASSEMBLY of replica-split (ZeRO-1) leaves — the real
    per-save cost — is deferred to :meth:`materialize` on the
    checkpoint worker thread instead of the train loop."""

    __slots__ = ("shape", "dtype", "slabs")

    def __init__(self, x: "jax.Array"):
        self.shape, self.dtype = tuple(x.shape), x.dtype
        self.slabs: list = []
        seen = set()
        for sh in x.addressable_shards:
            idx = tuple(sl.indices(dim)[:2]
                        for sl, dim in zip(sh.index, x.shape))
            if idx in seen:  # replicas of the same slab: keep one
                continue
            seen.add(idx)
            self.slabs.append((idx, np.asarray(sh.data)))

    def materialize(self) -> np.ndarray:
        if len(self.slabs) == 1 and self.slabs[0][1].shape == self.shape:
            return self.slabs[0][1]
        buf = np.empty(self.shape, self.dtype)
        for idx, data in self.slabs:
            buf[tuple(slice(a, b) for a, b in idx)] = data
        return buf


def host_view_snapshot(state: Any) -> Any:
    """Snapshot ``state`` as per-shard host views (:class:`_LeafViews`
    per jax leaf; other leaves pass through) — near-zero cost on the
    train loop. Pair with :func:`materialize_snapshot` on the worker.
    CPU-client only: on accelerators ``np.asarray(shard.data)`` is a
    blocking D2H transfer, exactly the stall this exists to avoid —
    those backends snapshot via an async on-device copy instead
    (train/loop.py)."""
    return jax.tree.map(
        lambda x: _LeafViews(x) if isinstance(x, jax.Array) else x, state)


def materialize_snapshot(tree: Any) -> Any:
    """Assemble a :func:`host_view_snapshot` back into plain numpy
    leaves (the worker-thread half)."""
    return jax.tree.map(
        lambda x: x.materialize() if isinstance(x, _LeafViews) else x,
        tree, is_leaf=lambda x: isinstance(x, _LeafViews))


def _digest_path(path: Path) -> Path:
    return path.with_suffix(path.suffix + _DIGEST_SUFFIX)


def _write_atomic(path: Path, data: bytes, digest: bool = True) -> None:
    def write() -> None:
        tmp = path.with_suffix(path.suffix + ".tmp")
        storage.write_bytes(tmp, data, role="data")
        dpath = _digest_path(path)
        if digest:
            # drop any PREVIOUS sidecar before the data lands: this
            # path can be overwritten (a NaN rollback or kill+resume
            # re-saves the same step), and a crash after the new data
            # but before the new digest must leave a digest-LESS
            # (legacy-accepted) file — never old-digest-over-new-bytes,
            # which would reject a perfectly good checkpoint
            dpath.unlink(missing_ok=True)
        storage.replace(tmp, path, role="data")
        if digest:
            dtmp = dpath.with_name(dpath.name + ".tmp")
            storage.write_text(dtmp, hashlib.sha256(data).hexdigest(),
                               role="sidecar")
            storage.replace(dtmp, dpath, role="sidecar")
    _io_retries(write, path.name)


def _verified_read(path: Path) -> bytes:
    """Read ``path`` (with I/O retries) and verify it against its
    digest sidecar when one exists — a file without a sidecar is
    accepted as-is (pre-checksum layout, or a crash between data and
    digest writes)."""
    data = _io_retries(lambda: storage.read_bytes(path), path.name)
    dpath = _digest_path(path)
    if dpath.exists():
        want = _io_retries(lambda: storage.read_text(dpath),
                           dpath.name).strip()
        got = hashlib.sha256(data).hexdigest()
        if want and got != want:
            raise CheckpointCorruptError(
                f"{path.name}: sha256 mismatch (file {got[:12]}… != "
                f"recorded {want[:12]}…)")
    return data


def verify_artifact(path: str | Path) -> None:
    """Public digest check for one checkpoint artifact: raises
    :class:`CheckpointCorruptError` when ``path`` fails its sha256
    sidecar (a file WITHOUT a sidecar is accepted — pre-checksum
    layout, or a crash between the data and digest writes). The
    invariant checker (obsv/invariants.py) audits checkpoint dirs
    through this so the sidecar contract lives in exactly one place."""
    _verified_read(Path(path))


def _msgpack_restore_checked(data: bytes, path: Path) -> Any:
    try:
        return serialization.msgpack_restore(data)
    except Exception as e:  # msgpack raises several unpack error types
        raise CheckpointCorruptError(
            f"{path.name}: torn or corrupt msgpack ({type(e).__name__}: "
            f"{e})") from e


def _manifest_checksum(manifest: dict) -> str:
    body = {k: v for k, v in manifest.items() if k != "checksum"}
    return hashlib.sha256(
        json.dumps(body, sort_keys=True).encode()).hexdigest()


def _read_manifest(train_dir: Path, step: int) -> dict:
    mpath = _manifest_path(train_dir, step)
    text = _io_retries(lambda: storage.read_text(mpath), mpath.name)
    try:
        manifest = json.loads(text)
    except json.JSONDecodeError as e:
        raise CheckpointCorruptError(
            f"{mpath.name}: torn or corrupt manifest ({e})") from e
    want = manifest.get("checksum")
    if want and _manifest_checksum(manifest) != want:
        raise CheckpointCorruptError(f"{mpath.name}: checksum mismatch")
    return manifest


def _write_pointer(train_dir: Path, step: int, latest_name: str) -> None:
    pointer = {"latest_step": step, "latest_path": latest_name,
               "written_at": time.time()}
    ptmp = train_dir / (_POINTER + ".tmp")
    storage.write_text(ptmp, json.dumps(pointer), role="pointer")
    storage.replace(ptmp, train_dir / _POINTER, role="pointer")


def save_checkpoint(train_dir: str | Path, state: Any, step: int,
                    extra: dict | None = None, keep: int = 5) -> Path:
    """Atomically write state (+ JSON-serializable ``extra``) at
    ``step``. ``state`` may be a live (possibly device-sharded) pytree
    or a snapshot from :func:`snapshot_for_save` (the async writer's
    path). Single-file when this process can materialize everything;
    per-host sharded otherwise (module docstring) — in the sharded case
    EVERY process must call this (each writes its own shard file)."""
    train_dir = Path(train_dir)
    train_dir.mkdir(parents=True, exist_ok=True)
    snap = (state if isinstance(state, tuple)
            and state and state[0] in ("full", "sharded")
            else snapshot_for_save(state))

    if snap[0] == "full":
        # extra goes through JSON (tuples etc. are not msgpack-clean)
        payload = {"state": snap[1], "extra": json.dumps(extra or {})}
        data = serialization.msgpack_serialize(payload)
        path = _ckpt_path(train_dir, step)
        _write_atomic(path, data)
        _write_pointer(train_dir, step, path.name)
        _garbage_collect(train_dir, keep)
        logger.info("saved checkpoint step=%d → %s", step, path.name)
        return path

    _, local, meta = snap
    pidx, pcount = jax.process_index(), jax.process_count()
    path = _shard_path(train_dir, step, pidx, pcount)
    _write_atomic(path, serialization.msgpack_serialize({"leaves": local}))
    if pidx == 0:
        manifest = {"step": step, "num_shards": pcount, "leaves": meta,
                    "extra": extra or {}}
        manifest["checksum"] = _manifest_checksum(manifest)
        mpath = _manifest_path(train_dir, step)
        _write_atomic(mpath, json.dumps(manifest).encode(), digest=False)
        _write_pointer(train_dir, step, mpath.name)
        logger.info("saved sharded checkpoint step=%d → %s (+%d shard files)",
                    step, mpath.name, pcount)
    _garbage_collect(train_dir, keep)
    return path


class AsyncCheckpointer:
    """Background-thread checkpoint writer.

    The reference's Supervisor saves synchronously from its own timer
    thread (src/distributed_train.py:244-252); here the *train loop*
    triggers saves, so serialization + file IO must not stall the step
    cadence. By default ``save`` fetches state to host synchronously
    (the step function donates its input buffers, so a background
    device read of the LIVE state would race with donation) and hands
    the numpy pytree to a worker that msgpacks and writes it.

    **Donation-safe device snapshots** (``prepare=``): a caller that
    has already copied the state into fresh un-donated device buffers
    (train/loop.py dispatches that copy right after the step, BEFORE
    the next step's program is enqueued — so the copy reads the
    donated buffers first) passes the device-array pytree plus a
    ``prepare`` callable; the WORKER thread runs ``prepare`` (D2H
    fetch + canonical-layout conversion) before writing, and the train
    loop's stall shrinks to the copy dispatch. A ``prepare`` failure
    counts as a failed write (logged + surfaced on ``wait``), same as
    an IO error.

    Latest-wins: if a save is still in flight when the next one
    arrives, the pending one is replaced — checkpoints are snapshots,
    not a journal. Worker errors surface on the next ``save``/``wait``.
    """

    def __init__(self, max_consecutive_failures: int = 3,
                 on_error: Callable[[int, Exception], None] | None = None):
        self._lock = threading.Lock()
        self._pending: tuple | None = None
        self._busy = False
        self._error: Exception | None = None  # last write's outcome
        self._last_failure: Exception | None = None  # never cleared by wait()
        self._consecutive_failures = 0
        self.max_consecutive_failures = max_consecutive_failures
        # Journal hook ``(step, exception)`` for a failed write — the
        # trainer records a schema-declared ``save_failed`` recovery
        # event so a skipped cadence save is auditable evidence, not
        # just a log line (obsv/invariants.py storage_faults licenses
        # it against the injected disk fault that caused it).
        self._on_error = on_error
        self._wake = threading.Condition(self._lock)
        self._stop = False
        self.closed = False
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="ckpt-writer")
        self._thread.start()

    def _worker(self) -> None:
        while True:
            with self._wake:
                while self._pending is None and not self._stop:
                    self._wake.wait()
                if self._stop and self._pending is None:
                    return
                job = self._pending
                self._pending = None
                self._busy = True
            try:
                *args, prepare, publish = job
                if prepare is not None:
                    # device snapshot → host + canonical layout, off
                    # the train loop's critical path
                    args[1] = prepare(args[1])
                if publish is not None:
                    # sidecar hook (the quant tiers): runs BEFORE the
                    # artifact/pointer write — a follower that sees
                    # the pointer name a new step must find its
                    # sidecar already on disk, or a fast poll lands in
                    # the gap, falls back to fp32, and (cursor
                    # advanced) never revisits that step's tier. A
                    # sidecar with no artifact yet is harmless: it
                    # never makes a step loadable and GCs with it. A
                    # sidecar failure must never read as a failed
                    # CHECKPOINT.
                    try:
                        publish(args[1], args[2])
                    except Exception as e:
                        logger.warning("pre-save publish hook for "
                                       "step=%d failed: %s", args[2], e)
                save_checkpoint(*args)
            except Exception as e:
                # Log NOW (the failure may otherwise go unnoticed for
                # hours of training); also kept for wait() to raise.
                logger.error("async checkpoint write for step=%d failed: %s",
                             job[2], e)
                with self._lock:
                    self._error = e
                    self._last_failure = e
                    self._consecutive_failures += 1
                if self._on_error is not None:
                    try:
                        self._on_error(job[2], e)
                    except Exception:
                        logger.exception("checkpoint on_error hook failed")
            else:
                with self._lock:
                    self._error = None  # a later success supersedes
                    self._consecutive_failures = 0
            finally:
                with self._wake:
                    self._busy = False
                    self._wake.notify_all()

    def _raise_pending_error(self) -> None:
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError("async checkpoint write failed") from err

    def save(self, train_dir: str | Path, state: Any, step: int,
             extra: dict | None = None, keep: int = 5,
             no_skip: bool = False,
             prepare: Callable[[Any], Any] | None = None,
             publish: Callable[[Any, int], Any] | None = None) -> None:
        """Queue a write. A single failed write never raises here —
        that already went to the log and a later save may well succeed
        (transient disk pressure); ``wait`` raises if the LAST write
        failed, so a broken final checkpoint is never silent. A
        persistently broken disk does stop training: after
        ``max_consecutive_failures`` failed writes in a row, ``save``
        raises instead of letting checkpoints go silently stale.

        ``no_skip``: drain a lagging queued write instead of replacing
        it — the per-host sharded layout needs EVERY process to write
        EVERY triggered step, or a process that skipped a different
        step than its siblings would leave that checkpoint torn.

        ``prepare``: defer the host snapshot to the worker thread (the
        donation-safe device-snapshot path, class docstring) — the
        caller must pass buffers the step will NOT donate (a fresh
        device copy).

        ``publish``: sidecar hook ``(prepared_state, step)`` run by
        the worker BEFORE the artifact/pointer write (the quantized-
        tier pass rides here so it stays off the step loop AND so a
        follower that sees the new pointer always finds the sidecar
        already published); its failures are logged, never surfaced
        as checkpoint failures (the sidecar is additive)."""
        with self._lock:
            if self._consecutive_failures >= self.max_consecutive_failures:
                raise RuntimeError(
                    f"{self._consecutive_failures} consecutive async "
                    "checkpoint writes failed; giving up"
                ) from self._last_failure
        if prepare is None:
            # sync snapshot: buffers get donated next step (sharded
            # states snapshot their addressable shards the same way)
            host_state = snapshot_for_save(state)
        else:
            host_state = state  # un-donated device copy; worker fetches
        if no_skip:
            with self._wake:
                while self._pending is not None and not self.closed:
                    self._wake.wait()
        with self._wake:
            if self.closed:
                raise RuntimeError("AsyncCheckpointer is closed")
            if self._pending is not None:
                logger.warning("checkpoint writer lagging; replacing queued "
                               "step=%d with step=%d", self._pending[2], step)
            self._pending = (train_dir, host_state, step, extra, keep,
                             prepare, publish)
            self._wake.notify_all()

    def wait(self) -> None:
        """Drain in-flight writes (call before exit / final save)."""
        with self._wake:
            while self._pending is not None or self._busy:
                self._wake.wait()
        self._raise_pending_error()

    def close(self) -> None:
        self.wait()
        with self._wake:
            self._stop = True
            self.closed = True
            self._wake.notify_all()
        self._thread.join(timeout=60)


_STEP_RE = re.compile(r"^ckpt-(\d+)")


def _ckpt_step_of(name: str) -> int | None:
    m = _STEP_RE.match(name)
    return int(m.group(1)) if m else None


def _garbage_collect(train_dir: Path, keep: int) -> None:
    """Keep the last ``keep`` STEPS — single files, shard files, and
    manifests all group by their step prefix. Every process of a
    sharded run GCs (concurrent unlinks race benignly)."""
    if keep <= 0:
        return
    by_step: dict[int, list[Path]] = {}
    for f in train_dir.glob("ckpt-*"):
        s = _ckpt_step_of(f.name)
        if s is not None and not f.name.endswith(".tmp"):
            by_step.setdefault(s, []).append(f)
    for s in sorted(by_step)[:-keep]:
        for old in by_step[s]:
            try:
                old.unlink()
            except OSError:
                pass


def latest_checkpoint_step(train_dir: str | Path) -> int | None:
    """Read the pointer (≙ tf.train.get_checkpoint_state,
    src/nn_eval.py:70); falls back to a directory scan if the pointer
    is missing/torn."""
    train_dir = Path(train_dir)
    ptr = train_dir / _POINTER
    if ptr.exists():
        try:
            d = json.loads(storage.read_text(ptr))
            if (train_dir / d["latest_path"]).exists():
                return int(d["latest_step"])
        except (json.JSONDecodeError, KeyError, ValueError, OSError):
            pass
    steps = _loadable_steps(train_dir)
    if not steps:
        return None
    return max(steps)


def loadable_steps(train_dir: str | Path) -> list[int]:
    """Public view of the restorable steps in ``train_dir`` (ascending)
    — what the NaN-guard rollback and the supervisor iterate over."""
    return _loadable_steps(Path(train_dir))


def _loadable_steps(train_dir: Path) -> list[int]:
    """Steps that can actually be restored: a single-file .msgpack or a
    manifest (shard files alone — a crash mid-publish — don't count)."""
    steps = set()
    for f in train_dir.glob("ckpt-*"):
        s = _ckpt_step_of(f.name)
        if s is None or f.name.endswith(".tmp"):
            continue
        if f.name.endswith(".manifest.json") or f.name == _ckpt_path(
                train_dir, s).name:
            steps.add(s)
    return sorted(steps)


def _digest_tree(tree: Any, h) -> None:
    """Fold a nested state-dict of arrays into ``h`` canonically:
    sorted key paths, then dtype/shape/bytes per leaf, every component
    NUL-delimited so adjacent fields can never be re-split into a
    colliding byte stream — two trees hash equal iff their structure
    and arrays are identical."""
    if isinstance(tree, dict):
        h.update(b"{\x00")
        for key in sorted(tree):
            h.update(str(key).encode() + b"\x00")
            _digest_tree(tree[key], h)
        h.update(b"}\x00")
        return
    if tree is None:
        h.update(b"<none>\x00")
        return
    a = np.ascontiguousarray(np.asarray(jax.device_get(tree)))
    h.update(str(a.dtype).encode() + b"\x00")
    h.update(str(a.shape).encode() + b"\x00")
    h.update(a.tobytes())
    h.update(b"\x00")


def state_params_digest(state: Any) -> str:
    """sha256 over the live state's param leaves — the model's bitwise
    identity, independent of where/when it was saved. The determinism
    seam the chaos invariant checker compares runs by: a faulted but
    fully-recovered run must reproduce the fault-free run's digest."""
    h = hashlib.sha256()
    _digest_tree(serialization.to_state_dict(state.params), h)
    return h.hexdigest()


def _checkpoint_state_dict(train_dir: Path, step: int | None
                           ) -> tuple[dict, int] | None:
    """The raw saved state dict of a checkpoint artifact (no model
    template) — the shared read behind the artifact digests. None when
    nothing is loadable. Single-file layout only (the local chaos
    workers are single-process); a sharded checkpoint raises so a
    silent cross-layout miscompare cannot happen."""
    if step is None:
        step = latest_checkpoint_step(train_dir)
        if step is None:
            return None
    if _manifest_path(train_dir, step).exists():
        raise NotImplementedError(
            "artifact digests over the sharded layout are not supported — "
            "restore through a template and use state_params_digest")
    path = _ckpt_path(train_dir, step)
    payload = _msgpack_restore_checked(_verified_read(path), path)
    state = payload.get("state")
    if not isinstance(state, dict) or state.get("params") is None:
        raise CheckpointCorruptError(
            f"{path.name}: payload has no state/params entry")
    return state, step


def checkpoint_params_digest(train_dir: str | Path,
                             step: int | None = None
                             ) -> tuple[str, int] | None:
    """(sha256-of-params, step) for a saved checkpoint — computed from
    the ARTIFACT alone (raw state dict, no model template), so the
    invariant checker can compare two runs' checkpoints without
    building either model. None when nothing is loadable."""
    got = _checkpoint_state_dict(Path(train_dir), step)
    if got is None:
        return None
    state, step = got
    h = hashlib.sha256()
    _digest_tree(state["params"], h)
    return h.hexdigest(), step


def checkpoint_state_digests(train_dir: str | Path,
                             step: int | None = None
                             ) -> tuple[str, str, int] | None:
    """(params_digest, opt_state_digest, step) from ONE artifact read —
    what the determinism invariant compares per worker; the split
    functions below each re-read the file, so batch consumers use
    this."""
    got = _checkpoint_state_dict(Path(train_dir), step)
    if got is None:
        return None
    state, step = got
    hp, ho = hashlib.sha256(), hashlib.sha256()
    _digest_tree(state["params"], hp)
    _digest_tree(state.get("momentum"), ho)
    return hp.hexdigest(), ho.hexdigest(), step


def checkpoint_opt_state_digest(train_dir: str | Path,
                                step: int | None = None
                                ) -> tuple[str, int] | None:
    """(sha256-of-optimizer-state, step) over the artifact's
    ``momentum`` subtree — the optimizer-state half of the chaos
    determinism invariant (obsv/invariants.py #3). Checkpoints store
    momentum in the CANONICAL logical layout regardless of
    ``parallel.shard_weight_update`` (train/loop.py ``_save`` via
    parallel.api.canonical_save_state), so this digest is comparable
    across runs — and meaningful, not skipped, for replica-sharded
    optimizer state. A momentum-less run (momentum=0) digests the
    canonical ``<none>`` marker, which still compares equal between a
    trial and its reference."""
    got = _checkpoint_state_dict(Path(train_dir), step)
    if got is None:
        return None
    state, step = got
    h = hashlib.sha256()
    _digest_tree(state.get("momentum"), h)
    return h.hexdigest(), step


def read_checkpoint_extra(train_dir: str | Path,
                          step: int | None = None) -> tuple[dict, int] | None:
    """Read only the JSON ``extra`` payload (saved config, data-iter
    position) — needs NO state template, so the evaluator can bootstrap
    its config from a checkpoint of *any* model/optimizer shape before
    it knows what to build."""
    train_dir = Path(train_dir)
    if step is None:
        step = latest_checkpoint_step(train_dir)
        if step is None:
            return None
    mpath = _manifest_path(train_dir, step)
    if mpath.exists():
        return _read_manifest(train_dir, step).get("extra", {}), step
    path = _ckpt_path(train_dir, step)
    payload = _msgpack_restore_checked(_verified_read(path), path)
    extra = payload.get("extra", {})
    if isinstance(extra, (str, bytes)):
        extra = json.loads(extra)
    return extra, step


def read_checkpoint_world(train_dir: str | Path,
                          step: int | None = None
                          ) -> tuple[dict | None, int] | None:
    """The ``world`` record a checkpoint was saved under (the Trainer
    stamps ``parallel.api.world_signature`` into ``extra``) — what the
    supervisor's reconfigure path reads to name old vs new world, and
    None for pre-elastic artifacts. Returns ``(world | None, step)``,
    or None when nothing is loadable."""
    got = read_checkpoint_extra(train_dir, step)
    if got is None:
        return None
    extra, step = got
    world = (extra or {}).get("world")
    return (world if isinstance(world, dict) else None), step


class CheckpointFollower:
    """The newest-checkpoint hot-follow loop shared by the long-running
    checkpoint consumers (``evalsvc`` evaluator, ``servesvc`` serving
    replica): atomic pointer read, step-advanced check, and
    skip-and-retry on an unreadable/torn/corrupt artifact.

    One poll: :meth:`poll(read)` reads the pointer; when the newest
    step has advanced past the last one successfully consumed, it calls
    ``read(step)`` and returns its result. ``read`` raising
    ``OSError`` / ``ValueError`` (which covers
    :class:`CheckpointCorruptError`) / ``KeyError`` — the trainer's GC
    unlinking the step between the pointer read and the restore, a
    shared fs serving a torn file, a failed digest — is a SKIP, not a
    crash: the failure is remembered per step (``last_error``), None is
    returned, and the next poll retries (or moves on to a newer
    publish). ``read`` returning None (e.g. nothing restorable) leaves
    the cursor unmoved the same way. A long-running service built on
    this never dies to a torn publish."""

    def __init__(self, train_dir: str | Path,
                 on_event: Callable[[dict], None] | None = None):
        self.train_dir = Path(train_dir)
        self.last_step = -1          # last step successfully consumed
        self.last_error: tuple[int, str] | None = None  # (step, error)
        self.skips = 0               # torn/corrupt publishes survived
        self._on_event = on_event

    def newest_step(self) -> int | None:
        """The pointer's current step (None before the first publish)
        — exposed so callers can log 'nothing yet' distinctly."""
        return latest_checkpoint_step(self.train_dir)

    def poll(self, read: Callable[[int], Any]) -> Any | None:
        """One follow tick; returns ``read(step)``'s result for a newly
        advanced step, else None (nothing new, or the read failed and
        will be retried)."""
        step = self.newest_step()
        if step is None or step == self.last_step:
            return None
        try:
            out = read(step)
        except (OSError, ValueError, KeyError) as e:
            self.skips += 1
            self.last_error = (step, f"{type(e).__name__}: {e}")
            logger.warning("checkpoint step=%s unreadable (%s); "
                           "skip-and-retry", step, e)
            if self._on_event is not None:
                self._on_event({"layer": "checkpoint",
                                "action": "follow_skip", "step": step,
                                "error": self.last_error[1]})
            return None
        if out is None:
            return None
        self.last_step = step
        return out


def wait_for_run_config(train_dir: str | Path,
                        timeout_s: float = 600.0):
    """Block until the first checkpoint publishes, then adopt its
    saved config — the bootstrap both long-running checkpoint
    consumers (the evaluator and the serving replica) start from, so
    there is no trainer/consumer graph skew. Reads only the JSON
    ``extra`` payload (no state template), so any model/optimizer
    shape works. Returns an ``ExperimentConfig``."""
    from ..core.config import ExperimentConfig
    train_dir = Path(train_dir)
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            out = read_checkpoint_extra(train_dir)
        except (OSError, ValueError, KeyError) as e:
            # mid-replace read on a shared fs / torn file — the caller
            # is a long-running service, retry on the next poll
            logger.warning("checkpoint read failed (%s); retrying", e)
            out = None
        if out is not None:
            extra, _ = out
            if "config" in extra:
                return ExperimentConfig.from_dict(extra["config"])
            logger.warning("checkpoint has no saved config; using defaults")
            return ExperimentConfig()
        time.sleep(1.0)
    raise TimeoutError(
        f"no checkpoint appeared in {train_dir} within {timeout_s:.0f}s")


def artifact_digest(train_dir: str | Path, step: int) -> str | None:
    """The recorded sha256 of a step's single-file artifact (its digest
    sidecar) — what a serving replica journals as the identity of the
    weights it swapped in. None when no sidecar exists (pre-checksum
    layout) or the artifact is sharded (manifest layout)."""
    train_dir = Path(train_dir)
    dpath = _digest_path(_ckpt_path(train_dir, step))
    try:
        return storage.read_text(dpath).strip() or None
    except OSError:
        return None


def quant_sidecar_path(train_dir: str | Path, step: int) -> Path:
    """Where a step's quantized-tier sidecar lives (module docstring:
    the ``.quant.msgpack`` next to the artifact). The ``ckpt-`` prefix
    keeps it inside the step-grouped GC and the invariant checker's
    digest sweep; the distinct suffix keeps it OUT of
    ``_loadable_steps`` — a sidecar alone never makes a step
    restorable."""
    return Path(train_dir) / f"ckpt-{step:08d}.quant.msgpack"


def write_quant_sidecar(train_dir: str | Path, step: int,
                        tiers: dict, meta: dict) -> Path:
    """Atomically publish the quantized tiers for ``step`` (tmp +
    rename + sha256 digest sidecar — the exact torn-write contract the
    checkpoint artifact has). ``tiers`` maps tier name → state-dict-
    shaped param tree; ``meta`` is JSON-serializable provenance (source
    params digest, calibration record)."""
    path = quant_sidecar_path(train_dir, step)
    payload = {"tiers": tiers, "meta": json.dumps(meta)}
    _write_atomic(path, serialization.msgpack_serialize(payload))
    return path


def read_quant_sidecar(train_dir: str | Path, step: int) -> dict:
    """Digest-verified read of a step's quant sidecar →
    ``{"tiers": {...}, "meta": dict}``. Raises ``FileNotFoundError``
    when no sidecar was published, :class:`CheckpointCorruptError` on
    a torn payload or sha256 mismatch — both flow into the
    :class:`CheckpointFollower` skip path, so a serving replica treats
    a bad sidecar as "fall back to the full-precision artifact", never
    as a crash and never as something to serve."""
    path = quant_sidecar_path(train_dir, step)
    payload = _msgpack_restore_checked(_verified_read(path), path)
    if not isinstance(payload, dict) or not isinstance(
            payload.get("tiers"), dict):
        raise CheckpointCorruptError(
            f"{path.name}: payload has no 'tiers' entry")
    meta = payload.get("meta", {})
    if isinstance(meta, (str, bytes)):
        try:
            meta = json.loads(meta)
        except json.JSONDecodeError as e:
            raise CheckpointCorruptError(
                f"{path.name}: torn meta payload ({e})") from e
    return {"tiers": payload["tiers"], "meta": meta}


def quant_sidecar_digest(train_dir: str | Path, step: int) -> str | None:
    """The recorded sha256 of a step's quant sidecar (its digest
    sidecar) — what a serving replica journals as the identity of a
    quantized tier it swapped in. None when no sidecar (or no digest)
    exists."""
    dpath = _digest_path(quant_sidecar_path(train_dir, step))
    try:
        return storage.read_text(dpath).strip() or None
    except OSError:
        return None


def _check_world(extra: Any, step: int, expect_world: dict | None) -> None:
    """Strict-world gate: callers that CANNOT reshard (no
    restore_for_topology in their path) pass the world they require;
    an artifact recorded under a different world raises the typed
    mismatch instead of whatever downstream structure error the
    foreign layout would eventually produce."""
    if expect_world is None:
        return
    saved = (extra or {}).get("world") if isinstance(extra, dict) else None
    if isinstance(saved, dict) and saved != expect_world:
        raise WorldSizeMismatchError(
            f"checkpoint step={step} was saved under world {saved} but "
            f"this consumer requires world {expect_world}; reshard it "
            "through parallel.api.restore_for_topology (mesh-portable "
            "restore) instead of a same-world restore",
            saved_world=saved, requested_world=expect_world)


def _from_state_dict_checked(template_state: Any, saved: Any, extra: Any,
                             step: int, where: str,
                             expect_world: dict | None) -> Any:
    """``from_state_dict`` with the raw structure error upgraded: when
    the artifact records the world it was saved under, a graft failure
    names saved vs requested world (the typed error the supervisor's
    reconfigure path branches on) instead of a bare flax KeyError."""
    try:
        return serialization.from_state_dict(template_state, saved)
    except WorldSizeMismatchError:
        raise
    except Exception as e:
        saved_world = ((extra or {}).get("world")
                       if isinstance(extra, dict) else None)
        if isinstance(saved_world, dict) and (
                expect_world is None or saved_world != expect_world):
            raise WorldSizeMismatchError(
                f"{where}: checkpoint step={step} does not fit this "
                f"run's state template ({type(e).__name__}: {e}); the "
                f"artifact was saved under world {saved_world}"
                + (f" but this run is world {expect_world}"
                   if expect_world is not None else "")
                + " — reshard it through parallel.api."
                "restore_for_topology",
                saved_world=saved_world,
                requested_world=expect_world) from e
        raise


def _restore_sharded(train_dir: Path, template_state: Any, step: int,
                     expect_world: dict | None = None
                     ) -> tuple[Any, dict, int]:
    """Reassemble full global arrays from every process's shard file
    (readable by ANY process count — the evaluator or a resumed
    cluster of a different size reads the same files)."""
    manifest = _read_manifest(train_dir, step)
    _check_world(manifest.get("extra"), step, expect_world)
    try:
        pcount = int(manifest["num_shards"])
        meta = manifest["leaves"]
    except (KeyError, TypeError, ValueError) as e:
        raise CheckpointCorruptError(
            f"{_manifest_path(train_dir, step).name}: manifest missing "
            f"required fields ({type(e).__name__}: {e})") from e
    leaves: dict[str, np.ndarray] = {}
    for p in range(pcount):
        spath = _shard_path(train_dir, step, p, pcount)
        payload = _msgpack_restore_checked(_verified_read(spath), spath)
        try:
            for key, val in payload["leaves"].items():
                if isinstance(val, dict) and "indices" in val:
                    m = meta[key]
                    buf = leaves.setdefault(
                        key,
                        np.empty(tuple(m["shape"]), np.dtype(m["dtype"])))
                    for idx, data in zip(val["indices"], val["datas"]):
                        buf[tuple(slice(a, b) for a, b in idx)] = data
                elif key not in leaves:  # locally-complete leaf (first wins)
                    leaves[key] = np.asarray(val)
        except (KeyError, ValueError, TypeError, IndexError) as e:
            # structure that contradicts the manifest (missing meta,
            # slab shapes that don't fit) is damage to THIS step —
            # distinct from a template mismatch, which surfaces later
            # in from_state_dict and must stay loud
            raise CheckpointCorruptError(
                f"{spath.name}: shard/manifest structure mismatch "
                f"({type(e).__name__}: {e})") from e
    nested: dict = {}
    for key, arr in leaves.items():
        node = nested
        parts = key.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = arr

    # None fields (momentum off, non-interval mode) have no leaves, so
    # the flattened files carry no entry — graft them back from the
    # template so from_state_dict sees every field. A missing non-None
    # leaf means the shard set doesn't actually hold this step's state:
    # damage to THIS step, so it surfaces as CheckpointCorruptError and
    # the restore falls back to an older one.
    def graft_nones(sub: Any, tmpl: Any) -> Any:
        if tmpl is None:
            return None
        if isinstance(tmpl, dict):
            got = sub if isinstance(sub, dict) else {}
            return {k: (None if tv is None
                        else graft_nones(got.get(k, {}), tv)
                        if isinstance(tv, dict) else got[k])
                    for k, tv in tmpl.items()}
        return sub

    try:
        nested = graft_nones(nested,
                             serialization.to_state_dict(template_state))
    except KeyError as e:
        raise CheckpointCorruptError(
            f"sharded checkpoint step={step} is missing leaf {e} that "
            "the state requires") from e
    state = _from_state_dict_checked(
        template_state, nested, manifest.get("extra"), step,
        _manifest_path(train_dir, step).name, expect_world)
    return state, manifest.get("extra", {}), step


# Exceptions that mean "THIS step is unusable, an older one may not
# be": incomplete publish (FileNotFoundError), torn/garbled/lying
# artifacts (CheckpointCorruptError — parse failures, checksum
# mismatches, and shard/manifest structure contradictions are all
# wrapped into it at the read sites), or I/O that stayed broken through
# the retry budget (OSError). Deliberately NOT broader: a
# template/model mismatch (from_state_dict errors) affects EVERY step
# equally and must surface loudly, not silently discard the run by
# "falling back" past all of it.
_FALLBACK_ERRORS = (FileNotFoundError, CheckpointCorruptError, OSError)


def restore_checkpoint(train_dir: str | Path, template_state: Any,
                       step: int | None = None,
                       on_event: Callable[[dict], None] | None = None,
                       expect_world: dict | None = None,
                       ) -> tuple[Any, dict, int] | None:
    """Restore (state, extra, step); None when nothing exists
    (≙ Supervisor's restore-if-present, src/distributed_train.py:262).
    Handles both the single-file and the per-host sharded layouts.

    When no explicit ``step`` is given, an unusable latest checkpoint —
    a torn sharded publish (interrupted between process 0's manifest
    and a sibling's shard file; there is no cross-process barrier in
    the async writer), a truncated file, or a checksum mismatch — falls
    back to the next older loadable step instead of wedging the resume
    forever. Each skipped step is reported through ``on_event`` (a
    recovery-journal hook; receives one dict per fallback and one for
    the step finally restored when any fallback happened).

    ``expect_world``: a strict same-world gate for consumers that
    cannot reshard — an artifact recorded under a different world
    raises :class:`WorldSizeMismatchError` (which, like any template
    mismatch, is NOT fallen back past: it affects every step equally).
    Mesh-portable consumers leave it None and restore through
    ``parallel.api.restore_for_topology``."""
    train_dir = Path(train_dir)
    if step is not None:
        return _restore_step(train_dir, template_state, step, expect_world)
    candidates = _loadable_steps(train_dir)
    latest = latest_checkpoint_step(train_dir)
    if latest is not None and latest not in candidates:
        candidates.append(latest)
    fell_back = False
    for s in sorted(set(candidates), reverse=True):
        try:
            got = _restore_step(train_dir, template_state, s, expect_world)
        except _FALLBACK_ERRORS as e:
            fell_back = True
            logger.warning("checkpoint step=%d is unusable (%s: %s); "
                           "falling back to an older step",
                           s, type(e).__name__, e)
            if on_event is not None:
                on_event({"layer": "checkpoint",
                          "action": "corrupt_checkpoint_fallback",
                          "bad_step": s, "error": f"{type(e).__name__}: {e}"})
            continue
        if fell_back and on_event is not None:
            on_event({"layer": "checkpoint", "action": "fallback_restore",
                      "step": got[2]})
        return got
    return None


def _restore_step(train_dir: Path, template_state: Any, step: int,
                  expect_world: dict | None = None) -> tuple[Any, dict, int]:
    if _manifest_path(train_dir, step).exists():
        return _restore_sharded(train_dir, template_state, step,
                                expect_world)
    path = _ckpt_path(train_dir, step)
    payload = _msgpack_restore_checked(_verified_read(path), path)
    if not isinstance(payload, dict) or "state" not in payload:
        raise CheckpointCorruptError(
            f"{path.name}: payload has no 'state' entry")
    saved = payload["state"]
    extra = payload.get("extra", {})
    if isinstance(extra, (str, bytes)):
        extra = json.loads(extra)
    _check_world(extra, step, expect_world)
    # Migration: drop top-level fields the current TrainState no longer
    # has (e.g. pre-round-3 checkpoints carried a measured_ms scalar) —
    # from_state_dict hard-fails on unknown keys, which would make every
    # old checkpoint unresumable instead of forward-compatible.
    template_dict = serialization.to_state_dict(template_state)
    if isinstance(saved, dict) and isinstance(template_dict, dict):
        stale = set(saved) - set(template_dict)
        if stale:
            logger.warning("dropping stale checkpoint fields %s", sorted(stale))
            saved = {k: v for k, v in saved.items() if k not in stale}
    state = _from_state_dict_checked(template_state, saved, extra, step,
                                     path.name, expect_world)
    return state, extra, step

"""Injectable storage shim — every durable write goes through here.

Nineteen PRs faulted processes (kill/hang/stall), checkpoints-at-rest
(``corrupt_latest_checkpoint_at_step`` truncation), and the network
wire (the chaos proxy), but the storage substrate every recovery path
stands on was still assumed perfect: ``_write_atomic`` believed
renames are durable, writes never hit ENOSPC/EIO, and a crash can only
land between steps.  This module is the single seam that drops both
assumptions:

* **Durability policy** (``train.durability``): ``none`` keeps the
  historical behavior (buffered writes, rename-only atomicity),
  ``data`` fsyncs checkpoint/manifest payload bytes before the rename
  publishes them, ``full`` additionally fsyncs digest sidecars, the
  latest-pointer, JSONL journal appends (:class:`core.log.JsonlSink`
  calls :func:`fsync_journal` when this module says so), and the
  parent directory after every rename — the power-cut-proof upper
  bound (its fsync tax was priced on a CPU runner's disk by the
  harness removed at PR 48; on the chip's host: not measured).

* **Deterministic disk-fault injection** (``FaultPlan.disk_faults``):
  per-worker fault scripts — :data:`DISK_FAULT_KINDS` — armed in the
  worker process from the ``DMT_DISK_FAULTS`` env var (the cluster
  backend threads each worker's script list through its environment)
  or programmatically via :func:`arm_faults` (tests).  Every firing is
  journaled as a schema-declared ``fault`` record
  (``action: disk_*``) into the worker's ``storage_faults.jsonl`` so
  the replay invariants can LICENSE the degradation they caused: a
  ``save_failed`` or ``fallback_restore`` with no matching injected
  fault is a violation (obsv/invariants.py ``storage_faults``).

Fault kinds and their script fields (every script also takes
``at_step`` — armed once the trainer has reached that step, default 0
— ``times`` — firings before the fault disarms, default 1 — and
``match`` — substring filter on the target file name, default all):

* ``enospc_after_bytes`` (``bytes``): matching writes pass through
  until the cumulative byte budget is exceeded, then writes fail with
  ``ENOSPC`` writing nothing, ``times`` firings long (the disk fills,
  then space frees).
* ``eio`` (``op`` = ``read``/``write``, ``nth``): the ``nth``
  matching op (and the next ``times - 1``) fails with ``EIO``.
* ``slow_io_ms`` (``ms``): each matching op sleeps first — a
  degraded-disk stall, not an error.
* ``torn_write_at_byte`` (``at_byte``): the write lands only its
  first ``at_byte`` bytes, then fails with ``EIO`` — the mid-write
  crash model; the torn ``.tmp`` stays on disk exactly as a power cut
  would leave it.
* ``crash_rename`` (``keep_bytes``, default 0): the rename IS applied
  but the renamed file's data is lost down to ``keep_bytes`` — the
  power-cut-after-rename model (metadata journaled, data never hit
  the platter).  No error is raised: the writer believes the save
  succeeded, and only the digest sidecar can catch it later.

Faults apply ONLY to shim-routed durable artifacts (checkpoints,
manifests, digest sidecars, the pointer, quant sidecars) — never to
the journals that record them, which would be circular evidence.
"""

from __future__ import annotations

import dataclasses
import errno as _errno
import json
import os
import threading
import time
from pathlib import Path
from typing import Any

from ..core.log import JsonlSink, get_logger

logger = get_logger("storage")

DISK_FAULT_KINDS = ("enospc_after_bytes", "eio", "slow_io_ms",
                    "torn_write_at_byte", "crash_rename")

_VALID_DURABILITY = ("none", "data", "full")

# Roles fsynced per policy: "data" syncs payload bytes only; "full"
# syncs everything (payloads, sidecars, pointer, journals, dirs).
_DATA_ROLES = ("data",)

_DURABILITY = "none"


def set_durability(policy: str) -> None:
    """Install the process-wide fsync policy (``train.durability``)."""
    if policy not in _VALID_DURABILITY:
        from ..core.config import ConfigError
        raise ConfigError(
            f"train.durability={policy!r} is not a known durability "
            f"policy; valid policies: {', '.join(_VALID_DURABILITY)}")
    global _DURABILITY
    _DURABILITY = policy


def durability() -> str:
    return _DURABILITY


def _role_synced(role: str) -> bool:
    if _DURABILITY == "full":
        return True
    if _DURABILITY == "data":
        return role in _DATA_ROLES
    return False


def journal_sync_enabled() -> bool:
    """True when JSONL journal appends must fsync (policy ``full``) —
    :class:`core.log.JsonlSink` consults this per write (via a
    ``sys.modules`` lookup, so processes that never import the trainer
    pay nothing)."""
    return _DURABILITY == "full"


def fsync_journal(fh: Any) -> None:
    try:
        fh.flush()
        os.fsync(fh.fileno())
    except (OSError, ValueError):  # closed fh / exotic sink: best effort
        pass


def _fsync_fd(fd: int) -> None:
    os.fsync(fd)


def _fsync_dir(dirpath: Path) -> None:
    try:
        fd = os.open(dirpath, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


# ---------------------------------------------------------------------------
# deterministic disk-fault injection
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Script:
    """One armed fault from a ``FaultPlan.disk_faults`` script dict."""

    kind: str
    at_step: int = 0
    times: int = 1
    match: str = ""
    op: str = "write"       # eio: which op class faults
    nth: int = 1            # eio: fire on the nth matching op
    bytes: int = 0          # enospc_after_bytes: byte budget
    ms: float = 0.0         # slow_io_ms: per-op stall
    at_byte: int = 0        # torn_write_at_byte: truncation point
    keep_bytes: int = 0     # crash_rename: surviving prefix
    # runtime counters
    fired: int = 0
    seen_ops: int = 0
    written: int = 0

    @classmethod
    def from_dict(cls, d: dict) -> "_Script":
        d = dict(d)
        kind = d.get("kind")
        if kind not in DISK_FAULT_KINDS:
            raise ValueError(
                f"unknown disk fault kind {kind!r}; valid kinds: "
                f"{', '.join(DISK_FAULT_KINDS)}")
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(
                f"disk fault {kind!r} has unknown field(s) "
                f"{sorted(unknown)}")
        return cls(**d)

    def spent(self) -> bool:
        return self.fired >= self.times

    def applies(self, step: int, name: str) -> bool:
        if self.spent() or step < self.at_step:
            return False
        return (not self.match) or (self.match in name)


class DiskFaultInjector:
    """Per-process fault engine consulted by every shim op.

    Scripts fire deterministically (list order, op counters, byte
    budgets — no randomness here; the chaos generator owns the seeded
    draw) and every firing lands in ``storage_faults.jsonl`` as a
    schema-declared ``fault`` record carrying the worker ordinal, so
    the trial-level invariant replay can collect licenses without the
    worker ever touching the supervisor's command journal."""

    def __init__(self, worker: int, scripts: list[dict],
                 journal_path: str | Path | None = None):
        self.worker = int(worker)
        self._scripts = [_Script.from_dict(s) for s in scripts]
        self._journal_path = Path(journal_path) if journal_path else None
        self._sink: JsonlSink | None = None
        self._lock = threading.Lock()
        self._step = 0

    def note_step(self, step: int) -> None:
        with self._lock:
            self._step = max(self._step, int(step))

    def _journal(self, action: str, path: Path, **fields: Any) -> None:
        rec = {"event": "fault", "action": action, "worker": self.worker,
               "path": path.name, "at_step": self._step, **fields}
        logger.warning("injected disk fault %s on %s", action, path.name)
        if self._journal_path is None:
            return
        try:
            if self._sink is None:
                self._sink = JsonlSink(self._journal_path)
            self._sink.write(rec)
        except OSError as e:
            logger.warning("storage fault journal write failed: %s", e)

    def on_write(self, path: Path, nbytes: int) -> int | None:
        """Consulted before a durable write of ``nbytes`` to ``path``.

        Raises ``OSError`` (ENOSPC/EIO), sleeps (slow_io), or returns
        a torn-write truncation point the shim must honor (write that
        prefix, then raise).  ``None`` → proceed normally."""
        name = path.name
        sleep_ms = 0.0
        torn_at: int | None = None
        with self._lock:
            for s in self._scripts:
                if not s.applies(self._step, name):
                    continue
                if s.kind == "slow_io_ms":
                    s.fired += 1
                    self._journal("disk_slow_io", path, op="write",
                                  ms=s.ms, planned_step=s.at_step)
                    sleep_ms += s.ms
                elif s.kind == "torn_write_at_byte":
                    s.fired += 1
                    self._journal("disk_torn_write", path, op="write",
                                  at_byte=s.at_byte,
                                  planned_step=s.at_step)
                    k = min(s.at_byte, nbytes)
                    torn_at = k if torn_at is None else min(torn_at, k)
                elif s.kind == "enospc_after_bytes":
                    if s.written + nbytes > s.bytes:
                        s.fired += 1
                        self._journal("disk_enospc", path, op="write",
                                      budget_bytes=s.bytes,
                                      planned_step=s.at_step)
                        raise OSError(
                            _errno.ENOSPC,
                            f"injected ENOSPC (budget {s.bytes}B)", name)
                    s.written += nbytes
                elif s.kind == "eio" and s.op == "write":
                    s.seen_ops += 1
                    if s.seen_ops >= s.nth:
                        s.fired += 1
                        self._journal("disk_eio", path, op="write",
                                      nth=s.seen_ops,
                                      planned_step=s.at_step)
                        raise OSError(_errno.EIO,
                                      "injected EIO on write", name)
        if sleep_ms:
            time.sleep(sleep_ms / 1000.0)
        return torn_at

    def on_read(self, path: Path) -> None:
        name = path.name
        sleep_ms = 0.0
        with self._lock:
            for s in self._scripts:
                if not s.applies(self._step, name):
                    continue
                if s.kind == "slow_io_ms":
                    s.fired += 1
                    self._journal("disk_slow_io", path, op="read",
                                  ms=s.ms, planned_step=s.at_step)
                    sleep_ms += s.ms
                elif s.kind == "eio" and s.op == "read":
                    s.seen_ops += 1
                    if s.seen_ops >= s.nth:
                        s.fired += 1
                        self._journal("disk_eio", path, op="read",
                                      nth=s.seen_ops,
                                      planned_step=s.at_step)
                        raise OSError(_errno.EIO,
                                      "injected EIO on read", name)
        if sleep_ms:
            time.sleep(sleep_ms / 1000.0)

    def on_replace(self, dst: Path) -> int | None:
        """Consulted before a publishing rename onto ``dst``.  Returns
        the surviving byte count when a ``crash_rename`` fires (the
        shim applies the rename, then loses the data) or ``None``."""
        with self._lock:
            for s in self._scripts:
                if s.kind != "crash_rename":
                    continue
                if not s.applies(self._step, dst.name):
                    continue
                s.fired += 1
                self._journal("disk_crash_rename", dst,
                              kept_bytes=s.keep_bytes,
                              planned_step=s.at_step)
                return s.keep_bytes
        return None

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()
            self._sink = None


_INJECTOR: DiskFaultInjector | None = None
_ENV_CHECKED = False

DISK_FAULTS_ENV = "DMT_DISK_FAULTS"


def arm_faults(worker: int, scripts: list[dict],
               journal_path: str | Path | None = None) -> DiskFaultInjector:
    """Programmatic arming (tests / in-process harnesses)."""
    global _INJECTOR, _ENV_CHECKED
    if _INJECTOR is not None:
        _INJECTOR.close()
    _INJECTOR = DiskFaultInjector(worker, scripts, journal_path)
    _ENV_CHECKED = True
    return _INJECTOR


def clear_faults() -> None:
    """Disarm (tests).  Also stops the env var from re-arming."""
    global _INJECTOR, _ENV_CHECKED
    if _INJECTOR is not None:
        _INJECTOR.close()
    _INJECTOR = None
    _ENV_CHECKED = True


def _injector() -> DiskFaultInjector | None:
    global _INJECTOR, _ENV_CHECKED
    if not _ENV_CHECKED:
        _ENV_CHECKED = True
        spec = os.environ.get(DISK_FAULTS_ENV, "")
        if spec:
            try:
                d = json.loads(spec)
                _INJECTOR = DiskFaultInjector(
                    int(d.get("worker", 0)), list(d.get("faults", [])),
                    d.get("journal"))
            except (ValueError, TypeError, KeyError) as e:
                logger.warning("ignoring malformed %s (%s)",
                               DISK_FAULTS_ENV, e)
    return _INJECTOR


def note_step(step: int) -> None:
    """Trainer progress hook — lets ``at_step``-gated scripts arm."""
    inj = _injector()
    if inj is not None:
        inj.note_step(step)


# ---------------------------------------------------------------------------
# the shim ops — what checkpoint.py / quant publish route through
# ---------------------------------------------------------------------------

def write_bytes(path: str | Path, data: bytes, role: str = "data") -> None:
    """Durable-write ``data`` to ``path`` (no rename — callers own the
    tmp+rename protocol), applying faults and the fsync policy."""
    path = Path(path)
    inj = _injector()
    torn_at = inj.on_write(path, len(data)) if inj is not None else None
    if torn_at is not None:
        with open(path, "wb") as fh:
            fh.write(data[:torn_at])
        raise OSError(_errno.EIO,
                      f"injected torn write at byte {torn_at}", path.name)
    with open(path, "wb") as fh:
        fh.write(data)
        if _role_synced(role):
            fh.flush()
            _fsync_fd(fh.fileno())


def write_text(path: str | Path, text: str, role: str = "sidecar") -> None:
    write_bytes(path, text.encode("utf-8"), role=role)


def read_bytes(path: str | Path) -> bytes:
    path = Path(path)
    inj = _injector()
    if inj is not None:
        inj.on_read(path)
    return path.read_bytes()


def read_text(path: str | Path) -> str:
    return read_bytes(path).decode("utf-8")


def replace(src: str | Path, dst: str | Path, role: str = "data") -> None:
    """The publishing rename (``os.replace``) — crash_rename faults
    land here, and policy ``full`` makes the rename itself durable by
    fsyncing the parent directory."""
    src, dst = Path(src), Path(dst)
    inj = _injector()
    keep = inj.on_replace(dst) if inj is not None else None
    os.replace(src, dst)
    if keep is not None:
        # power-cut model: the rename's metadata is journaled but the
        # file's data never hit the platter — only bytes the kernel
        # already flushed survive
        with open(dst, "r+b") as fh:
            fh.truncate(keep)
    if _DURABILITY == "full":
        _fsync_dir(dst.parent)

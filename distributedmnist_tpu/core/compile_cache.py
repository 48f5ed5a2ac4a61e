"""Persistent-compilation-cache wiring (restart-latency fast path).

Every supervisor restart, chaos trial and chip run pays the full XLA
compile of the train step on top of process boot unless a previous
process left its compiles behind. jax ships a persistent compilation
cache keyed on the lowered program + compile options + the cache
path; this module is the single place its knobs are applied so the CLI
entry points, ``chip_smoke.py`` and the cluster backends cannot drift
on where the cache lives:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own cache lives
  there — jax reads the variable itself, worker processes inherit it,
  and the program sets no other directory in code;
* where it is not set, the cache goes to ONE fixed path inside the
  checkout (:data:`DEFAULT_CACHE_DIR`). The path is part of the cache
  key, so a directory derived from a temporary name, a pid or the time
  would never hit;
* ``compile.persistent_cache=false`` turns the cache off for the
  process (an explicit cold start; tests/test_compile_cache.py).

:func:`cache_stats` reports entries/bytes on disk plus this process's
hit/miss counters (from jax's monitoring events), so compile-cache
regressions are visible in worker journals (and in the benchmark's
``setup_s``) instead of only as mysteriously slower restarts.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any

from .config import CompileConfig
from .log import get_logger

logger = get_logger("compile_cache")

#: jax's own variable: where it is set, that directory is the cache
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the one fixed in-checkout location used when the variable is unset
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# this process's persistent-cache hit/miss counters, fed by jax's
# monitoring events (registered once, on first enable)
_counters = {"hits": 0, "misses": 0}
_listener_installed = False
_applied = False  # enable_persistent_cache ran in this process
_enabled_dir: Path | None = None


def resolve_cache_dir(cfg: CompileConfig | None = None) -> Path | None:
    """The cache dir in force: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else :data:`DEFAULT_CACHE_DIR`; None when ``compile.
    persistent_cache`` is off."""
    cfg = cfg or CompileConfig()
    if not cfg.persistent_cache:
        return None
    raw = os.environ.get(CACHE_DIR_ENV, "")
    return Path(raw) if raw else DEFAULT_CACHE_DIR


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    from jax import monitoring

    def _on_event(name: str, **kw: Any) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _counters["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _counters["misses"] += 1

    monitoring.register_event_listener(_on_event)
    _listener_installed = True


def enable_persistent_cache(cfg: CompileConfig | None = None) -> Path | None:
    """Apply the persistent-cache knobs to ``jax.config``; returns the
    active cache dir (None = off). An entry-point action (launch CLI,
    ``chip_smoke.py``): call it once, before the first compile. Safe to
    call again — jax reads the config at each compile."""
    global _applied, _enabled_dir
    import jax

    cfg = cfg or CompileConfig()
    cache_dir = resolve_cache_dir(cfg)
    if cache_dir is not None and not os.environ.get(CACHE_DIR_ENV):
        try:
            cache_dir.mkdir(parents=True, exist_ok=True)
        except OSError as e:
            # a worker must train with a cold cache rather than not at all
            logger.warning("persistent compile cache unavailable (%s) — "
                           "compiles stay cold", e)
            cache_dir = None
        else:
            jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # off must mean cold even under an inherited
    # JAX_COMPILATION_CACHE_DIR, which jax honours on its own
    jax.config.update("jax_enable_compilation_cache", cache_dir is not None)
    if cache_dir is not None:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          cfg.min_entry_size_bytes)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          cfg.min_compile_time_secs)
        _install_listener()
    if not _applied or _enabled_dir != cache_dir:
        # jax 0.9.0 builds its cache object once, for the directory in
        # force then, and latches "cache in use" at the first compile
        # (jax/_src/compilation_cache.py): a change of directory or an
        # on/off flip in a live process takes effect only after a reset.
        # Merely setting the directory after a first compile needs none
        # (measured: entries are written either way).
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
        logger.info("persistent compile cache: %s", cache_dir or "off")
        _applied, _enabled_dir = True, cache_dir
    return cache_dir


def cache_stats(cache_dir: str | Path | None = None) -> dict[str, Any]:
    """On-disk entry count/bytes for ``cache_dir`` (default: the dir
    last enabled in this process) plus this process's hit/miss
    counters. The counters only move once :func:`enable_persistent_
    cache` installed the monitoring listener."""
    d = Path(cache_dir) if cache_dir is not None else _enabled_dir
    entries = 0
    size = 0
    if d is not None and d.is_dir():
        for p in d.glob("*-cache"):
            try:
                size += p.stat().st_size
                entries += 1
            except OSError:
                continue
    return {"dir": str(d) if d is not None else None,
            "entries": entries, "bytes": size,
            "hits": _counters["hits"], "misses": _counters["misses"]}

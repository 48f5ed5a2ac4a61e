"""Counts what this process compiles (or loads from the persistent
cache) and how long that takes, from jax's own monitoring events.
Copied from ``chip_smoke.py``'s ``_CompileMeter`` and given a count, so
that "nothing compiles inside the measured window" is a number."""

from __future__ import annotations


class CompileMeter:
    """Seconds in jax's compile-or-load-from-cache path and in tracing
    plus lowering, and the number of programs that went through the
    former, since the last :meth:`take`."""

    _EVENTS = {"/jax/core/compile/backend_compile_duration": "compile_s",
               "/jax/core/compile/jaxpr_trace_duration": "trace_lower_s",
               "/jax/core/compile/jaxpr_to_mlir_module_duration":
                   "trace_lower_s"}

    def __init__(self):
        from jax import monitoring
        self._acc = self._zero()
        monitoring.register_event_duration_secs_listener(self._on)

    @staticmethod
    def _zero() -> dict:
        return {"compile_s": 0.0, "trace_lower_s": 0.0, "programs": 0}

    def _on(self, event: str, secs: float, **kw) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            self._acc[key] += secs
            if key == "compile_s":
                self._acc["programs"] += 1

    def take(self) -> dict:
        got, self._acc = self._acc, self._zero()
        return got

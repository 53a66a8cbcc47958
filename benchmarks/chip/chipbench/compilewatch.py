"""Counts compilations between two reads: every ``/jax/core/compile/`` event
that ``jax.monitoring`` reports, plus the system's own ``compile.count{fn}``
counters where it keeps them."""

from __future__ import annotations

__all__ = ["CompileWatch"]


class CompileWatch:
    def __init__(self, counters=None):
        """``counters``: an optional callable returning ``{name: value}`` of
        the system's compile counters."""
        import jax

        self.events: dict[str, int] = {}
        self._counters = counters

        def listener(event, _duration, **_kw):
            if event.startswith("/jax/core/compile/"):
                self.events[event] = self.events.get(event, 0) + 1

        jax.monitoring.register_event_duration_secs_listener(listener)

    def read(self) -> dict:
        counts = dict(self._counters()) if self._counters is not None else {}
        counts.update(self.events)
        return counts

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {
            k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)
        }

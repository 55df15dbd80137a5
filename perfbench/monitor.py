"""The harness's own ear on ``jax.monitoring``: every backend compile and
every persistent-cache hit, with the phase of the run it fell in."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class CompileLog:
    """``compiles``: ``(phase, seconds)`` per backend compile, in order;
    ``hits``: phase per program served from the persistent cache."""

    phase: str = "setup"
    compiles: list = dataclasses.field(default_factory=list)
    hits: list = dataclasses.field(default_factory=list)
    _listening: bool = False

    def listen(self) -> "CompileLog":
        if not self._listening:
            from jax import monitoring

            def on_duration(event, duration, **kw):
                if event.endswith("/backend_compile_duration"):
                    self.compiles.append((self.phase, float(duration)))

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_hits":
                    self.hits.append(self.phase)

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
            self._listening = True
        return self

    def count(self, *phases: str) -> int:
        return sum(1 for p, _ in self.compiles if p in phases)

    def seconds(self, *phases: str) -> float:
        return sum(s for p, s in self.compiles if p in phases)

    def hit_count(self, *phases: str) -> int:
        return sum(1 for p in self.hits if p in phases)

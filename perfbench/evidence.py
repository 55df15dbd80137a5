"""What one run gathered, and the per-layer metrics read from it.

A per-layer metric is a file ``layer_metrics/<name>.json`` whose ``read``
block names a reader (a module ``readers/<kind>.py`` with ``read(spec, ev)``)
and its parameters. A reader that finds nothing to read returns ``None`` and
the metric is left out of the line. A trace reader names a reducer (a module
``reducers/<name>.py`` with ``reduce(ev) -> dict``), run once per run.
"""

from __future__ import annotations

import dataclasses
import importlib


@dataclasses.dataclass
class Evidence:
    clocks: dict = dataclasses.field(default_factory=dict)     # harness clocks, seconds
    compiles: object = None                                    # monitor.CompileLog
    sinks: dict = dataclasses.field(default_factory=dict)      # name -> events of a job's sink
    trace: object = None                                       # xplane.TraceView
    facts: dict = dataclasses.field(default_factory=dict)      # rounds traced, chips, cost, peaks
    notes: dict = dataclasses.field(default_factory=dict)      # for the earlier lines
    _reduced: dict = dataclasses.field(default_factory=dict)
    _values: dict = dataclasses.field(default_factory=dict)
    manifest: object = None

    def reduced(self, reducer: str) -> dict:
        if reducer not in self._reduced:
            mod = importlib.import_module(f"perfbench.reducers.{reducer}")
            self._reduced[reducer] = mod.reduce(self) or {}
        return self._reduced[reducer]

    def metric(self, name: str):
        """The per-layer metric ``name``, or ``None`` where there is
        nothing to read. ``scale`` multiplies what the reader returns and
        ``minus`` subtracts another per-layer metric of the same run."""
        if name not in self._values:
            spec = self.manifest.layer_metric(name)["read"]
            mod = importlib.import_module(f"perfbench.readers.{spec['kind']}")
            value = mod.read(spec, self)
            if value is not None:
                value = float(value) * float(spec.get("scale", 1.0))
                if "minus" in spec:
                    other = self.metric(spec["minus"])
                    value = None if other is None else value - other
            self._values[name] = value
        return self._values[name]

"""Trace reducers: ``reduce(ev) -> dict`` over ``ev.trace`` (xplane.TraceView)."""

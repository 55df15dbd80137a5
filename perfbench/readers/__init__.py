"""Readers of per-layer metrics: one module per kind of source."""

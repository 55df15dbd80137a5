"""Drivers: one module per kind of run, named by a traffic file's ``driver``."""

"""Chip benchmark of the compiled campaign sweep (``api.sweep(...,
engine="jax")``), driven by ``BENCHMARK.json`` at the repository root.

Each cell names a configuration (``configs/<name>.json``) and a traffic
mix (``traffic/<name>.json``); each per-layer metric is read by
``metrics/<name>.py`` and each tick kernel's trace names and byte count
live in ``kernels/<name>.py``.  Adding a cell, a mix or a metric adds
files and entries; no file here is edited for it.  ``run.py`` runs one
cell once.
"""

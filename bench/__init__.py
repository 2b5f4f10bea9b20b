"""Serving benchmark of this repo: see bench/run.py and PERF.md."""

"""Seconds from ``run.py``'s first line (``PROCESS_START``) to the import of the
program's ``utils/scopes`` (``IMPORTED_AT``), both on ``perf_counter``: the
interpreter, jax's import and the TPU client's start. Nothing of the program
runs in it, so only a lighter import or another runtime shortens it."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return host_spans.metric("before_program_s", trace, run)

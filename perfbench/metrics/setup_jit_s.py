"""Seconds before the first timed step in which jax reported a trace, a lowering
or a backend compilation (a cache read included), whatever the function:
``init``'s jits, the correctness check's programs, the step. From the
program's ``jax.monitoring`` listener; an event nested in another is counted
once (``utils/scopes.compile_events()["busy"]``)."""
from perfbench.harness import host_spans

LAYER, UNIT, MOVES = "set-up", "s", "setup_s"


def read(trace, run):
    return host_spans.metric("setup_jit_s", trace, run)

"""Seconds the engine's steps in the window spent building programs:
tracing, lowering, and compiling or loading from the persistent cache,
from ``jax.monitoring``'s compile events, summed over the ``build_s``
attribute of every engine span (each event lands on the innermost open
span alone)."""
import spans


def read(run):
    sp = spans.read(run, __file__)
    if sp is None:
        return None
    return float(sum(s.attrs.get("build_s", 0.0) for s in sp.spans))

"""Mean over the window's engine steps of the device programs (``XLA
Modules`` events of the trace) that start inside a ``serve.step`` span,
on the trace's own clock. A mean, not a median: a step with a prefill
chunk starts about twice the programs of a decode-only step, and a median
of two modes jumps between them as the window's mix of steps shifts."""
import spans


def read(run):
    sp = spans.read(run, __file__)
    if sp is None or not sp.programs_per_step:
        return None
    return sum(sp.programs_per_step) / len(sp.programs_per_step)

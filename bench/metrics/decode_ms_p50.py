"""Median host time of one ragged decode in the window: the engine's
``serve.decode`` span (copy-on-write checks, block tables, the forward and
its writeback, the wait for the sampled tokens), ms."""
import readings
import spans


def read(run):
    sp = spans.read(run, __file__)
    if sp is None:
        return None
    v = readings.percentile([s.seconds for s in sp.named("serve.decode")], 50)
    return None if v is None else 1e3 * v

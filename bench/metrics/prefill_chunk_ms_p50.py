"""Median host time of one prefill chunk in the window: the engine's
``serve.prefill_chunk`` span (the chunk's forward and writeback, and for a
prompt's last chunk the wait for its first token), ms."""
import readings
import spans


def read(run):
    sp = spans.read(run, __file__)
    if sp is None:
        return None
    v = readings.percentile([s.seconds for s in
                             sp.named("serve.prefill_chunk")], 50)
    return None if v is None else 1e3 * v

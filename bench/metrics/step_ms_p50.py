"""Median host time of one ``ContinuousBatchingEngine.step()`` in the
window (admission, one prefill chunk, one ragged decode, the sync), ms."""
import readings


def read(run):
    v = readings.percentile([s.t1 - s.t0 for s in run.window_steps()], 50)
    return None if v is None else 1e3 * v

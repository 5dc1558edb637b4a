"""Median time a request due in the window waits before the engine admits
it: the start of its ``serve.admit`` span minus its due time, ms. The
engine takes requests only between steps, so the harness's lateness in
submitting them is part of the wait. A request the window did not see
admitted counts to its first token (admission comes before it), or, with
none, to when the run gave up on it, as ``readings.ttfts_s`` counts."""
import readings
import spans


def read(run):
    sp = spans.read(run, __file__)
    if sp is None or not sp.named("serve.admit"):
        return None
    admitted = {s.attrs["seq_id"]: s.start_s for s in sp.named("serve.admit")}
    first = min(admitted)        # the window's first request is admitted in it
    end = run.seconds + run.counters.get("ttft_grace_s", 0.0)
    waits = []
    for k, r in enumerate(run.served):       # in the order of submission
        if r.due >= run.seconds:
            continue
        t = admitted.get(first + k)
        if t is None:
            t = r.token_times[0] if r.token_times else end
        waits.append(t - r.due)
    v = readings.percentile(waits, 50)
    return None if v is None else 1e3 * v

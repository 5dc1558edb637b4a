"""95th percentile of every gap between consecutive output tokens of every
request inside the window, host clock, ms."""
import readings


def read(run):
    v = readings.percentile(readings.token_gaps_s(run), 95)
    return None if v is None else 1e3 * v

"""Median time to first token over every request due in the window: first
token time minus due time, host clock, ms."""
import readings


def read(run):
    v = readings.percentile(readings.ttfts_s(run), 50)
    return None if v is None else 1e3 * v

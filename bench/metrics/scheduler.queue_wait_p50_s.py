"""Median time a request due in the window waited from its due time to
its admission into a decode slot (the step after which it left the
engine's waiting queue); one never admitted counts as a miss."""
import math

from bench.stats import percentile


def read(rec):
    waits = [t.admitted - t.due if t.admitted is not None else math.inf
             for t in rec["tracks"] if t.in_window]
    return percentile(waits, 50) if waits else None

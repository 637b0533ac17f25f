"""``idle_pct``: the share of the measured window in which no operation ran
on the device, from the trace (100 * (1 - busy / window), busy averaged over
the cell's chips)."""


def read(outcome, peaks):
    t = outcome.trace
    if t is None or not t.busy_ns or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

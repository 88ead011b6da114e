"""device_idle_in_close_pct (%): the device's idle share inside the
round-close intervals of the traced sub-window: 1 - (device-busy time
within them, averaged over the devices) / (their length). A close
interval runs from the round's close condition to its fused vector on
the host; only those wholly inside the traced sub-window count."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window
    closes = [(r.closed, r.on_host) for r in run.rounds
              if r.closed is not None and r.on_host is not None
              and lo <= r.closed < r.on_host <= hi]
    total = sum(b - a for a, b in closes)
    if total <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_within(closes) / total)

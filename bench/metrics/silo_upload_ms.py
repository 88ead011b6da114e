"""silo_upload_ms (ms): mean over the rounds started in the window of the
time from the round's first upload sent to its last acknowledged, taken
on the client side."""

import numpy as np


def read(run):
    by_cid = {u.cid: u for u in run.uploads}
    spans = []
    for r in run.window_rounds():
        ups = [by_cid[c] for c in r.included if c in by_cid]
        if ups and all(u.acked is not None for u in ups):
            spans.append(max(u.acked for u in ups) - min(u.sent for u in ups))
    if not spans:
        return None
    return 1e3 * float(np.mean(spans))

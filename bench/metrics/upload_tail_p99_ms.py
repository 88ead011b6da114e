"""upload_tail_p99_ms (ms): 99th percentile, over every upload due in
the window, of the time from when it was due to its acknowledgement.
Retries after 429 or 503 are inside that time. An upload never
acknowledged counts as beyond any limit: infinitely late.

A per-layer metric of the serving layer: the uploads beyond it are
those queued behind a pause of the host, so it swings from run to run
with how many pauses a window holds, too widely for an end-to-end
bound; ``upload_p50_ms`` is the end-to-end reading."""

import numpy as np


def read(run):
    lat = [(u.acked - u.due) if u.acked is not None else np.inf
           for u in run.window_uploads() if u.due is not None]
    if not lat:
        return None
    return 1e3 * float(np.percentile(lat, 99))

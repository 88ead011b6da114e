"""upload_p50_ms (ms): the median, over every upload due in the window,
of the time from when it was due to its acknowledgement. Retries after
429 or 503 are inside that time. An upload never acknowledged counts as
beyond any limit: infinitely late."""

import numpy as np


def read(run):
    lat = [(u.acked - u.due) if u.acked is not None else np.inf
           for u in run.window_uploads() if u.due is not None]
    if not lat:
        return None
    return 1e3 * float(np.percentile(lat, 50))

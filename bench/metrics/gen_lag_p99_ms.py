"""gen_lag_p99_ms (ms): 99th percentile, over the uploads due in the
window, of how late the load generator sent them: send time minus due
time. A high value means the generator, not the server, was slow."""

import numpy as np


def read(run):
    lag = [u.sent - u.due for u in run.window_uploads() if u.due is not None]
    if not lag:
        return None
    return 1e3 * float(np.percentile(lag, 99))

"""round_close_ms (ms): mean, over every round started in the window, of
the time from its close condition (its last included upload or write
acknowledged) to the fused vector on the host."""

import numpy as np


def read(run):
    rounds = [r for r in run.window_rounds()
              if r.closed is not None and r.on_host is not None]
    if not rounds:
        return None
    return 1e3 * float(np.mean([r.on_host - r.closed for r in rounds]))

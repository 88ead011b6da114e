"""store_read_ms (ms): mean over the rounds started in the window of the
store's block-load time, RoundReport.phase_seconds["ingest"]."""

import numpy as np


def read(run):
    vals = [r.phase["ingest"] for r in run.window_rounds()
            if "ingest" in r.phase]
    return 1e3 * float(np.mean(vals)) if vals else None

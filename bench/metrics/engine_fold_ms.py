"""engine_fold_ms (ms): mean over the rounds started in the window of the
engine's fold time, RoundReport.phase_seconds["compute"]: host-to-device
transfer, the fold steps, finalize and the state copy-out."""

import numpy as np


def read(run):
    vals = [r.phase["compute"] for r in run.window_rounds()
            if "compute" in r.phase]
    return 1e3 * float(np.mean(vals)) if vals else None

"""round_s (s): the time from the window's start to the end of the last
round that finished in the window, divided by the rounds finished in it.
Rounds run back to back from the window's start, so a stall anywhere
moves it."""


def read(run):
    w0, w1 = run.window
    ends = [r.on_host for r in run.rounds
            if r.started >= w0 and r.on_host is not None and r.on_host <= w1]
    if not ends:
        return None
    return (max(ends) - w0) / len(ends)

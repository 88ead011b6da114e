"""close_engine_host_ms (ms): mean, over the closes wholly inside the
traced sub-window, of the self time inside the close of the round's
``repro.engine.stage`` (per-block padding, effective weights, staleness
scale) and ``repro.engine.copyout`` (the reducer state copied to the
host) spans: the engine's host work."""

from bench import layers


def read(run):
    return layers.close_self_ms(run, ["repro.engine.stage",
                                      "repro.engine.copyout"])

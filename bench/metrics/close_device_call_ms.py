"""close_device_call_ms (ms): mean, over the closes wholly inside the
traced sub-window, of the self time inside the close of the round's
``repro.engine.step`` and ``repro.engine.finalize`` spans: each call
with its wait for the device under the semaphore (transfer, dispatch,
execution)."""

from bench import layers


def read(run):
    return layers.close_self_ms(run, ["repro.engine.step",
                                      "repro.engine.finalize"])

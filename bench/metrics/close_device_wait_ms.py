"""close_device_wait_ms (ms): mean, over the closes wholly inside the
traced sub-window, of the self time inside the close of the round's
``repro.engine.device_wait`` spans: the wait for the service's device
semaphore while another round's fold holds it."""

from bench import layers


def read(run):
    return layers.close_self_ms(run, ["repro.engine.device_wait"])

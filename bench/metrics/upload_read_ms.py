"""upload_read_ms (ms): mean, over the ``repro.frontend.read`` spans
wholly inside the traced sub-window (one an upload), of the time the
front-end took to read an upload's body off the socket."""

from bench import layers


def read(run):
    return layers.upload_span_ms(run, "repro.frontend.read")

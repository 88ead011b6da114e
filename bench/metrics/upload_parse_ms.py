"""upload_parse_ms (ms): mean, over the ``repro.frontend.parse`` spans
wholly inside the traced sub-window (one an upload), of the time the
front-end took to parse an upload's wire frame."""

from bench import layers


def read(run):
    return layers.upload_span_ms(run, "repro.frontend.parse")

"""close_store_ms (ms): mean, over the closes wholly inside the traced
sub-window, of the self time inside the close of the round's
``repro.store.load`` (block loads) and ``repro.store.consume`` (the
version-checked remove of the folded ids) spans."""

from bench import layers


def read(run):
    return layers.close_self_ms(run, ["repro.store.load",
                                      "repro.store.consume"])

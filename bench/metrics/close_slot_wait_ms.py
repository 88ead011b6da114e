"""close_slot_wait_ms (ms): mean, over the closes wholly inside the traced
sub-window, of the part of the close before the round's ``repro.round``
span opened: a round whose uploads have all landed, waiting for one of
the scheduler's running slots (``FairRoundScheduler``, ``max_running``)."""

from bench import layers


def read(run):
    return layers.close_slot_wait_ms(run)

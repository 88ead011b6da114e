"""ingest_queue_wait_ms (ms): mean, over the uploads of the
``repro.ingest.commit`` spans wholly inside the traced sub-window, of
their wait in the ``IngestQueue`` from enqueue to the committer's drain
(each commit span's ``queue_wait_s`` stat sums its batch's)."""

from bench import layers


def read(run):
    return layers.commit_ms(run, queued=True)

"""store_commit_ms (ms): mean, over the uploads of the
``repro.ingest.commit`` spans wholly inside the traced sub-window, of
their batch's ``store.write_batch`` time: an upload's wait for its
commit once the committer has drained it."""

from bench import layers


def read(run):
    return layers.commit_ms(run, queued=False)

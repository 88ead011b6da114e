"""ingest_batch_mean (uploads): uploads committed per store commit by
the IngestQueue over the window (its committed and batches counters)."""


def read(run):
    committed, batches = run.counter("committed"), run.counter("batches")
    if not batches:
        return None
    return committed / batches

"""ingest_queue_wait_ms.silo: ``ingest_queue_wait_ms`` read in the silo
cell, where it moves ``round_s``: a round's eight 553 MB uploads go
through the same front-end and committer."""

from bench import harness


def read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / "ingest_queue_wait_ms.py").read(run)

"""upload_parse_ms.silo: ``upload_parse_ms`` read in the silo cell, where
it moves ``round_s``: a round's eight 553 MB uploads go through the
same front-end and committer."""

from bench import harness


def read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / "upload_parse_ms.py").read(run)

"""engine_fold_ms.silo: ``engine_fold_ms`` read in the silo cell, where
``round_close_ms`` is no end-to-end metric and this moves ``round_s``. A
silo round's close varies with how many of its rows were folded before
its last upload was acknowledged, too widely for an end-to-end bound."""

from bench import harness


def read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / "engine_fold_ms.py").read(run)

"""round_close_ms.poisson: ``round_close_ms`` read in the open-loop Poisson
cell, where ``round_close_ms`` is no end-to-end metric and this moves
``upload_p50_ms``: the rounds' host work shares the interpreter with the
uploads. The mean close there swings with the host's pauses, too widely
for an end-to-end bound."""

from bench import harness


def read(run):
    return harness.load_module(
        harness.BENCH / "metrics" / "round_close_ms.py").read(run)

"""setup_s (s): process start to the window's start: device start-up,
payload generation, compiles or cache loads, and the warm-up rounds."""


def read(run):
    return run.setup_s

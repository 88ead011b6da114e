"""compiles_in_window (compiles): cold compiles of the service's engine
cache (CompiledCache.misses) during the window; 0 when set-up warmed
every shape."""


def read(run):
    return run.counter("compiles")

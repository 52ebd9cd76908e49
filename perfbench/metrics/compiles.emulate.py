"""Programs compiled, or loaded from the persistent compilation cache,
inside the window (the program's ``compile_stats()`` delta)."""


def read(run):
    return run.counters.get("compiles")

"""Programs compiled, or loaded from the persistent compilation cache,
inside the window (the program's ``compile_stats()`` deltas)."""


def read(run):
    if "compiles" not in run.counters:
        return None
    return run.counters["compiles"] + run.counters.get("cache_loads", 0)

"""Requests the router served per dispatch in the window
(``FleetRouter.stats()`` deltas)."""


def read(run):
    d = run.counters.get("dispatches")
    return run.counters["served"] / d if d else None

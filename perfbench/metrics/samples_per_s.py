"""Input samples whose work completed in the window, over the window."""


def read(run):
    return run.samples / run.window_s if run.samples else None

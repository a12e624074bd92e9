"""sweep_items_per_s: items of the window's whole passes over the window
(host clock)."""


def read(run):
    return run.items / run.window_s

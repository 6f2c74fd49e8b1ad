"""Engine layer: the mean over the window's ticks of the share of slots
that hold a request (``PagedServeEngine.utilization``), in %."""


def read(r):
    return 100.0 * sum(r.occupancy) / len(r.occupancy) if r.occupancy \
        else None

"""Traffic kind ``open_loop``: requests arrive on a seeded schedule at
a rate fixed in the mix, whether or not earlier ones have finished;
each is timed from when it was DUE, and the generator's lateness is
printed."""

from ..lib.serve import ServeRun


class Run(ServeRun):
    open_loop = True

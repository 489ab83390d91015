"""Traffic kind ``offline_batch``: a backlog submitted at the window's
start and topped up so that ``arrivals.min_waiting`` requests always
wait; the user pays per token, so tokens per second is what counts."""

from ..lib.serve import ServeRun


class Run(ServeRun):
    open_loop = False

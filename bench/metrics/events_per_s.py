"""events_per_s (events/s, host clock): simulated events retired, summed
over every lane of every request of the window (`SimResult.n_iters`),
over the wall seconds from the window's start to the end of its last
request."""


def read(run):
    return run.events / run.window_s

"""Mean wait in the engine's admission queue over the window: the change in
`netgen_engine_queue_wait_seconds` sum over the change in its count. Never
the histogram's windowed quantiles, which keep only recent observations."""


def read(run):
    n, total = run.hist_delta("netgen_engine_queue_wait_seconds")
    return total / n * 1e3 if n else None

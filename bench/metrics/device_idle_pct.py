"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window. BENCHMARK.json splits it by
cell kind (`.online`, `.offline`), since each kind moves its own metric."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share

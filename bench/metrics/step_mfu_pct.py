"""Whole-step share of the chip's int8 peak: predictions completed in the
window times the net's 2 * sum(K * N) operations, over the window, over the
peak. Only requested rows count, so padded slots are waste."""


def read(run):
    if not run.completed or run.peak is None:
        return None
    ops = run.work.ops(run.widths, run.completed)
    return 100.0 * ops / run.window_s / run.peak["int8_ops_per_s"]

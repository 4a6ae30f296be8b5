"""Whole-step share of the chip's int8 peak: predictions completed in the
window times the operations of one row of the configuration's net
(`net.ops`; for a dense chain 2 * sum(K * N)), over the window, over the
peak. Only requested rows count, so padded slots are waste."""


def read(run):
    if not run.completed or run.peak is None:
        return None
    ops = run.net.ops(run.config, run.completed)
    return 100.0 * ops / run.window_s / run.peak["int8_ops_per_s"]

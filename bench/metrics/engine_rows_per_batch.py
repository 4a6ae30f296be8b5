"""Rows per engine batch over the window: the change in
`netgen_engine_completed_total` over the change in `netgen_engine_batches_total`."""


def read(run):
    batches = run.delta("netgen_engine_batches_total")
    return run.delta("netgen_engine_completed_total") / batches if batches else None

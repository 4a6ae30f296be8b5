"""Device time of the conv kernel a launch: the summed device durations
of the ops named after it (`netgen_conv`, one op per conv layer) over the
change of `netgen_kernel_launches_total`. None where the trace has no
conv kernel op or no launch was counted."""
KERNEL = "netgen_conv"


def read(run):
    if run.trace is None:
        return None
    events = run.kernel_events(KERNEL)
    launches = run.delta("netgen_kernel_launches_total")
    if not events or not launches:
        return None
    return sum(d for _, _, d, *_ in events) * 1e-3 / launches

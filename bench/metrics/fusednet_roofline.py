"""The fusednet megakernel's share of its roofline: the least time the chip
could take for its launches over their device time. A launch is priced by
the configuration's net module (`net.min_seconds`: the larger of int8
operations over the int8 peak and bytes over HBM bandwidth, `bench/peaks.json`)
at the rows it ran for each version: the change of `netgen_slot_rounds_total`
times `slot_capacity` over the change of `netgen_kernel_launches_total`, or
`slot_capacity` where the rounds counter did not move (one round a launch)."""
# the megakernel's pallas_call, as the chip's trace names it (HLO instruction
# `binary_forward_planes.<n>`, a Mosaic `tpu_custom_call`)
KERNEL = "binary_forward_planes"


def rows_per_launch(run) -> float:
    rounds = run.delta("netgen_slot_rounds_total")
    launches = run.delta("netgen_kernel_launches_total")
    if not rounds or not launches:
        return float(run.slot_capacity)
    return run.slot_capacity * rounds / launches


def read(run):
    if run.trace is None or run.peak is None:
        return None
    events = run.kernel_events(KERNEL)
    if not events:
        return None
    least, _ = run.net.min_seconds(run.config, rows_per_launch(run), run.versions, run.peak)
    device_s = sum(d for _, _, d, *_ in events) * 1e-9
    return 100.0 * least * len(events) / device_s

"""The conv kernel's share of its roofline: the least time of the
configuration's conv layers (`net.conv_min_seconds`: int8 operations over
the int8 peak, or the input rows and conv weights over HBM bandwidth,
`bench/peaks.json`) at the rows each launch ran, times the launches, over
the summed device time of the ops named after the conv kernel. Rows a
launch: the change of `netgen_slot_rounds_total` times `slot_capacity`
over the change of `netgen_kernel_launches_total`. None where the net has
no conv layers, the trace has no conv kernel op, or no launch was
counted."""
# the conv kernel's pallas_call name, as the chip's trace names its ops
# (`netgen_conv`, `netgen_conv.<n>`)
KERNEL = "netgen_conv"


def read(run):
    least_fn = getattr(run.net, "conv_min_seconds", None)
    if run.trace is None or run.peak is None or least_fn is None:
        return None
    events = run.kernel_events(KERNEL)
    launches = run.delta("netgen_kernel_launches_total")
    rounds = run.delta("netgen_slot_rounds_total")
    if not events or not launches or not rounds:
        return None
    least, _ = least_fn(run.config, run.slot_capacity * rounds / launches, run.versions,
                        run.peak)
    device_s = sum(d for _, _, d, *_ in events) * 1e-9
    return 100.0 * least * launches / device_s

"""Host time of the server per kernel launch: the summed `netgen.dispatch`
span durations less the device's busy time in the traced window, over the
change in `netgen_kernel_launches_total`. BENCHMARK.json splits it by cell
kind (`.online`, `.offline`), since each kind moves its own metric."""
PATHS = ("single", "stacked", "fallback")


def read(run):
    if run.trace is None:
        return None
    spans = [s for s in run.spans if s.name == "netgen.dispatch"]
    calls = sum(run.delta("netgen_dispatch_total", path=p) for p in PATHS)
    if len(spans) < calls:
        raise RuntimeError(f"{len(spans)} netgen.dispatch spans kept for {calls:g} "
                           "dispatches: the registry dropped spans")
    launches = run.delta("netgen_kernel_launches_total")
    if not launches:
        return None
    return (sum(s.duration_s for s in spans) - run.trace.busy_s) / launches * 1e6

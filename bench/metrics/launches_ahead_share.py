"""Share of kernel launches dispatched while an earlier launch of the same
call was not yet fetched: the change of `netgen_launches_ahead_total` over the
change of `netgen_kernel_launches_total`. A call of n launches, fetched two in
flight, adds n - 1 of n. None where no launch was counted, and for a program
that keeps no such counter (the share would read 0 for want of it)."""
AHEAD = "netgen_launches_ahead_total"


def read(run):
    launches = run.delta("netgen_kernel_launches_total")
    if not launches or next(run._match("counter", AHEAD, {}), None) is None:
        return None
    return run.delta(AHEAD) / launches

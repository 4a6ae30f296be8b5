"""Slot rounds per kernel launch: the change in `netgen_slot_rounds_total`
over the change in `netgen_kernel_launches_total`. None where either did not
move, as in a program without the slot-round counter or a target that counts
no kernel launches."""


def read(run):
    rounds = run.delta("netgen_slot_rounds_total")
    launches = run.delta("netgen_kernel_launches_total")
    if not rounds or not launches:
        return None
    return rounds / launches

"""Seconds of one stage of compiling the main program's step during set-up,
as the program recorded them on its compile events
(`paddle_tpu.observe.observatory()`, `stages_s`): `trace` (the Program traced
through the lowering rules), `lower` (to an MLIR module), `backend` (the XLA
compile, or the load from the persistent cache). They are what
jax.monitoring reported while the step was being built, summed over every
compile event of the program (where jax builds the step again on a later
call, the program adds that to the same event). Nothing where the program
keeps no such record."""


def program_stages(events, program_uid):
    """{stage: seconds} summed over the program's compile events; None if
    there is none, or they carry no `stages_s`."""
    total = None
    for e in events:
        stages = getattr(e, "stages_s", None)
        if e.program_uid == program_uid and stages is not None:
            total = total or {}
            for stage, seconds in stages.items():
                total[stage] = total.get(stage, 0.0) + seconds
    return total


def read(ctx, stage):
    from paddle_tpu import observe
    stages = program_stages(observe.observatory().events(),
                            ctx["system"].main._uid)
    return stages.get(stage) if stages else None

"""Share of the pad's backlog depths that the compiled planners walked:
the program's ``plan_steps`` counter (each round's deepest live backlog)
over ``plan_steps_padded`` (the pad width ``L`` a round), traced window."""


def read(ctx):
    prof = ctx["out"]["profiler"]
    counters = getattr(prof, "counters", {}) if prof is not None else {}
    padded = counters.get("plan_steps_padded")
    if not padded:
        return None
    return counters.get("plan_steps", 0) / padded * 100

"""Share of the slow tier's forwards that served an escalated frame: the
window's escalated frames (offloaded or missed) over the program's
``slow_frames`` counter, the frames it sent through the slow tier."""


def read(ctx):
    out = ctx["out"]
    prof = out["profiler"]
    sent = getattr(prof, "counters", {}).get("slow_frames") if prof is not None else None
    if not sent:
        return None
    return out["escalated"] / sent * 100

"""Per cent of the request-seconds of the traced stretch that requests
spent queued for a slot (preempted ones included), the rest being spent
in prefill and in decode: the engine's
``repro_request_seconds_total{state}`` over the stretch
(``ctx["program_counters"]``).  A time integral over every open request,
not a mean over the few that finish in the stretch.  Nothing where the
engine has no such counter or no request was open."""

NAME = "repro_request_seconds_total"
STATES = ("queued", "prefill", "decode")


def read(ctx):
    counts = ctx.get("program_counters") or {}
    by = [counts.get(f"{NAME}{{state={s}}}") for s in STATES]
    if None in by or sum(by) <= 0:
        return None
    return 100.0 * by[0] / sum(by)

"""Device idle time under ``execute_plan``'s host spans (``repro.execute``
and its children ``repro.execute.*``: chunk assembly, dispatch, the
per-tensor pack with its blocking reads, the manifest), in ms per job of
the traced window.  Idle is counted under the innermost span, by
intersection with the span."""

SPAN = "repro.execute"


def _under(name: str) -> bool:
    return name == SPAN or name.startswith(SPAN + ".")


def read(ctx):
    red, jobs = ctx["trace"], ctx["window"]["jobs"]
    if not getattr(red, "span_counts", {}).get(SPAN) or not jobs:
        return None
    idle = sum(s for n, s in red.span_idle_s.items() if _under(n))
    return idle * 1e3 / len(jobs)

"""Mean host time of one of the program's own spans
(``repro.core.telemetry.SPANS``), from the clock the span keeps in the
program's registry: every span of that name the run closed in set-up
and the window, less those in which a program compiled (the traced
stretch is not booked). A program without that clock (an older
commit) gives None."""


def mean_ms(name: str):
    from repro.core import telemetry
    stats = getattr(telemetry.REGISTRY, "span_stats", None)
    s = stats().get(name) if stats is not None else None
    if not s or not s["count"]:
        return None
    return s["seconds"] / s["count"] * 1e3

"""Host time per wave spent composing, absorbing, admitting into and
releasing from the paged cache: the median, over the `wave` events that
ended inside the window, of the `pages.*` phases of their `host_ms`."""

from bench.yardstick import percentile


def read(run):
    per_wave = [sum(ms for phase, ms in w["host_ms"].items()
                    if phase.startswith("pages."))
                for t, w in run.waves if run.in_window(t) and "host_ms" in w]
    return percentile(per_wave, 50) if per_wave else None

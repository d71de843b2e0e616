"""Host time per wave that the chip cannot overlap: the median, over the
`wave` events that ended inside the window, of the program's host time by
phase (`host_ms`, the self times of serve_stream's spans since the last
wave event) without the blocking read-backs (`serve.wait`, the device's
time) and the caller's time between events (`consumer`)."""

from bench.yardstick import percentile

NOT_HOST = ("serve.wait", "consumer")


def read(run):
    per_wave = [sum(ms for phase, ms in w["host_ms"].items()
                    if phase not in NOT_HOST)
                for t, w in run.waves if run.in_window(t) and "host_ms" in w]
    return percentile(per_wave, 50) if per_wave else None

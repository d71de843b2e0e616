"""Share of its roofline that the decode step reaches: the least time the
chip needs for the step's bytes (for a dense decoder: weights once, the
live key/value cache, the new keys and values; the family's
`decode_bytes`) at peak bandwidth, over the step's device time, both per
step in the traced window.  The bytes bound it: a decode step does about
2 operations per byte read.  A family that cannot count a step's bytes
from its lengths gives no value."""

MODULE = "jit_decode_step"


def read(run):
    m = (run.trace or {}).get("modules", {}).get(MODULE)
    waves = run.decode_waves()
    if not m or not m["count"] or not waves:
        return None
    steps = [run.cell.family.decode_bytes(run.dm, w) for w in waves]
    if None in steps:
        return None
    floor_s = sum(steps) / len(waves) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / (m["seconds"] / m["count"])

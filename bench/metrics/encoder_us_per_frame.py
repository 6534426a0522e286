"""Device time of the split engine (the edge and server stages and the
wire kernel) per frame launched in the traced window, in us."""

PROGRAMS = ("split_stage", "jit_wire_roundtrip")


def read(run):
    tr = run.trace
    frames = run.frames_launched()
    if tr is None or not frames:
        return None
    dev = sum(tr["programs"].get(p, 0.0) for p in PROGRAMS)
    if dev <= 0:
        return None
    return 1e6 * dev / frames

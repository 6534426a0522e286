"""The wire kernel's share of its roofline: the least time the chip
needs to move its bytes (each float32 element read and written once, for
every padded row the gateway hands it) over its device time in the
traced window.  Memory-bound: the kernel does a handful of operations
per 8 bytes."""
import yardstick as ys

PROGRAM = "jit_wire_roundtrip"


def read(run):
    tr = run.trace
    t = tr and tr["programs"].get(PROGRAM, 0.0)
    if not t:
        return None
    L = len(run.enc["widths"])
    nbytes = sum(ys.wire_roundtrip_bytes(run.enc, k, rows)
                 for _, _, buckets in run.launches_traced()
                 for k, rows in buckets if k < L)
    if not nbytes:
        return None
    pk = ys.peaks(run.device_kind)
    return 100.0 * ys.roofline_time_s(0.0, nbytes, pk) / t

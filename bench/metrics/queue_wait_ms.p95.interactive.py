"""p95 queue wait of INTERACTIVE frames, submit to tick admission, from
the streaming runtime's own wait sketch at the end of the run.  The
server serves only the run's traffic (set-up warms the gateway
directly), so the sketch holds the ramp, the window and the drain."""


def read(run):
    st = run.stats.get("end")
    if st is None or run.arrivals is None:
        return None
    return float(st.queue_wait_ms["interactive"]["p95"])

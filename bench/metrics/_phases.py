"""The program's own tick-phase counters (``GatewayStats.phases`` and
``StreamStats.phases``: span name -> cumulative (calls, seconds, slow)),
read as the difference of two snapshots.  Every reader returns ``None``
where the snapshots carry no phases."""


def phases(st):
    """{span name: (calls, seconds, slow)} of one ``StreamStats``, the
    gateway's spans and the serving loop's together, or None."""
    own = getattr(st, "phases", None)
    gw = getattr(st.gateway, "phases", None)
    if own is None or gw is None:
        return None
    return {**gw, **own}


def delta(run, span, a="w0", b="w1"):
    """(calls, seconds, slow) of ``span`` between two snapshots, or
    None."""
    pa, pb = phases(run.stats[a]), phases(run.stats[b])
    if pa is None or pb is None or span not in pb:
        return None
    return tuple(y - x for x, y in zip(pa.get(span, (0, 0.0, 0)), pb[span]))


def mean_ms(calls_seconds_slow):
    """Mean ms a call, or None without calls."""
    if calls_seconds_slow is None or calls_seconds_slow[0] <= 0:
        return None
    calls, seconds, _ = calls_seconds_slow
    return 1e3 * seconds / calls


def window_mean_ms(run, span):
    """Mean ms a call of ``span`` over the measured window."""
    return mean_ms(delta(run, span))

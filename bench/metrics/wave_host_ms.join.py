"""Host milliseconds per wave that the wave pipeline spends on its own
work: the time covered by the program's ``wave/*`` spans inside
``bench.window``, less the time of their ``wave/fetch`` children (the
blocking transfers, where the host waits for the device), divided by the
number of ``wave/launch`` spans. Read from the program spans the traced
run's profiler records beside the device ops; a program without them
reads nothing."""

WINDOW = "bench.window"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b):
    """Length of the intersection of two disjoint sorted interval lists."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(hi - lo, 0.0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def read(run):
    rec = run.records
    if rec is None:
        return None
    win = [e for e in rec.host if e.name == WINDOW]
    if not win:
        return None
    lo, hi = min(e.start for e in win), max(e.end for e in win)
    waves = [(e.name, max(e.start, lo), min(e.end, hi)) for e in rec.host
             if e.name.startswith("wave/") and e.end > lo and e.start < hi]
    n = sum(1 for name, _, _ in waves if name == "wave/launch")
    if not n:
        return None
    own = _union([(s, e) for name, s, e in waves if name != "wave/fetch"])
    fetch = _union([(s, e) for name, s, e in waves if name == "wave/fetch"])
    host_ns = sum(e - s for s, e in own) - _overlap(own, fetch)
    return host_ns * 1e-6 / n

"""Share of the wave's lane-iterations in which a lane of a real query
was still traversing: the program's ``JoinStats.n_lane_iters`` over
wave_size x ``JoinStats.n_iters``, summed over the window's calls. The
rest are lanes that finished and wait for the wave's slowest lane, and
the padding lanes of a short last wave. A program without the lane
counter reads nothing."""


def read(run):
    lanes = [getattr(c.stats, "n_lane_iters", None) for c in run.calls]
    jcfg = getattr(run.driver, "jcfg", None)
    if not lanes or None in lanes or jcfg is None:
        return None
    iters = sum(c.stats.n_iters for c in run.calls)
    return sum(lanes) / (jcfg.wave_size * iters) if iters else None

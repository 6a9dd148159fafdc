"""Share of the traversal's work done on the hybrid (BBFS) path: the
program's ``JoinStats.n_hybrid_lane_iters`` over its ``n_lane_iters``,
summed over the window's calls. The hybrid path is the range expansion of
the waves of queries ``es_mi_adapt`` predicts out of distribution, which
also walks non-data nodes; the rest are plain BFS waves. A program
without the hybrid counter reads nothing."""


def read(run):
    hyb = [getattr(c.stats, "n_hybrid_lane_iters", None) for c in run.calls]
    if not hyb or None in hyb:
        return None
    lanes = sum(c.stats.n_lane_iters for c in run.calls)
    return sum(hyb) / lanes if lanes else None

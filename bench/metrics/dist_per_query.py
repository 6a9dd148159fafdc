"""Distance computations per query joined (the program's
``JoinStats.n_dist`` over the window's calls): the paper's
distance-computation count."""


def read(run):
    n = sum(c.n_queries for c in run.calls)
    return sum(c.stats.n_dist for c in run.calls) / n if n else None

"""Share of the float64 reference's pairs, on the sampled queries, that
the window's answers emitted."""


def read(run):
    return run.tally.recall if run.tally.wanted else None

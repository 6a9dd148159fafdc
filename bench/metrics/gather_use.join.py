"""Share of the candidate slots the traversal's probes evaluated whose
distance it kept: the program's ``JoinStats.n_dist`` over its
``JoinStats.n_slots``, summed over the window's calls. Every probe
gathers and scores B x K rows, lanes that finished and slots masked by
the visited test or the dedup included; this is the share of that
gather that does useful work. A program without the slot counter reads
nothing."""


def read(run):
    slots = [getattr(c.stats, "n_slots", None) for c in run.calls]
    if not slots or None in slots or not sum(slots):
        return None
    return sum(c.stats.n_dist for c in run.calls) / sum(slots)

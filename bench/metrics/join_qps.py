"""Queries joined per second: all queries of the window's back-to-back
join calls over the time from the first call's start to the last call's
end (host clock; each call ends in host-side pairs)."""


def read(run):
    if not run.calls:
        return None
    n = sum(c.n_queries for c in run.calls)
    return n / (run.calls[-1].t1 - run.calls[0].t0)

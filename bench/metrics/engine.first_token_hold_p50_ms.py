"""Median, in milliseconds, over the requests whose first token the
harness stamped in the window, of how long ``Engine.step()`` held a token
that already existed: the harness's first-token stamp (after ``step()``
returned) minus the engine's ``Request.token_times[0]`` (right after the
sample that brought the token to the host).  None where the program
keeps no token stamps."""
from harness import stats


def read(run):
    w = run.window
    holds = []
    for t in w.tracks:
        times = getattr(w.requests.get(t.uid), "token_times", None)
        if t.tokens and times and w.t0 <= t.tokens[0] <= w.close:
            holds.append(t.tokens[0] - times[0])
    ms = stats.percentile(holds, 50)
    return None if ms is None else 1e3 * ms

"""90th percentile over the requests due in the window of the engine's own
queue wait: ``Request.admit_time`` (the scheduler gave the request a
slot) minus ``arrival_time``; a request not admitted by the close counts
at close minus arrival.  None where the program keeps no admission
stamp."""
from harness import stats


def read(run):
    reqs = run.window.requests
    if not any(hasattr(r, "admit_time") for r in reqs.values()):
        return None
    close = run.window.close
    waits = []
    for t in run.due:
        r = reqs.get(t.uid)
        at = getattr(r, "admit_time", None)
        arrival = t.due if r is None else r.arrival_time
        waits.append((at if at is not None and at <= close else close)
                     - arrival)
    return stats.percentile(waits, 90)

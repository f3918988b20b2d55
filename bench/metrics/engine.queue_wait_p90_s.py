"""90th percentile over the requests due in the window of the wait from
due time to the first step after which the request holds a slot (the
harness's stamps; a request still queued at the close counts at its
elapsed wait)."""
from harness import stats


def read(run):
    return stats.percentile(
        stats.waits_until(run.due, run.window.close, "admit"), 90)

"""Mean milliseconds a served request waited for its micro-batch while the
server was busy with a flush or round for other requests: the program's
``ServedQuery.busy_wait_s`` over the window's served requests.  The rest of
``admission_wait_ms`` is the batcher holding requests on an idle server."""
import math


def read(run):
    w = [s.busy_wait_s for s in run["served"]
         if s.status == "served" and not math.isnan(
             getattr(s, "busy_wait_s", math.nan))]
    return 1e3 * sum(w) / len(w) if w else None

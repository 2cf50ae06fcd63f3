"""Mean milliseconds a served request waited for its micro-batch to flush:
``admitted_s - arrival_s`` on the server's clock, over the window's
served requests."""


def read(run):
    w = [s.admitted_s - s.arrival_s for s in run["served"]
         if s.status == "served"]
    return 1e3 * sum(w) / len(w) if w else None

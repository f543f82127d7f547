"""How late the load generator ran: the 95th percentile, in ms, of how far
each wake-up for a due request (or a batch deadline) overshot its time,
on the host clock. A window in which the service never waited for a
request has no wake-up to be late: 0."""
import numpy as np


def read(run):
    late = run.rec["late_s"]
    return float(np.percentile(late, 95)) * 1e3 if late.size else 0.0

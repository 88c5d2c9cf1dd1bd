"""95th percentile of time to first token, from each request's due time
to the return of the ``gw.step()`` that gave its first token, over every
request due in the window (each followed to completion).  Host clock."""
import numpy as np


def read(run):
    if run.arrivals == "backlog":
        return None
    v = [r.stamps[0] - r.due for r in run.judged() if r.stamps]
    return float(np.percentile(v, 95) * 1e3) if v else None

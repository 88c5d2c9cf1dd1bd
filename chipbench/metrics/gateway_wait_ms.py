"""Mean time from a request's due time to the engine's ``submit``
(``Request.t_submit``): the generator's lateness plus the gateway queue.
Program counter (the engine's own timestamp), same host clock."""
import numpy as np


def read(run):
    v = [run.engine_submit[r.stream.rid] - r.due for r in run.judged()
         if r.stream is not None and r.stream.rid in run.engine_submit]
    return float(np.mean(v) * 1e3) if v else None

"""Mean host wall time of the ``gw.step()`` calls inside the window that
ran a decode step (admission, prefill and bookkeeping of that step
included).  Benchmark-side spans, host clock."""
import numpy as np


def read(run):
    v = run.decode_step_s
    return float(np.mean(v) * 1e3) if v else None

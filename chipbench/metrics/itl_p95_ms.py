"""95th percentile of the gaps between consecutive tokens of a request,
as the client sees them (stamped after each ``gw.step()``), over every
judged request.  Open loop: every gap of every request due in the
window; backlog: every gap that closes inside the window.  Host clock."""
import numpy as np


def read(run):
    gaps = []
    for r in run.judged():
        for a, b in zip(r.stamps, r.stamps[1:]):
            if run.arrivals != "backlog" or run.in_window(b):
                gaps.append(b - a)
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None

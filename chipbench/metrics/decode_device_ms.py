"""Device time of one run of the decode program (``decode_step_paged``),
the mean over the traced window.  Device trace."""


def read(run):
    tr = run.trace
    n = tr.program_runs.get("decode_step_paged", 0) if tr else 0
    return tr.program_seconds["decode_step_paged"] / n * 1e3 if n else None

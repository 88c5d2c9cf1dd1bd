"""Time per decode step in which the shell's own threads (scheduler
worker, executor lanes) hold work: the union of the program's
``repro.sched.*`` spans on every thread but the serving one, over the
decode dispatches of the traced window.  Program spans in the device
trace (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    s = spans.of_run(run)
    return s.shell_thread_ms if s else None

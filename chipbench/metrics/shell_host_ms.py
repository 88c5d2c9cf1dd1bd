"""The shell's host time per decode step on the serving thread: the union
of the program's ``repro.port.*``, ``repro.mmu.*`` and ``repro.health.*``
spans inside its ``repro.engine.step`` spans, outside any device wait,
over the decode dispatches of the traced window.  Program spans in the
device trace (``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    s = spans.of_run(run)
    return s.shell_host_ms if s else None

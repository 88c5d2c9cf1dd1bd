"""The engine's and gateway's own host time per decode step: the union of
the program's ``repro.engine.step`` spans less the device waits and the
shell's spans (``device_wait_ms``, ``shell_host_ms``), over the decode
dispatches of the traced window.  Program spans in the device trace
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    s = spans.of_run(run)
    return s.engine_host_ms if s else None

"""Host time per decode step blocked on the device: the union of the
program's ``repro.engine.decode_wait`` and ``repro.engine.prefill_wait``
spans inside its ``repro.engine.step`` spans, over the decode dispatches
of the traced window.  Program spans in the device trace
(``chipbench/spans.py``)."""
from chipbench import spans


def read(run):
    s = spans.of_run(run)
    return s.device_wait_ms if s else None

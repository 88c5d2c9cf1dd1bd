"""Device time of the prefill programs (one-shot and chunk) per 1000
prompt tokens they computed in the traced window.  Device trace; the
token count is the programs' own query lengths."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    secs = sum(tr.program_seconds.get(p, 0.0)
               for p in ("prefill_shared_paged", "prefill_chunk_paged"))
    toks = sum(int(ql.sum()) for _, t, _, ql in run.prefill_calls
               if run.in_trace(t))
    return secs * 1e3 / (toks / 1000.0) if toks and secs else None

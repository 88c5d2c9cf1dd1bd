"""Share of prompt tokens whose KV came from shared prefix pages, over
the window and the drain: the engine's ``prefill_skipped`` over
``prefill_computed + prefill_skipped``.  Program counters."""


def read(run):
    total = run.prefill_computed + run.prefill_skipped
    return 100.0 * run.prefill_skipped / total if total else None

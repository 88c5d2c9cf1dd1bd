"""Model FLOP utilization of the whole serving step: the model FLOPs of
every token the window processed (prompt tokens the prefill programs
computed and decoded tokens in the traced part of the window, attention at each token's real causal
context, the head where logits were computed; the counts of the
configuration's architecture, ``chipbench/archs/``), per second of the
traced window, over the chip's bf16 peak."""


def read(run):
    if run.trace is None:
        return None
    a, d = run.arch, run.dims
    lin, head = a.linear_flops(d), a.head_flops(d)
    flops = 0
    for r in run.reqs:
        plen = len(r.arrival.prompt)
        for k, t in enumerate(r.stamps):
            if k >= 1 and run.in_trace(t):
                flops += lin + head + a.attn_flops(d, plen + k)
    for kind, t, q_starts, q_lens in run.prefill_calls:
        if not run.in_trace(t):
            continue
        for s, n in zip(q_starts.tolist(), q_lens.tolist()):
            if n > 0:
                flops += lin * n + a.prefill_attn_flops(d, s, n)
                flops += head if kind == "shared" else 0
    if not flops:
        return None
    secs = run.trace.window_s
    return 100.0 * flops / secs / run.peaks()["bf16_flops_per_s"]

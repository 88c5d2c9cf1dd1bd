"""Share of its roofline that the paged decode-attention kernel reaches:
the least time the chip could take for the attention the window's decode
steps needed (the larger of bytes over peak bandwidth and FLOPs over peak
compute, by the counts of the configuration's architecture,
``chipbench/archs/``: live K and V at the stated bfloat16, q and o) over
the kernel's device time in the traced window.  Device trace; the kernel
is the decode program's Mosaic custom call."""

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(run):
    tr = run.trace
    if tr is None:
        return None
    secs = sum(s for n, s in tr.op_seconds.items() if KERNEL in n)
    ctx_sum = rows = 0
    for r in run.reqs:
        plen = len(r.arrival.prompt)
        for k, t in enumerate(r.stamps):
            if k >= 1 and run.in_trace(t):
                ctx_sum += plen + k
                rows += 1
    if not secs or not rows:
        return None
    pk = run.peaks()
    least = max(run.arch.kernel_bytes(run.dims, ctx_sum, rows)
                / pk["hbm_bytes_per_s"],
                run.arch.attn_flops(run.dims, ctx_sum)
                / pk["bf16_flops_per_s"])
    return 100.0 * least / secs

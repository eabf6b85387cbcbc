"""Share of its roofline that the Mosaic flash-attention kernel reaches in
the traced steps, in %: the least time the chip could take for the calls
seen (the larger of operations over peak and bytes over bandwidth, from
``flops.flash_attention_work`` at the cell's ``flash_shape``) over the
device time of the forward, dq and dkv calls in the trace.  Finds nothing
to read where no such call ran."""


def read(run: dict):
    t, peaks = run["trace"], run["peaks"]
    shape = run["traffic"].get("flash_shape")
    if not t or peaks is None or not shape:
        return None
    secs, calls = t["by_category_s"], t["by_category_calls"]
    spent = sum(secs.get(k, 0.0) for k in
                ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    n_fwd = calls.get("flash_fwd", 0)
    n_bwd = min(calls.get("flash_bwd_dq", 0), calls.get("flash_bwd_dkv", 0))
    if spent <= 0.0 or n_fwd == 0:
        return None
    work = run["flops"].flash_attention_work(*shape, causal=True)
    least = n_fwd * run["flops"].roofline_seconds(work["fwd"], peaks)[0] \
        + n_bwd * run["flops"].roofline_seconds(work["bwd"], peaks)[0]
    return 100.0 * least / spent

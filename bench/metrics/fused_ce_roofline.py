"""The least time of the loss's necessary work (three (rows, vocab, d)
products; one read of h and W and one write of dH and dW) over the summed
device time of the fused_ce_fwd, _dh and _dw kernels."""
from bench.counts import transformer as C
from bench.trace_reduce import kernel_s


def read(ctx):
    t = kernel_s(ctx["trace"], "fused_ce_")
    if t <= 0 or not ctx["steps"]:
        return None
    spec = ctx["spec"]
    rows = int(ctx["mix"]["batch"]) * int(ctx["mix"]["seq_len"])
    flops, nbytes = C.fused_ce_work(rows, spec["vocab"], spec["d"])
    least, bound = C.least_time(flops, nbytes, ctx["peaks"])
    return {"value": 100.0 * least * ctx["steps"] / t, "unit": "%",
            "bound": bound}

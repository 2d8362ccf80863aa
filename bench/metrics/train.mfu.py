"""Model FLOPs of the traced window's trained tokens per second, over the
chips' bf16 peak (6 per matmul weight including the lm_head, plus causal
attention; recomputation is not counted).  Compute-bound by definition."""
from bench.counts import transformer as C


def read(ctx):
    if not ctx["steps"]:
        return None
    f = C.train_flops_per_token(ctx["spec"], int(ctx["mix"]["seq_len"]))
    rate = f * ctx["tokens"] / ctx["wall_s"]
    return {"value": 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops"]),
            "unit": "%", "bound": "compute"}

"""Model operations of the prompt and output tokens the window processed
(no padding, no recomputation; bench/counts/ of the configuration's
family), over the window times the chip's bf16 peak."""


def read(ctx):
    if not ctx.get("window_s"):
        return None
    return 100.0 * ctx["model_flops"] / (ctx["window_s"]
                                         * ctx["peak"]["bf16_flops"])

def read(ctx):
    if ctx["peaks"] is None:
        return None
    flops = ctx["config"]["flops_per_sample"]["train"]
    return 100.0 * flops * ctx["throughput"] / ctx["peaks"]["bf16_flops"]

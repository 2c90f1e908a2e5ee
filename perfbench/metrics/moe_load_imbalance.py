"""How unevenly the router loads the experts this chip holds: the fullest
held expert's token-slots over the mean, from the program's own counters
of the last optimizer step it read (``moe_load_max`` / ``moe_load_mean``,
obs.counters.last_model_scalars). 1.0 is a perfectly even load. None for a
program that keeps no such counters."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    if not last.get("moe_load_mean"):
        return None
    return last["moe_load_max"] / last["moe_load_mean"]

"""How evenly the looped decoder's exit gate spreads the tokens' exit
distribution over its passes: the mean entropy of p over ln(passes), from
the program's own counters of the last optimizer step it read
(``loop_exit_entropy``, obs.counters.last_model_scalars). 1.0 is an even
distribution, 0.0 a gate collapsed onto one pass: what the objective's
entropy term guards. None for a program that keeps no such counter."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    return last.get("loop_exit_entropy")

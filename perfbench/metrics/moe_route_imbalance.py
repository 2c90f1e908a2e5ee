"""How unevenly the router chooses among ALL of the model's experts, held on
this chip or not: the tokens that chose the fullest expert over the mean,
from the program's own counters of the last optimizer step it read
(``moe_count_max`` / ``moe_count_mean``, obs.counters.last_model_scalars).
It is what a balancing bias acts on; 1.0 is a perfectly even choice. None
for a program that keeps no such counters."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    if not last.get("moe_count_mean"):
        return None
    return last["moe_count_max"] / last["moe_count_mean"]

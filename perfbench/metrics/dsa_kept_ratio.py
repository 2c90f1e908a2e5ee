"""Keys the sparse attention's queries kept over the keys due to them,
from the program's own counters of the last optimizer step it read
(``dsa_keys_kept`` / ``dsa_keys_due``, obs.counters.last_model_scalars):
1.0 when every query keeps min(t + 1, topk) keys, above it when index
scores tie at a threshold, never below. None for a program that keeps no
such counters."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    if not last.get("dsa_keys_due"):
        return None
    return last["dsa_keys_kept"] / last["dsa_keys_due"]

"""The most negative log decay that a key channel of the Kimi Delta
Attention layers added up to inside one chunk, in the last optimizer step
the program read (``kda_log_decay_min``, obs.counters.last_model_scalars):
gamma_C <= 0, in nats; below -87.3 the chunk's decay of that row of the
state is under float32's smallest normal number. None for a program that
keeps no such counter."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    return last.get("kda_log_decay_min")

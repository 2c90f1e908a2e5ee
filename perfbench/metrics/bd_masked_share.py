"""The share of the noised half's positions that the block-diffusion
decoder's own draw masked in the last optimizer step the program read
(``bd_masked_share``, obs.counters.last_model_scalars): sum m / L, about 1/2
(the mean of t), every step another draw. None for a program that keeps no
such counter."""


def read(ctx):
    try:
        from gtopkssgd_tpu.obs import counters
        last = counters.last_model_scalars()
    except (ImportError, AttributeError):
        return None
    return last.get("bd_masked_share")

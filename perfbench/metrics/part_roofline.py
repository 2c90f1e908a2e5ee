"""A part of a layer kind's share of its roofline: ``work_roofline.py``'s
quotient over the device time of the operations whose innermost
``layer/<kind>`` scope is one of ``kinds`` **and** whose innermost
``part/<name>`` scope is one of ``parts`` (``part_ms``): a kernel's own
share, beside its kind's. The metric's file names ``work``, ``kinds`` and
``parts``. None off the TPU, on a program without the scopes, or for a
configuration whose reference model counts no such work."""
import importlib

from perfbench.metrics import part_ms
from perfbench.metrics.gdn_scan_roofline import _batch


def read(ctx, work, kinds, parts):
    peaks, config = ctx["peaks"], ctx["config"]
    ref = importlib.import_module(
        f"perfbench.refmodels.{config['reference_model']}")
    spent = part_ms.read(ctx, kinds, parts)
    batch = _batch(config)
    if peaks is None or not spent or batch is None or not hasattr(ref, work):
        return None
    operations, moved = getattr(ref, work)(config["sizes"], batch)
    least_ms = 1e3 * max(operations / peaks["bf16_flops"],
                         moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / spent

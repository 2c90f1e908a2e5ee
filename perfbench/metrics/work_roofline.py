"""A layer kind's share of its roofline: the least time the chip could take
for it, max(operations / bf16 peak, bytes / HBM bandwidth) with both from
the reference model's ``<work>(sizes, batch)``, over the device time of the
operations whose innermost ``layer/<kind>`` scope is one of ``kinds``
(``layer_ms``). The metric's file names ``work`` and ``kinds``. ``batch``
is the per-chip batch of this configuration's cells (their traffic files
must agree). None off the TPU, without the layer scopes, or for a
configuration whose reference model counts no such work."""
import importlib

from perfbench.metrics import layer_ms
from perfbench.metrics.gdn_scan_roofline import _batch


def read(ctx, work, kinds):
    peaks, config = ctx["peaks"], ctx["config"]
    ref = importlib.import_module(
        f"perfbench.refmodels.{config['reference_model']}")
    spent = layer_ms.read(ctx, kinds)
    batch = _batch(config)
    if peaks is None or not spent or batch is None or not hasattr(ref, work):
        return None
    operations, moved = getattr(ref, work)(config["sizes"], batch)
    least_ms = 1e3 * max(operations / peaks["bf16_flops"],
                         moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / spent

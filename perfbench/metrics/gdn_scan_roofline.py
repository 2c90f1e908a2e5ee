"""The chunked delta rule's share of its roofline: the least time the chip
could take for it, max(operations / bf16 peak, bytes / HBM bandwidth) with
both from the reference model's ``gdn_scan_work(sizes, batch)`` (the
chunked form's matrix products at one pass each, forward once and backward
twice; the least a chunked pass must move), over ``gdn_scan_ms``. ``batch``
is the per-chip batch of this configuration's cells (their traffic files
must agree). None off the TPU, without the layer scopes, or for a
configuration whose reference model counts no such work."""
import importlib
import os

from perfbench import harness
from perfbench.metrics import layer_ms


def _batch(config):
    bench = harness._read(os.path.join(harness.ROOT, "BENCHMARK.json"))
    sizes = {harness._read(os.path.join(
        harness.ROOT, "perfbench", "traffic", w["traffic"] + ".json"))["batch_size"]
        for w in bench["workloads"] if w["config"] == config["name"]}
    return sizes.pop() if len(sizes) == 1 else None


def read(ctx):
    peaks, config = ctx["peaks"], ctx["config"]
    ref = importlib.import_module(
        f"perfbench.refmodels.{config['reference_model']}")
    spent = layer_ms.read(ctx, ["gdn_scan"])
    batch = _batch(config)
    if peaks is None or not spent or batch is None \
            or not hasattr(ref, "gdn_scan_work"):
        return None
    operations, moved = ref.gdn_scan_work(config["sizes"], batch)
    least_ms = 1e3 * max(operations / peaks["bf16_flops"],
                         moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least_ms / spent

"""The names the compiled step's operations carry beyond ``gtopk/<stage>``
and ``layer/<kind>``: ``part/<name>`` through the three attention mixers,
and the three stages round the flat gradient. Static, on the CPU: a layer
of each decoder at its ``tiny`` sizes is lowered, forward and backward under
its remat, in both forms of its attention, and every operation's name stack
is read from the lowered module's locations by the benchmark's own rules
(``perfbench/metrics``: ``layer_ms.kind_of``, ``part_ms.part_of`` /
``pass_of``, ``scoped.scope_of``). Nothing here runs or is timed."""

import collections
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from gtopkssgd_tpu.models import (
    decoder, kanana2, keye_vl2, kimi_linear, ouro, qwen3_next, sdar,
    trinity_mini)
from gtopkssgd_tpu.ops import dsa_attention, dsa_index, flash_attention
from perfbench.metrics import layer_ms, part_ms, scoped

ATTENTION = ("attn", "attn_window", "attn_full", "attn_latent")
PARTS = {"proj", "pointwise", "layout", "kernel"}
# What the contract asks of by name: the products, the transposes and the
# custom calls (a Pallas call off the TPU is interpreted: its products).
ROOTS = ("dot_general", "transpose", "custom_call")


def operations(lowered):
    """[(operation, name stack)] of a lowered module, every call of a
    nested ``jit`` followed into its function: the operations in there
    carry the stack from that ``jit`` on, which is laid behind the call's
    (as XLA does when it inlines the call)."""
    text = lowered.as_text(debug_info=True)
    named = {loc: (name, inner) for loc, name, inner in re.findall(
        r'^(#loc\d+) = loc\("([^"]*)"(?:\((#loc\d+)\))?', text, re.M)}

    def name_of(loc):
        # ``"closed_call:"(#loc7)`` wraps the location that has the stack.
        name, inner = named[loc]
        return name_of(inner) if name.endswith(":") and inner in named \
            else name

    names = {loc: name_of(loc) for loc in named}
    functions, body = {}, None
    for line in text.splitlines():
        head = re.match(r"\s*func\.func (?:public |private )?@([\w.\-]+)\(",
                        line)
        if head:
            body = functions.setdefault(head.group(1), [])
            continue
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if body is None or not loc or loc.group(1) not in names:
            continue
        call = re.search(r"= (?:func\.)?call @([\w.\-]+)\(", line)
        op = re.search(r"= \"?stablehlo\.(\w+)", line)
        if call or op:
            body.append((None if call else op.group(1),
                         call and call.group(1), names[loc.group(1)]))

    def walk(function, prefix):
        for op, callee, path in functions[function]:
            path = f"{prefix}/{path}" if prefix else path
            if callee:
                yield from walk(callee, path)
            else:
                yield op, path

    return list(walk("main", ""))


def remat_layer(make, kept):
    return nn.remat(make, policy=jax.checkpoint_policies
                    .save_only_these_names(*kept))


def trinity(sliding):
    sizes = trinity_mini.PRESETS["tiny"]
    layer = remat_layer(trinity_mini.Layer, [trinity_mini.KEPT_ATTENTION])(
        sizes, jnp.float32, sliding, True)
    return layer, sizes, lambda out: jnp.sum(out[0])


def kanana():
    sizes = kanana2.PRESETS["tiny"]
    layer = remat_layer(kanana2.Layer, [kanana2.KEPT_ATTENTION])(
        sizes, jnp.float32, True)
    return layer, sizes, lambda out: jnp.sum(out[0])


def kimi():
    # The hybrid's latent-attention layer: Kanana's mixer, no rotary.
    sizes = kimi_linear.PRESETS["tiny"]
    layer = remat_layer(kimi_linear.Layer, [
        kimi_linear.KEPT_CHUNKS, kanana2.KEPT_ATTENTION])(
            sizes, jnp.float32, "mla")
    return layer, sizes, lambda out: jnp.sum(out[0])


def looped():
    sizes = ouro.PRESETS["tiny"]
    layer = remat_layer(ouro.Layer, [ouro.KEPT_ATTENTION])(sizes, jnp.float32)
    return layer, sizes, lambda out: jnp.sum(out[0])


def block_diffusion():
    # The layer's rows are a sequence's clean tokens and its noised copy.
    sizes = dict(sdar.PRESETS["tiny"], seq_len=2 * 64)
    layer = remat_layer(sdar.Layer, [sdar.KEPT_ATTENTION])(sizes, jnp.float32)
    return layer, sizes, lambda out: jnp.sum(out[0])


def qwen():
    sizes = qwen3_next.PRESETS["tiny"]
    layer = remat_layer(qwen3_next.Layer, [
        qwen3_next.KEPT_CHUNKS, qwen3_next.KEPT_ATTENTION])(
            sizes, jnp.float32, True)
    return layer, sizes, lambda out: jnp.sum(out[0])


def keye():
    sizes = dict(keye_vl2.PRESETS["tiny"], seq_len=48)
    layer = remat_layer(keye_vl2.Layer, [
        keye_vl2.KEPT_SELECTION, keye_vl2.KEPT_ATTENTION, keye_vl2.KEPT_MASKS,
        keye_vl2.KEPT_PROBABILITIES])(sizes, jnp.float32)
    # The indexer's loss too, so that its gradient's path is lowered.
    return layer, sizes, lambda out: jnp.sum(out[0]) + out[1][3]


LAYERS = {"trinity_sliding": (lambda: trinity(True), "attn_window"),
          "trinity_full": (lambda: trinity(False), "attn_full"),
          "qwen": (qwen, "attn"), "keye": (keye, "attn"),
          "kanana": (kanana, "attn_latent"), "kimi": (kimi, "attn_latent"),
          "ouro": (looped, "attn"),
          "sdar": (block_diffusion, "attn")}


def lowered_layer(name):
    (layer, sizes, scalar), kind = LAYERS[name][0](), LAYERS[name][1]
    x = jnp.ones((2, sizes["seq_len"], sizes["hidden_size"]), jnp.float32)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    loss = lambda v, x: scalar(layer.apply(v, x))
    return jax.jit(jax.grad(loss)).lower(variables, x), kind


@pytest.fixture
def kernel_form(monkeypatch):
    """The attention as its kernels (interpret mode), at tiles the ``tiny``
    sizes fill; jax's caches hold the other form's traces."""
    monkeypatch.setattr(flash_attention, "TILE_Q", 16)
    monkeypatch.setattr(flash_attention, "TILE_K", 16)
    for module in (dsa_attention, dsa_index):
        monkeypatch.setattr(module, "TILE_Q", 8)
        monkeypatch.setattr(module, "TILE_K", 8)
    monkeypatch.setattr(decoder, "attention_form", lambda *a: "kernel")
    monkeypatch.setattr(decoder, "diffusion_attention_form",
                        lambda *a: "kernel")
    monkeypatch.setattr(keye_vl2, "attention_form", lambda *a: "kernel")
    jax.clear_caches()
    yield
    jax.clear_caches()


def check_parts(name):
    lowered, kind = lowered_layer(name)
    mine = [(op, path) for op, path in operations(lowered)
            if layer_ms.kind_of(path) in ATTENTION]
    assert {layer_ms.kind_of(path) for _, path in mine} == {kind}
    roots = [(op, path) for op, path in mine if op in ROOTS]
    assert {op for op, _ in roots} >= {"dot_general", "transpose"}
    for op, path in roots:
        found = part_ms.PART.findall(path)
        assert len(found) == 1 and found[0] in PARTS, (op, path)
    # The products are the projections' and the attention's, each in every
    # pass it runs in; layouts and pointwise passes in all three.
    by_part = collections.defaultdict(set)
    for op, path in mine:
        by_part[part_ms.part_of(path)].add(part_ms.pass_of(path))
    every = set(part_ms.PASSES)
    assert by_part["proj"] == by_part["pointwise"] == every
    assert by_part["layout"] >= {"forward", "backward"}
    assert by_part["kernel"] >= {"forward", "backward"}
    assert {part_ms.part_of(path) for op, path in roots
            if op == "dot_general"} == {"proj", "kernel"}
    return mine


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_every_operation_of_an_attention_layer_carries_one_part(name):
    """The form every CPU run compiles: ``blocked`` / ``masked``."""
    mine = check_parts(name)
    # XLA's products stand where the kernels would: no Pallas call here.
    assert not any("pallas_call" in path for _, path in mine)


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_kernel_form_carries_the_same_parts(name, kernel_form):
    mine = check_parts(name)
    calls = {re.search(r"(\w+)/pallas_call", path).group(1)
             for _, path in mine if "pallas_call" in path}
    if name == "keye":
        assert calls == {
            "dsa_attention_forward", "dsa_attention_probabilities",
            "dsa_attention_backward_q", "dsa_attention_backward_kv"}
    else:
        assert calls == {
            "flash_attention_forward", "flash_attention_backward_q",
            "flash_attention_backward_kv"}
    for _, path in mine:
        if "pallas_call" in path:
            assert part_ms.part_of(path) == "kernel", path
            # The layer's replay runs no kernel: its outputs are kept.
            assert part_ms.pass_of(path) != "replay", path
    # In the form the chip runs nothing under the kind is left without a
    # part, in Keye neither since its indexer runs as kernels: the one loop
    # left (``kth_largest``'s query blocks over a bucket's scores) stands
    # whole under the selection's kind, its slicing included, and the
    # backward rule has none. ``attn_parts_share``'s gap was the three
    # loops' own slicing and counting.
    bare = [(op, path) for op, path in mine
            if not part_ms.part_of(path) and op != "constant"]
    assert not bare, bare
    if name == "keye":
        # (A kernel in interpret mode lowers as a loop of its own: not
        # the program's.)
        loops = [path for _, path in operations(lowered_layer(name)[0])
                 if "/while/" in path
                 and layer_ms.kind_of(path) in ("dsa_index", "dsa_select")
                 and not re.search(r"/dsa_(index|attention)_\w+/", path)]
        assert loops and {layer_ms.kind_of(path) for path in loops} == {
            "dsa_select"}
        assert not any(part_ms.part_of(path) for path in loops)


def test_the_other_kinds_and_the_stage_are_read_as_before():
    """A ``part/`` in a path moves neither the kind nor the stage."""
    lowered, _ = lowered_layer("qwen")
    kinds = collections.Counter(
        layer_ms.kind_of(path) for _, path in operations(lowered))
    assert {"attn", "moe_router", "shared_expert"} <= set(kinds)
    for _, path in operations(lowered):
        stripped = re.sub(r"part/[a-z_]+/", "", path)
        assert layer_ms.kind_of(path) == layer_ms.kind_of(stripped)
        assert scoped.scope_of(path) == scoped.scope_of(stripped) == ""


# ---------------------------------------------- round the flat gradient
def stages(lowered):
    """{outermost gtopk/ stage: Counter of its operations}."""
    found = collections.defaultdict(collections.Counter)
    for op, path in operations(lowered):
        found[scoped.scope_of(path)][op] += 1
    return found


MOVES = ("concatenate", "slice", "reshape", "dynamic_update_slice",
         "dynamic_slice", "pad")


def largest_moves(lowered):
    """{operation: the most elements any of its operands or results has}
    over the lowered module's data-movement operations."""
    largest = collections.Counter()
    for line in lowered.as_text().splitlines():
        op = re.search(r"stablehlo\.(\w+)", line)
        if not op or op.group(1) not in MOVES:
            continue
        for dims in re.findall(r"tensor<((?:\d+x)*)\w+>", line):
            size = 1
            for d in dims.split("x")[:-1]:
                size *= int(d)
            largest[op.group(1)] = max(largest[op.group(1)], size)
    return largest


def one_device_step(tmp_path, **flags):
    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(
            batch_size=2, nworkers=1, compression="gtopk", density=0.01,
            prefetch=0, out_dir=str(tmp_path), **flags)) as t:
        batch = t._device_batch(t._shard_batches(t._iters)[0])
        return (t._train_step.lower(t.state, t.carry, batch),
                [leaf.shape for leaf in jax.tree.leaves(t.state.params)],
                t._manifest)


def test_the_one_device_step_works_on_leaves_under_the_same_stages(
        tmp_path, monkeypatch):
    """``gtopk_train_step`` at a sparse decoder cell's flags (``tiny``,
    with the in-place rule brought down to its sizes): no [N] vector.
    ``gtopk/flatten`` holds the one concatenate of the grouped small
    leaves, ``gtopk/clip`` a reduction a slab and one over those,
    ``gtopk/unflatten`` the
    grouped leaves' slices, and the large leaves pass through all three
    untouched; accumulate, select, mask and the counters keep their names."""
    from gtopkssgd_tpu import compression

    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 2048)
    monkeypatch.setattr(compression, "IN_PLACE_MIN_LAST", 16)
    lowered, shapes, manifest = one_device_step(
        tmp_path, dnn="trinity_mini", model_preset="tiny",
        clip_grad_norm=1.0)
    plan = compression.plan_leaves(shapes)
    assert len(plan.in_place) > 10 and len(plan.grouped) > 10
    found = stages(lowered)
    slabs = len(plan.slab_shapes)
    assert found["gtopk/flatten"]["concatenate"] == 1
    assert found["gtopk/flatten"]["reshape"] == sum(
        len(plan.shapes[i]) != 1 for i in plan.grouped)
    assert found["gtopk/clip"]["reduce"] == slabs + 1   # and their sum
    assert found["gtopk/clip"]["multiply"] == 2 * slabs   # f * f, f * scale
    assert found["gtopk/unflatten"]["slice"] == len(plan.grouped)
    assert found["gtopk/accumulate"]["add"] == slabs
    assert found["gtopk/mask"]["select"] >= 2 * slabs  # residual, update
    assert {"gtopk/fwd_bwd", "gtopk/select", "gtopk/apply",
            "gtopk/telemetry"} <= set(found)
    # What is left under no stage is the step's bookkeeping on scalars (the
    # batch's leading axis, the loss's and the counters' means, the step's
    # count): no product and no pass over N.
    assert set(found[""]) <= {"constant", "slice", "reshape", "subtract",
                              "add", "reduce", "divide"}, found[""]
    assert manifest["leaves_in_place"] == len(plan.in_place)
    assert manifest["leaves_grouped"] == len(plan.grouped)
    assert manifest["elems_in_place_share"] == pytest.approx(
        sum(plan.sizes[i] for i in plan.in_place) / plan.n)


@pytest.mark.parametrize("flags", [
    dict(dnn="qwen3_next", model_preset="tiny"),
    dict(dnn="keye_vl2", model_preset="tiny"),
    dict(dnn="trinity_mini", model_preset="tiny"),
    dict(dnn="kanana2", model_preset="tiny"),
    dict(dnn="ouro", model_preset="tiny"),
    dict(dnn="sdar", model_preset="tiny"),
    dict(dnn="kimi_linear", model_preset="tiny"),
    dict(dnn="resnet20", dataset="cifar10"),
], ids=lambda f: f["dnn"])
def test_the_one_device_step_moves_no_whole_vector(tmp_path, monkeypatch,
                                                   flags):
    """The P = 1 ``gtopk`` step lowered: no concatenate, slice, reshape,
    pad or dynamic (update) slice with an operand or result of N elements,
    nor of half of them (the threshold's candidates and the grouped small
    leaves are what such operations may touch). The manifest says how far
    the form engaged: in place + grouped = the tree's leaves."""
    from gtopkssgd_tpu import compression

    monkeypatch.setattr(compression, "IN_PLACE_MIN_ELEMS", 1024)
    monkeypatch.setattr(compression, "IN_PLACE_MIN_LAST", 8)
    lowered, shapes, manifest = one_device_step(tmp_path, **flags)
    plan = compression.plan_leaves(shapes)
    assert manifest["leaves_in_place"] + manifest["leaves_grouped"] == len(
        shapes)
    assert manifest["leaves_in_place"] == len(plan.in_place) > 0
    assert manifest["elems_in_place_share"] > 0.8
    grouped = sum(plan.sizes[i] for i in plan.grouped)
    largest = largest_moves(lowered)
    assert largest["concatenate"] >= grouped       # the reader reads
    for op, size in largest.items():
        assert size < plan.n // 2, (op, size, plan.n)


def test_the_layerwise_form_names_the_same_three_stages():
    """``gtopk_layerwise`` has no [N] vector: the leaves are flattened one
    by one, the norm is a sum of their sums, and each is shaped back."""
    from gtopkssgd_tpu.optimizer import gtopk_sgd

    params = {"a": jnp.ones((8, 16)), "b": jnp.ones((32,)),
              "c": jnp.ones((4, 4, 4))}
    tx = gtopk_sgd(0.1, compression="gtopk_layerwise", density=0.1,
                   clip_grad_norm=1.0, axis_name=None)
    state = tx.init(params)
    found = stages(jax.jit(tx.update).lower(params, state, params))
    assert found["gtopk/flatten"]["reshape"] == 2    # "b" is flat already
    assert found["gtopk/clip"]["reduce"] == 3
    assert found["gtopk/clip"]["multiply"] >= 6
    assert found["gtopk/unflatten"]["reshape"] == 2
    assert "gtopk/apply" in found

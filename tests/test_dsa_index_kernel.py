"""The sparse-attention decoder's index kernels (ops/dsa_index.py) in
interpret mode on the CPU, against XLA's form they replace on the TPU
(models/keye_vl2.py::index_scores and its ``jax.vjp``, the oracle): the
scores, d_qI, d_kI and d_w of a span of rows; ``kernel_attention`` with them
against ``select_thresholds`` + ``sparse_attention``; what a query then
keeps; and the rule and the records that say which form compiled. (That the
kernels compile for the chip is tests/test_pallas_compile.py's.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import keye_vl2 as prog
from gtopkssgd_tpu.ops import dsa_attention as attention_kernels
from gtopkssgd_tpu.ops import dsa_index as kernels

F32 = jnp.float32
LENGTH, HEADS, DIM = 256, 4, 16
# float32: sums in another order. bfloat16: the oracle rounds d_qI and d_kI
# to ``dtype`` (2^-9 an element) and, on the CPU, multiplies d_dots in
# float32 where the TPU and the kernels round it to ``dtype`` first.
CLOSE = {jnp.float32: 1e-5, jnp.bfloat16: 4e-3}


def rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def index_inputs(scenario, seed=0):
    """qi [1, S, J, D], ki [1, S, D], w [1, S, J]. ``negative``: every
    weight below 0; ``ties``: whole numbers and whole 4096ths, so that the
    scores are exact in either precision and tie; ``dead``: one head whose
    products are all below 0 (its ReLU passes nothing, d_w's column is 0)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(keys[0], (1, LENGTH, HEADS, DIM))
    ki = jax.random.normal(keys[1], (1, LENGTH, DIM))
    w = jax.random.normal(keys[2], (1, LENGTH, HEADS)) / 8.0
    if scenario == "negative":
        w = -jnp.abs(w)
    elif scenario == "ties":
        qi, ki = jnp.round(2.0 * qi), jnp.round(2.0 * ki)
        w = jnp.round(w * 4096.0) / 4096.0
    elif scenario == "dead":
        ki = jnp.abs(ki)
        qi = qi.at[:, :, 1].set(-jnp.abs(qi[:, :, 1]))
    return qi, ki, w


def visited(span, tile_q, tile_k):
    """[rows, keys] bool: the pairs in a key tile that a query tile of the
    span visits (the others are written as zeros)."""
    start, count = span
    last = (np.arange(start, start + count) // tile_q * tile_q + tile_q - 1
            ) // tile_k
    return np.arange(start + count)[None, :] // tile_k <= last[:, None]


# A span of 128 rows in query tiles of 64: the first tile's last key tile is
# on the diagonal, and the key tiles after it are not visited (at tile_k 32:
# two of them; at 128 the one key tile holds both query tiles' diagonals).
SPANS = {"whole": ((0, LENGTH), 64, 32), "late": ((128, 128), 64, 32),
         "one_tile": ((192, 64), 64, 64), "wide_keys": ((0, 128), 64, 128),
         "tall_rows": ((0, LENGTH), 128, 32)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("scenario", ["plain", "negative", "ties", "dead"])
@pytest.mark.parametrize("span", sorted(SPANS))
def test_kernels_equal_index_scores_and_its_vjp(span, scenario, dtype):
    (start, count), tile_q, tile_k = SPANS[span]
    keys = start + count
    qi, ki, w = index_inputs(scenario)
    rows = slice(start, keys)
    want, back = jax.vjp(lambda a, b, c: prog.index_scores(
        a[:, rows], b[:, :keys], c[:, rows], dtype), qi, ki, w)
    seen = visited((start, count), tile_q, tile_k)
    # A cotangent as the loss's is: 0 for the keys after a row.
    d_scores = jax.random.normal(jax.random.PRNGKey(7), want.shape) * (
        jnp.arange(start, keys)[:, None] >= jnp.arange(keys)[None, :])
    d_qi, d_ki, d_w = back(d_scores)

    qi_l, ki_l = prog._index_layout(qi, ki, dtype)
    tiles = dict(span=(start, count), tile_q=tile_q, tile_k=tile_k,
                 interpret=True)
    got = np.asarray(kernels.scores(qi_l, ki_l, w, **tiles))
    assert got.shape == (1, count, keys) and not got[0][~seen].any()
    assert seen.all() == (span in ("one_tile", "wide_keys"))
    if scenario == "ties":          # exact sums: no order to differ in
        assert np.array_equal(got[0][seen], np.asarray(want)[0][seen])
    assert rel(got * seen, np.asarray(want) * seen) < 1e-6
    mine_q, mine_w = kernels.backward_q(qi_l, ki_l, w, d_scores, dtype=dtype,
                                        **tiles)
    mine_k = kernels.backward_k(qi_l, ki_l, jnp.swapaxes(w, 1, 2), d_scores,
                                dtype=dtype, **tiles)
    for name, mine, theirs in (
            ("d_qi", jnp.moveaxis(mine_q, 1, 2), d_qi[:, rows]),
            ("d_w", mine_w, d_w[:, rows]), ("d_ki", mine_k, d_ki[:, :keys])):
        assert mine.shape == theirs.shape and mine.dtype == F32
        assert rel(mine, theirs) < CLOSE[dtype], (name, rel(mine, theirs))
    if scenario == "dead":
        assert not np.asarray(mine_w)[..., 1].any()
        assert not np.asarray(mine_q)[:, 1].any()


@pytest.mark.parametrize("span,tile_q,tile_k", [
    ((96, 64), 64, 32), ((0, 96), 64, 32), ((64, 64), 64, 96),
    ((192, 128), 64, 32)])
def test_a_span_of_no_whole_tiles_is_refused(span, tile_q, tile_k):
    qi_l, ki_l = prog._index_layout(*index_inputs("plain")[:2], jnp.float32)
    with pytest.raises(ValueError, match="not whole tiles"):
        kernels.scores(qi_l, ki_l, index_inputs("plain")[2], span=span,
                       tile_q=tile_q, tile_k=tile_k, interpret=True)


# ------------------------------------------------- the attention as a whole
def attention_inputs(length, exact, heads=4, groups=2, dim=128):
    """q, k, v, qi, ki, w and the two cotangents; ``exact``: index scores of
    whole 4096ths (they tie); else of continuous numbers, the first head's
    products all above 0 (so that no score is the exact 0 of sixteen dead
    ReLUs, and none ties)."""
    keys = jax.random.split(jax.random.PRNGKey(11), 8)
    qi = jax.random.normal(keys[3], (1, length, HEADS, DIM))
    ki = jax.random.normal(keys[4], (1, length, DIM))
    w = jax.random.normal(keys[5], (1, length, HEADS)) / 8.0
    if exact:
        qi, ki = jnp.round(2.0 * qi), jnp.round(2.0 * ki)
        w = jnp.round(w * 4096.0) / 4096.0
    else:
        ki, qi = jnp.abs(ki), qi.at[:, :, 0].set(jnp.abs(qi[:, :, 0]))
    return (jax.random.normal(keys[0], (1, length, heads, dim)),
            jax.random.normal(keys[1], (1, length, groups, dim)),
            jax.random.normal(keys[2], (1, length, groups, dim)), qi, ki, w,
            jax.random.normal(keys[6], (1, length, heads, dim)),
            jax.random.normal(keys[7], (1, length)))


@pytest.fixture
def small_tiles(monkeypatch):
    for module in (attention_kernels, kernels):
        monkeypatch.setattr(module, "TILE_Q", 64)
        monkeypatch.setattr(module, "TILE_K", 64)
    monkeypatch.setattr(prog, "BUCKET", 2)


GRADIENTS = "q k v qi ki w".split()


@pytest.fixture(scope="module")
def both_forms():
    """{(dtype, exact): ((o, kl, count), grads) of the kernel form and of
    the masked form} at 256 tokens, blocks of 64 in buckets of two, top 40
    keys: computed once for the cases below."""
    made = {}

    def run(dtype, exact):
        if (dtype, exact) not in made:
            length, block, topk = 256, 64, 40
            *inputs, d_o, d_kl = attention_inputs(length, exact)
            tau = prog.select_thresholds(*inputs[3:], topk, dtype, block)

            def both(form, selection):
                def loss(*inputs):
                    o, kl, count = form(*inputs, selection, dtype, block)
                    return jnp.sum(o * d_o) + jnp.sum(kl * d_kl), (o, kl,
                                                                  count)
                (_, out), grads = jax.jit(jax.value_and_grad(
                    loss, argnums=tuple(range(6)), has_aux=True))(*inputs)
                return out, grads
            made[dtype, exact] = (both(prog.kernel_attention, topk),
                                  both(prog.sparse_attention, tau))
        return made[dtype, exact]
    return run


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("exact", [True, False])
def test_kernel_attention_takes_the_masked_forms_values(
        exact, dtype, small_tiles, both_forms):
    (o, kl, count), _ = both_forms(dtype, exact)[0]
    (o_m, kl_m, count_m), _ = both_forms(dtype, exact)[1]
    if exact:
        # The same scores bit for bit in both forms: the same key sets.
        assert np.array_equal(np.asarray(count), np.asarray(count_m))
        assert rel(o, o_m) < 1e-6 and rel(kl, kl_m) < 1e-5
    else:
        # Another order of one sum may move a score across its threshold
        # by an ulp: the masked form then keeps one key more or fewer where
        # the kernel form, whose mask reads the scores its thresholds
        # counted, keeps exactly what is due.
        assert int(np.abs(np.asarray(count) - np.asarray(count_m)).sum()) <= 2
        assert rel(o, o_m) < 1e-2 and rel(kl, kl_m) < 1e-2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", GRADIENTS)
def test_kernel_attention_takes_the_masked_forms_gradient(
        name, dtype, small_tiles, both_forms):
    """With exact index scores (the key sets are the masked form's for
    certain) every one of the six gradients, each a case."""
    (_, grads), (_, grads_m) = both_forms(dtype, True)
    mine, theirs = (g[GRADIENTS.index(name)] for g in (grads, grads_m))
    assert np.isfinite(np.asarray(mine)).all()
    assert rel(mine, theirs) < CLOSE[dtype], (name, rel(mine, theirs))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_every_query_keeps_what_is_due_when_no_scores_tie(
        dtype, small_tiles, both_forms):
    """min(t + 1, topk) keys for query t: the thresholds and the mask read
    one array, so no rounding can put a key on both sides."""
    (_, _, count), _ = both_forms(dtype, False)[0]
    due = np.minimum(np.arange(256) + 1, 40)
    assert np.array_equal(np.asarray(count)[0], due)
    assert int(count.sum()) == prog.keys_due(256, 40)
    # And with ties at a threshold, every key tied there is kept.
    (_, _, tied), _ = both_forms(dtype, True)[0]
    assert (np.asarray(tied)[0] >= due).all() \
        and int(tied.sum()) > prog.keys_due(256, 40)


# ----------------------------------------------------------- which form runs
PUBLISHED = prog.PRESETS["30b_a3b_ep16"]
TINY = prog.PRESETS["tiny"]


@pytest.mark.parametrize("preset,length,tpu,form", [
    ("30b_a3b_ep16", 16384, True, "kernel"),
    ("30b_a3b_ep16", 16000, True, "kernel"),        # padded to 16,384
    ("30b_a3b_ep16", 300, True, "xla"),             # one short block
    ("30b_a3b_ep16", 16384, False, "xla"),          # any CPU run
    ("tiny", 48, True, "xla"), ("tiny", 48, False, "xla")])
def test_forms_name_the_index_form_beside_the_attentions(
        preset, length, tpu, form, monkeypatch):
    monkeypatch.setattr(prog, "on_tpu", lambda: tpu)
    forms = prog.KeyeVL2(preset).forms(length)
    assert forms == {
        "dsa_index_form": form,
        "dsa_attention_form": "kernel" if form == "kernel" else "masked"}


@pytest.mark.parametrize("sizes,form", [
    (dict(indexer_head_dim=64), "kernel"), (dict(indexer_head_dim=128),
                                            "kernel"),
    (dict(indexer_head_dim=32), "masked"), (dict(indexer_head_dim=96),
                                            "masked"),
    (dict(q_chunk_size=256), "masked")])
def test_the_rule_asks_what_the_index_kernels_need(sizes, form, monkeypatch):
    """An indexer head of whole half rows (64 lanes), blocks and buckets of
    the index kernels' whole tiles: one rule for both sets of kernels."""
    monkeypatch.setattr(prog, "on_tpu", lambda: True)
    s = dict(PUBLISHED, **sizes)
    assert prog.attention_form(16384, s["head_dim"], s["q_chunk_size"],
                               s["indexer_head_dim"]) == form
    monkeypatch.setattr(kernels, "TILE_K", 4096)    # a bucket holds 2,048
    assert prog.attention_form(16384, 128, 512, 64) == "masked"


def test_the_train_record_names_the_index_form(tmp_path):
    import json

    from gtopkssgd_tpu.trainer import TrainConfig, Trainer

    with Trainer(TrainConfig(dnn="keye_vl2", model_preset="tiny",
                             batch_size=2, compression="gtopk", density=0.01,
                             log_interval=1, out_dir=str(tmp_path))) as t:
        assert t._model_forms == {"dsa_attention_form": "masked",
                                  "dsa_index_form": "xla"}
        t.train(1)
    rows = [json.loads(line) for line in open(tmp_path / "metrics.jsonl")]
    named = [r for r in rows if r["kind"] in ("manifest", "train")]
    assert len(named) == 2 and all(r["dsa_index_form"] == "xla"
                                   for r in named)
    assert not any("dsa_index_form" in r for r in rows
                   if r["kind"] not in ("manifest", "train"))

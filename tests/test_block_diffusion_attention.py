"""The block-diffusion visibility rule in the shared attention
(models/decoder.py::block_diffusion_attention, ops/flash_attention.py told
``block_length``) on the CPU: the set of (query, key) pairs each form lets
through, read back by one-hot values, against the comparison of block
indices; the published schedule's 288 tiles and 67,141,632 pairs counted
from the kernels' own arithmetic; the kernels' values and gradients
against the blocked form (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gtopkssgd_tpu.models import decoder
from gtopkssgd_tpu.models import sdar as prog
from gtopkssgd_tpu.ops import flash_attention as flash
from perfbench.refmodels import sdar as ref

F32 = jnp.float32
PUBLISHED = prog.PRESETS["30b_a3b_ep8"]


def rule(length, block):
    """The block-index comparison, [2L, 2L] bool."""
    rows = np.arange(2 * length)
    noised, b = rows >= length, rows % length // block
    return np.where(noised[None, :], noised[:, None] & (b[None, :] == b[:, None]),
                    np.where(noised[:, None], b[None, :] < b[:, None],
                             b[None, :] <= b[:, None]))


def pairs_let_through(tiles, length, block):
    """Which (query, key) pairs a form lets through: with equal scores a
    row's weights are uniform over what it sees, and one-hot values (a
    value width of 2L) read them back. ``tiles`` None: the blocked form at
    query blocks of 5; else the forward kernel's (tile_q, tile_k)."""
    rows = 2 * length
    q = k = jnp.zeros((1, rows, 1, 16), F32)
    v = jnp.eye(rows, dtype=F32)[None, :, None, :]
    if tiles is None:
        out = jax.jit(lambda *a: decoder.block_diffusion_attention(
            *a, F32, 5, block))(q, k, v)
    else:
        out, _ = flash.forward(*decoder.kernel_layout(q, k, v, F32),
                               block_length=block, tile_q=tiles[0],
                               tile_k=tiles[1], interpret=True)
        out = out.reshape(1, rows, 1, rows)
    return np.asarray(out[0, :, 0]) > 0


@pytest.mark.parametrize("tiles,length", [
    (None, 64), (None, 44), ((16, 16), 64), ((44, 44), 44), ((32, 16), 64),
    ((16, 32), 64)], ids=str)
def test_the_pairs_a_form_lets_through_are_the_rule(tiles, length):
    seen = pairs_let_through(tiles, length, 4)
    assert seen.shape == (2 * length, 2 * length)
    assert np.array_equal(seen, rule(length, 4))
    blocks = length // 4
    assert seen.sum() == 16 * (blocks * (blocks + 1) // 2
                               + blocks * (blocks - 1) // 2 + blocks)
    # A clean row sees no noised key; a noised row sees itself, its block
    # both ways and no other noised key.
    assert not seen[:length, length:].any()
    assert (seen[length:, length:].sum(1) == 4).all()


def test_the_published_schedule_visits_288_tiles_and_67_141_632_pairs():
    """The forward kernel's grid at 2 x 8,192 rows and tiles of 512: 136
    clean -> clean, 136 noised -> clean and 16 noised -> noised tiles of
    the 1,024 a head group, and in them the rule's live pairs, counted
    from the schedule and the kernels' own mask arithmetic."""
    length, block = PUBLISHED["seq_len"], PUBLISHED["block_length"]
    visits = flash.visited_tiles(2 * length, 512, 512, block)
    assert [len(v) for v in visits[:16]] == list(range(1, 17))
    assert [len(v) for v in visits[16:]] == list(range(2, 18))
    assert sum(map(len, visits)) == 288
    assert visits[16 + 5] == [0, 1, 2, 3, 4, 5, 16 + 5]
    sched = flash.BlockDiffusion(block, 2 * length, 512, 512)
    assert (sched.key_steps(), sched.query_steps()) == (17, 32)
    live, inside = 0, 0
    rows, keys = np.arange(512)[:, None], np.arange(512)[None, :]
    for i, tiles in enumerate(visits):
        for j in tiles:
            if sched.inside(i, j):
                inside += 1
                live += 512 * 512
            else:
                live += int(sched.seen(i * 512 + rows, j * 512 + keys, i,
                                       j).sum())
    assert inside == 2 * 120 and live == 67_141_632 == sum(
        ref.live_pairs(PUBLISHED))
    assert ref.live_pairs(PUBLISHED) == (33_570_816, 33_538_048, 32_768)
    # backward_kv sweeps the same tiles from the keys' side.
    back = {(sched.query_tile(j, at), j) for j in range(32)
            for at in range(sched.query_steps())
            if sched.visits_query(j, at, None)}
    assert back == {(i, j) for i, tiles in enumerate(visits) for j in tiles}
    with pytest.raises(ValueError, match="power of two"):
        flash.BlockDiffusion(3, 2 * length, 512, 512)


def test_kernels_at_unequal_tiles_equal_the_blocked_form():
    """Values and the three gradients at query tiles of 32 beside key tiles
    of 16 against the blocked form in float32 (the other way round: the
    pairs above, and the two sweeps' agreement below)."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 128, 4, 16))
    k, v, ct = (jax.random.normal(jax.random.fold_in(key, n), shape)
                for n, shape in enumerate(((1, 128, 2, 16), (1, 128, 2, 16),
                                           (1, 128, 4, 16))))
    want = jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
        decoder.block_diffusion_attention(*a, F32, 8, 4) * ct),
        (0, 1, 2)))(q, k, v)
    for tq, tk in ((32, 16),):
        def kernels(q, k, v):
            q_l, k_l, v_l = decoder.kernel_layout(q, k, v, F32)
            run = dict(block_length=4, tile_q=tq, tile_k=tk, interpret=True)
            out, lse = flash.forward(q_l, k_l, v_l, **run)
            d_out = decoder.kernel_layout(ct, k, v, F32)[0]
            delta = jnp.sum(d_out * out, -1)
            d_q = flash.backward_q(q_l, k_l, v_l, lse, delta, d_out, **run)
            d_k, d_v = flash.backward_kv(q_l, k_l, v_l, lse, delta, d_out,
                                         **run)
            return jnp.sum(out * d_out), (
                jnp.moveaxis(d_q, 3, 1).reshape(q.shape),
                d_k.transpose(0, 2, 1, 3), d_v.transpose(0, 2, 1, 3))

        got = jax.jit(kernels)(q, k, v)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert float(jnp.max(jnp.abs(a - b))) \
                < 1e-5 * float(jnp.max(jnp.abs(b))), (tq, tk)
    # Both sweeps visit the same tiles whatever the two tile sizes.
    for tq, tk in ((32, 16), (16, 32), (8, 64), (64, 8)):
        sched = flash.BlockDiffusion(4, 128, tq, tk)
        forward = {(i, j) for i, tiles in enumerate(
            flash.visited_tiles(128, tq, tk, 4)) for j in tiles}
        backward = {(sched.query_tile(j, at), j) for j in range(128 // tk)
                    for at in range(sched.query_steps())
                    if sched.visits_query(j, at, None)}
        live = {(i // tq, j // tk) for i, j in zip(*np.nonzero(rule(64, 4)))}
        assert forward == backward == live, (tq, tk)



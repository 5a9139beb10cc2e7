"""Keye-VL-2.0's language model against its plain reference
(``benchmarks/families/keye_lm.py``) at a small size on the CPU, and the
properties its pieces promise: the selection is exact with its tie rule,
the experts drop nothing and their shares add up, the two losses stay on
their own sides of the indexer.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu.nn import functional_call as F                # noqa: E402
from paddle_tpu.models import (KeyeLMForCausalLM,             # noqa: E402
                               KeyeLMPretrainingCriterion, keye_lm_tiny)
from paddle_tpu.models import keye_lm                         # noqa: E402
from paddle_tpu.ops import sparse_attention as dsa            # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import grouped  # noqa: E402
from benchmarks.families import keye_lm as family             # noqa: E402

VOCAB, SEQ, BATCH = 64, 64, 2


def family_config(c):
    """The tiny program config under the published keys."""
    return {
        "hidden_size": c.hidden_size, "head_dim": c.head_dim,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads,
        "num_hidden_layers": c.num_hidden_layers,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_local_experts": c.num_experts,
        "num_experts": c.experts_held[1], "experts_held": c.experts_held,
        "num_experts_per_tok": c.num_experts_per_tok,
        "vocab_size": c.vocab_rows_held, "rms_norm_eps": c.rms_norm_eps,
        "rope_theta": c.rope_theta,
        "rope_scaling": {"mrope_section": list(c.mrope_section)},
        "sa_config": {"indexer_num_heads": c.indexer_num_heads,
                      "indexer_head_dim": c.indexer_head_dim,
                      "topk": c.topk, "q_chunk_size": c.q_chunk_size},
    }


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(11)
    config = keye_lm_tiny(experts_held=(2, 4), vocab_rows_held=VOCAB)
    net = KeyeLMForCausalLM(config)
    # seeded weights away from their symmetric start: norms and the
    # indexer's LayerNorm are no longer the identity
    rng = np.random.default_rng(5)
    for name, p in net.named_parameters():
        if "norm" in name:
            p._value = p._value + jnp.asarray(
                0.1 * rng.standard_normal(p.shape), p._value.dtype)
    ids = rng.integers(0, VOCAB, (BATCH, SEQ), dtype=np.int64)
    return net, config, ids, np.roll(ids, -1, axis=1)


def program_losses(net, params, ids, labels):
    out, _ = F.functional_call(net, params, F.buffer_dict(net),
                               (paddle.to_tensor(ids),))
    logits, indexer_loss = (o._value for o in out)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    lm = -jnp.take_along_axis(logp, jnp.asarray(labels)[..., None], -1).mean()
    return lm, indexer_loss, logits


def test_logits_losses_and_every_gradient_agree_with_the_reference(tiny):
    net, config, ids, labels = tiny
    params = F.param_dict(net)
    cfg = family_config(config)

    def both(fn):
        return jax.value_and_grad(lambda p: sum(fn(p)[:2]), has_aux=False)

    lm, idx, logits = program_losses(net, params, ids, labels)
    want_lm, want_idx = family.reference_losses(params, cfg, jnp.asarray(ids),
                                                jnp.asarray(labels))
    assert float(lm) == pytest.approx(float(want_lm), rel=1e-5)
    assert float(idx) == pytest.approx(float(want_idx), rel=1e-4)
    assert float(idx) > 1e-3, "the indexer has something to learn"

    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    for b in range(BATCH):
        ref = family.reference_forward(param, cfg, jnp.asarray(ids[b]))
        want = family.reference_logits(ref["hidden"], params[family.HEAD])
        np.testing.assert_allclose(logits[b], want, rtol=2e-4, atol=2e-5)

    _, got = both(lambda p: program_losses(net, p, ids, labels))(params)
    _, want = both(lambda p: family.reference_losses(
        p, cfg, jnp.asarray(ids), jnp.asarray(labels)))(params)
    assert set(got) == set(want)
    for name in sorted(got):
        scale = float(jnp.abs(want[name]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got[name], want[name], rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


def test_each_loss_stays_on_its_side_of_the_indexer(tiny):
    net, _, ids, labels = tiny
    params = F.param_dict(net)
    d_lm = jax.grad(lambda p: program_losses(net, p, ids, labels)[0])(params)
    d_idx = jax.grad(lambda p: program_losses(net, p, ids, labels)[1])(params)
    indexer = [n for n in params if "indexer." in n]
    assert len(indexer) == 5 * 2          # five tensors a layer
    for name in params:
        mine, other = (d_idx, d_lm) if name in indexer else (d_lm, d_idx)
        assert float(jnp.abs(other[name]).max()) == 0.0, name
        # the last layer's experts see no later attention: L_LM reaches all
        assert float(jnp.abs(mine[name]).max()) > 0.0, name


def test_criterion_adds_the_two_losses(tiny):
    net, _, ids, labels = tiny
    logits, indexer_loss = net(paddle.to_tensor(ids))
    total = KeyeLMPretrainingCriterion()(logits, indexer_loss,
                                         paddle.to_tensor(labels))
    lm, idx, _ = program_losses(net, F.param_dict(net), ids, labels)
    assert float(total) == pytest.approx(float(lm) + float(idx), rel=1e-5)
    assert float(net.indexer_loss._value) == pytest.approx(float(idx),
                                                           rel=1e-6)
    # what the step counted, as observe_step publishes it
    tokens = np.asarray(net.expert_tokens._value)
    assert tokens.shape == (2, 4) and tokens.sum() > 0
    assert float(net.selected_keys._value) == BATCH * 2 * sum(
        min(t + 1, 16) for t in range(SEQ))
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    before = reg.counter("moe_pairs_total", labels={"layer": "1"}).collect()
    net.observe_step()
    after = reg.counter("moe_pairs_total", labels={"layer": "1"}).collect()
    assert after - before == tokens[1].sum()
    assert reg.gauge("moe_expert_tokens_max",
                     labels={"layer": "1"}).collect() == tokens[1].max()


# --------------------------------------------------------------------------
# the selection
# --------------------------------------------------------------------------
def brute_force_selection(scores, topk):
    seq = scores.shape[0]
    keep = np.zeros((seq, seq), np.int8)
    for t in range(seq):
        order = sorted(range(t + 1), key=lambda s: (scores[t, s], s),
                       reverse=True)
        keep[t, order[:min(t + 1, topk)]] = 1
    return keep


@pytest.mark.parametrize("quantised,topk,chunk", [
    (False, 24, 32), (True, 24, 32), (True, 40, 32), (False, 200, 32)])
def test_selection_is_exact_and_a_tie_goes_to_the_later_position(
        quantised, topk, chunk):
    rng = np.random.default_rng(3)
    seq, heads, width = 128, 4, 16
    q, k, w = (rng.standard_normal(s).astype(np.float32) for s in (
        (seq, heads, width), (seq, width), (seq, heads)))
    if quantised:       # small integers: most rows tie at the border
        q, k, w = np.round(q), np.round(k), np.round(w)
    mask, scores = jax.jit(lambda *a: dsa.select(
        *a, topk, chunk, with_scores=True))(q, k, w)
    scores = np.asarray(scores)
    if quantised:
        assert len(np.unique(scores)) < seq * seq // 8
    np.testing.assert_array_equal(np.asarray(mask),
                                  brute_force_selection(scores, topk))
    assert int(np.asarray(mask).sum()) == keye_lm.selected_keys(seq, topk)
    # the reference's own rule is the same rule
    ref = family.top_k_mask(jnp.asarray(scores), 0, topk)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(mask) != 0)


def test_topk_at_least_the_sequence_is_dense_causal_attention():
    rng = np.random.default_rng(4)
    seq, heads, kv, d = 128, 4, 2, 128
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (seq, heads, d), (seq, kv, d), (seq, kv, d)))
    qi, ki, wi = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (seq, 2, 8), (seq, 8), (seq, 2)))
    mask = dsa.select(qi, ki, wi, seq, 32)
    np.testing.assert_array_equal(np.asarray(mask), np.tril(np.ones(
        (seq, seq), np.int8)))
    out, _ = dsa.core(q, k, v, mask)
    from paddle_tpu.nn.functional import flash_attention
    want, _ = flash_attention(*(paddle.to_tensor(np.asarray(a)[None])
                                for a in (q, k, v)), causal=True)
    np.testing.assert_allclose(out, want._value[0], rtol=1e-4, atol=1e-5)


def _selection(kind, seq, block, rng):
    """An int8 ``[seq, seq]`` selection of the named kind."""
    rows, cols = np.arange(seq)[:, None], np.arange(seq)[None, :]
    causal = cols <= rows
    if kind in ("topk", "causal"):      # what the program makes: the top
        qi, ki, wi = (                  # 48 of a row, or topk >= S
            jnp.asarray(rng.standard_normal(s), jnp.float32)
            for s in ((seq, 2, 8), (seq, 8), (seq, 2)))
        mask = dsa.select(qi, ki, wi, 48 if kind == "topk" else seq, 64)
        assert (np.asarray(mask) == causal).all() == (kind == "causal")
        return mask
    if kind == "rows_without_a_tile":
        # odd rows keep their own block's keys only: in every tile below
        # the diagonal half the rows keep nothing, and no tile is empty
        keep = causal & ((rows % 2 == 0) | (cols >= rows // block * block))
        tiles = keep.reshape(seq // block, block, seq // block, block)
        assert tiles.any((1, 3))[np.tril_indices(seq // block)].all()
        assert not tiles.any(3)[:, 1::2][1:, :, 0].any()
    else:                       # "an_empty_tile": the last 100 keys
        keep = causal & (cols > rows - 100)
        tiles = keep.reshape(seq // block, block, seq // block, block)
        assert not tiles.any((1, 3))[2, 0]
    return jnp.asarray(keep, jnp.int8)


@pytest.mark.parametrize("heads, kv, blocks, selection, group", [
    pytest.param(8, 1, 3, "topk", 8, id="rep8-three_blocks"),
    pytest.param(4, 2, 3, "topk", 8, id="rep2-three_blocks"),
    pytest.param(2, 2, 3, "topk", 8, id="rep1-three_blocks"),
    pytest.param(4, 2, 1, "topk", 8, id="rep2-one_block"),
    pytest.param(4, 2, 4, "rows_without_a_tile", 8,
                 id="rep2-rows_that_keep_no_key_of_a_tile"),
    pytest.param(4, 2, 4, "an_empty_tile", 8, id="rep2-an_empty_tile"),
    pytest.param(4, 2, 2, "causal", 8, id="rep2-topk_at_least_the_sequence"),
    pytest.param(4, 1, 3, "topk", 2, id="rep4-a_group_in_two_parts"),
])
def test_mosaic_kernels_agree_with_the_plain_core(monkeypatch, heads, kv,
                                                  blocks, selection, group):
    """The four kernels, interpreted, against the plain core: out, lse,
    dq, dk, dv and the head-averaged probabilities, for a visit of
    eight, two and one query head, one block and several, and the
    selections that leave a row or a tile without a key."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(dsa, "BLOCK", 128)
    monkeypatch.setattr(dsa, "GROUP", group)
    rng = np.random.default_rng(6)
    seq, d = blocks * 128, 128
    q, k, v, w = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (seq, heads, d), (seq, kv, d), (seq, kv, d), (seq, heads, d)))
    mask = _selection(selection, seq, 128, rng)
    assert dsa.kernels_eligible(seq, d)
    assert dsa._geometry(q, k) == (128, blocks, min(heads // kv, group),
                                   max(heads // kv // group, 1))

    def weighted(core):
        def fn(q_, k_, v_):
            out, lse = core(q_, k_, v_, mask)
            return (out * w).sum(), (out, lse)
        return jax.grad(fn, (0, 1, 2), has_aux=True)(q, k, v)

    got, (out, lse) = weighted(dsa.core)
    want, (out_p, lse_p) = weighted(dsa.core_plain)
    for a, b in zip((out, lse) + got, (out_p, lse_p) + want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        dsa.mean_head_probs(q, k, lse, mask),
        dsa.mean_head_probs_plain(q, k, lse_p, mask), atol=1e-6)


def _visits(kind, name="dsa_core_visits_total"):
    from paddle_tpu.observability import metrics
    return metrics.registry().counter(name, labels={"kind": kind}).collect()


@pytest.mark.parametrize("seq, heads, kv", [
    (8192, 32, 4), (2048, 8, 8), (1024, 16, 1)])
def test_visit_counter_reads_the_closed_form(monkeypatch, seq, heads, kv):
    """``dsa_core_visits_total`` counts, as a kernel call is traced, the
    steps of a square grid of visits and the visits of the causal
    triangle, G n² and G n (n + 1) / 2 for n = S / 512 where a visit is a
    key head's whole group, and ``dsa_core_heads_per_visit`` is the
    group: H / G, at most ``GROUP``."""
    from paddle_tpu.observability import metrics
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    d, n = 128, seq // 512
    per_visit = min(heads // kv, dsa.GROUP)
    want = [heads // per_visit * n * n,
            heads // per_visit * n * (n + 1) // 2]
    if heads // kv <= dsa.GROUP:
        assert want == [kv * n * n, kv * n * (n + 1) // 2]
    q = jax.ShapeDtypeStruct((seq, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((seq, kv, d), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((heads, seq), jnp.float32)
    mask = jax.ShapeDtypeStruct((seq, seq), jnp.int8)
    for calls, fn, args in (
            (1, dsa._core_fwd_kernels, (q, k, k, mask)),
            (2, dsa._core_bwd_kernels, (q, k, k, mask, q, lse, q)),
            (1, dsa._mean_head_probs_kernels, (q, k, lse, mask))):
        before = [_visits(kind) for kind in ("square", "visited")]
        jax.eval_shape(fn, *args)
        assert [_visits(kind) - was for kind, was in zip(
            ("square", "visited"), before)] == [calls * w for w in want]
        assert metrics.registry().gauge(
            "dsa_core_heads_per_visit").collect() == per_visit


def _indexer_inputs(seq, heads, width, rng, dead_row=None):
    q, k, w = (jnp.asarray(rng.standard_normal(s), jnp.float32) for s in (
        (seq, heads, width), (seq, width), (seq, heads)))
    if dead_row is not None:
        # a query against every key's opposite: all its products are
        # negative, ReLU kills every head and the row scores 0
        q = q.at[dead_row].set(-jnp.abs(q[dead_row]))
        k = jnp.abs(k)
    return q, k, w


@pytest.mark.parametrize("blocks, heads, width, dead_row, kernels", [
    pytest.param(1, 4, 64, None, True, id="one_block"),
    pytest.param(3, 8, 64, None, True, id="three_blocks"),
    pytest.param(3, 6, 64, None, True,
                 id="three_lane_groups_no_multiple_of_the_turn"),
    pytest.param(2, 2, 128, None, True, id="a_head_a_lane_group"),
    pytest.param(2, 8, 32, None, True, id="four_heads_a_lane_group"),
    pytest.param(3, 4, 64, 200, True, id="a_row_relu_kills"),
    pytest.param(3, 3, 64, None, False, id="refused-heads_fill_no_group"),
    pytest.param(2, 4, 48, None, False, id="refused-width_48"),
])
def test_score_kernels_agree_with_chunk_scores(monkeypatch, blocks, heads,
                                               width, dead_row, kernels):
    """The index scores and their three gradients by the two Mosaic
    kernels, interpreted, against ``chunk_scores`` and its ``jax.vjp``:
    through ``select(with_scores)`` and ``indexer_kl``, so the mask, the
    loss and dq_idx, dk_idx, dw_idx of both paths; a shape the
    predicate refuses takes the plain path and is equal to the bit."""
    monkeypatch.setattr(dsa, "BLOCK", 128)
    rng = np.random.default_rng(8)
    seq, topk, chunk = blocks * 128, 48, 64
    q, k, w = _indexer_inputs(seq, heads, width, rng, dead_row)
    probs = jax.nn.softmax(jnp.where(
        np.tril(np.ones((seq, seq), bool)),
        jnp.asarray(rng.standard_normal((seq, seq)), jnp.float32), -1e30), -1)

    def both():
        mask, scores = dsa.select(q, k, w, topk, chunk, with_scores=True)
        return (mask, scores) + jax.value_and_grad(
            dsa.indexer_kl, (0, 1, 2))(q, k, w, probs, mask, chunk)

    want = both()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    assert dsa.scores_eligible(seq, heads, width) == kernels
    before = [_visits(kind, "dsa_indexer_visits_total")
              for kind in ("square", "visited")]
    got = both()
    counted = [_visits(kind, "dsa_indexer_visits_total") - was
               for kind, was in zip(("square", "visited"), before)]
    # two forward calls and one backward
    assert counted == [3 * heads * n * kernels for n in (
        blocks * blocks, blocks * (blocks + 1) // 2)]
    flat = lambda out: out[:3] + tuple(out[3])
    for name, a, b in zip(("mask", "scores", "loss", "dq", "dk", "dw"),
                          flat(got), flat(want)):
        if not kernels or name == "mask":
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            scale = float(jnp.abs(b).max())
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * scale,
                                       err_msg=name)
    if dead_row is not None:
        assert float(jnp.abs(want[1][dead_row]).max()) == 0.0
        assert float(jnp.abs(got[1][dead_row]).max()) == 0.0
        assert float(jnp.abs(got[3][0][dead_row]).max()) == 0.0
    if kernels:
        # the kernels themselves, on a gradient that is not the loss's
        d = jnp.asarray(rng.standard_normal((seq, seq)), jnp.float32
                        ) * np.tril(np.ones((seq, seq), np.float32))
        scores, back = jax.vjp(dsa._scores_kernels, q, k, w)
        want_scores, want_back = jax.vjp(dsa.chunk_scores, q, k, w)
        tiles = np.tril(np.ones((blocks, blocks), bool)).repeat(
            128, 0).repeat(128, 1)
        np.testing.assert_allclose(np.where(tiles, scores, 0.0),
                                   np.where(tiles, want_scores, 0.0),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(back(d), want_back(d)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5 * float(
                jnp.abs(b).max()))


@pytest.mark.parametrize("seq, heads, width", [
    (8192, 16, 64), (1024, 4, 128), (2048, 8, 32)])
def test_score_visit_counter_reads_the_closed_form(monkeypatch, seq, heads,
                                                   width):
    """``dsa_indexer_visits_total`` counts, as a score-kernel call is
    traced, the head-tiles of the square and of the causal triangle:
    J n² and J n (n + 1) / 2 for n = S / 512."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    n = seq // 512
    q = jax.ShapeDtypeStruct((seq, heads, width), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((seq, width), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((seq, heads), jnp.bfloat16)
    d = jax.ShapeDtypeStruct((seq, seq), jnp.float32)
    assert dsa.scores_eligible(seq, heads, width)
    for fn, args in ((dsa._scores_fwd_kernels, (q, k, w)),
                     (dsa._scores_bwd_kernels, (q, k, w, d))):
        before = [_visits(kind, "dsa_indexer_visits_total")
                  for kind in ("square", "visited")]
        jax.eval_shape(fn, *args)
        assert [_visits(kind, "dsa_indexer_visits_total") - was
                for kind, was in zip(("square", "visited"), before)] == [
            heads * n * n, heads * n * (n + 1) // 2]


def test_three_equal_position_streams_are_plain_rotary():
    seq, dim, theta = 40, 128, 1e7
    pos = jnp.arange(3, seq + 3)
    plain = keye_lm.rotary_angles(pos, dim, theta)
    three = keye_lm.rotary_angles(jnp.stack([pos, pos, pos]), dim, theta,
                                  (16, 24, 24))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(three))
    np.testing.assert_allclose(
        family.rotary_angles(jnp.stack([pos, pos, pos]), dim, theta,
                             (16, 24, 24)), plain, rtol=1e-6)
    # streams that differ turn their own sections only
    other = keye_lm.rotary_angles(jnp.stack([pos, pos + 5, pos]), dim,
                                  theta, (16, 24, 24))
    same = np.asarray(other) == np.asarray(plain)
    assert same[:, :16].all() and same[:, 40:].all()
    assert not same[:, 16:40].any()
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (seq, 2, dim)), jnp.float32)
    np.testing.assert_allclose(keye_lm.apply_rotary(x, plain),
                               family.rotate(x, plain), rtol=1e-6)


# --------------------------------------------------------------------------
# the experts
# --------------------------------------------------------------------------
def _expert_layer(seed, tokens=128, d=32, f=16, experts=64, k=8):
    rng = np.random.default_rng(seed)
    y, router, w1, w3, w2 = (jnp.asarray(rng.standard_normal(s) * scale,
                                         jnp.float32) for s, scale in (
        ((tokens, d), 1.0), ((d, experts), 1.0), ((experts, d, f), 0.2),
        ((experts, d, f), 0.2), ((experts, f, d), 0.2)))
    return y, router, w1, w3, w2, k


def _share(y, router, w1, w3, w2, k, first, held):
    chosen, gates = grouped.route(y @ router, k)
    return grouped.experts_forward(
        y, chosen, gates, (w1[first:first + held], w3[first:first + held],
                           w2[first:first + held]), first, w1.shape[0])


def _reference_layer(y, router, w1, w3, w2, k, first, held):
    p = {"router": router, "w1": w1[first:first + held],
         "w3": w3[first:first + held], "w2": w2[first:first + held]}
    return family._experts(y, p, jnp.zeros((y.shape[0], k), jnp.int32),
                           top_k=k, first=first, given=False)


def test_the_shares_of_eight_ranks_add_up_to_the_uncut_layer():
    layer = _expert_layer(0, experts=16)
    whole, _, counts = _reference_layer(*layer, 0, 16)
    assert int(counts.sum()) == 128 * 8
    total = 0.0
    for rank in range(8):
        part, sizes = _share(*layer, 2 * rank, 2)
        want, _, want_sizes = _reference_layer(*layer, 2 * rank, 2)
        np.testing.assert_allclose(part, want, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(sizes, want_sizes)
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("favoured", [[3], [0, 1, 2, 3, 4, 5, 6, 7]])
def test_no_pair_is_dropped_however_uneven_the_routing(favoured):
    """A biased router sends every token to one held expert, or all its
    eight choices to the eight held: more pairs than the usual buffer
    has rows in the second case, and none goes missing."""
    y, router, w1, w3, w2, k = _expert_layer(1)
    y = y.at[:, 0].set(1.0)
    router = router.at[0, jnp.asarray(favoured)].set(200.0)
    tokens = y.shape[0]
    got, sizes = jax.jit(_share, static_argnums=(5, 6, 7))(
        y, router, w1, w3, w2, k, 0, 8)
    want, chosen, counts = _reference_layer(y, router, w1, w3, w2, k, 0, 8)
    for e in favoured:
        assert int((chosen == e).sum()) == tokens
    np.testing.assert_array_equal(sizes, counts)
    assert int(sizes.sum()) >= tokens * len(favoured)
    if len(favoured) == 8:
        assert int(sizes.sum()) == tokens * k > grouped.usual_rows(
            tokens, k, 8, 64)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # and the gradients pass through the same pairs
    args = (y, router, w1, w3, w2)
    got_g = jax.grad(lambda *a: (_share(*a, k, 0, 8)[0] ** 2).sum(),
                     (0, 1, 2, 3, 4))(*args)
    want_g = jax.grad(lambda *a: (_reference_layer(*a, k, 0, 8)[0] ** 2
                                  ).sum(), (0, 1, 2, 3, 4))(*args)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-4)


def test_trains_through_the_runner_with_master_weights():
    """The normal path: seed -> model -> AdamW -> amp.decorate O2 ->
    DistributedRunner.train_step; stacked [held, ...] expert tensors go
    through apply_gradients_tree with their float32 masters."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    paddle.seed(2)
    net = KeyeLMForCausalLM(keye_lm_tiny(experts_held=(0, 2),
                                         vocab_rows_held=VOCAB))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    runner = DistributedRunner(net, opt, KeyeLMPretrainingCriterion(),
                               mesh=mesh)
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, SEQ),
                                            dtype=np.int64)
    before = np.asarray(net.model.layers[0].mlp.experts.w1._value,
                        np.float32)
    losses = [float(runner.train_step([ids], [np.roll(ids, -1, 1)]))
              for _ in range(6)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    w1 = net.model.layers[0].mlp.experts.w1
    assert w1._value.dtype == jnp.bfloat16 and w1.shape == [2, 64, 32]
    assert np.abs(np.asarray(w1._value, np.float32) - before).max() > 0
    state = runner._opt_state["model.layers.0.mlp.experts.w1"]
    assert state["master_weight"].dtype == jnp.float32
    assert np.asarray(net.expert_tokens._value).sum() > 0
    assert abs(losses[0] - float(net.indexer_loss._value)
               - np.log(VOCAB)) < 1.0

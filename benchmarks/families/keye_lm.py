"""The language model of Keye-VL-2.0-30B-A3B as the benchmark knows it,
from its published ``config.json`` and the equations of ISSUE 28, not
from the program: counts from shapes, and a plain float32 reference of
the forward pass, its two losses and (by ``jax.grad``) their gradients.

One layer, ``h [S, hidden]``, query position t, key position s <= t:

    x = RMSNorm(h);  q = x Wq (heads x D), k = x Wk, v = x Wv (kv heads
    x D); q and k RMS-normalised per head with a learned D-vector, then
    rotated (theta over the D-wide head, the 64 frequency pairs in the
    sections of ``mrope_section``, each with its own position stream; on
    text all three are the token's index).  Query head i reads key head
    i // (heads / kv heads).
    indexer: qI = x WIq (J x DI), kI = LayerNorm(x WIk) (one head), both
    rotated over their whole width; w = x WIw (J);
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(DI J)
    S_t = the min(t + 1, topk) causal s with the largest I[t, s]; a tie
    at the border goes to the later position
    o[t, i] = softmax over S_t of (q[t, i] . k[s] / sqrt(D)) v[s];
    h += concat_i(o) Wo
    y = RMSNorm(h);  p = softmax(y Wr) over all experts;  T_t = the k
    largest;  g = p / sum of p over T_t;
    h += sum over e in T_t held here of g[t, e] (silu(y W1_e) * (y W3_e)) W2_e
    indexer loss of the layer: mean_t KL(P[t, S_t] || softmax over S_t
    of I[t, .]), P the probabilities above averaged over the heads; the
    indexer's input x and its target P are detached.

Then a final RMSNorm, logits over the rows of the vocabulary held here,
mean cross-entropy.  What the absent experts would add is left out.

The reference follows these lines with no kernel, no sorted tokens (a
loop over the held experts with masks) and explicit ``[block, S]``
scores with a top-k mask, a block of queries at a time.  It takes an
optional ``selection`` (the program's S_t as ``[L, S, S]`` masks) and
``routing`` (the program's T_t as ``[L, S, k]``): given them it attends
and routes as the program did, given none it decides itself.

Configuration keys are those of the published ``config.json``; the
counts of what this chip holds come from ``experts_held`` and
``vocab_size`` as the configuration file states them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def _sa(config):
    return config["sa_config"]


def layer_weights(config: dict) -> dict:
    """Matrix weights of one layer on this chip, by part."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, g = config["num_attention_heads"], config["num_key_value_heads"]
    sa = _sa(config)
    return {
        "attention": d * h * dh + 2 * d * g * dh + h * dh * d,
        "indexer": d * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + d * sa["indexer_head_dim"] + d * sa["indexer_num_heads"],
        "router": d * config["num_local_experts"],
        "expert": 3 * d * config["moe_intermediate_size"],
    }


def param_count(config: dict) -> int:
    """Every parameter on this chip: the experts held, the rows of the
    embedding and of the untied head held, the norms."""
    d, dh = config["hidden_size"], config["head_dim"]
    w = layer_weights(config)
    layer = (w["attention"] + w["indexer"] + w["router"]
             + config["num_experts"] * w["expert"]
             + 2 * d + 2 * dh + 2 * _sa(config)["indexer_head_dim"])
    return (config["num_hidden_layers"] * layer
            + 2 * config["vocab_size"] * d + d)


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs the attention reads: sum_t min(t + 1, topk)."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def pairs_per_token(config: dict) -> float:
    """(token, expert) pairs a token brings to the experts held here
    under a balanced router: k x held / all."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["num_local_experts"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    experts: the expected pairs a token), the attention over the
    selected keys once forward and twice backward (2 products of 2 D
    operations a head and pair), and the indexer's scores over the
    causal half square likewise.  Masked-out or recomputed work counts
    for nothing."""
    w = layer_weights(config)
    sa = _sa(config)
    weights = config["num_hidden_layers"] * (
        w["attention"] + w["indexer"] + w["router"]
        + pairs_per_token(config) * w["expert"]
    ) + config["vocab_size"] * config["hidden_size"]
    core = 3 * 4 * config["num_attention_heads"] * config["head_dim"] \
        * selected_pairs(seq_len, sa["topk"]) / seq_len
    indexer = 3 * 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"] \
        * (seq_len + 1) / 2
    return 6.0 * weights + config["num_hidden_layers"] * (core + indexer)


def sparse_core_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the selected-key attention of one step needs
    whatever implements it: six products (two forward; dv, dp, dq, dk
    backward) of 2 D operations a head over the selected pairs only, and
    q, out (forward), q, out, dout, dq (backward) and as many of the
    key-head arrays moved once in bf16."""
    h, g, dh = (config["num_attention_heads"],
                config["num_key_value_heads"], config["head_dim"])
    calls = config["num_hidden_layers"] * batch
    pairs = selected_pairs(seq_len, _sa(config)["topk"])
    return {"flops": float(calls * 6 * 2 * h * dh * pairs),
            "bytes": float(calls * 6 * 2 * seq_len * dh * (h + g))}


def experts_cost(config: dict, pairs: float) -> dict:
    """Operations and bytes of the held experts of one step over
    ``pairs`` (token, expert) pairs, all layers together: 6 a weight and
    pair; every held expert's weights read forward, read backward and
    their gradient written, in bf16; a pair's rows (in, two hidden, gated
    hidden, out) once forward and twice backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    w = layer_weights(config)["expert"]
    return {"flops": 6.0 * w * pairs,
            "bytes": float(config["num_hidden_layers"]
                           * config["num_experts"] * w * 2 * 3
                           + pairs * 3 * 2 * (2 * d + 3 * f))}


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy, no kernel, no sorted tokens
# --------------------------------------------------------------------------
def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _layer_norm(x, weight, bias, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def rotary_angles(positions, dim: int, theta: float, sections=None):
    """``[S, dim / 2]``: position times theta^(-2 i / dim); with
    ``sections`` the frequency pair i takes the position stream (row of
    ``positions [3, S]``) its section names."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    positions = jnp.asarray(positions, jnp.float32)
    if sections is None:
        return positions[:, None] * inv_freq[None, :]
    stream = jnp.concatenate([jnp.full((n,), i, jnp.int32)
                              for i, n in enumerate(sections)])
    return positions[stream, :].T * inv_freq[None, :]


def rotate(x, angles):
    """Pairs (i, i + dim / 2) of the last axis of ``x [S, ..., dim]``."""
    shape = (angles.shape[0],) + (1,) * (x.ndim - 2) + (angles.shape[1],)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def top_k_mask(scores, first_row, topk: int):
    """bool ``[B, S]``: for the query rows ``first_row ..`` the min(t + 1,
    topk) causal keys with the largest score, a tie going to the later
    position (the row is searched from its end)."""
    rows, seq = scores.shape
    t = first_row + jnp.arange(rows)[:, None]
    causal = jnp.arange(seq)[None, :] <= t
    k = min(topk, seq)
    _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf)[:, ::-1], k)
    chosen = jnp.zeros((rows, seq), bool).at[
        jnp.arange(rows)[:, None], seq - 1 - at].set(True)
    return chosen & causal


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "topk",
                                             "eps", "block", "given"))
def _attention(x, p, angles, idx_angles, selection, *, heads, kv_heads, topk,
               eps, block, given):
    """(attention output ``[S, hidden]``, index scores, selection, the
    layer's indexer loss) of one sequence, a block of queries at a time
    against all keys."""
    with jax.default_matmul_precision("highest"):
        seq = x.shape[0]
        dh = p["q_norm"].shape[0]
        q = rotate(_rms_norm((x @ p["wq"]).reshape(seq, heads, dh),
                             p["q_norm"], eps), angles)
        k = rotate(_rms_norm((x @ p["wk"]).reshape(seq, kv_heads, dh),
                             p["k_norm"], eps), angles)
        v = (x @ p["wv"]).reshape(seq, kv_heads, dh)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
        xi = jax.lax.stop_gradient(x)
        j = p["iw"].shape[1]
        di = p["ik"].shape[1]
        q_idx = rotate((xi @ p["iq"]).reshape(seq, j, di), idx_angles)
        k_idx = rotate(_layer_norm(xi @ p["ik"], p["iln_w"], p["iln_b"]),
                       idx_angles)
        w_idx = xi @ p["iw"]

        def one_block(args):
            first, q_b, qi_b, wi_b, given_b = args
            scores = (jax.nn.relu(jnp.einsum("bjd,sd->bjs", qi_b, k_idx))
                      * wi_b[:, :, None]).sum(1) / math.sqrt(di * j)
            keep = given_b if given else top_k_mask(scores, first, topk)
            logits = jnp.einsum("bhd,shd->hbs", q_b, k) / math.sqrt(dh)
            probs = jax.nn.softmax(jnp.where(keep[None], logits, -jnp.inf),
                                   axis=-1)
            out = jnp.einsum("hbs,shd->bhd", probs, v)
            target = jax.lax.stop_gradient(probs.mean(0))
            log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
            kl = jnp.where(target > 0, target * (
                jnp.log(jnp.where(target > 0, target, 1.0))
                - jnp.where(keep, log_q, 0.0)), 0.0).sum()
            causal = jnp.arange(seq)[None, :] <= first + jnp.arange(
                q_b.shape[0])[:, None]
            return out, jnp.where(causal, scores, 0.0), keep, kl

        block = min(block, seq)
        n = seq // block
        blocks = lambda a: a.reshape((n, block) + a.shape[1:])  # noqa: E731
        out, scores, keep, kl = jax.lax.map(one_block, (
            jnp.arange(n) * block, blocks(q), blocks(q_idx), blocks(w_idx),
            blocks(selection)))
        out = out.reshape(seq, heads * dh) @ p["wo"]
        return (out, scores.reshape(seq, seq), keep.reshape(seq, seq),
                kl.sum() / seq)


@functools.partial(jax.jit, static_argnames=("top_k", "first", "given"))
def _experts(y, p, routing, *, top_k, first, given):
    """(this chip's part of the layer's result, the experts chosen ``[S,
    k]``, pairs of each held expert): one held expert at a time on every
    token, weighted by the gate where the token chose it and by 0 where
    not."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(y @ p["router"], axis=-1)
        chosen = routing if given else jax.lax.top_k(probs, top_k)[1]
        gates = jnp.take_along_axis(probs, chosen, axis=-1)
        gates = gates / gates.sum(-1, keepdims=True)

        def one_expert(acc, args):
            e, w1, w3, w2 = args
            weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
            return acc + weight[:, None] * out, (chosen == e).sum()

        held = p["w1"].shape[0]
        out, counts = jax.lax.scan(one_expert, jnp.zeros_like(y), (
            first + jnp.arange(held), p["w1"], p["w3"], p["w2"]))
        return out, chosen, counts


PREFIX = "model.layers.{}."
LAYER_PARAMS = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "iq": "self_attn.indexer.wq.weight", "ik": "self_attn.indexer.wk.weight",
    "iln_w": "self_attn.indexer.k_norm.weight",
    "iln_b": "self_attn.indexer.k_norm.bias",
    "iw": "self_attn.indexer.weights_proj.weight",
    "router": "mlp.gate.weight", "w1": "mlp.experts.w1",
    "w3": "mlp.experts.w3", "w2": "mlp.experts.w2",
}
EMBEDDING = "model.embed_tokens.weight"
FINAL_NORM = "model.norm.weight"
HEAD = "lm_head.weight"          # [hidden, rows held]


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight, eps)


def reference_forward(param, config: dict, ids, selection=None, routing=None,
                      positions=None, block: int = QUERY_BLOCK) -> dict:
    """One sequence of token ids through the reference.  ``param(name,
    rows=None)`` returns the program's parameter of that name (or the
    given rows of it) as float32, a layer at a time.  Returns ``hidden``
    (what the head multiplies, ``[S, hidden]``), per layer ``scores``,
    ``selection`` (bool ``[S, S]``), ``experts`` (``[S, k]``), ``counts``
    (``[held]``), and ``indexer_loss`` summed over the layers."""
    seq = ids.shape[0]
    eps = float(config["rms_norm_eps"])
    sa = _sa(config)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(seq), (3, seq))
    angles = rotary_angles(positions, config["head_dim"],
                           float(config["rope_theta"]),
                           tuple(config["rope_scaling"]["mrope_section"]))
    idx_angles = rotary_angles(positions[0], sa["indexer_head_dim"],
                               float(config["rope_theta"]))
    first = config["experts_held"][0]
    h = param(EMBEDDING, ids)
    out = {"scores": [], "selection": [], "experts": [], "counts": [],
           "indexer_loss": 0.0}
    for i in range(config["num_hidden_layers"]):
        p = {k: param(PREFIX.format(i) + n) for k, n in LAYER_PARAMS.items()}
        given = (jnp.zeros((seq, seq), bool) if selection is None
                 else jnp.asarray(selection[i]) != 0)
        attn, scores, keep, kl = _attention(
            _norm(h, p["ln1"], eps=eps), p, angles, idx_angles, given,
            heads=config["num_attention_heads"],
            kv_heads=config["num_key_value_heads"], topk=sa["topk"],
            eps=eps, block=block, given=selection is not None)
        h = h + attn
        chosen = (jnp.zeros((seq, config["num_experts_per_tok"]), jnp.int32)
                  if routing is None else jnp.asarray(routing[i]))
        moe, experts, counts = _experts(
            _norm(h, p["ln2"], eps=eps), p, chosen,
            top_k=config["num_experts_per_tok"], first=first,
            given=routing is not None)
        h = h + moe
        out["scores"].append(scores)
        out["selection"].append(keep)
        out["experts"].append(experts)
        out["counts"].append(counts)
        out["indexer_loss"] = out["indexer_loss"] + kl
    out["hidden"] = _norm(h, param(FINAL_NORM), eps=eps)
    return out


def reference_logits(hidden, head_columns):
    """Logits ``[S, columns]`` for some columns of the untied head
    (float32 ``[hidden, columns]``): a part of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        return hidden @ head_columns


def reference_losses(params: dict, config: dict, ids, labels, **kw):
    """(L_LM, L_I) of a batch ``ids``/``labels`` ``[B, S]`` from a dict of
    float32 parameters by the program's names: differentiable, for the
    small sizes of the tests."""
    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    lm, idx = 0.0, 0.0
    for b in range(ids.shape[0]):
        out = reference_forward(param, config, ids[b], **kw)
        logp = jax.nn.log_softmax(
            reference_logits(out["hidden"], params[HEAD]), axis=-1)
        lm = lm - jnp.take_along_axis(logp, labels[b][:, None], 1).mean()
        idx = idx + out["indexer_loss"]
    return lm / ids.shape[0], idx / ids.shape[0]


def selection_of(scores, topk: int, block: int = QUERY_BLOCK):
    """bool ``[S, S]``: :func:`top_k_mask` of whole index scores, a block
    of rows at a time."""
    seq = scores.shape[0]
    block = min(block, seq)
    one = jax.jit(functools.partial(top_k_mask, topk=topk))
    return jnp.concatenate([one(scores[a:a + block], a)
                            for a in range(0, seq, block)])


def _one_head(q, k, v, keep):
    """Plain attention of one head over the keys ``keep [S, S]`` keeps:
    (out ``[S, D]``, probabilities ``[S, S]``), float32."""
    with jax.default_matmul_precision("highest"):
        logits = (q @ k.T) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        return probs @ v, probs


@jax.jit
def reference_core_grads(q, k, v, w, keep):
    """Plain selected-key attention of q, w ``[H, S, D]`` over k, v ``[G,
    S, D]`` (float32) and the gradients of ``sum(out * w)``: ``(out, dq,
    dk, dv, mean probabilities [S, S])``.  One head at a time, forward
    and backward, so that one head's squares are all that is alive."""
    heads, groups = q.shape[0], k.shape[0]
    rep = heads // groups

    def one(total, args):
        q_, w_, g = args
        (out, probs), vjp = jax.vjp(
            lambda a, b, c: _one_head(a, b, c, keep), q_, k[g], v[g])
        dq, dk, dv = vjp((w_, jnp.zeros_like(probs)))
        return total + probs / heads, (out, dq, dk, dv)

    probs, (out, dq, dk, dv) = jax.lax.scan(
        one, jnp.zeros(keep.shape, jnp.float32),
        (q, w, jnp.arange(heads) // rep))
    dk, dv = (x.reshape((groups, rep) + x.shape[1:]).sum(1)
              for x in (dk, dv))
    return out, dq, dk, dv, probs

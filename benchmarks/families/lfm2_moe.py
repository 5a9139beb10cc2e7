"""LFM2-MoE (``lfm2_moe``: LiquidAI LFM2-8B-A1B) as the benchmark knows
it, from its published ``config.json`` and the equations of ISSUE 40 (HF
transformers' ``modeling_lfm2_moe.py``; the operator is LFM2's
double-gated short convolution), not from the program: counts from
shapes, and a plain float32 reference of the forward pass, its loss and
(by ``jax.grad``) its gradients.

    h = E[ids]
    layer:  h += op(n(h; operator_norm));  h += ff(n(h; ffn_norm))
    logits = n(h; embedding_norm) E^T     (n RMSNorm; E tied, the rows held)

    conv op:   [B | C | x] = u W_in  (hidden -> 3 x hidden, in this order)
               z = B * x;  c[t] = sum_{k < W} w[:, k] * z[t - (W - 1) + k]
               (depthwise, causal, z before the sequence = 0)
               op(u) = (C * c) W_out        (no activation, no bias)
    attention: q, k, v = u W_q, u W_k, u W_v;  q, k <- RMSNorm over each
               head's width (own weights), then rotary over the whole head
               (rotate-half: pairs (i, i + D / 2), angle t theta^(-2 i / D))
               causal softmax(q k^T / sqrt(D)) v, H / G query heads a key
               head;  W_o
    dense ff:  (silu(y W1) * (y W3)) W2     (layers < num_dense_layers)
    expert ff: s = sigmoid(y W_r) (all experts);  T = the k largest of s + b
               g_e = routed_scaling_factor s_e / (sum of s over T + 1e-6)
               sum over e in T held here of g_e (silu(y W1_e) * (y W3_e)) W2_e

(b, ``expert_bias``, is read as the program holds it.  What moves it
between passes is the balancing rule, ``balanced_bias``: after a pass in
training, ``b_e += rate * sign(mean load - load_e)`` over the tokens'
choices among all experts.)

The reference runs the convolution as a sum of shifted copies (and, for
the operator's own check, a position at a time: ``short_conv_loop``),
attention a block of queries at a time against all keys, and the experts
as a loop over the held ones on every token, weighted by the gate where
the token chose the expert and by 0 where not.  Configuration keys are
those of the published ``config.json``; ``vocab_size``,
``num_hidden_layers`` (with ``layer_types`` and ``num_dense_layers``) and
``num_experts`` are what this chip holds, as the configuration file
states them; ``experts_held`` is (first, count) and
``published["num_experts"]`` the router's width.

Departures from the published description: none in the mathematics.  HF
computes the operator in the stream's dtype (``B * x`` rounded to bf16
before the taps); here every product and sum is float32.  What
``config.json`` has no key for (the tie, the balancing rule, where the
weights start) is listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .granite_hybrid import (QUERY_BLOCK, _attend,  # noqa: F401
                             reference_attention_grads)
from .sambay import ADAMW, reference_adamw  # noqa: F401

GATE_EPS = 1e-6


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def kinds(config: dict) -> tuple:
    """The held layers, operator and ff: ``conv_dense``, ``conv_moe``,
    ``attention_dense`` or ``attention_moe``."""
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types names an operator for each of "
                         "num_hidden_layers")
    return tuple(
        ("conv" if t == "conv" else "attention") + "_"
        + ("dense" if i < config["num_dense_layers"] else "moe")
        for i, t in enumerate(types))


def router_width(config: dict) -> int:
    """The experts the router chooses among: the published count."""
    return config["published"]["num_experts"]


def _head(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def layer_weights(config: dict) -> dict:
    """Matrix weights by part: what a token multiplies in a convolution
    operator (its taps among them), in attention, in the dense MLP, in the
    router, and in one routed expert."""
    d, kv = config["hidden_size"], config["num_key_value_heads"] * _head(
        config)
    return {
        "conv": d * 3 * d + d * config["conv_L_cache"] + d * d,
        "attention": 2 * d * d + 2 * d * kv,
        "dense": 3 * d * config["intermediate_size"],
        "router": d * router_width(config),
        "expert": 3 * d * config["moe_intermediate_size"],
    }


def layer_params(config: dict) -> dict:
    """Every parameter of a layer on this chip, by kind: operator, ff and
    the layer's two norms (the router's bias is a buffer)."""
    d, w = config["hidden_size"], layer_weights(config)
    op = {"conv": w["conv"], "attention": w["attention"] + 2 * _head(config)}
    ff = {"dense": w["dense"],
          "moe": w["router"] + config["num_experts"] * w["expert"]}
    return {f"{o}_{f}": op[o] + ff[f] + 2 * d for o in op for f in ff}


def param_count(config: dict) -> int:
    """Every parameter on this chip; the tied matrix once, its rows
    held, and the final norm."""
    per = layer_params(config)
    return sum(per[k] for k in kinds(config)) \
        + config["vocab_size"] * config["hidden_size"] \
        + config["hidden_size"]


def pairs_per_token(config: dict) -> float:
    """(token, expert) pairs a token is expected to bring to the experts
    held here, an expert layer, under a balanced router."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / router_width(config))


def attention_flops_per_token(config: dict, seq_len: int) -> float:
    """Causal attention's two products over the half square, once forward
    and twice backward, a token of one layer."""
    return 3 * 4 * config["hidden_size"] * (seq_len + 1) / 2


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    operators' projections and taps, the dense MLP, the router whole, the
    expected pairs on the held experts, the tied head; the lookup
    multiplies nothing), and the attention layers' two products over the
    causal half square once forward and twice backward.  Recomputed work
    counts for nothing."""
    w, ks = layer_weights(config), kinds(config)
    count = lambda part: sum(part in k for k in ks)          # noqa: E731
    weights = (count("conv_") * w["conv"]
               + count("attention_") * w["attention"]
               + count("_dense") * w["dense"]
               + count("_moe") * (w["router"]
                                  + pairs_per_token(config) * w["expert"])
               + config["vocab_size"] * config["hidden_size"])
    return 6.0 * weights + count("attention_") * attention_flops_per_token(
        config, seq_len)


def conv_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the convolution operators of one step need
    whatever implements them, all of them together and each whole, both
    projections with what stands between them: 6 a weight and token; u,
    ``[B | C | x]``, y and the output written or read once forward (6
    widths of ``hidden``); the output's gradient, y and its gradient,
    ``[B | C | x]`` and its gradient, u and its gradient once backward
    (11); the weights read forward, read backward and their gradient
    written; all in bf16."""
    d, w = config["hidden_size"], layer_weights(config)["conv"]
    calls = sum(k.startswith("conv_") for k in kinds(config))
    return {"flops": float(calls * batch * seq_len * 6 * w),
            "bytes": float(calls * (batch * seq_len * 17 * d * 2
                                    + w * 2 * 3))}


def gqa_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the attention layers' cores need: the causal
    triangle's two products once forward and twice backward; q and the
    output (forward), q, the output, its gradient and dq (backward) at the
    query heads' width and as many arrays at the key heads', once, in
    bf16."""
    d, kv = config["hidden_size"], config["num_key_value_heads"] * _head(
        config)
    calls = sum(k.startswith("attention_") for k in kinds(config)) * batch
    return {"flops": float(calls * seq_len
                           * attention_flops_per_token(config, seq_len)),
            "bytes": float(calls * 6 * 2 * seq_len * (d + kv))}


def experts_cost(config: dict, pairs: float) -> dict:
    """Operations and bytes of the held routed experts of one step over
    ``pairs`` (token, expert) pairs, all expert layers together: 6 a
    weight and pair, three matrices a pair; every held expert's weights
    read forward, read backward and their gradient written, in bf16; a
    pair's rows (in, two hidden, gated hidden, out) once forward and twice
    backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    w = layer_weights(config)["expert"]
    layers = sum(k.endswith("_moe") for k in kinds(config))
    return {"flops": 6.0 * w * pairs,
            "bytes": float(layers * config["num_experts"] * w * 2 * 3
                           + pairs * 3 * 2 * (2 * d + 3 * f))}


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy
# --------------------------------------------------------------------------
def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def short_conv(bcx, weight):
    """``C * conv(B * x)`` of ``bcx [S, 3 x channels]`` with taps ``weight
    [channels, W]``: the convolution a sum of W shifted copies."""
    b, c, x = jnp.split(bcx, 3, -1)
    seq, width = bcx.shape[0], weight.shape[1]
    z = jnp.pad(b * x, ((width - 1, 0), (0, 0)))
    return c * sum(z[k:k + seq] * weight[:, k] for k in range(width))


def short_conv_loop(bcx, weight):
    """The same a position at a time: the last ``W - 1`` products ``B * x``
    are the state, zeros before the sequence."""
    channels, width = weight.shape

    def step(past, row):
        b, c, x = jnp.split(row, 3)
        window = jnp.concatenate([past, (b * x)[None]])       # [W, channels]
        return window[1:], c * (window * weight.T).sum(0)

    return jax.lax.scan(step, jnp.zeros((width - 1, channels), bcx.dtype),
                        bcx)[1]


@jax.jit
def reference_conv_grads(bcx, weight, w):
    """The operator a position at a time on float32 inputs and the
    gradients of ``sum(y * w)``: ``(y, dbcx, dweight)``."""
    y, vjp = jax.vjp(short_conv_loop, bcx, weight)
    return (y,) + vjp(w)


def rotary_angles(seq: int, dim: int, theta: float):
    """Angles ``[S, dim / 2]``: position t turns pair i by ``t theta^(-2
    i / dim)``."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]


def rotate(x, angles):
    """``x [S, heads, dim]`` rotated: the pairs are (i, i + dim / 2)."""
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.jit
def _conv_op(u, p):
    with jax.default_matmul_precision("highest"):
        return short_conv(u @ p["in_proj"], p["taps"]) @ p["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "block"))
def _attention_op(u, p, *, heads, kv_heads, theta, eps, block):
    with jax.default_matmul_precision("highest"):
        seq = u.shape[0]
        q = (u @ p["wq"]).reshape(seq, heads, -1)
        k = (u @ p["wk"]).reshape(seq, kv_heads, -1)
        v = (u @ p["wv"]).reshape(seq, kv_heads, -1)
        angles = rotary_angles(seq, q.shape[-1], theta)
        q = rotate(_rms_norm(q, p["q_norm"], eps), angles)
        k = rotate(_rms_norm(k, p["k_norm"], eps), angles)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
        scale = 1.0 / math.sqrt(q.shape[-1])
        return _attend(q, k, v, scale, block).reshape(seq, -1) @ p["wo"]


@jax.jit
def _dense_ff(y, p):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(y @ p["w1"]) * (y @ p["w3"])) @ p["w2"]


def route(y, router, bias, top_k: int):
    """(scores ``[S, E]``, the experts chosen ``[S, k]``): the k largest
    of ``sigmoid(y W_r) + b``."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(y @ router)
    return scores, jax.lax.top_k(scores + bias, top_k)[1]


@functools.partial(jax.jit, static_argnames=("top_k", "first", "scale",
                                             "given"))
def _moe_ff(y, p, routing, *, top_k, first, scale, given):
    """(this chip's part of the layer's result, the experts chosen ``[S,
    k]``, pairs of each held expert): one held expert at a time on every
    token, weighted by the gate where the token chose it and by 0 where
    not."""
    with jax.default_matmul_precision("highest"):
        scores, own = route(y, p["router"], p["bias"], top_k)
        chosen = routing if given else own
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + GATE_EPS)

        def one_expert(acc, args):
            e, w1, w3, w2 = args
            weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
            return acc + weight[:, None] * out, (chosen == e).sum()

        held = p["w1"].shape[0]
        out, counts = jax.lax.scan(one_expert, jnp.zeros_like(y), (
            first + jnp.arange(held), p["w1"], p["w3"], p["w2"]))
        return out, chosen, counts


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight, eps)


PREFIX = "model.layers.{}."
NORMS = {"op_norm": "operator_norm.weight", "ff_norm": "ffn_norm.weight"}
OPERATOR = {
    "conv": {"in_proj": "conv.in_proj.weight", "taps": "conv.conv_weight",
             "out_proj": "conv.out_proj.weight"},
    "attention": {"wq": "self_attn.q_proj.weight",
                  "wk": "self_attn.k_proj.weight",
                  "wv": "self_attn.v_proj.weight",
                  "wo": "self_attn.out_proj.weight",
                  "q_norm": "self_attn.q_layernorm.weight",
                  "k_norm": "self_attn.k_layernorm.weight"},
}
FF = {
    "dense": {"w1": "feed_forward.w1.weight", "w3": "feed_forward.w3.weight",
              "w2": "feed_forward.w2.weight"},
    "moe": {"router": "feed_forward.gate.weight",
            "bias": "feed_forward.expert_bias",
            "w1": "feed_forward.experts.w1", "w3": "feed_forward.experts.w3",
            "w2": "feed_forward.experts.w2"},
}
EMBEDDING = "model.embed_tokens.weight"      # [rows held, hidden], tied
FINAL_NORM = "model.embedding_norm.weight"


def layer_parameters(config: dict, i: int) -> list:
    """The names of held layer ``i``'s parameters (its router's bias is a
    buffer)."""
    op, ff = kinds(config)[i].split("_")
    return [PREFIX.format(i) + n for k, n in {
        **NORMS, **OPERATOR[op], **FF[ff]}.items() if k != "bias"]


def reference_forward(param, config: dict, ids, routing=None,
                      block: int = QUERY_BLOCK, layers=None,
                      stream=None) -> dict:
    """One sequence of token ids through the reference.  ``param(name,
    rows=None)`` returns the program's parameter or buffer of that name
    (or the given rows of it) as float32, a layer at a time.  ``routing``
    gives the experts chosen, an expert layer at a time, in the layers'
    order; left out, the reference routes for itself.  ``layers`` (first,
    end) runs those layers only, from ``stream`` where one is given
    instead of the embedding's rows.  Returns ``stream`` (the residual
    stream after the last layer run), ``hidden`` (what the head
    multiplies, ``[S, hidden]``) and, an expert layer at a time,
    ``experts`` (``[S, k]``) and ``counts`` (``[held]``)."""
    eps = float(config["norm_eps"])
    all_kinds = kinds(config)
    first, end = layers or (0, len(all_kinds))
    h = param(EMBEDDING, ids) if stream is None else stream
    out = {"experts": [], "counts": []}
    for i in range(first, end):
        op, ff = all_kinds[i].split("_")
        prefix = PREFIX.format(i)
        p = {k: param(prefix + n) for k, n in {
            **NORMS, **OPERATOR[op], **FF[ff]}.items()}
        u = _norm(h, p["op_norm"], eps=eps)
        if op == "conv":
            h = h + _conv_op(u, p)
        else:
            h = h + _attention_op(
                u, p, heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                theta=float(config["rope_theta"]), eps=eps, block=block)
        y = _norm(h, p["ff_norm"], eps=eps)
        if ff == "dense":
            h = h + _dense_ff(y, p)
            continue
        k = config["num_experts_per_tok"]
        at = sum(kind.endswith("_moe") for kind in all_kinds[:i])
        chosen = (jnp.zeros((ids.shape[0], k), jnp.int32)
                  if routing is None else jnp.asarray(routing[at]))
        mixed, experts, counts = _moe_ff(
            y, p, chosen, top_k=k, first=config["experts_held"][0],
            scale=float(config["routed_scaling_factor"]),
            given=routing is not None)
        out["experts"].append(experts)
        out["counts"].append(counts)
        h = h + mixed
    out["stream"] = h
    out["hidden"] = _norm(h, param(FINAL_NORM), eps=eps)
    return out


def reference_logits(hidden, embedding_rows):
    """Logits ``[S, rows]`` for some rows of the tied matrix (float32
    ``[rows, hidden]``): a part of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding_rows.T


def _cross_entropy(hidden, embedding, labels):
    logp = jax.nn.log_softmax(reference_logits(hidden, embedding), -1)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()


def reference_loss(params: dict, config: dict, ids, labels, **kw):
    """Mean cross-entropy of a batch ``ids``/``labels`` ``[B, S]`` from a
    dict of float32 parameters and buffers by the program's names:
    differentiable, for the small sizes of the tests."""
    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    loss = 0.0
    for b in range(ids.shape[0]):
        hidden = reference_forward(param, config, ids[b], **kw)["hidden"]
        loss = loss + _cross_entropy(hidden, params[EMBEDDING], labels[b])
    return loss / ids.shape[0]


def checked_layers(config: dict) -> tuple:
    """The layers whose gradients the driver holds against the
    reference's: the first convolution layer with experts and the first
    attention layer with experts."""
    ks = kinds(config)
    return tuple(sorted(ks.index(k) for k in ("conv_moe", "attention_moe")))


def reference_layer_grads(param, config: dict, ids, labels, routing,
                          layers, block: int = QUERY_BLOCK) -> dict:
    """The gradients of one sequence's mean cross-entropy by every
    parameter of the held layers ``layers``, by name, given the experts
    chosen: what the whole model's gradient holds for these parameters.
    The layers before the first of them run forward only; every other
    parameter, the tied matrix among them, is held as it is."""
    first, end = min(layers), len(kinds(config))
    stream = reference_forward(param, config, ids, routing, block,
                               layers=(0, first))["stream"]

    def loss_of(own):
        def param_(name, rows=None):
            return own[name] if name in own else param(name, rows)

        hidden = reference_forward(param_, config, ids, routing, block,
                                   layers=(first, end),
                                   stream=stream)["hidden"]
        return _cross_entropy(hidden, param(EMBEDDING), labels)

    return jax.grad(loss_of)({name: param(name) for i in layers
                              for name in layer_parameters(config, i)})


def balanced_bias(bias, chosen, rate: float):
    """One step of the balancing rule on a router's bias ``[E]`` from the
    experts a pass's tokens chose ``[S, k]``: up by ``rate`` where an
    expert drew fewer tokens than the experts' mean, down where more."""
    load = jnp.zeros(bias.shape, jnp.float32).at[chosen.reshape(-1)].add(1.0)
    return bias + rate * jnp.sign(load.mean() - load)


class rounded_through:
    """This family with its reference computed in ``dtype``, the control
    of the driver's limits: every weight the reference reads, and the
    inputs of a kernel's reference that the program holds in bf16 (the
    operator's ``[B | C | x]`` and taps; attention's q, k, v), rounded
    through ``dtype`` and back to float32.  Everything else is the
    family's.  With ``float8_e4m3fn``, the nearest precision below the
    configuration's bf16, each of the driver's checks of values has to
    come out wrong by it."""

    def __init__(self, dtype):
        self._round = lambda a: a.astype(dtype).astype(jnp.float32)

    def __getattr__(self, name):
        try:
            return globals()[name]
        except KeyError:
            raise AttributeError(name) from None

    def _rounded(self, param):
        # the router's bias chooses and is no weight: it is read as it is
        return lambda name, rows=None: (
            param(name, rows) if name.endswith(FF["moe"]["bias"])
            else self._round(param(name, rows)))

    def reference_forward(self, param, config, ids, *args, **kw):
        return reference_forward(self._rounded(param), config, ids, *args,
                                 **kw)

    def reference_logits(self, hidden, embedding_rows):
        return reference_logits(hidden, self._round(embedding_rows))

    def reference_layer_grads(self, param, config, ids, labels, *args, **kw):
        return reference_layer_grads(self._rounded(param), config, ids,
                                     labels, *args, **kw)

    def reference_conv_grads(self, bcx, weight, w):
        return reference_conv_grads(self._round(bcx), self._round(weight), w)

    def reference_attention_grads(self, q, k, v, w, *, scale):
        r = self._round
        return reference_attention_grads(r(q), r(k), r(v), w, scale=scale)

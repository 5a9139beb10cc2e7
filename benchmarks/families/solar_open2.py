"""Solar Open 2 (``solar_open2``: Upstage Solar-Open2-250B) as the
benchmark knows it, from its published ``config.json``, Kimi Linear's
equations for KDA (arXiv:2510.26692; fla's ``KimiDeltaAttention``), not
from the program: counts from shapes, and a plain float32 reference of
the forward pass, its loss and (by ``jax.grad``) its gradients.

    h = E[ids]
    layer:  h += mixer(n(h; input_layernorm));  h += moe(n(h; post_attn))
    logits = n(h; norm) W_head          (n RMSNorm; W_head untied, rows held)

    KDA, a head of width d:
        q~, k~, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
        (depthwise, causal, no bias; u before the sequence = 0)
        q = q~ / sqrt(|q~|^2 + 1e-6) d^-1/2;  k = k~ / sqrt(|k~|^2 + 1e-6)
        log alpha = -exp(A_log) softplus(u W_fa W_fb + dt_bias)  a channel
        beta = 2 sigmoid(u W_b)                                  a head
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                          (a position at a time)
        out = (RMSNorm_head(o) * sigmoid(u W_ga W_gb + b_g)) W_o
    GQA: causal softmax(q k^T / sqrt(d)) v, H / G query heads a key head,
        no positions;  out = (attention * sigmoid(u W_gate)) W_o
    MoE: s = sigmoid(y W_r) (all experts);  T = the k largest of s + b
        g_e = routed_scaling_factor s_e / (sum of s over T + 1e-20)
        sum over e in T held here of g_e (silu(y W1_e) * (y W3_e)) W2_e
        + (silu(y W1_s) * (y W3_s)) W2_s        (the shared expert, whole)

(b, ``e_score_correction_bias``, is read as the program holds it; what
moves it between passes is the balancing rule, ``balanced_bias``.)

The reference runs the delta rule a position at a time (``kda_loop``:
the state ``[H, d, d]`` float32, kept every 64 positions and made again
in between by ``jax.grad``), attention a block of queries at a time
against all keys, and the experts as a loop over the held ones on every
token, weighted by the gate where the token chose the expert and by 0
where not.  Configuration keys are those of the published
``config.json``; ``vocab_size``, ``num_hidden_layers``,
``n_routed_experts``, ``num_attention_heads`` and ``num_key_value_heads``
are what this chip holds, as the configuration file states them;
``published`` has the model's.

Departures from the published description: none in the mathematics.  fla
computes the convolutions and gates in the stream's dtype; here every
product and sum is float32.  What ``config.json`` has no key for (the
gates' forms and rank, the router, where the weights start) is listed
under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .granite_hybrid import (QUERY_BLOCK, _attend,  # noqa: F401
                             reference_attention_grads)
from .lfm2_moe import balanced_bias  # noqa: F401
from .sambay import ADAMW, reference_adamw  # noqa: F401

GATE_EPS = 1e-20
L2_EPS = 1e-6
CHUNK = 64
SEGMENT = 64          # positions between two kept states of kda_loop


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def kinds(config: dict) -> tuple:
    """The held layers' mixers, ``gqa`` or ``kda``, by the published
    ``gqa_layers`` and ``layers_held``."""
    first, count = config["layers_held"]
    if count != config["num_hidden_layers"]:
        raise ValueError("num_hidden_layers is the count of layers_held")
    return tuple("gqa" if i in config["gqa_layers"] else "kda"
                 for i in range(first, first + count))


def router_width(config: dict) -> int:
    """The experts the router chooses among: the published count."""
    return config["published"]["n_routed_experts"]


def _kda(config: dict) -> dict:
    lin = config["linear_attn_config"]
    heads = config["num_attention_heads"]        # held here
    return {"heads": heads, "dim": lin["head_dim"],
            "width": heads * lin["head_dim"], "rank": config["kda_gate_rank"],
            "taps": lin["short_conv_kernel_size"]}


def layer_weights(config: dict) -> dict:
    """Matrix weights by part: what a token multiplies in a KDA mixer (its
    taps among them), in the GQA mixer, in the router, in one routed
    expert and in the shared expert."""
    d, k = config["hidden_size"], _kda(config)
    w, r = k["width"], k["rank"]
    gqa = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "kda": 4 * d * w + 2 * (d * r + r * w) + d * k["heads"]
        + 3 * w * k["taps"],
        "gqa": 3 * d * gqa + 2 * d * kv,
        "router": d * router_width(config),
        "expert": 3 * d * config["moe_intermediate_size"],
        "shared": 3 * d * config["moe_intermediate_size"]
        * config["n_shared_experts"],
    }


def layer_params(config: dict) -> dict:
    """Every parameter of a layer on this chip, by its mixer: the mixer
    (KDA's A_log, dt_bias, the gate's bias and the output norm among
    them), the two norms, the router, the held experts and the shared
    expert (the router's bias is a buffer)."""
    d, w, k = config["hidden_size"], layer_weights(config), _kda(config)
    ff = w["router"] + config["n_routed_experts"] * w["expert"] + w["shared"]
    kda = w["kda"] + k["heads"] + 2 * k["width"] + k["dim"]
    return {"kda": kda + ff + 2 * d, "gqa": w["gqa"] + ff + 2 * d}


def param_count(config: dict) -> int:
    """Every parameter on this chip: embedding and head (rows held), the
    final norm."""
    per = layer_params(config)
    return sum(per[k] for k in kinds(config)) \
        + 2 * config["vocab_size"] * config["hidden_size"] \
        + config["hidden_size"]


def pairs_per_token(config: dict) -> float:
    """(token, expert) pairs a token is expected to bring to the experts
    held here, a layer, under a balanced router."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / router_width(config))


def attention_flops_per_token(config: dict, seq_len: int) -> float:
    """Causal attention's two products over the half square, once forward
    and twice backward, a token of one layer at the heads held."""
    width = config["num_attention_heads"] * config["head_dim"]
    return 3 * 4 * width * (seq_len + 1) / 2


def kda_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the delta rule of one step needs, all KDA
    layers together, by the chunked form at chunk 64 whatever form runs:
    a chunk of C positions and a head of width d makes the pairs of keys
    and of queries with keys (2 C^2 d each), solves its triangle against
    ``[beta K e^g | beta V]`` (C^2 2d), multiplies the state three times
    (2 C d^2 each: W S, (Q e^g) S, K^T U) and the pairs once (2 C^2 d);
    the backward pass twice as much.  Bytes as the program hands them
    over: q, k, log alpha, beta and their gradients in float32, v, the
    output and their gradients in bf16, each read or written once: q, k,
    v, log alpha, beta read and o written forward; the same and do read,
    dq, dk, dv, dlog alpha and dbeta written backward."""
    k = _kda(config)
    c, d = CHUNK, k["dim"]
    calls = sum(kind == "kda" for kind in kinds(config)) * batch
    chunk_flops = 4 * c * c * d + c * c * 2 * d + 6 * c * d * d + 2 * c * c * d
    inputs = 4 * 2 * d + 2 * d + 4 * d + 4         # q, k; v; log alpha; beta
    forward_bytes = inputs + 2 * d
    backward_bytes = inputs + 2 * d + inputs
    rows = calls * seq_len * k["heads"]
    return {"flops": float(3 * chunk_flops * rows / c),
            "bytes": float(rows * (forward_bytes + backward_bytes))}


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    mixers' projections and taps, the router whole, the expected pairs on
    the held experts, the shared expert, the head; the lookup multiplies
    nothing), the GQA layers' two products over the causal half square
    once forward and twice backward, and the delta rule's work a token as
    ``kda_cost`` counts it.  Recomputed work counts for nothing."""
    w, ks = layer_weights(config), kinds(config)
    weights = (ks.count("kda") * w["kda"] + ks.count("gqa") * w["gqa"]
               + len(ks) * (w["router"] + w["shared"]
                            + pairs_per_token(config) * w["expert"])
               + config["vocab_size"] * config["hidden_size"])
    return (6.0 * weights
            + ks.count("gqa") * attention_flops_per_token(config, seq_len)
            + kda_cost(config, 1, seq_len)["flops"] / seq_len)


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy
# --------------------------------------------------------------------------
def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _kda_step(state, xs):
    q, k, v, log_alpha, beta = xs
    state = jnp.exp(log_alpha)[..., None] * state      # Diag(alpha) S
    # (I - beta k k^T) S' + beta k v^T = S' + beta k (v - S'^T k)^T
    new = v - jnp.einsum("hkv,hk->hv", state, k)
    state = state + beta[:, None, None] * k[..., None] * new[:, None, :]
    return state, jnp.einsum("hkv,hk->hv", state, q)


def kda_loop(q, k, v, log_alpha, beta):
    """The delta rule a position at a time, ``q, k, log_alpha [S, H, d]``,
    ``v [S, H, d]``, ``beta [S, H]``, from a zero state.  The state is
    kept every SEGMENT positions, so that ``jax.grad`` makes the states
    between again instead of keeping all of them."""
    seq, heads, dim = k.shape
    seg = SEGMENT if seq % SEGMENT == 0 else seq
    parts = tuple(a.astype(jnp.float32).reshape((seq // seg, seg)
                                                + a.shape[1:])
                  for a in (q, k, v, log_alpha, beta))
    segment = jax.checkpoint(lambda state, xs: jax.lax.scan(
        _kda_step, state, xs))
    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(segment, jnp.zeros(
            (heads, dim, v.shape[-1]), jnp.float32), parts)
    return y.reshape(v.shape)


@jax.jit
def reference_kda_grads(q, k, v, log_alpha, beta, w):
    """The delta rule on float32 inputs and the gradients of ``sum(o *
    w)``: ``(o, dq, dk, dv, dlog_alpha, dbeta)``."""
    o, vjp = jax.vjp(kda_loop, q, k, v, log_alpha, beta)
    return (o,) + vjp(w)


def causal_conv(x, weight):
    """``x [S, C]``, ``weight [C, W]``, no bias: a sum of W shifted
    copies."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(padded[i:i + seq] * weight[:, i] for i in range(width))


def _f32(p: dict, keep=()) -> dict:
    """The parameters in float32, but ``keep``, which a loop takes a slice
    of at a time and widens there: a layer held in bf16 is widened as it
    is used, and one held expert's matrices at a time."""
    return {k: v if k in keep else v.astype(jnp.float32)
            for k, v in p.items()}


def _l2(x, scale=1.0):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS) \
        * scale


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _kda_mixer(u, p, *, heads, eps):
    p = _f32({k: p[k] for k in MIXER["kda"]})
    with jax.default_matmul_precision("highest"):
        seq = u.shape[0]
        width = p["wq"].shape[1]
        dim = width // heads
        taps = jnp.split(p["conv"], 3, 0)
        q, k, v = (jax.nn.silu(causal_conv(u @ p[m], t)).reshape(
            seq, heads, dim) for m, t in zip(("wq", "wk", "wv"), taps))
        q, k = _l2(q, dim ** -0.5), _l2(k)
        log_alpha = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
            u @ p["wfa"] @ p["wfb"] + p["dt_bias"]).reshape(seq, heads, dim)
        beta = 2.0 * jax.nn.sigmoid(u @ p["wb"])
        o = kda_loop(q, k, v, log_alpha, beta)
        gate = jax.nn.sigmoid(u @ p["wga"] @ p["wgb"] + p["g_bias"])
        return (_rms_norm(o, p["o_norm"], eps).reshape(seq, width)
                * gate) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "block"))
def _gqa_mixer(u, p, *, heads, kv_heads, block):
    p = _f32({k: p[k] for k in MIXER["gqa"]})
    with jax.default_matmul_precision("highest"):
        seq = u.shape[0]
        q = (u @ p["wq"]).reshape(seq, heads, -1)
        k = (u @ p["wk"]).reshape(seq, kv_heads, -1)
        v = (u @ p["wv"]).reshape(seq, kv_heads, -1)
        k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))
        out = _attend(q, k, v, 1.0 / math.sqrt(q.shape[-1]), block)
        return (out.reshape(seq, -1) * jax.nn.sigmoid(u @ p["wg"])) @ p["wo"]


def route(y, router, bias, top_k: int):
    """(scores ``[S, E]``, the experts chosen ``[S, k]``): the k largest
    of ``sigmoid(y W_r) + b``."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(y @ router)
    return scores, jax.lax.top_k(scores + bias, top_k)[1]


@functools.partial(jax.jit, static_argnames=("top_k", "first", "scale",
                                             "given"))
def _moe(y, p, routing, *, top_k, first, scale, given):
    """(this chip's part of the routed experts' result plus the shared
    expert's, the experts chosen ``[S, k]``, pairs of each held expert):
    one held expert at a time on every token, weighted by the gate where
    the token chose it and by 0 where not."""
    with jax.default_matmul_precision("highest"):
        scores, own = route(y, p["router"], p["bias"], top_k)
        chosen = routing if given else own
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + GATE_EPS)

        def one_expert(acc, args):
            e, *ws = args
            w1, w3, w2 = (w.astype(jnp.float32) for w in ws)
            weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
            out = (jax.nn.silu(y @ w1) * (y @ w3)) @ w2
            return acc + weight[:, None] * out, (chosen == e).sum()

        held = p["w1"].shape[0]
        out, counts = jax.lax.scan(one_expert, jnp.zeros_like(y), (
            first + jnp.arange(held), p["w1"], p["w3"], p["w2"]))
        shared = (jax.nn.silu(y @ p["s1"]) * (y @ p["s3"])) @ p["s2"]
        return out + shared, chosen, counts


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight.astype(jnp.float32), eps)


PREFIX = "model.layers.{}."
NORMS = {"mixer_norm": "mixer.input_layernorm.weight",
         "moe_norm": "post_attention_layernorm.weight"}
MIXER = {
    "kda": {"wq": "q_proj.weight", "wk": "k_proj.weight",
            "wv": "v_proj.weight", "conv": "conv_weight",
            "wfa": "f_a_proj.weight", "wfb": "f_b_proj.weight",
            "A_log": "A_log", "dt_bias": "dt_bias", "wb": "b_proj.weight",
            "wga": "g_a_proj.weight", "wgb": "g_b_proj.weight",
            "g_bias": "g_bias", "o_norm": "o_norm.weight",
            "wo": "o_proj.weight"},
    "gqa": {"wq": "q_proj.weight", "wk": "k_proj.weight",
            "wv": "v_proj.weight", "wo": "o_proj.weight",
            "wg": "g_proj.weight"},
}
MIXER_AT = {"kda": "mixer.linear_attn.", "gqa": "mixer.self_attn."}
MOE = {"router": "mlp.gate.weight", "bias": "mlp.e_score_correction_bias",
       "w1": "mlp.experts.w1", "w3": "mlp.experts.w3",
       "w2": "mlp.experts.w2", "s1": "mlp.shared_gate.weight",
       "s3": "mlp.shared_up.weight", "s2": "mlp.shared_down.weight"}
EMBEDDING = "model.embed_tokens.weight"      # [rows held, hidden]
FINAL_NORM = "model.norm.weight"
HEAD = "lm_head.weight"                      # [hidden, rows held]


def _names(config: dict, i: int) -> dict:
    kind = kinds(config)[i]
    return {**{k: PREFIX.format(i) + n for k, n in NORMS.items()},
            **{k: PREFIX.format(i) + MIXER_AT[kind] + n
               for k, n in MIXER[kind].items()},
            **{k: PREFIX.format(i) + n for k, n in MOE.items()}}


def layer_parameters(config: dict, i: int) -> list:
    """The names of held layer ``i``'s parameters (its router's bias is a
    buffer)."""
    return [n for k, n in _names(config, i).items() if k != "bias"]


def reference_forward(param, config: dict, ids, routing=None,
                      block: int = QUERY_BLOCK, layers=None,
                      stream=None) -> dict:
    """One sequence of token ids through the reference.  ``param(name,
    rows=None)`` returns the program's parameter or buffer of that name
    (or the given rows of it) as float32, a layer at a time.  ``routing``
    gives the experts chosen, a layer at a time; left out, the reference
    routes for itself.  ``layers`` (first, end) runs those layers only,
    from ``stream`` where one is given instead of the embedding's rows.
    Returns ``stream`` (after the last layer run), ``hidden`` (what the
    head multiplies, ``[S, hidden]``) and, a layer at a time, ``experts``
    (``[S, k]``) and ``counts`` (``[held]``)."""
    eps = float(config["rms_norm_eps"])
    all_kinds = kinds(config)
    first, end = layers or (0, len(all_kinds))
    h = param(EMBEDDING, ids).astype(jnp.float32) if stream is None \
        else stream
    out = {"experts": [], "counts": []}
    k = config["num_experts_per_tok"]
    for i in range(first, end):
        p = {key: param(name) for key, name in _names(config, i).items()}
        u = _norm(h, p["mixer_norm"], eps=eps)
        if all_kinds[i] == "kda":
            h = h + _kda_mixer(u, p, heads=config["num_attention_heads"],
                               eps=eps)
        else:
            h = h + _gqa_mixer(u, p, heads=config["num_attention_heads"],
                               kv_heads=config["num_key_value_heads"],
                               block=block)
        y = _norm(h, p["moe_norm"], eps=eps)
        chosen = (jnp.zeros((h.shape[0], k), jnp.int32) if routing is None
                  else jnp.asarray(routing[i]))
        mixed, experts, counts = _moe(
            y, p, chosen, top_k=k, first=config["experts_held"][0],
            scale=float(config["routed_scaling_factor"]),
            given=routing is not None)
        out["experts"].append(experts)
        out["counts"].append(counts)
        h = h + mixed
    out["stream"] = h
    out["hidden"] = _norm(h, param(FINAL_NORM), eps=eps)
    return out


def reference_logits(hidden, head_rows):
    """Logits ``[S, rows]`` for some rows of the head (float32 ``[rows,
    hidden]``, the head's matrix transposed): a part of the vocabulary at
    a time."""
    with jax.default_matmul_precision("highest"):
        return hidden @ head_rows.T


def _cross_entropy(hidden, head, labels):
    logp = jax.nn.log_softmax(reference_logits(hidden, head.T), -1)
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
        loss = loss + _cross_entropy(hidden, params[HEAD], labels[b])
    return loss / ids.shape[0]


def checked_layers(config: dict) -> tuple:
    """The layers whose gradients ``train_solar_lm`` holds against the
    reference's: the first GQA layer and the first KDA layer."""
    ks = kinds(config)
    return tuple(sorted(ks.index(k) for k in ("gqa", "kda")))


def reference_layer_grads(param, config: dict, ids, labels, routing,
                          layers, block: int = QUERY_BLOCK) -> dict:
    """The gradients of one sequence's mean cross-entropy by every
    parameter of the held layers ``layers``, by name, given the experts
    chosen: what the whole model's gradient holds for these parameters.
    The layers before the first of them run forward only; every other
    parameter, the head among them, is held as it is."""
    first, end = min(layers), len(kinds(config))
    stream = reference_forward(param, config, ids, routing, block,
                               layers=(0, first))["stream"]

    def loss_of(own):
        def param_(name, rows=None):
            return own[name] if name in own else param(name, rows)

        hidden = reference_forward(param_, config, ids, routing, block,
                                   layers=(first, end),
                                   stream=stream)["hidden"]
        return _cross_entropy(hidden, param(HEAD), labels)

    return jax.grad(loss_of)({name: param(name) for i in layers
                              for name in layer_parameters(config, i)})


class rounded_through:
    """This family with its reference computed in ``dtype``, the control
    of ``train_solar_lm``'s limits: every weight the reference reads, and the
    inputs of a kernel's reference that the program holds in bf16 (the
    delta rule's q, k, v, log alpha and beta; attention's q, k, v),
    rounded through ``dtype`` and back to float32.  Everything else is
    the family's.  With ``float8_e4m3fn``, the nearest precision below the
    configuration's bf16, each of ``train_solar_lm``'s checks of values has to
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
            param(name, rows) if name.endswith(MOE["bias"])
            else self._round(param(name, rows)))

    def reference_forward(self, param, config, ids, *args, **kw):
        return reference_forward(self._rounded(param), config, ids, *args,
                                 **kw)

    def reference_logits(self, hidden, head_rows):
        return reference_logits(hidden, self._round(head_rows))

    def reference_layer_grads(self, param, config, ids, labels, *args, **kw):
        return reference_layer_grads(self._rounded(param), config, ids,
                                     labels, *args, **kw)

    def reference_kda_grads(self, q, k, v, log_alpha, beta, w):
        r = self._round
        return reference_kda_grads(r(q), r(k), r(v), r(log_alpha), r(beta),
                                   w)

    def reference_attention_grads(self, q, k, v, w, *, scale):
        r = self._round
        return reference_attention_grads(r(q), r(k), r(v), w, scale=scale)

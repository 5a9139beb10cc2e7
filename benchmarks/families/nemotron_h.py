"""Nemotron-H (``nemotron_h``: NVIDIA-Nemotron-3-Nano-30B-A3B) as the
benchmark knows it, from its published ``config.json`` and the equations
of ISSUE 34 (HF ``modeling_nemotron_h.py``, arXiv:2504.03624), not from
the program: counts from shapes, and a plain float32 reference of the
forward pass, its loss and (by ``jax.grad``) its gradients.

    h = E[ids]
    block l:  h += mixer_l(RMSNorm(h; w_l)),  the mixer by the pattern's
              character: M Mamba-2, E experts, * attention
    logits = RMSNorm(h; norm_f) W_head               (untied, the rows held)

``M``, H heads of width P in G groups, state N:

    [z, xBC, dt] = W_in u          (H P | H P + 2 G N | H)
    xBC = silu(conv(xBC))          depthwise, causal, width 4, with bias
    [x, B, C] = split(xBC)         (H P | G N | G N); head h reads group h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)                  a head
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;  y_t = H_t C_t + D x_t
    y = RMSNorm_g(y * silu(z)) w   the mean square over each group's H P / G channels
    out = W_out y

``*``: 32 query heads on 2 key/value heads of width 128, no bias, no
positions; causal ``softmax(q . k / sqrt(128)) v``.
``E``, k of the experts a token, the held ones computed here:

    s = sigmoid(u W_r);  T = the k largest of s + b
    g_e = routed_scaling_factor s_e / (sum of s over T + 1e-20)
    out = sum over e in T held here of g_e W2_e relu(W1_e u)^2 + W2_s relu(W1_s u)^2

(b, ``e_score_correction_bias``, is read as the program holds it.  What
moves it between passes is the balancing rule, ``balanced_bias``: after a
pass in training, ``b_e += rate * sign(mean load - load_e)`` over the
tokens' choices among all experts.)

The reference runs the recurrence as it stands, a position at a time
(the Granite family's ``selective_scan``, which takes groups), attention a
block of queries at a time, and the experts as a loop over the held ones
on every token, weighted by the gate where the token chose the expert
and by 0 where not.  Configuration keys are those of the published
``config.json``; ``vocab_size``, ``num_hidden_layers`` (with the
pattern) and ``n_routed_experts`` are what this chip holds, as the
configuration file states them; ``experts_held`` is (first, count) and
``published["n_routed_experts"]`` the router's width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .granite_hybrid import (QUERY_BLOCK, _attend, causal_conv,  # noqa: F401
                             reference_attention_grads, reference_scan_grads,
                             selective_scan)

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def kinds(config: dict) -> list:
    return [KINDS[ch] for ch in config["hybrid_override_pattern"]]


def router_width(config: dict) -> int:
    """The experts the router chooses among: the published count."""
    return config["published"]["n_routed_experts"]


def _mamba(config: dict) -> dict:
    heads, width = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner = heads * width
    return {"heads": heads, "width": width, "groups": groups,
            "state": state, "inner": inner,
            "conv": inner + 2 * groups * state, "chunk": config["chunk_size"]}


def layer_weights(config: dict) -> dict:
    """Matrix weights by part: what a token multiplies in a Mamba-2 and
    in an attention block; in an expert block the router, the shared
    expert, and one routed expert."""
    d, m = config["hidden_size"], _mamba(config)
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    return {
        "mamba": d * (m["inner"] + m["conv"] + m["heads"])
        + m["conv"] * config["conv_kernel"] + m["inner"] * d,
        "attention": 2 * d * q + 2 * d * kv,
        "router": d * router_width(config),
        "shared": 2 * d * config["moe_shared_expert_intermediate_size"],
        "expert": 2 * d * config["moe_intermediate_size"],
    }


def param_count(config: dict) -> int:
    """Every parameter on this chip (the router's bias is a buffer)."""
    d = config["hidden_size"]
    w, m = layer_weights(config), _mamba(config)
    block = {"mamba": w["mamba"] + m["conv"] + 3 * m["heads"] + m["inner"],
             "attention": w["attention"],
             "moe": w["router"] + w["shared"]
             + config["n_routed_experts"] * w["expert"]}
    return sum(block[kind] + d for kind in kinds(config)) \
        + 2 * config["vocab_size"] * d + d


def pairs_per_token(config: dict) -> float:
    """(token, expert) pairs a token is expected to bring to the experts
    held here, an expert block, under a balanced router."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / router_width(config))


def scan_flops_per_token(config: dict) -> float:
    """The scan's products for one token of one block, forward, in the
    chunked form at the published chunk Q with the causal half inside a
    chunk: ``C . B`` over (Q + 1) / 2 pairs a group, that many rows of
    ``[Q, Q] x [Q, P]`` a head, and a token's part of its chunk's state
    and of what the state adds to y, 2 P N each a head."""
    m = _mamba(config)
    pairs = (m["chunk"] + 1) / 2
    return (pairs * 2 * (m["groups"] * m["state"] + m["inner"])
            + 4 * m["inner"] * m["state"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    router and the shared expert whole, the expected pairs on the held
    experts, the head; the lookup multiplies nothing), the attention
    blocks' two products over the causal half square once forward and
    twice backward, and the scan's products likewise.  Recomputed work
    counts for nothing."""
    w, blocks = layer_weights(config), kinds(config)
    weights = (blocks.count("mamba") * w["mamba"]
               + blocks.count("attention") * w["attention"]
               + blocks.count("moe") * (
                   w["router"] + w["shared"]
                   + pairs_per_token(config) * w["expert"])
               + config["vocab_size"] * config["hidden_size"])
    attention = (3 * 4 * config["num_attention_heads"] * config["head_dim"]
                 * (seq_len + 1) / 2)
    return (6.0 * weights + blocks.count("attention") * attention
            + blocks.count("mamba") * 3 * scan_flops_per_token(config))


def scan_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the scans of one step need whatever
    implements them, all Mamba-2 blocks together: the products above once
    forward and twice backward; x, B, C (bf16) and dt (float32) read and
    y written once forward; x, B, C, dt and dy read and the four
    gradients written once backward."""
    m = _mamba(config)
    calls = kinds(config).count("mamba") * batch
    wide, narrow = 2 * m["inner"], 2 * 2 * m["groups"] * m["state"]
    steps = 4 * m["heads"]
    forward = wide + narrow + steps + wide
    backward = 2 * wide + narrow + steps + wide + narrow + steps
    return {"flops": float(calls * seq_len * 3 * scan_flops_per_token(config)),
            "bytes": float(calls * seq_len * (forward + backward))}


def experts_cost(config: dict, pairs: float) -> dict:
    """Operations and bytes of the held routed experts of one step over
    ``pairs`` (token, expert) pairs, all expert blocks together: 6 a
    weight and pair, two matrices a pair; every held expert's weights
    read forward, read backward and their gradient written, in bf16; a
    pair's rows (in, hidden, squared hidden, out) once forward and twice
    backward."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    w = layer_weights(config)["expert"]
    return {"flops": 6.0 * w * pairs,
            "bytes": float(kinds(config).count("moe")
                           * config["n_routed_experts"] * w * 2 * 3
                           + pairs * 3 * 2 * (2 * d + 2 * f))}


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy
# --------------------------------------------------------------------------
def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def gated_norm(y, z, weight, groups: int, eps: float):
    """``RMSNorm_g(y * silu(z)) w``: the gate first, then the norm with
    its mean square over each of ``groups`` groups of channels."""
    gated = (y * jax.nn.silu(z)).reshape(y.shape[0], groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + eps)
    return normed.reshape(y.shape) * weight


@functools.partial(jax.jit, static_argnames=("heads", "groups", "state",
                                             "eps"))
def _mamba_mixer(u, p, *, heads, groups, state, eps):
    with jax.default_matmul_precision("highest"):
        seq = u.shape[0]
        inner = p["norm"].shape[0]
        z, xbc, dt = jnp.split(
            u @ p["in_proj"], (inner, 2 * inner + 2 * groups * state), -1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, b, c = jnp.split(xbc, (inner, inner + groups * state), -1)
        y = selective_scan(
            xs.reshape(seq, heads, inner // heads),
            jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
            b.reshape(seq, groups, state), c.reshape(seq, groups, state),
            p["D"])
        y = gated_norm(y.reshape(seq, inner), z, p["norm"], groups, eps)
        return y @ p["out_proj"]


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "block"))
def _attention_mixer(u, p, *, heads, kv_heads, block):
    with jax.default_matmul_precision("highest"):
        seq = u.shape[0]
        q = (u @ p["wq"]).reshape(seq, heads, -1)
        k, v = (jnp.repeat((u @ p[w]).reshape(seq, kv_heads, -1),
                           heads // kv_heads, axis=1) for w in ("wk", "wv"))
        scale = 1.0 / q.shape[-1] ** 0.5
        return _attend(q, k, v, scale, block).reshape(seq, -1) @ p["wo"]


def route(u, router, bias, top_k: int, scale: float):
    """(scores ``[S, E]``, the experts chosen ``[S, k]``): the k largest
    of ``sigmoid(u W_r) + b``."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(u @ router)
    return scores, jax.lax.top_k(scores + bias, top_k)[1]


@functools.partial(jax.jit, static_argnames=("top_k", "first", "scale",
                                             "given"))
def _moe_mixer(u, p, routing, *, top_k, first, scale, given):
    """(this chip's part of the block's result: its routed experts' and
    the shared expert's, the experts chosen ``[S, k]``, pairs of each held
    expert): one held expert at a time on every token, weighted by the
    gate where the token chose it and by 0 where not."""
    with jax.default_matmul_precision("highest"):
        scores, own = route(u, p["router"], p["bias"], top_k, scale)
        chosen = routing if given else own
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
        gates = scale * gates / (gates.sum(-1, keepdims=True) + 1e-20)

        def one_expert(acc, args):
            e, w1, w2 = args
            weight = jnp.where(chosen == e, gates, 0.0).sum(-1)
            return (acc + weight[:, None] * (_relu2(u @ w1) @ w2),
                    (chosen == e).sum())

        held = p["w1"].shape[0]
        routed, counts = jax.lax.scan(one_expert, jnp.zeros_like(u), (
            first + jnp.arange(held), p["w1"], p["w2"]))
        shared = _relu2(u @ p["shared_up"]) @ p["shared_down"]
        return routed + shared, chosen, counts


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight, eps)


PREFIX = "backbone.layers.{}."
NORM = "norm.weight"
MIXER = {
    "mamba": {"in_proj": "mixer.in_proj.weight",
              "conv_w": "mixer.conv1d.weight", "conv_b": "mixer.conv1d.bias",
              "dt_bias": "mixer.dt_bias", "A_log": "mixer.A_log",
              "D": "mixer.D", "norm": "mixer.norm.weight",
              "out_proj": "mixer.out_proj.weight"},
    "attention": {"wq": "mixer.q_proj.weight", "wk": "mixer.k_proj.weight",
                  "wv": "mixer.v_proj.weight", "wo": "mixer.o_proj.weight"},
    "moe": {"router": "mixer.gate.weight",
            "bias": "mixer.e_score_correction_bias",
            "w1": "mixer.experts.w1", "w2": "mixer.experts.w2",
            "shared_up": "mixer.shared_up.weight",
            "shared_down": "mixer.shared_down.weight"},
}
EMBEDDING = "backbone.embeddings.weight"     # [rows held, hidden]
FINAL_NORM = "backbone.norm_f.weight"
HEAD = "lm_head.weight"                      # [hidden, rows held]


def reference_forward(param, config: dict, ids, routing=None,
                      block: int = QUERY_BLOCK, blocks=None,
                      stream=None) -> dict:
    """One sequence of token ids through the reference.  ``param(name,
    rows=None)`` returns the program's parameter or buffer of that name
    (or the given rows of it) as float32, a block at a time.  ``routing``
    gives the experts chosen, an expert block at a time, in the blocks'
    order; left out, the reference routes for itself.  ``blocks`` (first,
    end) runs those blocks only, from ``stream`` where one is given
    instead of the embedding's rows.  Returns ``stream`` (the residual
    stream after the last block run), ``hidden`` (what the head
    multiplies, ``[S, hidden]``) and, an expert block at a time,
    ``experts`` (``[S, k]``) and ``counts`` (``[held]``)."""
    eps = float(config["layer_norm_epsilon"])
    m = _mamba(config)
    all_kinds = kinds(config)
    first, end = blocks or (0, len(all_kinds))
    h = param(EMBEDDING, ids) if stream is None else stream
    out = {"experts": [], "counts": []}
    for i in range(first, end):
        kind = all_kinds[i]
        prefix = PREFIX.format(i)
        p = {k: param(prefix + n) for k, n in MIXER[kind].items()}
        u = _norm(h, param(prefix + NORM), eps=eps)
        if kind == "mamba":
            mixed = _mamba_mixer(u, p, heads=m["heads"], groups=m["groups"],
                                 state=m["state"], eps=eps)
        elif kind == "attention":
            mixed = _attention_mixer(
                u, p, heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"], block=block)
        else:
            k = config["num_experts_per_tok"]
            at = all_kinds[:i].count("moe")
            chosen = (jnp.zeros((ids.shape[0], k), jnp.int32)
                      if routing is None else jnp.asarray(routing[at]))
            mixed, experts, counts = _moe_mixer(
                u, p, chosen, top_k=k, first=config["experts_held"][0],
                scale=float(config["routed_scaling_factor"]),
                given=routing is not None)
            out["experts"].append(experts)
            out["counts"].append(counts)
        h = h + mixed
    out["stream"] = h
    out["hidden"] = _norm(h, param(FINAL_NORM), eps=eps)
    return out


def block_parameters(config: dict, i: int) -> list:
    """The names of block ``i``'s parameters (its router's bias is a
    buffer)."""
    prefix = PREFIX.format(i)
    return [prefix + NORM] + [prefix + n for k, n in MIXER[
        kinds(config)[i]].items() if k != "bias"]


def reference_tail_grads(param, config: dict, ids, labels, routing,
                         first: int, block: int = QUERY_BLOCK) -> dict:
    """The gradients of one sequence's mean cross-entropy by every
    parameter of the blocks from ``first`` on, by name, given the experts
    chosen: what the whole model's gradient holds for these parameters.
    The blocks before them run forward only; the final norm and the head
    are held as they are."""
    end = len(kinds(config))
    stream = reference_forward(param, config, ids, routing, block,
                               blocks=(0, first))["stream"]
    head = param(HEAD)

    def loss_of(tail):
        def param_(name, rows=None):
            return tail[name] if name in tail else param(name, rows)

        hidden = reference_forward(param_, config, ids, routing, block,
                                   blocks=(first, end),
                                   stream=stream)["hidden"]
        logp = jax.nn.log_softmax(reference_logits(hidden, head), -1)
        return -jnp.take_along_axis(logp, labels[:, None], 1).mean()

    return jax.grad(loss_of)({name: param(name) for i in range(first, end)
                              for name in block_parameters(config, i)})


def balanced_bias(bias, chosen, rate: float):
    """One step of the balancing rule on a router's bias ``[E]`` from the
    experts a pass's tokens chose ``[S, k]``: up by ``rate`` where an
    expert drew fewer tokens than the experts' mean, down where more."""
    load = jnp.zeros(bias.shape, jnp.float32).at[chosen.reshape(-1)].add(1.0)
    return bias + rate * jnp.sign(load.mean() - load)


def reference_logits(hidden, head_columns):
    """Logits ``[S, columns]`` for some columns of the untied head
    (float32 ``[hidden, columns]``): a part of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        return hidden @ head_columns


def reference_loss(params: dict, config: dict, ids, labels, **kw):
    """Mean cross-entropy of a batch ``ids``/``labels`` ``[B, S]`` from a
    dict of float32 parameters and buffers by the program's names:
    differentiable, for the small sizes of the tests."""
    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    loss = 0.0
    for b in range(ids.shape[0]):
        hidden = reference_forward(param, config, ids[b], **kw)["hidden"]
        logp = jax.nn.log_softmax(reference_logits(hidden, params[HEAD]), -1)
        loss = loss - jnp.take_along_axis(logp, labels[b][:, None], 1).mean()
    return loss / ids.shape[0]

"""Granite 4.0-H (``granitemoehybrid`` with no routed experts) as the
benchmark knows it, from its published ``config.json`` and the equations
of ISSUE 32 (HF ``modeling_granitemoehybrid.py``), not from the program:
counts from shapes, and a plain float32 reference of the forward pass,
its loss and (by ``jax.grad``) its gradients.

    h = E[ids] * embedding_multiplier
    layer:  h += residual_multiplier * Mixer(RMSNorm(h))
            h += residual_multiplier * W_out(silu(a) * b), [a, b] = W_in RMSNorm(h)
    logits = RMSNorm(h) E^T / logits_scaling          (E tied, the rows held)

Mixer ``attention``: 32 query heads on 8 key/value heads, no bias, no
positions; scores ``q . k * attention_multiplier``, causal softmax.
Mixer ``mamba`` (Mamba-2), H heads of width P in G groups, state N:

    [z, xBC, dt] = W_in x          (d_inner | d_inner + 2 G N | H)
    xBC = silu(conv(xBC))          depthwise, causal, width 4, with bias
    [x, B, C] = split(xBC)         (d_inner | G N | G N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)                  a head
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t;  y_t = H_t C_t + D x_t
    y = RMSNorm(y * silu(z)) w     over all of d_inner (one group)
    out = W_out y

The reference runs the recurrence as it stands, a position at a time
(``lax.scan``, one ``[P, N]`` state a head): no chunks, no kernels, no
recomputation.  Attention takes a block of queries at a time.
Configuration keys are those of the published ``config.json``;
``vocab_size`` and ``num_hidden_layers`` are what this chip holds, as the
configuration file states them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SCAN_SEGMENT = 128       # positions whose states a gradient keeps at once


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def _mamba(config: dict) -> dict:
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    inner = heads * width
    return {"heads": heads, "width": width, "groups": groups,
            "state": state, "inner": inner,
            "conv": inner + 2 * groups * state,
            "chunk": config["mamba_chunk_size"]}


def layer_weights(config: dict) -> dict:
    """Matrix weights a token multiplies, by part of a layer."""
    d, ff = config["hidden_size"], config["shared_intermediate_size"]
    m = _mamba(config)
    kv = (config["num_key_value_heads"] * d
          // config["num_attention_heads"])
    return {
        "mamba": d * (m["inner"] + m["conv"] + m["heads"])
        + m["conv"] * config["mamba_d_conv"] + m["inner"] * d,
        "attention": 2 * d * d + 2 * d * kv,
        "mlp": d * 2 * ff + ff * d,
    }


def param_count(config: dict) -> int:
    """Every parameter on this chip; the tied matrix once, its rows
    held."""
    d = config["hidden_size"]
    w, m = layer_weights(config), _mamba(config)
    small = {"mamba": m["conv"] + 3 * m["heads"] + m["inner"],
             "attention": 0}
    return sum(w[kind] + small[kind] + w["mlp"] + 2 * d
               for kind in config["layer_types"]) \
        + config["vocab_size"] * d + d


def scan_flops_per_token(config: dict) -> float:
    """The scan's products for one token of one layer, forward, in the
    chunked form at the published chunk Q with the causal half inside a
    chunk: ``C . B`` over (Q + 1) / 2 pairs a group, that many rows of
    ``[Q, Q] x [Q, P]`` a head, and a token's part of its chunk's state
    and of what the state adds to y, 2 P N each a head."""
    m = _mamba(config)
    pairs = (m["chunk"] + 1) / 2
    return (pairs * 2 * (m["groups"] * m["state"]
                         + m["heads"] * m["width"])
            + 4 * m["heads"] * m["width"] * m["state"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    tied matrix once, for the head; the lookup multiplies nothing), the
    attention layers' two products over the causal half square once
    forward and twice backward, and the scan's products likewise.
    Recomputed work counts for nothing."""
    w = layer_weights(config)
    kinds = config["layer_types"]
    weights = sum(w[kind] + w["mlp"] for kind in kinds) \
        + config["vocab_size"] * config["hidden_size"]
    attention = 3 * 4 * config["hidden_size"] * (seq_len + 1) / 2
    return (6.0 * weights + kinds.count("attention") * attention
            + kinds.count("mamba") * 3 * scan_flops_per_token(config))


def scan_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the scans of one step need whatever
    implements them, all Mamba layers together: the products above once
    forward and twice backward; x, B, C (bf16) and dt (float32) read and
    y written once forward; x, B, C, dt and dy read and the four
    gradients written once backward."""
    m = _mamba(config)
    calls = config["layer_types"].count("mamba") * batch
    wide, narrow = 2 * m["inner"], 2 * 2 * m["groups"] * m["state"]
    steps = 4 * m["heads"]
    forward = wide + narrow + steps + wide
    backward = 2 * wide + narrow + steps + wide + narrow + steps
    return {"flops": float(calls * seq_len * 3 * scan_flops_per_token(config)),
            "bytes": float(calls * seq_len * (forward + backward))}


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy, the recurrence as it stands
# --------------------------------------------------------------------------
def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def causal_conv(x, weight, bias):
    """``out[t, c] = bias[c] + sum_k weight[c, k] x[t - (W - 1) + k, c]``,
    ``x [S, C]``, ``weight [C, W]``; before the sequence there is 0."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return bias + sum(padded[k:k + seq] * weight[:, k] for k in range(width))


def _recurrence(state, x, dt, A, B, C):
    """Some positions of the recurrence from ``state [H, P, N]``: the
    state after them and ``H_t C_t`` of each."""
    def step(h, args):
        x_t, dt_t, b_t, c_t = args
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, (h * c_t[:, None, :]).sum(-1)

    return jax.lax.scan(step, state, (x, dt, B, C))


def selective_scan(x, dt, A, B, C, D):
    """``y [S, H, P]`` of ``x [S, H, P]``, ``dt [S, H]``, ``A [H]``, ``B``
    and ``C [S, G, N]``, ``D [H]``, float32, a position at a time.  A
    gradient keeps the states of ``SCAN_SEGMENT`` positions at once and
    the state each segment starts from."""
    seq, heads, width = x.shape
    rep = heads // B.shape[1]
    B, C = (jnp.repeat(a, rep, axis=1) for a in (B, C))
    seg = min(SCAN_SEGMENT, seq)
    while seq % seg:
        seg -= 1
    parts = tuple(a.reshape((seq // seg, seg) + a.shape[1:])
                  for a in (x, dt, B, C))
    segment = jax.checkpoint(
        lambda state, args: _recurrence(state, *args[:2], A, *args[2:]))
    _, y = jax.lax.scan(
        segment, jnp.zeros((heads, width, B.shape[-1]), x.dtype), parts)
    return y.reshape(x.shape) + D[:, None] * x


@functools.partial(jax.jit, static_argnames=("heads", "groups", "state",
                                             "eps"))
def _mamba_mixer(x, p, *, heads, groups, state, eps):
    with jax.default_matmul_precision("highest"):
        seq = x.shape[0]
        inner = p["norm"].shape[0]
        z, xbc, dt = jnp.split(
            x @ p["in_proj"], (inner, 2 * inner + 2 * groups * state), -1)
        xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
        xs, b, c = jnp.split(xbc, (inner, inner + groups * state), -1)
        y = selective_scan(
            xs.reshape(seq, heads, inner // heads),
            jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
            b.reshape(seq, groups, state), c.reshape(seq, groups, state),
            p["D"])
        y = _rms_norm(y.reshape(seq, inner) * jax.nn.silu(z), p["norm"], eps)
        return y @ p["out_proj"]


def _attend(q, k, v, scale, block):
    """Causal attention of ``q [S, H, D]`` over ``k, v [S, H, D]`` at
    ``scale``, a block of queries at a time against all keys."""
    seq = q.shape[0]
    block = min(block, seq)

    def one_block(args):
        first, q_b = args
        scores = jnp.einsum("bhd,shd->hbs", q_b, k) * scale
        keep = jnp.arange(seq)[None, :] <= first + jnp.arange(
            q_b.shape[0])[:, None]
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return jnp.einsum("hbs,shd->bhd", probs, v)

    n = seq // block
    out = jax.lax.map(one_block, (
        jnp.arange(n) * block, q.reshape((n, block) + q.shape[1:])))
    return out.reshape(q.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "block"))
def _attention_mixer(x, p, *, heads, kv_heads, scale, block):
    with jax.default_matmul_precision("highest"):
        seq = x.shape[0]
        q = (x @ p["wq"]).reshape(seq, heads, -1)
        k, v = (jnp.repeat((x @ p[w]).reshape(seq, kv_heads, -1),
                           heads // kv_heads, axis=1) for w in ("wk", "wv"))
        return _attend(q, k, v, scale, block).reshape(seq, -1) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp(h, p, *, eps):
    with jax.default_matmul_precision("highest"):
        a, b = jnp.split(_rms_norm(h, p["ln2"], eps) @ p["mlp_in"], 2, -1)
        return (jax.nn.silu(a) * b) @ p["mlp_out"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, *, eps):
    return _rms_norm(x, weight, eps)


PREFIX = "model.layers.{}."
SHARED = {"ln1": "input_layernorm.weight",
          "ln2": "post_attention_layernorm.weight",
          "mlp_in": "shared_mlp.input_linear.weight",
          "mlp_out": "shared_mlp.output_linear.weight"}
MIXER = {
    "mamba": {"in_proj": "mamba.in_proj.weight",
              "conv_w": "mamba.conv1d.weight", "conv_b": "mamba.conv1d.bias",
              "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log",
              "D": "mamba.D", "norm": "mamba.norm.weight",
              "out_proj": "mamba.out_proj.weight"},
    "attention": {"wq": "self_attn.q_proj.weight",
                  "wk": "self_attn.k_proj.weight",
                  "wv": "self_attn.v_proj.weight",
                  "wo": "self_attn.o_proj.weight"},
}
EMBEDDING = "model.embed_tokens.weight"      # [rows held, hidden], tied
FINAL_NORM = "model.norm.weight"


def reference_hidden(param, config: dict, ids, block: int = QUERY_BLOCK):
    """What the head multiplies, ``[S, hidden]`` float32, for one
    sequence of token ids.  ``param(name, rows=None)`` returns the
    program's parameter of that name (or the given rows of it) as
    float32, a layer at a time."""
    eps = float(config["rms_norm_eps"])
    scale = float(config["residual_multiplier"])
    m = _mamba(config)
    h = param(EMBEDDING, ids) * float(config["embedding_multiplier"])
    for i, kind in enumerate(config["layer_types"]):
        p = {k: param(PREFIX.format(i) + n)
             for k, n in {**SHARED, **MIXER[kind]}.items()}
        x = _norm(h, p["ln1"], eps=eps)
        if kind == "mamba":
            mixed = _mamba_mixer(x, p, heads=m["heads"], groups=m["groups"],
                                 state=m["state"], eps=eps)
        else:
            mixed = _attention_mixer(
                x, p, heads=config["num_attention_heads"],
                kv_heads=config["num_key_value_heads"],
                scale=float(config["attention_multiplier"]), block=block)
        h = h + scale * mixed
        h = h + scale * _mlp(h, p, eps=eps)
    return _norm(h, param(FINAL_NORM), eps=eps)


def reference_logits(hidden, embedding_rows, config: dict):
    """Logits ``[S, rows]`` for some rows of the tied matrix (float32
    ``[rows, hidden]``): a part of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding_rows.T / float(config["logits_scaling"])


def reference_loss(params: dict, config: dict, ids, labels):
    """Mean cross-entropy of a batch ``ids``/``labels`` ``[B, S]`` from a
    dict of float32 parameters by the program's names: differentiable,
    for the small sizes of the tests."""
    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    loss = 0.0
    for b in range(ids.shape[0]):
        logits = reference_logits(reference_hidden(param, config, ids[b]),
                                  params[EMBEDDING], config)
        logp = jax.nn.log_softmax(logits, axis=-1)
        loss = loss - jnp.take_along_axis(logp, labels[b][:, None], 1).mean()
    return loss / ids.shape[0]


@jax.jit
def reference_scan_grads(x, dt, A, B, C, D, w):
    """The recurrence on float32 inputs and the gradients of ``sum(y *
    w)``: ``(y, dx, ddt, dA, dB, dC, dD)``."""
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(selective_scan, x, dt, A, B, C, D)
        return (y,) + vjp(w)


@functools.partial(jax.jit, static_argnames=("scale",))
def reference_attention_grads(q, k, v, w, *, scale):
    """Plain causal attention of q, w ``[H, S, D]`` over k, v ``[G, S,
    D]`` (float32) at ``scale`` and the gradients of ``sum(out * w)``:
    ``(out, dq, dk, dv)``.  One head at a time, forward and backward, so
    that one head's squares are all that is alive."""
    heads, groups = q.shape[0], k.shape[0]
    rep = heads // groups
    keep = jnp.tril(jnp.ones((q.shape[1],) * 2, bool))

    def one_head(q_, k_, v_):
        with jax.default_matmul_precision("highest"):
            scores = (q_ @ k_.T) * scale
            return jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1) @ v_

    def one(args):
        q_, w_, g = args
        out, vjp = jax.vjp(one_head, q_, k[g], v[g])
        return (out,) + vjp(w_)

    out, dq, dk, dv = jax.lax.map(one, (q, w, jnp.arange(heads) // rep))
    dk, dv = (a.reshape((groups, rep) + a.shape[1:]).sum(1) for a in (dk, dv))
    return out, dq, dk, dv

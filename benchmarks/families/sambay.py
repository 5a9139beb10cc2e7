"""SambaY (``model_type`` phi4flash: Phi-4-mini-flash-reasoning) as the
benchmark knows it, from its published ``config.json``, the papers
(arXiv:2507.06607 SambaY, arXiv:2410.05258 differential attention,
arXiv:2312.00752 Mamba) and the equations of ISSUE 38, not from the
program: counts from shapes, and a plain float32 reference of the forward
pass, its loss and (by ``jax.grad``) its gradients.

    h = E[ids]
    layer:  h += mixer(LN1(h));  h += W2 (up * silu(gate)), [gate, up] = W1 LN2(h)
    logits = LN(h) E^T                      (E tied, the rows held; LN with bias)

Layer ``l`` of ``n_self + 2 + n_cross`` (``config["layers"]``): below
``n_self`` even ``l`` is ``mamba`` and odd ``swa``; ``l = n_self`` is
``mamba_memory`` (a Mamba layer whose scan output m is handed on), ``l =
n_self + 1`` is ``full_kv`` (full causal attention whose K and V are
handed on); above, even ``l`` is ``gmu`` (on m) and odd ``cross`` (on that
K and V).

Mamba-1, ``C = expand x hidden`` channels, state N, rank R:

    [x, z] = W_in u;  x = silu(conv(x) + b)          depthwise, causal, width 4
    [d, B, C] = W_x x  (R | N | N);  dt = softplus(W_dt d + b_dt);  A = -exp(A_log)  [C, N]
    s_t = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t^T;  y_t = s_t C_t + D * x_t
    out = W_out (y * silu(z));                        m = y

Gated memory unit: ``W_out (m * silu(W_in u))``.  Differential attention
(every attention kind): ``[q, k, v] = W_qkv u + b`` (``cross``: ``q = W_q u
+ b``, k and v handed on); adjacent heads pair; a pair's output is
``(softmax(q1 k1^T / sqrt(head)) - lambda softmax(q2 k2^T / sqrt(head)))
[v1, v2]`` under the causal mask (``swa``: and ``t - s < sliding_window``);
``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda0``, ``lambda0 = 0.8 -
0.6 exp(-0.3 l)``; then ``(1 - lambda0) RMSNorm(o) w`` over the pair's ``2
x head`` values, eps 1e-5; out ``W_o o + b``.

The reference runs the recurrence as it stands, a position at a time
(``lax.scan``, one ``[C, N]`` state): no chunks, no kernels, no
recomputation but ``jax.checkpoint`` around a layer and around a segment
of the recurrence where a gradient is taken (it changes no value).
Attention takes a block of queries at a time against all keys, each of
a pair's two maps computed once.  Configuration keys are those of the
published ``config.json``; ``vocab_size`` and ``num_hidden_layers`` are
what this chip holds, as the configuration file states them.

Departures from the sources: none in the mathematics.  What
``config.json`` has no key for (the Mamba sizes, the layout rule, the
pairing, lambda's form) is listed under ``assumed`` in the configuration
file.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SCAN_SEGMENT = 128       # positions whose states a gradient keeps at once
SUBLN_EPS = 1e-5
# operations of one channel-and-state update, forward: dt A, its
# exponential, two products and a sum for the state, a product and a sum
# for y
UPDATE_OPS = 7


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def kinds(config: dict) -> tuple:
    n_self, n_cross = config["layers"]["n_self"], config["layers"]["n_cross"]
    if n_self + 2 + n_cross != config["num_hidden_layers"] \
            or config["mb_per_layer"] != 2 or n_self % 2 or n_cross % 2:
        raise ValueError("num_hidden_layers is n_self + 2 + n_cross, both "
                         "even (mb_per_layer 2)")

    def kind(l):
        if l < n_self:
            return "swa" if l % 2 else "mamba"
        if l < n_self + 2:
            return "full_kv" if l % 2 else "mamba_memory"
        return "cross" if l % 2 else "gmu"

    return tuple(kind(l) for l in range(config["num_hidden_layers"]))


def _sizes(config: dict) -> dict:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    head = d // heads
    return {"d": d, "ff": config["intermediate_size"], "heads": heads,
            "kv_heads": config["num_key_value_heads"], "head": head,
            "kv": config["num_key_value_heads"] * head,
            "inner": config["mamba_expand"] * d,
            "state": config["mamba_d_state"], "rank": config["mamba_dt_rank"],
            "conv": config["mamba_d_conv"]}


def layer_weights(config: dict) -> dict:
    """Matrix weights a token multiplies, by kind of mixer, and ``mlp``."""
    s = _sizes(config)
    d, i = s["d"], s["inner"]
    mamba = (d * 2 * i + i * s["conv"] + i * (s["rank"] + 2 * s["state"])
             + s["rank"] * i + i * d)
    attention = d * (d + 2 * s["kv"]) + d * d
    return {"mamba": mamba, "mamba_memory": mamba, "swa": attention,
            "full_kv": attention, "cross": 2 * d * d, "gmu": 2 * d * i,
            "mlp": d * 2 * s["ff"] + s["ff"] * d}


def layer_params(config: dict) -> dict:
    """Every parameter of a layer, by kind: the matrices, the biases, the
    vectors and the two LayerNorms."""
    s, w = _sizes(config), layer_weights(config)
    d, i = s["d"], s["inner"]
    mamba = i + i + i * s["state"] + i          # conv bias, b_dt, A_log, D
    lam = 4 * s["head"] + 2 * s["head"]         # four vectors, the pair's norm
    small = {"mamba": mamba, "mamba_memory": mamba,
             "swa": d + 2 * s["kv"] + d + lam, "full_kv": d + 2 * s["kv"]
             + d + lam, "cross": 2 * d + lam, "gmu": 0}
    return {k: w[k] + small[k] + w["mlp"] + 4 * d for k in small}


def param_count(config: dict) -> int:
    """Every parameter on this chip; the tied matrix once, its rows
    held."""
    per = layer_params(config)
    return sum(per[k] for k in kinds(config)) \
        + config["vocab_size"] * config["hidden_size"] \
        + 2 * config["hidden_size"]


def band_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs a causal mask keeps in one map, inside the
    ``window`` where there is one: ``sum_t min(t + 1, window)``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops_per_pair(config: dict, products: int = 3) -> float:
    """Operations of all heads for one (query, key) pair of a layer:
    every head pair makes two score maps (``q . k`` over ``head``) and
    multiplies each with a V of ``2 x head``; ``products`` 3 counts them
    once forward and twice backward, 7 a streaming kernel's (2 forward;
    backward the scores again and one each for dv, dp, dq, dk)."""
    s = _sizes(config)
    qk, pv = 2 * s["head"], 2 * 2 * s["head"]
    per_map = {3: 3 * (qk + pv), 7: (qk + pv) + (3 * qk + 2 * pv)}[products]
    return float(s["heads"] // 2 * 2 * per_map)


def scan_flops_per_token(config: dict) -> float:
    """The scan's updates for one token of one layer, forward:
    ``UPDATE_OPS`` for each of ``channels x state``."""
    s = _sizes(config)
    return float(UPDATE_OPS * s["inner"] * s["state"])


def flops_per_token(config: dict, seq_len: int) -> float:
    """Forward and backward operations for one token of a ``seq_len``
    sequence on this chip: 6 for each weight the token multiplies (the
    tied matrix once, for the head; the lookup multiplies nothing);
    attention's pairs once forward and twice backward, inside the band in
    the window layers and over the causal half square in the full and the
    cross layers, two score maps and a V of twice the head's width a head
    pair; the scans' updates likewise.  Recomputed work and a score map
    computed twice count for nothing."""
    w, ks = layer_weights(config), kinds(config)
    weights = sum(w[k] + w["mlp"] for k in ks) \
        + config["vocab_size"] * config["hidden_size"]
    per_pair = attention_flops_per_pair(config, 3)
    pairs = (ks.count("swa") * band_pairs(seq_len, config["sliding_window"])
             + (ks.count("full_kv") + ks.count("cross"))
             * band_pairs(seq_len)) / seq_len
    scans = ks.count("mamba") + ks.count("mamba_memory")
    return (6.0 * weights + pairs * per_pair
            + scans * 3 * scan_flops_per_token(config))


def scan_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the selective scans of one step need whatever
    implements them, all Mamba layers together: the updates once forward
    and twice backward; x, B, C (bf16) and dt (float32) read and y
    written once forward; x, B, C, dt and dy read and dx, ddt, dB, dC
    written once backward.  A and D and their gradients are ``[C, N]``
    and ``[C]`` once a call and left out."""
    s, ks = _sizes(config), kinds(config)
    calls = (ks.count("mamba") + ks.count("mamba_memory")) * batch
    wide, narrow, steps = 2 * s["inner"], 2 * 2 * s["state"], 4 * s["inner"]
    forward = wide + narrow + steps + wide
    backward = 2 * wide + narrow + steps + wide + narrow + steps
    return {"flops": float(calls * seq_len * 3
                           * scan_flops_per_token(config)),
            "bytes": float(calls * seq_len * (forward + backward))}


def _attention_cost(config: dict, calls: int, pairs: int,
                    seq_len: int) -> dict:
    """A streaming kernel's seven products
    (``attention_flops_per_pair``) over ``pairs`` (query, key) pairs a
    map and no other; q, k, v read and the output written once forward;
    q, k, v, the output and its gradient read and dq, dk, dv written once
    backward, all bf16; ``calls`` layers and sequences."""
    s = _sizes(config)
    row = 2 * (s["d"] + 2 * s["kv"])                   # q, k, v of a token
    out = 2 * s["d"]
    return {"flops": float(calls * pairs
                           * attention_flops_per_pair(config, 7)),
            "bytes": float(calls * seq_len
                           * ((row + out) + (row + 2 * out) + row))}


def window_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the window layers' attention needs for one
    step whatever implements it (``_attention_cost``), over the band's
    pairs."""
    return _attention_cost(
        config, kinds(config).count("swa") * batch,
        band_pairs(seq_len, config["sliding_window"]), seq_len)


def full_cost(config: dict, batch: int, seq_len: int) -> dict:
    """The same for the layers that attend over the whole causal
    triangle, the K/V producers and the cross layers together.  A cross
    layer reads the handed-on K and V and writes its share of their
    gradients, as a layer that made them would."""
    ks = kinds(config)
    return _attention_cost(
        config, (ks.count("full_kv") + ks.count("cross")) * batch,
        band_pairs(seq_len), seq_len)


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy, the recurrence as it stands
# --------------------------------------------------------------------------
def _layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def causal_conv(x, weight, bias):
    """``out[t, c] = bias[c] + sum_k weight[c, k] x[t - (W - 1) + k, c]``,
    ``x [S, C]``, ``weight [C, W]``; before the sequence there is 0."""
    seq, width = x.shape[0], weight.shape[1]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return bias + sum(padded[k:k + seq] * weight[:, k] for k in range(width))


def _recurrence(state, x, dt, A, B, C):
    """Some positions of the recurrence from ``state [C, N]``: the state
    after them and ``s_t C_t`` of each."""
    def step(s, args):
        x_t, dt_t, b_t, c_t = args
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * x_t)[:, None] * b_t[None]
        return s, (s * c_t[None]).sum(-1)

    return jax.lax.scan(step, state, (x, dt, B, C))


def selective_scan(x, dt, A, B, C, D):
    """``y [S, C]`` of ``x [S, C]``, ``dt [S, C]``, ``A [C, N]``, ``B`` and
    ``C [S, N]``, ``D [C]``, float32, a position at a time.  A gradient
    keeps the states of ``SCAN_SEGMENT`` positions at once and the state
    each segment starts from."""
    seq = x.shape[0]
    seg = min(SCAN_SEGMENT, seq)
    while seq % seg:
        seg -= 1
    parts = tuple(a.reshape((seq // seg, seg) + a.shape[1:])
                  for a in (x, dt, B, C))
    segment = jax.checkpoint(
        lambda state, args: _recurrence(state, *args[:2], A, *args[2:]))
    _, y = jax.lax.scan(segment, jnp.zeros(A.shape, x.dtype), parts)
    return y.reshape(x.shape) + D * x


@functools.partial(jax.jit, static_argnames=("rank", "state"))
def _mamba_mixer(u, p, *, rank, state):
    """(the mixer's output, the memory ``m``)."""
    with jax.default_matmul_precision("highest"):
        x, z = jnp.split(u @ p["in_proj"], 2, -1)
        x = jax.nn.silu(causal_conv(x, p["conv_w"], p["conv_b"]))
        low, b, c = jnp.split(x @ p["x_proj"], (rank, rank + state), -1)
        dt = jax.nn.softplus(low @ p["dt_w"] + p["dt_b"])
        y = selective_scan(x, dt, -jnp.exp(p["A_log"]), b, c, p["D"])
        return (y * jax.nn.silu(z)) @ p["out_proj"], y


@jax.jit
def _gmu_mixer(u, memory, p):
    with jax.default_matmul_precision("highest"):
        return (memory * jax.nn.silu(u @ p["in_proj"])) @ p["out_proj"]


def _attend(q, k, v, scale, window, block):
    """Causal attention of ``q [S, H, D]`` over ``k [S, H, D]`` and ``v
    [S, H, E]`` at ``scale``, inside the ``window`` where there is one, a
    block of queries at a time against all keys: ``[S, H, E]``."""
    seq = q.shape[0]
    block = min(block, seq)
    n = seq // block

    def one_block(args):
        first, q_b = args
        scores = jnp.einsum("bhd,shd->hbs", q_b, k) * scale
        t = first + jnp.arange(q_b.shape[0])[:, None]
        s = jnp.arange(seq)[None, :]
        keep = s <= t
        if window is not None:
            keep = keep & (t - s < window)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return jnp.einsum("hbs,shd->bhd", probs, v)

    out = jax.lax.map(one_block, (
        jnp.arange(n) * block, q.reshape((n, block) + q.shape[1:])))
    return out.reshape((seq,) + out.shape[2:])


def differential_attention(q, k, v, lam, weight, lam0, window, block):
    """``q [S, heads, D]`` over ``k, v [S, kv heads, D]`` -> ``[S, heads x
    D]``: adjacent heads pair; a pair's two maps, each computed once,
    times the pair's V of ``2 D``; their difference at ``lam``, its RMS
    norm over ``2 D`` with ``weight`` and the scale ``1 - lam0``."""
    seq, heads, dim = q.shape
    pairs, kv_pairs = heads // 2, k.shape[1] // 2
    rep = pairs // kv_pairs
    q = q.reshape(seq, pairs, 2, dim)
    k = jnp.repeat(k.reshape(seq, kv_pairs, 2, dim), rep, axis=1)
    v = jnp.repeat(v.reshape(seq, kv_pairs, 2 * dim), rep, axis=1)
    scale = 1.0 / math.sqrt(dim)
    first, second = (_attend(q[:, :, half], k[:, :, half], v, scale, window,
                             block) for half in (0, 1))
    out = first - lam * second
    out = out * jax.lax.rsqrt(
        jnp.mean(out * out, -1, keepdims=True) + SUBLN_EPS) * weight
    return (out * (1.0 - lam0)).reshape(seq, heads * dim)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "lam0", "window", "block"))
def _attention_mixer(u, p, handed, *, heads, kv_heads, lam0, window, block):
    """(the mixer's output, k, v); ``handed`` (k, v) or None."""
    with jax.default_matmul_precision("highest"):
        seq, d = u.shape
        dim = d // heads
        qkv = u @ p["wqkv"] + p["bqkv"]
        q = qkv[:, :d].reshape(seq, heads, dim)
        if handed is None:
            k, v = (a.reshape(seq, kv_heads, dim)
                    for a in jnp.split(qkv[:, d:], 2, -1))
        else:
            k, v = handed
        lam = (jnp.exp(jnp.sum(p["lq1"] * p["lk1"]))
               - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0)
        out = differential_attention(q, k, v, lam, p["subln"], lam0, window,
                                     block)
        return out @ p["wo"] + p["bo"], k, v


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp(h, p, *, eps):
    with jax.default_matmul_precision("highest"):
        gate, up = jnp.split(
            _layer_norm(h, p["ln2_w"], p["ln2_b"], eps) @ p["fc1"], 2, -1)
        return (up * jax.nn.silu(gate)) @ p["fc2"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, bias, *, eps):
    return _layer_norm(x, weight, bias, eps)


def lambda_init(layer_idx: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


PREFIX = "model.layers.{}."
SHARED = {"ln1_w": "input_layernorm.weight", "ln1_b": "input_layernorm.bias",
          "ln2_w": "post_attention_layernorm.weight",
          "ln2_b": "post_attention_layernorm.bias",
          "fc1": "mlp.fc1.weight", "fc2": "mlp.fc2.weight"}
_MAMBA = {"in_proj": "mixer.in_proj.weight", "conv_w": "mixer.conv1d.weight",
          "conv_b": "mixer.conv1d.bias", "x_proj": "mixer.x_proj.weight",
          "dt_w": "mixer.dt_proj.weight", "dt_b": "mixer.dt_proj.bias",
          "A_log": "mixer.A_log", "D": "mixer.D",
          "out_proj": "mixer.out_proj.weight"}
_ATTENTION = {"wqkv": "mixer.Wqkv.weight", "bqkv": "mixer.Wqkv.bias",
              "wo": "mixer.out_proj.weight", "bo": "mixer.out_proj.bias",
              "lq1": "mixer.lambda_q1", "lk1": "mixer.lambda_k1",
              "lq2": "mixer.lambda_q2", "lk2": "mixer.lambda_k2",
              "subln": "mixer.subln"}
MIXER = {"mamba": _MAMBA, "mamba_memory": _MAMBA, "swa": _ATTENTION,
         "full_kv": _ATTENTION, "cross": _ATTENTION,
         "gmu": {"in_proj": "mixer.in_proj.weight",
                 "out_proj": "mixer.out_proj.weight"}}
EMBEDDING = "model.embed_tokens.weight"      # [rows held, hidden], tied
FINAL_NORM = ("model.final_layernorm.weight", "model.final_layernorm.bias")


def layer_parameters(config: dict, layer: int) -> list:
    """The program's names of layer ``layer``'s parameters."""
    return [PREFIX.format(layer) + n for n in {
        **SHARED, **MIXER[kinds(config)[layer]]}.values()]


def reference_layers(param, config: dict, h, layers, handed=None,
                     block: int = QUERY_BLOCK, checkpoint: bool = False):
    """The stream after the layers ``layers`` (a range) from ``h [S,
    hidden]``, and what has been handed on by then: ``{"m", "k", "v"}``.
    ``checkpoint`` keeps a layer's input only for a gradient."""
    eps = float(config["layer_norm_eps"])
    s, ks = _sizes(config), kinds(config)
    handed = dict(handed or {})

    def one_layer(h, p, read, *, kind, l):
        x = _norm(h, p["ln1_w"], p["ln1_b"], eps=eps)
        made = {}
        if kind in ("mamba", "mamba_memory"):
            mixed, y = _mamba_mixer(x, p, rank=s["rank"], state=s["state"])
            if kind == "mamba_memory":
                made["m"] = y
        elif kind == "gmu":
            mixed = _gmu_mixer(x, read["m"], p)
        else:
            mixed, k, v = _attention_mixer(
                x, p, (read["k"], read["v"]) if kind == "cross" else None,
                heads=s["heads"], kv_heads=s["kv_heads"],
                lam0=lambda_init(l),
                window=config["sliding_window"] if kind == "swa" else None,
                block=block)
            if kind == "full_kv":
                made.update(k=k, v=v)
        h = h + mixed
        return h + _mlp(h, p, eps=eps), made

    for l in layers:
        kind = ks[l]
        p = {k: param(PREFIX.format(l) + n)
             for k, n in {**SHARED, **MIXER[kind]}.items()}
        read = {k: handed[k] for k in {"gmu": ("m",), "cross": ("k", "v")}.get(
            kind, ())}
        fn = functools.partial(one_layer, kind=kind, l=l)
        h, made = (jax.checkpoint(fn) if checkpoint else fn)(h, p, read)
        handed.update(made)
    return h, handed


def reference_hidden(param, config: dict, ids, block: int = QUERY_BLOCK):
    """What the head multiplies, ``[S, hidden]`` float32, for one
    sequence of token ids.  ``param(name, rows=None)`` returns the
    program's parameter of that name (or the given rows of it) as
    float32, a layer at a time."""
    h, _ = reference_layers(param, config, param(EMBEDDING, ids),
                            range(config["num_hidden_layers"]), block=block)
    return _norm(h, *(param(n) for n in FINAL_NORM),
                 eps=float(config["layer_norm_eps"]))


def reference_logits(hidden, embedding_rows, config=None):
    """Logits ``[S, rows]`` for some rows of the tied matrix (float32
    ``[rows, hidden]``): a part of the vocabulary at a time.  ``config``
    is what the drivers' shared check hands every family: this one has no
    multiplier on its logits to read from it."""
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding_rows.T


def _cross_entropy(hidden, embedding, labels):
    logp = jax.nn.log_softmax(reference_logits(hidden, embedding), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], 1).mean()


def reference_loss(params: dict, config: dict, ids, labels):
    """Mean cross-entropy of a batch ``ids``/``labels`` ``[B, S]`` from a
    dict of float32 parameters by the program's names: differentiable,
    for the small sizes of the tests."""
    def param(name, rows=None):
        return params[name] if rows is None else params[name][rows]

    loss = 0.0
    for b in range(ids.shape[0]):
        loss = loss + _cross_entropy(
            reference_hidden(param, config, ids[b]), params[EMBEDDING],
            labels[b])
    return loss / ids.shape[0]


def producers(config: dict) -> tuple:
    """The layers that hand on: (the memory's, the keys' and values')."""
    n_self = config["layers"]["n_self"]
    return n_self, n_self + 1


def reference_producer_grads(param, config: dict, ids, labels,
                             block: int = QUERY_BLOCK) -> dict:
    """The gradients of one sequence's mean cross-entropy by every
    parameter of the two producer layers, by name: what the whole
    model's gradient holds for them, collected from the layers after
    them, the readers of m, K and V among them.  The layers before them
    run forward only; every other parameter is held as it is."""
    first, last = producers(config)
    end = config["num_hidden_layers"]
    stream, _ = reference_layers(param, config, param(EMBEDDING, ids),
                                 range(first), block=block)
    eps = float(config["layer_norm_eps"])

    def loss_of(own):
        def param_(name, rows=None):
            return own[name] if name in own else param(name, rows)

        h, _ = reference_layers(param_, config, stream, range(first, end),
                                block=block, checkpoint=True)
        hidden = _norm(h, *(param(n) for n in FINAL_NORM), eps=eps)
        return _cross_entropy(hidden, param(EMBEDDING), labels)

    return jax.grad(loss_of)({
        name: param(name) for l in (first, last)
        for name in layer_parameters(config, l)})


@jax.jit
def reference_scan_grads(x, dt, A, B, C, D, w):
    """The recurrence on float32 inputs and the gradients of ``sum(y *
    w)``: ``(y, dx, ddt, dA, dB, dC, dD)``."""
    y, vjp = jax.vjp(selective_scan, x, dt, A, B, C, D)
    return (y,) + vjp(w)


@functools.partial(jax.jit, static_argnames=("window",))
def reference_attention_grads(q, k, v, w, *, window=None):
    """Plain causal attention of q, w ``[H, S, D]`` over k, v ``[G, S,
    D]`` (float32) at ``1 / sqrt(D)``, inside the band ``t - s < window``
    where there is one, and the gradients of ``sum(out * w)``: ``(out,
    dq, dk, dv)``.  One head at a time, forward and backward, so that one
    head's squares are all that is alive."""
    heads, groups = q.shape[0], k.shape[0]
    rep = heads // groups
    seq = q.shape[1]
    t, s = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    keep = s <= t
    if window is not None:
        keep = keep & (t - s < window)
    scale = 1.0 / math.sqrt(q.shape[-1])

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


# --------------------------------------------------------------------------
# the optimizer's rule, and the control
# --------------------------------------------------------------------------
# Decoupled weight decay (Loshchilov and Hutter 2019, algorithm 2) with
# the constants the paper and ``paddle.optimizer.AdamW`` both start from.
ADAMW = {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8, "weight_decay": 0.01}


def reference_adamw(weight, moment1, moment2, grad, step: int, lr: float):
    """The weight after step ``step`` (1 the first) of AdamW, in float64,
    from float32 numpy arrays: the weight and the moments as the step
    found them and the gradient it took.  ``m' = b1 m + (1 - b1) g``, ``v'
    = b2 v + (1 - b2) g^2``, both divided by ``1 - b^step``; ``w' = w - lr
    (m^ / (sqrt(v^) + eps) + decay w)``."""
    import numpy as np
    b1, b2 = ADAMW["beta1"], ADAMW["beta2"]
    w, g = weight.astype(np.float64), grad.astype(np.float64)
    m = (b1 * moment1 + (1.0 - b1) * g) / (1.0 - b1 ** step)
    v = (b2 * moment2 + (1.0 - b2) * g * g) / (1.0 - b2 ** step)
    return w - lr * (m / (np.sqrt(v) + ADAMW["eps"])
                     + ADAMW["weight_decay"] * w)


class rounded_through:
    """This family with its reference computed in ``dtype``, the control
    of the driver's limits: every weight the reference reads, and the
    inputs of a kernel's reference that the program holds in bf16 (a
    scan's x, B, C; attention's q, k, v), rounded through ``dtype`` and
    back to float32.  Everything else is the family's.  With
    ``float8_e4m3fn``, the nearest precision below the configuration's
    bf16, each of the driver's checks has to come out wrong by it."""

    def __init__(self, dtype):
        self._round = lambda a: a.astype(dtype).astype(jnp.float32)

    def __getattr__(self, name):
        try:
            return globals()[name]
        except KeyError:
            raise AttributeError(name) from None

    def _rounded(self, param):
        return lambda name, rows=None: self._round(param(name, rows))

    def reference_hidden(self, param, config, ids, **kw):
        return reference_hidden(self._rounded(param), config, ids, **kw)

    def reference_logits(self, hidden, embedding_rows, config=None):
        return reference_logits(hidden, self._round(embedding_rows), config)

    def reference_producer_grads(self, param, config, ids, labels, **kw):
        return reference_producer_grads(self._rounded(param), config, ids,
                                        labels, **kw)

    def reference_scan_grads(self, x, dt, A, B, C, D, w):
        r = self._round
        return reference_scan_grads(r(x), dt, A, r(B), r(C), D, w)

    def reference_attention_grads(self, q, k, v, w, *, window=None):
        r = self._round
        return reference_attention_grads(r(q), r(k), r(v), w, window=window)

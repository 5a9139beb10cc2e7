"""The GPT-2 / GPT-3 family as the benchmark knows it, from the papers
and not from the program: parameter and operation counts from shapes,
and a plain float32 reference of the forward pass and of attention.

Architecture (Radford et al. 2019; Brown et al. 2020 keeps it): learned
token and position embeddings; ``n_layer`` pre-LayerNorm blocks of causal
multi-head attention (one fused q,k,v projection whose columns are q,
then k, then v, each ``n_head`` heads of ``n_embd / n_head``) and a
``n_inner`` GELU (tanh form, "gelu_new") feed-forward, each added to the
residual; a final LayerNorm; the output head is the token embedding
transposed.  Every projection has a bias.  Configuration keys are those
of the published GPT-2 ``config.json``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# counts from shapes
# --------------------------------------------------------------------------
def param_count(config: dict) -> int:
    """Every parameter of the model, the tied head counted once."""
    d, ff = config["n_embd"], config["n_inner"]
    block = (2 * d                  # ln1
             + d * 3 * d + 3 * d    # q,k,v projection
             + d * d + d            # attention output projection
             + 2 * d                # ln2
             + d * ff + ff          # feed-forward in
             + ff * d + d)          # feed-forward out
    return (config["vocab_size"] * d + config["n_positions"] * d
            + config["n_layer"] * block + 2 * d)


def matmul_weights(config: dict) -> int:
    """Weights that a token is multiplied by: the four projections of
    every block and the output head.  Embedding look-ups, biases and
    LayerNorms multiply no matrix."""
    d, ff = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (4 * d * d + 2 * d * ff)
            + config["vocab_size"] * d)


def flops_per_token(config: dict, seq_len: int) -> float:
    """Floating-point operations the forward and backward passes need for
    one token of a ``seq_len`` sequence: 2 for each weight forward and 4
    backward, and causal attention's two products (scores, and
    probabilities times values) over the half of the square a causal mask
    keeps: 2 · 2 · (seq_len / 2) · n_embd forward a layer, twice that
    backward.  Operations a backward pass computes again (attention
    scores in a streaming kernel, recomputed activations) do not count:
    this is the numerator of model FLOP/s utilization."""
    attention = 6 * config["n_layer"] * config["n_embd"] * seq_len
    return 6.0 * matmul_weights(config) + attention


def attention_step_cost(config: dict, batch: int, seq_len: int) -> dict:
    """Operations and bytes the streaming attention algorithm needs for
    all of one training step's attention calls (every layer, forward and
    backward, the whole batch), for a kernel's roofline.

    One product over the causal half of a head's square is
    ``seq_len**2 * head`` operations (2 · S²/2 · head).  Forward makes
    two (scores; probabilities times values).  A streaming backward
    makes five: the scores again, which is the algorithm and not waste,
    and one each for dv, dp, dq and dk.  Bytes are each operand read once
    and each result written once in bf16: q, k, v, out forward; q, k, v,
    out, dout in and dq, dk, dv out backward.  The log-sum-exp rows are
    1/head of an operand and left out."""
    heads = config["n_head"]
    head = config["n_embd"] // heads
    calls = config["n_layer"] * batch * heads
    product = seq_len * seq_len * head
    operand_bytes = 2 * seq_len * head
    return {"flops": float(calls * 7 * product),
            "bytes": float(calls * 12 * operand_bytes)}


# --------------------------------------------------------------------------
# the plain reference: float32 jax.numpy, no kernel, cache or sharding
# --------------------------------------------------------------------------
def _layer_norm(x, weight, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def _one_head(q, k, v):
    """Plain causal attention of one head of one sequence: ``[S, D]``
    float32 in and out, the whole ``[S, S]`` score square materialised."""
    with jax.default_matmul_precision("highest"):
        scores = (q @ k.T) / math.sqrt(q.shape[-1])
        keep = jnp.tril(jnp.ones(scores.shape, bool))
        probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        return probs @ v


def causal_attention(q, k, v):
    """Plain causal attention of one sequence, ``[S, H, D]`` float32 in
    and out.  One head at a time, so that one ``[S, S]`` square is all
    that is alive: the reference runs on a device the cell has filled."""
    heads_first = tuple(x.swapaxes(0, 1) for x in (q, k, v))
    return jax.lax.map(lambda qkv: _one_head(*qkv), heads_first).swapaxes(0, 1)


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, p, *, n_head, eps):
    """One pre-LayerNorm block on ``[S, n_embd]``; weights are stored
    ``[in, out]``."""
    with jax.default_matmul_precision("highest"):
        s, d = x.shape
        h = _layer_norm(x, p["ln1.weight"], p["ln1.bias"], eps)
        qkv = (h @ p["attn.qkv_proj.weight"] + p["attn.qkv_proj.bias"]
               ).reshape(s, 3, n_head, d // n_head)
        a = causal_attention(qkv[:, 0], qkv[:, 1], qkv[:, 2]).reshape(s, d)
        x = x + a @ p["attn.out_proj.weight"] + p["attn.out_proj.bias"]
        h = _layer_norm(x, p["ln2.weight"], p["ln2.bias"], eps)
        h = jax.nn.gelu(h @ p["mlp.fc1.weight"] + p["mlp.fc1.bias"],
                        approximate=True)
        return x + h @ p["mlp.fc2.weight"] + p["mlp.fc2.bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(x, weight, bias, *, eps):
    return _layer_norm(x, weight, bias, eps)


BLOCK_PARAMS = ("ln1.weight", "ln1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln2.weight", "ln2.bias",
                "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
                "mlp.fc2.bias")
EMBEDDING = "gpt.embeddings.word_embeddings.weight"


def reference_hidden(param, config: dict, ids):
    """What the output head multiplies: ``[S, n_embd]`` float32, the last
    block's output of one sequence of token ids after the final LayerNorm.

    ``param(name, rows=None)`` returns the named parameter, or the given
    rows of it, as a float32 array; names are the program's
    (``gpt.layers.3.mlp.fc1.weight``), so a parameter wired to the wrong
    place shows.  It is asked for one layer at a time and for the
    embedding's rows of these tokens only, so no more than a layer's
    float32 copy is alive beside the model."""
    eps = float(config["layer_norm_epsilon"])
    x = param(EMBEDDING, ids) + param(
        "gpt.embeddings.position_embeddings.weight", slice(ids.shape[0]))
    for i in range(config["n_layer"]):
        x = _block(x, {n: param(f"gpt.layers.{i}.{n}") for n in BLOCK_PARAMS},
                   n_head=config["n_head"], eps=eps)
    return _final_norm(x, param("gpt.final_norm.weight"),
                       param("gpt.final_norm.bias"), eps=eps)


def reference_logits(hidden, embedding_rows):
    """Logits ``[S, rows]`` of the tied output head for some rows of the
    token embedding (float32 ``[rows, n_embd]``): a part of the vocabulary
    at a time, so that the whole ``[S, vocab]`` never has to exist."""
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding_rows.T


@jax.jit
def reference_attention_grads(q, k, v, w):
    """Plain attention of a batch ``[B, S, H, D]`` (float32) and the
    gradients of ``sum(out * w)``: ``(out, dq, dk, dv)``.  One head of one
    sequence at a time, forward and backward, so that the score squares
    of one head are all that is alive."""
    b, s, h, d = q.shape

    def one(args):
        q_, k_, v_, w_ = args
        out, vjp = jax.vjp(_one_head, q_, k_, v_)
        return (out,) + vjp(w_)

    heads = jax.lax.map(one, tuple(
        x.swapaxes(1, 2).reshape(b * h, s, d) for x in (q, k, v, w)))
    return tuple(x.reshape(b, h, s, d).swapaxes(1, 2) for x in heads)

"""Dropless routed experts for one rank of an expert-parallel layout.

The layer is told which experts it holds (``first .. first + held`` of
``num_experts``).  It routes over all of them, keeps every (token,
expert) pair whose expert lives here however uneven the routing, and
returns its own experts' part of the layer's result:

    out[t]  = sum over e in T_t held here of g[t, e] * expert_e(y[t])

with T_t the k experts token t chose and g its gates, normalised over
all k, held here or not.  Two routers (:func:`route`,
:func:`route_sigmoid`) and two expert forms, told apart by how many
matrices an expert has:

    softmax router   p = softmax(y W_r);  T_t = the k largest p
                     g[t, e] = p[t, e] / sum of p over T_t
    sigmoid router   s = sigmoid(y W_r);  T_t = the k largest s + b
                     g[t, e] = scale * s[t, e] / (sum of s over T_t + eps)
                     (b chooses and does not weigh; no gradient reaches it)
    SiLU-gated       expert_e(y) = (silu(y W1_e) * (y W3_e)) W2_e
    squared ReLU     expert_e(y) = relu(y W1_e)^2 W2_e

What the other ranks' experts would add is left out; summed over the
ranks the parts give the whole layer (``tests/test_keye_lm.py``,
``tests/test_nemotron_h.py``).  A shared expert that every token visits
is no part of this: it lies outside the plan, with the model, which
counts it once.  On one chip there is no exchange, and nothing here
stands in for one.

How: the pairs are sorted by expert (pairs of absent experts last), the
tokens of the pairs held here are gathered into that order, the
projections (three or two) run as grouped matrix products over the uneven
groups, and the results come back to their tokens, added up in float32.
The products and their two gradients have two forms, and
``ops/grouped_matmul.form`` names the one that runs from platform, shape
and dtype (:func:`_dot`, :func:`_dot_back`): on a TPU, for windows of
whole row tiles, the Mosaic kernels of ``ops/grouped_matmul.py``, which
visit only the row tiles that hold pairs; everywhere else (the CPU, odd
shapes) ``jax.lax.ragged_dot`` and its ``jax.vjp``, which the kernels
are checked against.  Both leave the rows past the count 0.

The way back (``combine``, and its transpose in the backward pass's
``dispatch``) has two forms too, which ``ops/token_rows.form`` names from
platform and shape (:func:`_add_back`): on a TPU, ``ops/token_rows.py``'s
kernel adds each row of the window that holds a pair into its token, so
only the held pairs are read; everywhere else (the CPU, odd shapes)
every token gathers its k slots of the window, masks the ones not held
here and sums them, which is what the kernel is checked against.

Shapes must be static and the number of pairs held here is not.  The
sorted pairs are taken a window of rows at a time, a window being twice
the share a balanced router sends here.  The first window always runs;
where the count overflows it, the later windows follow one after the
other (``lax.cond`` on the count, then a ``lax.scan``), so no pair is
ever dropped and the buffers stay a window large.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .....nn.layer import Layer
from .....nn import initializer as I
from .....ops import grouped_matmul, token_rows
from .....ops._primitive import apply_closure


class Plan(NamedTuple):
    """Where every (token, choice) pair goes."""
    dest: jax.Array      # [T, k] row of the pair in the sorted order
    here: jax.Array      # [T, k] bool: the pair's expert is held here
    pairs: jax.Array     # [T * k] pair index (t * k + choice) of each row
    sizes: jax.Array     # [held] pairs of each held expert
    count: jax.Array     # [] pairs held here: the rows in use


def route(logits, k: int):
    """(experts ``[T, k]`` int32, gates ``[T, k]`` float32) from float32
    router logits over all experts: the k largest probabilities,
    normalised over the k chosen."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(probs, k)
    return experts.astype(jnp.int32), top / top.sum(-1, keepdims=True)


def route_sigmoid(logits, bias, k: int, scale: float, eps: float = 1e-20):
    """(experts ``[T, k]`` int32, gates ``[T, k]`` float32) from float32
    router logits over all experts: the k largest of ``sigmoid(logits) +
    bias`` are chosen, and weighed by the sigmoid alone, normalised over
    the k chosen (their sum plus ``eps``) and multiplied by ``scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    return (experts.astype(jnp.int32),
            scale * top / (top.sum(-1, keepdims=True) + eps))


def plan(experts, first: int, held: int) -> Plan:
    local = experts - first
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(-1)       # absent: last
    pairs = jnp.argsort(group, stable=True).astype(jnp.int32)
    dest = jnp.argsort(pairs).astype(jnp.int32).reshape(experts.shape)
    sizes = (group[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :]
             ).sum(0, dtype=jnp.int32)
    return Plan(dest, here, pairs, sizes, sizes.sum())


@jax.checkpoint
def _gated(gate, up):
    """silu(gate) * up in float32; the backward pass keeps the two
    operands as they are stored and computes the rest again."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


@jax.checkpoint
def _relu2(up):
    """relu(up)^2 in float32; the backward pass keeps the operand as it
    is stored."""
    r = jnp.maximum(up.astype(jnp.float32), 0.0)
    return (r * r).astype(up.dtype)


def _activation(inner: int):
    """What stands between an expert's ``inner`` first matrices and its
    last."""
    return {1: _relu2, 2: _gated}[inner]


def usual_rows(tokens: int, k: int, held: int, num_experts: int) -> int:
    """Twice the share a balanced router sends here, in whole tiles."""
    share = -(-2 * tokens * k * held // num_experts)
    return min(tokens * k, -(-share // 512) * 512)


def _dot(lhs, rhs, sizes):
    """``out[r] = lhs[r] . rhs[group(r)]`` for rows sorted by group,
    ``sizes`` rows a group; rows past the groups are 0."""
    if grouped_matmul.form(lhs, rhs) == "kernels":
        return grouped_matmul.dot(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes=sizes)


def _dot_back(lhs, rhs, sizes, d_out):
    """Gradients of :func:`_dot` for (lhs, rhs), in the form it ran."""
    if grouped_matmul.form(lhs, rhs) == "kernels":
        return (grouped_matmul.dot(d_out, rhs, sizes, transposed=True),
                grouped_matmul.dot_weights(lhs, d_out, sizes))
    # the product's forward result is not used again: XLA drops it
    return jax.vjp(functools.partial(jax.lax.ragged_dot, group_sizes=sizes),
                   lhs, rhs)[1](d_out)


class _Window(NamedTuple):
    """The rows ``start .. start + rows`` of the sorted order."""
    pairs: jax.Array     # [rows] pair index of each row
    sizes: jax.Array     # [held] rows of each held expert in the window
    dest: jax.Array      # [T, k] row of the pair inside the window
    here: jax.Array      # [T, k] the pair is held here and in the window
    in_use: jax.Array    # [rows, 1] the row holds a pair held here


def _window(p: Plan, start, rows: int) -> _Window:
    ends = jnp.cumsum(p.sizes)
    clip = functools.partial(jnp.clip, min=start, max=start + rows)
    padded = jnp.pad(p.pairs, (0, rows))        # a window may overhang
    return _Window(
        jax.lax.dynamic_slice(padded, (start,), (rows,)),
        (clip(ends) - clip(ends - p.sizes)).astype(jnp.int32),
        p.dest - start,
        p.here & (p.dest >= start) & (p.dest < start + rows),
        (start + jnp.arange(rows) < p.count)[:, None])


def _note_combine(form: str) -> None:
    from .....observability import metrics
    metrics.registry().counter(
        "moe_combine_calls_total",
        "calls of the routed experts' way back to their tokens, the "
        "forward combine and the backward dispatch, counted a call when "
        "the call is traced: held_rows each window row added into its "
        "token, per_slot every token's k slots gathered and summed",
        labels={"form": form}).inc()


def _add_back(w: _Window, rows, k: int, gates=None, dtype=jnp.float32):
    """``[T, d]`` of ``dtype``: each token's rows of the window that hold
    its pairs here, weighed by their ``gates [T, k]`` where given, added
    up in float32."""
    tokens = w.here.shape[0]
    held_rows = token_rows.form(rows, tokens, k) == "kernel"
    _note_combine("held_rows" if held_rows else "per_slot")
    if held_rows:
        pair = jnp.where(w.in_use[:, 0], w.pairs, tokens * k)
        return token_rows.add(rows, pair, k, tokens, gates, dtype)
    picked = jnp.where(w.here[..., None], rows[w.dest], 0).astype(jnp.float32)
    if gates is not None:
        picked = picked * gates[..., None]
    return picked.sum(1).astype(dtype)


def _window_forward(w: _Window, y, gates, weights):
    """(the window's part of the result ``[T, d]`` float32, what its
    backward pass reads: the pairs' rows, their products with each of an
    expert's first matrices, the experts' results).  ``weights`` are an
    expert's matrices, stacked over the held: (w1, w3, w2) SiLU-gated,
    (w1, w2) squared ReLU."""
    dot = functools.partial(_dot, sizes=w.sizes)
    *inner, last = weights
    with jax.named_scope("dispatch"):
        x = y[w.pairs // gates.shape[1]]
    with jax.named_scope("experts"):
        pre = tuple(dot(x, m) for m in inner)
        rows = dot(_activation(len(inner))(*pre), last)
    with jax.named_scope("combine"):
        out = _add_back(w, rows, gates.shape[1], gates)
    return out, (x, pre, rows)


def _window_backward(w: _Window, kept, gates, weights, g):
    """Gradients for (y, gates, weights) of the window's part.  The
    forward pass's way back has a gather as its transpose, and its
    gather into expert order the way back (:func:`_add_back`), because
    the sorted order is a permutation of the pairs."""
    x, pre, rows = kept
    *inner, last = weights
    k = gates.shape[1]
    with jax.named_scope("combine"):
        g_rows = g[w.pairs // k]                                # [rows, d]
        d_rows = jnp.where(w.in_use, g_rows * gates.reshape(-1)[w.pairs][
            :, None], 0).astype(rows.dtype)
        d_gate_rows = (rows.astype(jnp.float32) * g_rows).sum(-1)
        d_gates = jnp.where(w.here, d_gate_rows[w.dest], 0).astype(
            gates.dtype)
    with jax.named_scope("experts"):
        h, activation_back = jax.vjp(_activation(len(inner)), *pre)
        d_h, d_last = _dot_back(h, last, w.sizes, d_rows)
        back = [_dot_back(x, m, w.sizes, d)
                for m, d in zip(inner, activation_back(d_h))]
    with jax.named_scope("dispatch"):
        d_y = _add_back(w, sum(d_x for d_x, _ in back), k, dtype=x.dtype)
    return d_y, d_gates, tuple(d_m for _, d_m in back) + (d_last,)


def _later_windows(usual: int, p: Plan):
    """First rows of the windows after the first."""
    return jnp.arange(usual, p.pairs.shape[0], usual, dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts(usual: int, y, gates, weights, p: Plan):
    """The layer's result, the sorted pairs taken ``usual`` rows at a
    time.  The first window always runs and keeps its intermediates for
    the backward pass.  Where the pairs held here overflow it
    (``lax.cond`` on the count, forward and backward), the later windows
    run one after the other in the same buffers, and the backward pass
    computes theirs again."""
    return _experts_fwd(usual, y, gates, weights, p)[0]


def _experts_fwd(usual, y, gates, weights, p):
    operands = (y, gates, weights)
    out, kept = _window_forward(_window(p, 0, usual), *operands)

    def overflow(out_):
        def one(acc, start):
            return acc + _window_forward(_window(p, start, usual),
                                         *operands)[0], None
        return jax.lax.scan(one, out_, _later_windows(usual, p))[0]

    if usual < p.pairs.shape[0]:
        out = jax.lax.cond(p.count <= usual, lambda o: o, overflow, out)
    return out, operands + (p, kept)


def _experts_bwd(usual, res, g):
    y, gates, weights, p, kept = res
    grads = _window_backward(_window(p, 0, usual), kept, gates, weights, g)

    def overflow(grads_):
        def one(acc, start):
            w = _window(p, start, usual)
            again = _window_forward(w, y, gates, weights)[1]
            more = _window_backward(w, again, gates, weights, g)
            return jax.tree_util.tree_map(jnp.add, acc, more), None
        return jax.lax.scan(one, grads_, _later_windows(usual, p))[0]

    if usual < p.pairs.shape[0]:
        grads = jax.lax.cond(p.count <= usual, lambda x: x, overflow, grads)
    return tuple(grads) + (None,)


_experts.defvjp(_experts_fwd, _experts_bwd)


def experts_forward(y, experts, gates, weights, first: int,
                    num_experts: int):
    """(out float32 ``[T, d]``, pairs of each held expert ``[held]``)
    for tokens ``y [T, d]`` routed to ``experts``/``gates`` ``[T, k]``;
    ``weights`` the held experts' matrices, (w1, w3, w2) or (w1, w2)."""
    tokens, k = experts.shape
    held = weights[0].shape[0]
    with jax.named_scope("dispatch"):
        p = plan(experts, first, held)
    usual = usual_rows(tokens, k, held, num_experts)
    return _experts(usual, y, gates, tuple(weights), p), p.sizes


class _GroupedExperts(Layer):
    """``held`` experts of ``num_experts``, their matrices stacked;
    expert e of the model is row ``e - first``."""

    def __init__(self, num_experts: int, first: int, held: int):
        super().__init__()
        if not 0 <= first <= first + held <= num_experts:
            raise ValueError(f"experts {first}..{first + held} of "
                             f"{num_experts}")
        self.num_experts, self.first, self.held = num_experts, first, held

    def _matrices(self):
        raise NotImplementedError

    def forward(self, y, experts, gates):
        """``y [T, d]``, ``experts``/``gates`` ``[T, k]`` over all
        ``num_experts`` -> (float32 ``[T, d]``, int32 ``[held]``)."""
        idx = experts._value

        def closure(y_, gates_, *weights):
            return experts_forward(y_, idx, gates_, weights, self.first,
                                   self.num_experts)

        return apply_closure(closure, [y, gates] + self._matrices(),
                             name="grouped_experts")


class GroupedSwiGLUExperts(_GroupedExperts):
    """SiLU-gated: ``w1``, ``w3`` ``[held, d_model, d_hidden]`` and ``w2``
    ``[held, d_hidden, d_model]``."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 first: int, held: int, initializer_range: float = 0.02):
        super().__init__(num_experts, first, held)
        init = I.Normal(0.0, initializer_range)
        self.w1 = self.create_parameter(shape=[held, d_model, d_hidden],
                                        default_initializer=init)
        self.w3 = self.create_parameter(shape=[held, d_model, d_hidden],
                                        default_initializer=init)
        self.w2 = self.create_parameter(shape=[held, d_hidden, d_model],
                                        default_initializer=init)

    def _matrices(self):
        return [self.w1, self.w3, self.w2]


class GroupedRelu2Experts(_GroupedExperts):
    """Squared ReLU, no gate: ``w1 [held, d_model, d_hidden]`` and ``w2
    [held, d_hidden, d_model]``; ``w2`` may start at a range of its
    own."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 first: int, held: int, initializer_range: float = 0.02,
                 w2_range: Optional[float] = None):
        super().__init__(num_experts, first, held)
        self.w1 = self.create_parameter(
            shape=[held, d_model, d_hidden],
            default_initializer=I.Normal(0.0, initializer_range))
        self.w2 = self.create_parameter(
            shape=[held, d_hidden, d_model],
            default_initializer=I.Normal(
                0.0, initializer_range if w2_range is None else w2_range))

    def _matrices(self):
        return [self.w1, self.w2]

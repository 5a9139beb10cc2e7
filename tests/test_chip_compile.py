"""Compile the main path's kernels for a TPU v5e that is described, not
attached (on-chip-measurement guide §2, third rehearsal).

Interpret mode and the CPU mesh partition and lower anything; the
chip's compiler does not.  Each case lowers one kernel (or the sharded
public call) at GPT-2-small widths — b8 · s1024 · 12 heads · d64,
bf16 — for a described ``v5e:2x2`` and compiles it with libtpu.
Nothing runs, so this says nothing about values or times: it says the
chip's compiler accepts the program.  The ``xfail(strict=True)``
case carries the compiler's own message; the PR that repairs the
kernel flips it.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import pytest
from pytest import approx
import jax
import jax.numpy as jnp
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import paddle_tpu  # noqa: F401 — turns on x64, which the kernels must survive
from paddle_tpu.distributed import collective
from paddle_tpu.ops import pallas_ops

B, S, H, D = 8, 1024, 12, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: the next run would
    warn and compile again.  Keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _one_chip_spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


def _bh_fwd(topo, monkeypatch):
    x = _one_chip_spec(topo)((B * H, S, D))
    return (lambda q, k, v: pallas_ops._pallas_flash_bh(
        q, k, v, causal=True)), (x, x, x)


def _bh_bwd(topo, monkeypatch):
    spec = _one_chip_spec(topo)
    x = spec((B * H, S, D))
    lse = spec((B * H, S, pallas_ops._LANES), jnp.float32)
    return (lambda q, k, v, o, l, do: pallas_ops._pallas_flash_bwd(
        q, k, v, o, l, do, causal=True)), (x, x, x, x, lse, x)


def _bh_own_geometry(topo, monkeypatch):
    """The [BH, S, D] kernels at a geometry that reaches them
    (``_attention_form``): GPT-2 XL's 25 heads of 64, which leave half
    a 128-lane group empty.  Forward, dq and dkv, b2 x s1024."""
    b, h = 2, 25
    assert pallas_ops._packed_geometry(h, D) is None
    x = _one_chip_spec(topo)((b * h, S, D))

    def fwd_bwd(q, k, v, do):
        out, lse = pallas_ops._pallas_flash_bh(q, k, v, causal=True)
        return pallas_ops._pallas_flash_bwd(q, k, v, out, lse, do,
                                            causal=True)

    return fwd_bwd, (x, x, x, x)


def _packed_fwd(topo, monkeypatch):
    x = _one_chip_spec(topo)((B, S, H * D))
    return (lambda q, k, v: pallas_ops._pallas_flash_packed(
        q, k, v, H, D, causal=True)), (x, x, x)


def _packed_bwd(topo, monkeypatch):
    spec = _one_chip_spec(topo)
    x = spec((B, S, H * D))
    lse = spec((B, S, H * D), jnp.float32)
    return (lambda q, k, v, o, l, do: pallas_ops._pallas_flash_packed_bwd(
        q, k, v, o, l, do, H, D, causal=True)), (x, x, x, x, lse, x)


def _packed_varlen(topo, monkeypatch, s=S):
    """Packed forward and backward with segment ids (packed documents):
    the two segment-id layouts take their own index maps."""
    spec = _one_chip_spec(topo)
    x = spec((B, s, H * D))
    seg = spec((B, s), jnp.int32)

    def fwd_bwd(q, k, v, do, seg_):
        out, lse = pallas_ops._pallas_flash_packed(
            q, k, v, H, D, seg_, seg_, causal=True)
        return pallas_ops._pallas_flash_packed_bwd(
            q, k, v, out, lse, do, H, D, seg_, seg_, causal=True)

    return fwd_bwd, (x, x, x, x, seg)


def _packed_varlen_s2048(topo, monkeypatch):
    """... at 2048 tokens, where dq keeps all the keys resident and
    walks them as two tiles: the key-side segment ids are sliced along
    the lanes at a loop index."""
    return _packed_varlen(topo, monkeypatch, s=2048)


def _packed_two_key_blocks(topo, monkeypatch):
    """Packed forward and backward at the four-chip cell's local shape,
    [2, 2048, 8 x 128]: one head a lane block, and two resident key
    blocks, so the grid's skip and the tile loop's bounds compile
    together."""
    spec = _one_chip_spec(topo)
    b, s, h, d = 2, 2048, 8, 128
    x = spec((b, s, h * d))

    def fwd_bwd(q, k, v, do):
        out, lse = pallas_ops._pallas_flash_packed(q, k, v, h, d,
                                                   causal=True)
        return pallas_ops._pallas_flash_packed_bwd(
            q, k, v, out, lse, do, h, d, causal=True)

    return fwd_bwd, (x, x, x, x)


def _sharded_public_op(topo, monkeypatch):
    """The public op, forward and backward, under the implicit-SPMD
    step's shardings on a dp2 x mp2 mesh: GSPMD cannot partition a
    Mosaic kernel, so the op must hand each device its own
    [4, 1024, 6, 64] block (``pallas_ops._per_device``)."""
    # jax.default_backend() is the CPU here; the kernel branch is what
    # this case compiles, so the test answers the platform question
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    mesh = collective.build_mesh({"dp": 2, "mp": 2}, devices=topo.devices)
    collective.set_mesh(mesh)   # conftest's _reset_state clears it
    x = jax.ShapeDtypeStruct(
        (B, S, H, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("dp", None, "mp", None)))

    def loss(q, k, v):
        out = pallas_ops.flash_attention.raw(q, k, v, causal=True)
        return out.astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)


def _sparse_core(topo, monkeypatch, heads=32, kv=4, seq=8192):
    """The selected-key attention of the cell keye2-lm-ep8share-s8192 at
    its own shape, [8192, 32 over 4 heads, 128] under an int8 [8192,
    8192] selection: forward, dq, dkv and the head-averaged
    probabilities (``ops/sparse_attention.py``), a visit eight query
    heads of a key head, with the VMEM limit the calls reckon
    themselves (25 to 81 MiB; the compiler's default is 16)."""
    from paddle_tpu.ops import sparse_attention as dsa
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    dim = 128
    q, k = spec((seq, heads, dim)), spec((seq, kv, dim))
    assert dsa.kernels_eligible(seq, dim)

    def fwd_bwd(q_, k_, v_, mask):
        def loss(a, b, c):
            out, lse = dsa.core(a, b, c, mask)
            return out.astype(jnp.float32).sum(), lse
        grads, lse = jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q_, k_, v_)
        return grads, dsa.mean_head_probs(q_, k_, lse, mask)

    return fwd_bwd, (q, k, k, spec((seq, seq), jnp.int8))


def _sparse_core_group_of_one(topo, monkeypatch):
    """... with as many key heads as query heads, [2048, 8 over 8, 128]:
    a visit is one query head."""
    return _sparse_core(topo, monkeypatch, heads=8, kv=8, seq=2048)


def _indexer_scores(topo, monkeypatch):
    """The same cell's index scores, qI ``[8192, 16 heads, 64]`` against
    one key head with a weight a head: the forward kernel and the one
    that makes the three gradients, over the causal triangle of 512 x
    512 tiles (``ops/sparse_attention.py``)."""
    from paddle_tpu.ops import sparse_attention as dsa
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, heads, width = 8192, 16, 64
    assert dsa.scores_eligible(seq, heads, width)

    def fwd_bwd(q_idx, k_idx, w_idx, d_scores):
        scores, back = jax.vjp(dsa._scores_kernels, q_idx, k_idx, w_idx)
        return scores, back(d_scores)

    return fwd_bwd, (spec((seq, heads, width)), spec((seq, width)),
                     spec((seq, heads)), spec((seq, seq), jnp.float32))


def _dropless_experts(topo, monkeypatch, tokens=8192, d=2048, f=768,
                      held=16, k=8, matrices=3):
    """The same cell's expert layer, 16 held experts of 128 at width 768
    on 8192 tokens, 8 a token, three matrices an expert: the grouped
    products and their two gradients as the Mosaic kernels of
    ``ops/grouped_matmul.py``, which ``grouped_matmul.form`` gives these
    shapes, in the first window and in the overflow branch's scan
    (``incubate/distributed/models/moe/grouped.py``)."""
    from paddle_tpu.incubate.distributed.models.moe import grouped
    from paddle_tpu.ops import grouped_matmul
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    inner = [spec((held, d, f))] * (matrices - 1)
    assert grouped_matmul.form(
        spec((grouped.usual_rows(tokens, k, held, 128), d)),
        inner[0]) == "kernels"

    def loss(y, logits, *weights):
        experts, gates = grouped.route(logits, k)
        out, _ = grouped.experts_forward(y, experts, gates, weights, 0, 128)
        return out.sum()

    return jax.grad(loss, argnums=tuple(range(2 + matrices))), (
        spec((tokens, d)), spec((tokens, 128), jnp.float32), *inner,
        spec((held, f, d)))


def _dropless_experts_of_1856(topo, monkeypatch):
    """... and at the shape of the cell nemotron3-nano-ep16stage0-s8192:
    8 held of 128, 2688 x 1856, two matrices an expert, 6 a token; 1856
    is fourteen and a half lane groups, taken as it is."""
    return _dropless_experts(topo, monkeypatch, d=2688, f=1856, held=8,
                             k=6, matrices=2)


def _held_rows(topo, monkeypatch, tokens=8192, d=2688, held=8, k=6):
    """The experts' way back at the shape of the cell
    nemotron3-nano-ep16stage0-s8192 (8 held of 128, 6 a token, a window of
    6 144 rows for 49 152 slots, width 2688): the forward combine, gates
    and float32 sums, and the backward dispatch, bf16, each one call of
    ``ops/token_rows.py``'s kernel, which ``token_rows.form`` gives these
    shapes."""
    from paddle_tpu.incubate.distributed.models.moe import grouped
    from paddle_tpu.ops import token_rows
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    usual = grouped.usual_rows(tokens, k, held, 128)
    assert token_rows.form(spec((usual, d)), tokens, k) == "kernel"

    def both(rows, d_x, logits):
        experts, gates = grouped.route(logits, k)
        w = grouped._window(grouped.plan(experts, 0, held), 0, usual)
        return (grouped._add_back(w, rows, k, gates),
                grouped._add_back(w, d_x, k, dtype=d_x.dtype))

    return both, (spec((usual, d)), spec((usual, d)),
                  spec((tokens, 128), jnp.float32))


def _held_rows_of_keye(topo, monkeypatch):
    """... and at the shape of the cell keye2-lm-ep8share-s8192: 16 held
    of 128, 8 a token, a window of 16 384 rows for 65 536 slots, width
    2048."""
    return _held_rows(topo, monkeypatch, d=2048, held=16, k=8)


def _scan_operands(topo, monkeypatch):
    """``ssd_scan`` at the shape of the cell granite4h-micro-stage0-s8192:
    x ``[8192, 64 heads, 64]``, one group of B and C, state 128, chunk
    256, which ``ssm.scan_form`` gives to the kernels of
    ``ops/ssm_kernels.py`` (a visit a chunk of 256 for all its heads,
    two heads of a lane group at a time)."""
    from paddle_tpu.ops import ssm
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, heads, width, state = 8192, 64, 64, 128
    assert ssm.scan_form(seq, heads, width, 1, state, 256) == "kernels"
    by_head = spec((heads,), jnp.float32)
    return (spec((seq, heads, width)), spec((seq, heads), jnp.float32),
            by_head, spec((seq, 1, state)), spec((seq, 1, state)), by_head)


def _scan_fwd(topo, monkeypatch):
    from paddle_tpu.ops import ssm
    return (lambda *a: ssm.ssd_scan(*a, 256)), _scan_operands(topo,
                                                              monkeypatch)


def _scan_bwd(topo, monkeypatch):
    """... differentiated: the forward kernel once more, which hands on
    the states the chunks start from, and the walk back."""
    from paddle_tpu.ops import ssm
    args = _scan_operands(topo, monkeypatch)

    def grads(x, dt, A, B, C, D, dy):
        return jax.vjp(lambda *a: ssm.ssd_scan(*a, 256),
                       x, dt, A, B, C, D)[1](dy)

    return grads, args + (args[0],)


def _scan_operands_8_groups(topo, monkeypatch):
    """``ssd_scan`` at the shape of the cell nemotron3-nano-ep16stage0-s8192:
    x ``[8192, 64 heads, 64]``, eight groups of B and C, state 128, chunk
    128: a visit is a chunk of 128 for all heads, ``C . B^T`` made at a
    group's first of four lane groups, its gradient gathered at its last."""
    from paddle_tpu.ops import ssm
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, heads, width, groups, state = 8192, 64, 64, 8, 128
    assert ssm.scan_form(seq, heads, width, groups, state, 128) == "kernels"
    by_head = spec((heads,), jnp.float32)
    return (spec((seq, heads, width)), spec((seq, heads), jnp.float32),
            by_head, spec((seq, groups, state)), spec((seq, groups, state)),
            by_head)


def _scan_fwd_8_groups(topo, monkeypatch):
    from paddle_tpu.ops import ssm
    return (lambda *a: ssm.ssd_scan(*a, 128)), _scan_operands_8_groups(
        topo, monkeypatch)


def _scan_bwd_8_groups(topo, monkeypatch):
    from paddle_tpu.ops import ssm
    args = _scan_operands_8_groups(topo, monkeypatch)

    def grads(x, dt, A, B, C, D, dy):
        return jax.vjp(lambda *a: ssm.ssd_scan(*a, 128),
                       x, dt, A, B, C, D)[1](dy)

    return grads, args + (args[0],)


def _conv_operands(topo, monkeypatch, groups):
    """The mixers' convolution at s8192: the in-projection's result ``[8192,
    2 * 4096 + 2 * groups * 128 + 64]``, four taps over its 4096 + 2 *
    groups * 128 channels of xBC."""
    from paddle_tpu.ops import ssm
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, inner, bc = 8192, 4096, groups * 128
    channels = inner + 2 * bc
    assert ssm.conv_form(seq, channels, inner, groups, 128, 4) == "kernels"

    def split(proj, weight, bias):
        return ssm.conv_silu_split(
            proj[:, inner:inner + channels], weight, bias, inner, groups,
            128, lies_in=(proj, inner))

    return split, (spec((seq, inner + channels + 64)),
                   spec((channels, 4), jnp.float32),
                   spec((channels,), jnp.float32)), (
        spec((seq, inner)), spec((seq, bc)), spec((seq, bc)))


def _conv_fwd(groups):
    def build(topo, monkeypatch):
        return _conv_operands(topo, monkeypatch, groups)[:2]
    return build


def _conv_bwd(groups):
    def build(topo, monkeypatch):
        split, args, dys = _conv_operands(topo, monkeypatch, groups)
        return (lambda p, w, b, *d: jax.vjp(split, p, w, b)[1](d)), args + dys
    return build


def _short_conv_operands(topo, monkeypatch):
    """LFM2's gated short convolution at the cell
    lfm2-8b-a1b-ep4share-s8192's shape: the in-projection's result ``[8192,
    3 x 2048]``, three taps, and y's gradient."""
    from paddle_tpu.ops import short_conv
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    assert short_conv.gated_short_conv_form(8192, 2048, 3) == "kernels"
    return short_conv.gated_short_conv, (spec((8192, 3 * 2048)),
                                         spec((2048, 3))), spec((8192, 2048))


def _short_conv_fwd(topo, monkeypatch):
    return _short_conv_operands(topo, monkeypatch)[:2]


def _short_conv_bwd(topo, monkeypatch):
    op, args, dy = _short_conv_operands(topo, monkeypatch)
    return (lambda bcx, w, d: jax.vjp(op, bcx, w)[1](d)), args + (dy,)


def _flash_32_on_2(topo, monkeypatch):
    """The same cell's attention block: 32 query heads on 2 key/value
    heads of width 128 at s8192, forward and backward, through the public
    ``flash_attention`` (its GQA branch repeats K and V 16-fold)."""
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    q, kv = spec((1, 8192, 32, 128)), spec((1, 8192, 2, 128))

    def grads(q_, k_, v_, w_):
        return jax.grad(lambda *a: (pallas_ops.flash_attention.raw(
            *a, causal=True) * w_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q_, k_, v_)

    return grads, (q, kv, kv, q)


def _flash_window_20_on_10(topo, monkeypatch):
    """The cell phi4flash-depth6-s8192's window layer: one of its four
    calls, 20 query heads on 10 key/value heads of 64 at s8192 inside a
    window of 512, forward and backward through the public
    ``flash_attention``: the band grid (2 of 8 key blocks a query block
    forward, 2 of 4 in dq, 3 of 16 query blocks a key block in dkv) and
    its clamped index maps."""
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    q, kv = spec((1, 8192, 20, 64)), spec((1, 8192, 10, 64))

    def grads(q_, k_, v_, w_):
        return jax.grad(lambda *a: (pallas_ops.flash_attention.raw(
            *a, causal=True, window=512) * w_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q_, k_, v_)

    return grads, (q, kv, kv, q)


def _selective_scan(topo, monkeypatch):
    """The same cell's selective scan at ``[8192, 5120]``, state 16,
    bf16, differentiated: the two Mosaic kernels of
    ``ops/ssm_s6_kernels.py`` (the forward call, which also writes the
    chunks' starting states, and the walk back) at the chunk the length
    gives, 128, and all 5120 channels a visit.  Held: the cell's shape
    compiles for v5e, so its inner loops' row loads, the rolls down the
    sublanes and the product that sums dB and dC are all accepted, and a
    visit's blocks fit the VMEM the calls ask for."""
    from paddle_tpu.ops import ssm, ssm_s6_kernels
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, channels, state = 8192, 5120, 16
    assert ssm.selective_chunk(seq) == 128
    assert ssm.selective_scan_form(seq, 128, channels, state, 2) == "kernels"
    assert ssm_s6_kernels.block_of(128, channels, state, 2) == channels
    args = (spec((seq, channels)), spec((seq, channels), jnp.float32),
            spec((channels, state), jnp.float32), spec((seq, state)),
            spec((seq, state)), spec((channels,), jnp.float32),
            spec((seq, channels)))

    def grads(x, dt, A, B, C, D, w):
        return jax.grad(lambda *a: (ssm.selective_scan(*a) * w).astype(
            jnp.float32).sum(), argnums=tuple(range(6)))(x, dt, A, B, C, D)

    return grads, args


def _paged_decode(topo, monkeypatch):
    from paddle_tpu.inference.serving.paged_attention_kernel import \
        paged_ragged_attention
    spec = _one_chip_spec(topo)
    pool = spec((512, 16, H, D))
    return (lambda pk, pv, table, lens, q: paged_ragged_attention(
        pk, pv, table, lens, q, interpret=False)), (
        pool, pool, spec((B, 64), jnp.int32), spec((B,), jnp.int32),
        spec((B, H, D)))


class CompilerRefused(Exception):
    """The chip's compiler refused the kernel with the recorded message."""


def _refused(build, case_id, pattern, why):
    """A case held as xfail(strict) only while the compiler's own
    message matches ``pattern``: a compile that passes, or fails
    otherwise, fails the case."""
    return pytest.param(build, 1, pattern, id=case_id,
                        marks=pytest.mark.xfail(
                            strict=True, raises=CompilerRefused,
                            reason=f"{pattern}: {why}"))


def _delta_rule_operands(topo, monkeypatch):
    """The cell solar-open2-kda-rank0-s8192's delta rule: q, k and log
    alpha ``[8192, 8 heads, 128]`` float32, v bf16, beta ``[8192, 8]``,
    chunk 64: 128 visits of four turns of two heads stacked."""
    from paddle_tpu.ops import delta_rule, delta_rule_kernels
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    spec = _one_chip_spec(topo)
    seq, heads, dim = 8192, 8, 128
    assert delta_rule.rule_form(seq, heads, dim, dim, 64) == "kernels"
    assert delta_rule_kernels.group_of(heads, 64) == 2
    keys = spec((seq, heads, dim), jnp.float32)
    return (keys, keys, spec((seq, heads, dim)), keys,
            spec((seq, heads), jnp.float32))


def _delta_rule_fwd(topo, monkeypatch):
    from paddle_tpu.ops import delta_rule
    return (lambda *a: delta_rule.gated_delta_rule(*a, 64)), \
        _delta_rule_operands(topo, monkeypatch)


def _delta_rule_bwd(topo, monkeypatch):
    """... differentiated: the forward kernel, which also writes the
    states the chunks start from, and the walk back."""
    from paddle_tpu.ops import delta_rule
    args = _delta_rule_operands(topo, monkeypatch)

    def grads(q, k, v, log_alpha, beta, do):
        return jax.vjp(lambda *a: delta_rule.gated_delta_rule(*a, 64),
                       q, k, v, log_alpha, beta)[1](do)

    return grads, args + (args[2],)


@pytest.mark.parametrize("build, n_calls, refused", [
    pytest.param(_bh_fwd, 1, None, id="flash_bh_fwd"),
    pytest.param(_bh_bwd, 2, None, id="flash_bh_bwd"),
    pytest.param(_bh_own_geometry, 3, None, id="flash_bh_25_heads_of_64"),
    pytest.param(_packed_fwd, 1, None, id="flash_packed_fwd"),
    pytest.param(_packed_bwd, 2, None, id="flash_packed_bwd"),
    pytest.param(_packed_varlen, 3, None, id="flash_packed_varlen"),
    pytest.param(_packed_varlen_s2048, 3, None,
                 id="flash_packed_varlen_s2048"),
    pytest.param(_packed_two_key_blocks, 3, None,
                 id="flash_packed_two_key_blocks"),
    pytest.param(_sharded_public_op, 3, None,
                 id="flash_attention_dp2_mp2"),
    pytest.param(_sparse_core, 4, None, id="sparse_core_s8192"),
    pytest.param(_sparse_core_group_of_one, 4, None,
                 id="sparse_core_group_of_one_s2048"),
    pytest.param(_indexer_scores, 2, None, id="indexer_scores_s8192"),
    pytest.param(_dropless_experts, 18, None, id="dropless_experts"),
    pytest.param(_dropless_experts_of_1856, 12, None,
                 id="dropless_experts_8_held_2688_by_1856"),
    pytest.param(_held_rows, 2, None,
                 id="held_rows_back_6144_rows_of_2688_to_8192_tokens"),
    pytest.param(_held_rows_of_keye, 2, None,
                 id="held_rows_back_16384_rows_of_2048_to_8192_tokens"),
    pytest.param(_scan_fwd, 1, None, id="ssd_scan_fwd_s8192"),
    pytest.param(_scan_bwd, 2, None, id="ssd_scan_bwd_s8192"),
    pytest.param(_scan_fwd_8_groups, 1, None,
                 id="ssd_scan_fwd_s8192_8_groups_chunk_128"),
    pytest.param(_scan_bwd_8_groups, 2, None,
                 id="ssd_scan_bwd_s8192_8_groups_chunk_128"),
    pytest.param(_flash_32_on_2, 3, None, id="flash_32_on_2_heads_of_128"),
    pytest.param(_conv_fwd(1), 1, None, id="ssm_conv_fwd_s8192_4352_channels"),
    pytest.param(_conv_bwd(1), 1, None, id="ssm_conv_bwd_s8192_4352_channels"),
    pytest.param(_conv_fwd(8), 1, None, id="ssm_conv_fwd_s8192_6144_channels"),
    pytest.param(_conv_bwd(8), 1, None, id="ssm_conv_bwd_s8192_6144_channels"),
    pytest.param(_short_conv_fwd, 1, None,
                 id="short_conv_fwd_s8192_2048_channels_3_taps"),
    pytest.param(_short_conv_bwd, 1, None,
                 id="short_conv_bwd_s8192_2048_channels_3_taps"),
    pytest.param(_flash_window_20_on_10, 3, None,
                 id="flash_window_512_20_on_10_heads_of_64_s8192"),
    pytest.param(_selective_scan, 2, None,
                 id="selective_scan_s8192_5120_channels_state_16"),
    pytest.param(_delta_rule_fwd, 1, None, id="delta_rule_fwd_s8192"),
    pytest.param(_delta_rule_bwd, 2, None, id="delta_rule_bwd_s8192"),
    _refused(_paged_decode, "paged_ragged_attention",
             r"Unable to parse attribute:\s+error: "
             r"\"#tpu\.dot_dimension_numbers",
             "the kernel's 3-D einsums; it also has no BlockSpecs "
             "(ROADMAP Reach: serving bring-up on the chip)"),
])
def test_compiles_for_v5e(topo, monkeypatch, build, n_calls, refused):
    fn, args = build(topo, monkeypatch)
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
    except Exception as e:
        if refused and re.search(refused, str(e)):
            raise CompilerRefused(refused) from e
        raise
    assert text.count("tpu_custom_call") >= n_calls


def _shifted_terms_in_hbm(text: str, channels: int):
    """The fusions under ``ssm_conv`` whose result holds four ``bf16[8192,
    channels]`` arrays: the XLA form's backward pass wrote the four
    shifted, weighted copies of the convolution's gradient so, one
    fusion a Mamba-2 layer, and read them back to add them (ISSUE 37's
    compile rehearsal; nine in Granite's step at PR 36)."""
    shape = f"bf16[8192,{channels}]"
    return [line for line in text.splitlines()
            if "ssm_conv" in line and " fusion(" in line
            and line.split(" fusion(")[0].count(shape) >= 4]


def _compiled_step(topo, monkeypatch, driver, config, *more):
    """(the compiled step, its ``memory_analysis()``, the bytes it needs on
    a device) of the ``DistributedRunner`` that ``driver.build_runner``
    makes of ``config`` for one described chip, on one sequence of 8192
    tokens.  The parameters are zeros placeholders (``LazyGuard``) and
    nothing is put on a device: the step is lowered on shapes."""
    import numpy as np
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    prev_mesh = collective.get_mesh()
    try:
        with paddle_tpu.LazyGuard():
            runner = driver.build_runner(config, 0, topo.devices[:1], *more)
        monkeypatch.setattr(runner, "_shard", lambda value, spec: value)
        ids = np.zeros((1, 8192), np.int64)
        data = sum(runner._prep_step_args([ids], [ids]), [])
        on_chip = NamedSharding(runner.mesh, P())

        def shapes(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=on_chip), tree)

        compiled = runner._step_fn.lower(
            *shapes(runner._sync_val_cache()), shapes(runner._opt_state),
            *shapes([jnp.float32(0), jnp.uint32(1)] + data)).compile()
    finally:
        collective.set_mesh(prev_mesh)
    memory = compiled.memory_analysis()
    return compiled, memory, (
        memory.argument_size_in_bytes + memory.temp_size_in_bytes
        + memory.output_size_in_bytes - memory.alias_size_in_bytes)


def test_granite_stage0_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the cell granite4h-micro-stage0-s8192
    (ten layers at published widths, b1 x s8192, bf16 O2 with float32
    master weights, every layer recomputed), as ``DistributedRunner``
    builds it, compiled for one described v5e chip: what it needs on the
    device stays under the configuration's limit, and the attention
    layer's kernels (forward, the forward again, dq, dkv), the nine
    scans' and the nine convolutions' (three each a layer) are in it,
    and the convolution's four shifted gradient terms are not.
    The parameters are zeros placeholders (``LazyGuard``) and nothing is
    put on a device: the step is lowered on shapes."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks.drivers import train_granite_lm as driver
    from benchmarks.harness import cells
    config = cells.load_cell("granite4h-micro-stage0-s8192", root).config
    compiled, memory, step = _compiled_step(topo, monkeypatch, driver,
                                            config)
    # 772 160 448 parameters at 14 bytes, and the batch
    assert memory.argument_size_in_bytes == approx(10.81e9, rel=2e-3)
    assert step < config["step_bytes_limit"] == 15.6e9
    # the notes' figures are PR 32's, when the scans were XLA operations
    # (14.42e9, 4 sites): the step may need less, never 2 % more; the
    # sites are now counted here (the notes wait for a `benchmark` PR)
    recorded = config["notes"]["compiled_step_bytes_a_device"][
        "pretrain-b1-s8192"]
    assert step <= recorded["step"] * 1.02
    print(f"compiled step: {step} bytes a device")
    kinds = config["layer_types"]
    text = compiled.as_text()
    # the attention layer: forward, the forward again, dq, dkv; a Mamba
    # layer's scan: forward, the forward again, the walk back; and, new
    # with PR 37, its convolution (taps, bias, SiLU and split): forward,
    # the forward again, the walk back
    assert text.count("tpu_custom_call") == \
        4 * kinds.count("attention") + (3 + 3) * kinds.count("mamba") == 58
    assert _shifted_terms_in_hbm(text, 4096 + 2 * 128) == []


def test_nemotron_stage0_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the cell nemotron3-nano-ep16stage0-s8192
    (nine blocks MEMEM*EME at published widths, 8 of 128 experts, b1 x
    s8192, bf16 O2 with float32 master weights, the blocks the
    configuration names recomputed), as ``DistributedRunner`` builds it,
    compiled for one described v5e chip: what it needs on the device stays
    under the configuration's limit, and its own kernels are in it: a
    Mamba-2 block's scan and its convolution, each forward and the walk
    back, the attention block's forward, dq and dkv, the forward once more
    where a block is recomputed; the rest are the experts' grouped products, the kernels
    of ``ops/grouped_matmul.py`` by their names: fourteen an expert
    block, six of them the first window's (two products forward, their
    four gradients) and eight the overflow branch's; and the way back to
    the tokens, ``ops/token_rows.py``'s kernel by its name, four a block,
    with no ``[8192, 6, 2688]`` array of a token's slots left."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks.drivers import train_nemotron_lm as driver
    from benchmarks.families import nemotron_h as family
    from benchmarks.harness import cells
    config = cells.load_json(os.path.join(
        root, "benchmarks", "configs", "nemotron-3-nano-30b-a3b.json"))
    from paddle_tpu.observability import metrics

    def product_calls():
        return [metrics.registry().counter(
            "moe_grouped_kernel_calls_total", labels={"kind": kind}
        ).collect() for kind in ("fwd", "dlhs", "drhs")]

    def back_calls():
        return metrics.registry().counter(
            "moe_combine_calls_total", labels={"form": "held_rows"}).collect()

    before, back_before = product_calls(), back_calls()
    compiled, memory, step = _compiled_step(topo, monkeypatch, driver,
                                            config)
    # 666 962 944 parameters at 14 bytes, and the batch
    assert memory.argument_size_in_bytes == approx(9.3375e9, rel=1e-3)
    assert step < config["step_bytes_limit"] == 15.6e9
    print(f"compiled step: {step} bytes a device")
    text = compiled.as_text()
    kinds = family.kinds(config)
    own = driver.kernel_sites(kinds, set(config["recompute"]))
    assert own == 4 * 2 + 3 + len(config["recompute"])
    # a Mamba-2 block's convolution: forward and the walk back, the
    # forward once more where the block is recomputed (the driver's count
    # is the benchmark's and does not know them)
    convolutions = sum(2 + (i in config["recompute"])
                       for i, kind in enumerate(kinds) if kind == "mamba")
    assert convolutions == 10
    sites = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    products = [line for line in sites if "grouped_dot" in line]
    print(f"{len(sites)} tpu_custom_call sites, {len(products)} of them the "
          "experts' grouped products")
    blocks = kinds.count("moe")
    assert len(products) == 14 * blocks
    # as the step was traced, an expert block: two products forward, in
    # the overflow branch and there again for its backward pass; their
    # four gradients in the first window and in the branch
    assert [now - was for now, was in zip(product_calls(), before)] == [
        6 * blocks, 4 * blocks, 4 * blocks]
    # the way back by held rows: as traced, the first window's combine and
    # dispatch, the overflow branch's and its forward again; compiled,
    # four a block, the last one's result unused
    back = [line for line in sites if "token_rows_add" in line]
    assert back_calls() - back_before == 5 * blocks
    assert len(back) == 4 * blocks
    assert len(sites) - len(products) - len(back) == own + convolutions
    assert "bf16[8192,6,2688]" not in text
    assert "ragged-dot" not in text
    assert _shifted_terms_in_hbm(text, 4096 + 2 * 8 * 128) == []


def test_phi4flash_depth6_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the cell phi4flash-depth6-s8192 (six
    layers, one of each kind of SambaY's, at published widths, b1 x s8192,
    bf16 O2 with float32 master weights, the layers the configuration
    names recomputed), as ``DistributedRunner`` builds it, compiled for
    one described v5e chip: what it needs on the device stays a tenth
    under the configuration's limit (the rule its ``recompute`` was chosen
    by), and its kernels are in it: an attention-kind layer's four calls
    of ``flash_attention``, each forward, dq and dkv, and the forward once
    more where the layer is recomputed; and, since PR 39, a Mamba layer's
    selective scan, forward and the walk back, and the forward again where
    the layer is recomputed, as both are."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks.drivers import train_sambay_lm as driver
    from benchmarks.families import sambay as family
    from benchmarks.harness import cells
    config = cells.load_cell("phi4flash-depth6-s8192", root).config
    compiled, memory, step = _compiled_step(topo, monkeypatch, driver,
                                            config)
    # 697 094 272 parameters at 14 bytes (the LayerNorms' 66 560 hold no
    # bf16 copy), and the batch
    assert memory.argument_size_in_bytes == approx(9.76e9, rel=1e-3)
    assert step < 0.9 * config["step_bytes_limit"] == 0.9 * 15.6e9
    print(f"compiled step: {step} bytes a device")
    kinds = family.kinds(config)
    sites = driver.kernel_sites(kinds, set(config["recompute"]))
    # layers 0, 1, 2 and 4 are recomputed: of the attention kinds the
    # window layer alone
    assert config["recompute"] == [0, 1, 2, 4]
    assert sites == 3 * 12 + 4
    scans = sum(2 + (i in config["recompute"]) for i, kind in enumerate(kinds)
                if kind in ("mamba", "mamba_memory"))
    assert compiled.as_text().count("tpu_custom_call") == sites + scans == \
        40 + 6


def test_lfm2_ep4share_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the cell lfm2-8b-a1b-ep4share-s8192 (the
    published layers 1-5 of LFM2-8B-A1B at published widths, 8 of 32
    experts, b1 x s8192, bf16 O2 with float32 master weights, nothing
    recomputed), as ``DistributedRunner`` builds it, compiled for one
    described v5e chip: what it needs on the device stays a tenth under
    the configuration's limit (the rule its empty ``recompute`` was chosen
    by), and its kernels are in it: the attention layer's forward, dq and
    dkv, and the experts' grouped products, the kernels of
    ``ops/grouped_matmul.py`` by their names, at ``[16384, 2048] x [8,
    2048, 1792]`` and its transpose, with the way back to the tokens,
    ``ops/token_rows.py``'s kernel, and the gated short convolution's two
    kernels a convolution layer (``ops/short_conv_kernels.py``), counted
    by ``short_conv_kernel_calls_total`` as the step is traced."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks.drivers import train_lfm2_lm as driver
    from benchmarks.families import lfm2_moe as family
    from benchmarks.harness import cells
    from paddle_tpu.observability import metrics
    config = cells.load_cell("lfm2-8b-a1b-ep4share-s8192", root).config
    calls = [metrics.registry().counter("short_conv_kernel_calls_total",
                                        labels={"kind": kind})
             for kind in ("fwd", "bwd")]
    before = [c.collect() for c in calls]
    compiled, memory, step = _compiled_step(topo, monkeypatch, driver,
                                            config, 8192)
    assert [c.collect() - b for c, b in zip(calls, before)] == [4, 4]
    # 507 820 160 parameters at 14 bytes (the norms' 24 704 hold no bf16
    # copy), the routers' biases, the step's counts and choices, the batch
    assert memory.argument_size_in_bytes == approx(7.1098e9, rel=1e-3)
    assert step < 0.9 * config["step_bytes_limit"] == 0.9 * 15.6e9
    print(f"compiled step: {step} bytes a device")
    kinds = family.kinds(config)
    assert config["recompute"] == []
    own = driver.kernel_sites(kinds, set())
    assert own == 3
    sites = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    products = [line for line in sites if "grouped_dot" in line]
    print(f"{len(sites)} tpu_custom_call sites, {len(products)} of them the "
          "experts' grouped products")
    # an expert layer: nine the first window's (three products forward,
    # their six gradients), fourteen the overflow branch's (three forward
    # and, in its loop's backward pass, eleven as the compiler leaves
    # them: the three again and the gradients)
    layers = sum(k.endswith("_moe") for k in kinds)
    assert len(products) == 23 * layers == 92
    # and the way back by held rows: the first window's combine and
    # dispatch and the overflow branch's
    back = [line for line in sites if "token_rows_add" in line]
    assert len(back) == 4 * layers
    # the four convolution layers' operators, a call forward and one back
    conv = [line for line in sites if "%_gated_conv_" in line]
    assert [sum(f"%_gated_conv_{kind}" in line for line in conv)
            for kind in ("fwd", "bwd")] == [4, 4]
    assert len(sites) - len(products) - len(back) - len(conv) == own
    assert "bf16[8192,4,2048]" not in compiled.as_text()
    assert "ragged-dot" not in compiled.as_text()


def test_solar_kda_rank0_step_fits_a_v5e(topo, monkeypatch):
    """The whole training step of the cell solar-open2-kda-rank0-s8192
    (layers 0-3 of Solar-Open2-250B, GQA then three KDA layers, at
    published widths, 8 of 64 heads, 8 of 320 experts, b1 x s8192, bf16
    O2 with float32 master weights, every mixer recomputed), as
    ``DistributedRunner`` builds it, compiled for one described v5e chip:
    what it needs on the device stays under the configuration's limit, and
    its kernels are in it: the GQA layer's forward, the forward again,
    dq and dkv; a KDA layer's short convolutions (``ops/ssm.py``'s kernels
    over ``[q | k | v]``), forward, the forward again and the walk back;
    the experts' grouped products, 21 a layer, and the way back to the
    tokens, 4 a layer; and a KDA layer's delta rule, the Mosaic kernels of
    ``ops/delta_rule_kernels.py``: forward, the forward again (which also
    writes the chunks' starting states) and the walk back, traced a layer
    once forward (perhaps once again for the recomputation)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmarks.drivers import train_solar_lm as driver
    from benchmarks.families import solar_open2 as family
    from benchmarks.harness import cells
    from paddle_tpu.observability import metrics
    config = cells.load_cell("solar-open2-kda-rank0-s8192", root).config
    calls = metrics.registry().counter("delta_rule_calls_total")
    visits = [metrics.registry().counter("delta_rule_kernel_visits_total",
                                         labels={"kind": kind})
              for kind in ("fwd", "bwd")]
    before = calls.collect()
    visits_before = [v.collect() for v in visits]
    compiled, memory, step = _compiled_step(topo, monkeypatch, driver,
                                            config, 8192)
    kinds = family.kinds(config)
    assert kinds == ("gqa", "kda", "kda", "kda")
    assert config["recompute"] == [0, 1, 2, 3]
    # the forward pass once a KDA layer, and once more for the
    # recomputation where jax's cache of traces does not serve that trace
    # (it does or not by what the process traced before)
    assert calls.collect() - before in (3, 6)
    # a call is 128 chunks of 8 heads: the forward kernel traced once a
    # forward pass and once for the states, the walk back once a layer
    fwd, bwd = [v.collect() - was for v, was in zip(visits, visits_before)]
    assert fwd in (3 * 2 * 1024, 3 * 3 * 1024) and bwd == 3 * 1024
    # 840 874 392 parameters at 14 bytes (the norms hold no bf16 copy),
    # the routers' biases, the step's counts and choices, the batch
    assert memory.argument_size_in_bytes == approx(11.7725e9, rel=1e-3)
    assert step < config["step_bytes_limit"] == 15.6e9
    print(f"compiled step: {step} bytes a device")
    sites = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    products = [line for line in sites if "grouped_dot" in line]
    back = [line for line in sites if "token_rows_add" in line]
    conv = [line for line in sites
            if re.search(r"%_(forward|backward)_call", line)]
    rule = [line for line in sites if re.search(r"_delta_(fwd|bwd)", line)]
    flash = [line for line in sites if "%_flash_packed_" in line]
    print(f"{len(sites)} tpu_custom_call sites, {len(products)} of them the "
          "experts' grouped products")
    assert len(products) == 21 * 4 and len(back) == 4 * 4
    assert len(conv) == 3 * 3 and len(rule) == 3 * 3
    assert not set(conv) & set(rule)
    assert len(flash) == driver.kernel_sites(kinds, {0, 1, 2, 3}) == 4
    assert len(sites) == (len(products) + len(back) + len(conv) + len(rule)
                          + len(flash))


def _dp2_mp2_gpt_step(topo, monkeypatch, options):
    """The compiled text of the GPT train step that ``DistributedRunner``
    builds on a dp2 x mp2 mesh of the described v5e:2x2 (two layers of
    1024, 8 heads of 128, b4 x s512, bf16 O2 AdamW): as the runner
    compiles it where ``options`` is true, with no compiler options
    where it is false.  Zeros placeholders, lowered on shapes."""
    import numpy as np
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    monkeypatch.setattr(pallas_ops, "_on_tpu", lambda: True)
    mesh = collective.build_mesh({"dp": 2, "mp": 2}, devices=topo.devices)
    collective.set_mesh(mesh)
    with paddle_tpu.LazyGuard():
        net = GPTForCausalLM(GPTConfig(
            vocab_size=4096, hidden_size=1024, num_hidden_layers=2,
            num_attention_heads=8, intermediate_size=4096,
            max_position_embeddings=512, hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0))
        opt = optimizer.AdamW(1e-4, parameters=net.parameters(),
                              multi_precision=True)
        amp.decorate(net, opt, level="O2", dtype="bfloat16")
        runner = DistributedRunner(net, opt, GPTPretrainingCriterion(),
                                   mesh=mesh)
    monkeypatch.setattr(runner, "_shard", lambda value, spec: value)
    ids = np.zeros((4, 512), np.int64)
    data = sum(runner._prep_step_args([ids], [ids]), [])
    step_fn = runner._step_fn if options else jax.jit(
        runner._step_fn._fun, donate_argnums=(0, 3))

    def on(v, spec):
        return jax.ShapeDtypeStruct(v.shape, v.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params, frozen, bufs = runner._sync_val_cache()
    state = {n: {k: on(v, runner._state_spec(runner._pspecs[n], v, name=n))
                 for k, v in st.items()}
             for n, st in runner._opt_state.items()}
    return step_fn.lower(
        {n: on(v, runner._pspecs[n]) for n, v in params.items()},
        {n: on(v, runner._pspecs[n]) for n, v in frozen.items()},
        {n: on(v, P()) for n, v in bufs.items()}, state,
        on(jnp.float32(0), P()), on(jnp.uint32(1), P()),
        *[on(d, P("dp")) for d in data]).compile().as_text()


def test_dp2_mp2_step_reduces_gradients_under_the_backward_pass(
        topo, monkeypatch):
    """On four TPU devices the runner asks for the collective schedule of
    ``step_schedule.COMPILER_OPTIONS``: the step's all-reduces become
    asynchronous, the weight gradients' among them run under compute,
    and the all-gathers stay plain.  Without the options the same step's
    all-reduces are all plain (at this size 15 of them; with the options
    11 run under compute, 13 have nothing under them and 2 small ones
    stay plain)."""
    from paddle_tpu.distributed import step_schedule
    parent = step_schedule.collective_modes(
        _dp2_mp2_gpt_step(topo, monkeypatch, options=False))
    change = step_schedule.collective_modes(
        _dp2_mp2_gpt_step(topo, monkeypatch, options=True))
    print("without the options", dict(parent), "with them", dict(change))
    assert parent[("all-reduce", "sync")] > 0
    assert parent[("all-reduce", "overlapped")] == 0
    assert change[("all-reduce", "overlapped")] > 0
    assert change[("all-reduce", "sync")] * 4 <= parent[("all-reduce", "sync")]
    assert change[("all-gather", "sync")] == sum(
        n for (kind, _), n in change.items() if kind == "all-gather") > 0

"""``ops/token_rows.py``'s kernel, interpreted on the CPU, against a
scatter-add of the same rows; and which form runs where."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — turns on x64, which the kernel must survive
from paddle_tpu.ops import token_rows


K = 6


def _reference(rows, pair, tokens, gates):
    ok = (pair >= 0) & (pair < tokens * K)
    safe = jnp.where(ok, pair, 0)
    weight = (1.0 if gates is None
              else gates.reshape(-1)[safe][:, None])
    rows = jnp.where(ok[:, None], weight * rows.astype(jnp.float32), 0.0)
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[
        safe // K].add(rows)


def _pairs_of(case, n, tokens, rng):
    """Distinct pairs, as a window holds them: sorted by expert, so not
    by token."""
    pair = rng.permutation(tokens * K)[:n]
    if case == "a_tile_with_more_rows_than_a_visit":
        pair[:120] = rng.permutation(20 * K)[:120]   # 120 rows of tile 0
    elif case == "empty_tiles_around_a_full_one":
        pair = 128 * K + rng.permutation(128 * K)[:n]
    elif case == "rows_of_no_pair":
        pair[::3] = tokens * K
        pair[1::7] = -1
    elif case == "no_row_of_any_pair":
        pair[:] = tokens * K
    return pair.astype(np.int32)


_CASES = ("uniform", "a_tile_with_more_rows_than_a_visit",
          "empty_tiles_around_a_full_one", "rows_of_no_pair",
          "no_row_of_any_pair")


@pytest.mark.parametrize("width", [128, 192], ids=["lanes", "no_whole_lanes"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "counted"])
@pytest.mark.parametrize("case", _CASES)
def test_rows_add_into_their_tokens(monkeypatch, case, gated, dtype, width):
    """Each row, times its pair's gate or once, lands in its token in
    float32; rows of no pair and rows whose pair is out of range count
    for none, whatever they hold; a tile of more rows than a visit takes
    several."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    tokens, n = 384, 300
    rng = np.random.default_rng(len(case))
    pair = jnp.asarray(_pairs_of(case, n, tokens, rng))
    rows = jnp.asarray(rng.standard_normal((n, width)), dtype)
    # what lies in a row of no pair is no part of the result
    rows = jnp.where((pair == tokens * K)[:, None], jnp.nan,
                     rows).astype(dtype)
    gates = (jnp.asarray(rng.random((tokens, K)) + 0.1, jnp.float32)
             if gated else None)
    assert token_rows.form(rows, tokens, K) == "kernel"
    got = token_rows.add(rows, pair, K, tokens, gates)
    want = _reference(rows, pair, tokens, gates)
    assert got.dtype == jnp.float32 and got.shape == (tokens, width)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * float(jnp.abs(want).max(
                                   initial=1.0)))
    low = token_rows.add(rows, pair, K, tokens, gates, dtype=dtype)
    assert low.dtype == dtype
    np.testing.assert_allclose(low.astype(jnp.float32),
                               got.astype(dtype).astype(jnp.float32))


@pytest.mark.parametrize("interpreted, disabled, shape, tokens, form", [
    pytest.param(True, False, ((512, 256), jnp.bfloat16), 1024, "kernel",
                 id="interpreted"),
    pytest.param(False, False, ((512, 256), jnp.bfloat16), 1024, "xla",
                 id="the_cpu"),
    pytest.param(True, True, ((512, 256), jnp.bfloat16), 1024, "xla",
                 id="kernels_disabled"),
    pytest.param(True, False, ((512, 256), jnp.bfloat16), 1000, "xla",
                 id="tokens_of_no_whole_tile"),
    pytest.param(True, False, ((512, 256), jnp.int32), 1024, "xla",
                 id="integers"),
    pytest.param(True, False, ((512, 1 << 16), jnp.float32), 1024, "xla",
                 id="rows_too_wide_for_vmem"),
])
def test_form_reads_platform_shape_and_dtype(monkeypatch, interpreted,
                                             disabled, shape, tokens, form):
    if interpreted:
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    if disabled:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert token_rows.form(jax.ShapeDtypeStruct(*shape), tokens, 8) == form

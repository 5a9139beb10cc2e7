"""Serving subsystem tests (ISSUE 6): paged KV cache invariants,
ragged-decode exactness vs per-request sequential decode,
continuous-batching join/leave recompile pins, streaming ordering,
admission behavior, and the persistent compilation cache.

Exactness contract under test (DESIGN-SERVING.md §Exactness): greedy
token sequences from the batched mixed-length paged path match the
per-request sequential dense-cache reference EXACTLY; logits match to
float32 tolerance (the padded-axis reduction order is the only
difference, ~1 ulp).
"""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import paddle_tpu as paddle

# retrace sentinel armed module-wide (ISSUE 17): any trace of a
# single-trace compiled entry after its first dispatch raises,
# making every recompile pin in here an ambient property
pytestmark = pytest.mark.usefixtures("retrace_strict")

from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.inference.serving import (
    BlockAllocator, DecodeEngine, LLMServer, OutOfBlocks, QueueFull,
    SCRATCH_BLOCK, ServingModelConfig, extract_decode_params,
    prefill_forward, ragged_decode_attention, reference_decode)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------
def test_allocator_alloc_free_invariants():
    a = BlockAllocator(17)          # 16 usable, block 0 scratch
    assert a.capacity == 16
    got = a.allocate(5)
    assert len(got) == 5 and len(set(got)) == 5
    assert SCRATCH_BLOCK not in got
    assert a.num_free == 11 and a.num_allocated == 5
    more = a.allocate(3)
    assert not (set(got) & set(more))
    a.free(got)
    assert a.num_free == 13         # 16 - 5 - 3 + 5
    with pytest.raises(ValueError):
        a.free(got[:1])             # double free
    with pytest.raises(OutOfBlocks):
        a.allocate(15)              # only 13 free
    # freed blocks are reusable
    again = a.allocate(13)
    assert len(again) == 13 and a.num_free == 0


def test_allocator_contiguous_best_fit_and_fragmentation():
    a = BlockAllocator(17)
    first = a.allocate(16)          # drain
    a.free(first)
    assert a.stats()["fragmentation"] == 0.0  # one contiguous run
    # punch holes: allocate all, free two separated runs of 3 and 6
    blocks = a.allocate(16)
    run3 = blocks[2:5]
    run6 = blocks[8:14]
    a.free(run3)
    a.free(run6)
    st = a.stats()
    assert st["free_runs"] == 2 and st["largest_run"] == 6
    assert 0.0 < st["fragmentation"] < 1.0
    # best-fit: a 3-block ask takes the SMALLEST fitting run, keeping
    # the 6-run intact for larger requests
    got = a.allocate(3)
    assert sorted(got) == sorted(run3)
    assert a.stats()["largest_run"] == 6
    # scattered fallback: free one more single, ask for 4 → no single
    # run fits a contiguity-first match of 7? (runs: 6 + 1) → 4 comes
    # out of the 6-run; ask for 7 then must scatter across runs
    a.free(blocks[0:1])
    got7 = a.allocate(7)
    assert len(got7) == 7 and len(set(got7)) == 7


def test_allocator_reservation_accounting():
    a = BlockAllocator(9)           # 8 usable
    assert a.reserve(5)
    assert a.reserved == 5
    assert not a.can_reserve(4)     # 5+4 > 8
    assert a.reserve(3)
    assert not a.reserve(1)
    a.release(5)
    assert a.reserve(5)
    a.release(8)
    assert a.reserved == 0


# ---------------------------------------------------------------------------
# ragged attention
# ---------------------------------------------------------------------------
def test_ragged_decode_attention_matches_per_request_dense():
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    B, T, H, Dh = 3, 24, 2, 8
    lengths = np.array([24, 7, 1], dtype=np.int32)
    q = rng.randn(B, H, Dh).astype(np.float32)
    k = rng.randn(B, T, H, Dh).astype(np.float32)
    v = rng.randn(B, T, H, Dh).astype(np.float32)
    out = np.asarray(ragged_decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lengths)))
    for b in range(B):
        L = int(lengths[b])
        ref = np.asarray(ragged_decode_attention(
            jnp.asarray(q[b:b + 1]), jnp.asarray(k[b:b + 1, :L]),
            jnp.asarray(v[b:b + 1, :L]),
            jnp.asarray(np.array([L], np.int32))))
        np.testing.assert_allclose(out[b], ref[0], rtol=2e-6,
                                   atol=2e-6)


def test_ragged_attention_empty_row_yields_zero_not_nan():
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(2, 2, 4).astype(np.float32))
    k = jnp.asarray(rng.randn(2, 8, 2, 4).astype(np.float32))
    v = jnp.asarray(rng.randn(2, 8, 2, 4).astype(np.float32))
    out = np.asarray(ragged_decode_attention(
        q, k, v, jnp.asarray(np.array([0, 8], np.int32))))
    assert np.all(np.isfinite(out))
    assert np.all(out[0] == 0.0)


# ---------------------------------------------------------------------------
# decode exactness
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_net():
    paddle.seed(0)
    cfg = gpt_tiny(use_flash_attention=False)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net, cfg


def test_prefill_logits_match_training_forward(tiny_net):
    """Weight extraction + serving math vs the hapi training forward:
    bit-identical last-position logits on this CPU backend (both paths
    run the same f32 row-wise primitives)."""
    import jax.numpy as jnp
    from paddle_tpu.tensor import Tensor
    from paddle_tpu.autograd import tape
    net, cfg = tiny_net
    params = extract_decode_params(net)
    scfg = ServingModelConfig.from_gpt_config(cfg)
    rng = np.random.RandomState(2)
    L = 13
    ids = rng.randint(0, cfg.vocab_size, (1, L)).astype(np.int64)
    with tape.no_grad_ctx():
        want = net(Tensor(ids)).numpy()[0, L - 1]
    _, _, got = prefill_forward(params, scfg,
                                jnp.asarray(ids, jnp.int32),
                                jnp.int32(L))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5)


def test_batched_paged_decode_exact_vs_sequential(tiny_net):
    """THE acceptance pin: mixed-length batched decode over the paged
    cache = per-request sequential dense decode, token-for-token."""
    net, cfg = tiny_net
    params = extract_decode_params(net)
    scfg = ServingModelConfig.from_gpt_config(cfg)
    eng = DecodeEngine(net, max_batch=4, block_size=8, num_blocks=64)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in (5, 11, 3, 17)]
    futs = [eng.submit(p, max_tokens=12).future for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        got = f.result(timeout=0).tokens
        ref_toks, _ = reference_decode(params, scfg, p, 12)
        assert got == [int(t) for t in ref_toks]


def test_prefill_bucket_padding_is_harmless(tiny_net):
    """A prompt prefilled at a larger bucket produces the same first
    token and same-to-tolerance logits as the exact-length prefill."""
    import jax.numpy as jnp
    net, cfg = tiny_net
    params = extract_decode_params(net)
    scfg = ServingModelConfig.from_gpt_config(cfg)
    rng = np.random.RandomState(3)
    L, bucket = 11, 32
    prompt = rng.randint(0, cfg.vocab_size, (L,))
    exact = np.zeros((1, L), np.int32)
    exact[0] = prompt
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :L] = prompt
    _, tok_e, lg_e = prefill_forward(params, scfg, jnp.asarray(exact),
                                     jnp.int32(L))
    _, tok_p, lg_p = prefill_forward(params, scfg, jnp.asarray(padded),
                                     jnp.int32(L))
    assert int(tok_e) == int(tok_p)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_e),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------
def test_join_leave_across_groups_zero_recompiles(tiny_net):
    """Acceptance pin: requests join/leave the running batch across
    >= 3 dispatch groups with ZERO new decode compilations."""
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=2, block_size=8, num_blocks=64)
    rng = np.random.RandomState(4)

    def run_some(n):
        for _ in range(n):
            if not eng.step():
                break

    # group 1: two requests fill the batch
    f1 = eng.submit(rng.randint(0, 256, (5,)).tolist(), 4).future
    f2 = eng.submit(rng.randint(0, 256, (9,)).tolist(), 10).future
    run_some(3)
    base = eng.compile_stats()["decode_traces"]
    assert base == 1
    # group 2: r1 leaves (max_tokens hit), r3 joins the running batch
    f3 = eng.submit(rng.randint(0, 256, (12,)).tolist(), 6).future
    run_some(3)
    assert f1.done()
    # group 3: r4 joins after r3/r2 churn
    f4 = eng.submit(rng.randint(0, 256, (3,)).tolist(), 8).future
    eng.run_until_idle()
    assert all(f.done() for f in (f2, f3, f4))
    assert eng.compile_stats()["decode_traces"] == 1
    assert eng._dispatch_count >= 9
    # pool fully reclaimed after the churn
    st = eng._kv.allocator.stats()
    assert st["allocated"] == 0 and st["reserved"] == 0


def test_page_table_grows_lazily_across_blocks(tiny_net):
    """A request whose generation crosses block boundaries allocates
    pages one at a time, and the page-table row fills in order."""
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=32)
    req = eng.submit(list(range(1, 7)), max_tokens=20)   # 6 + 19 > 3*8
    eng.step()                      # admit + prefill: 6 tokens → 1 blk
    assert len(req.blocks) == 1
    eng.run_until_idle()
    # 6 + 19 = 25 cache slots → 4 blocks by the end
    assert req.future.result(timeout=0).stats.generated == 20
    st = eng._kv.allocator.stats()
    assert st["allocated"] == 0     # freed at finalize


def test_streaming_callbacks_ordered_and_match_result(tiny_net):
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=2, block_size=8, num_blocks=64)
    events = {}
    lock = threading.Lock()

    def cb(rid, idx, lazy_tok):
        with lock:
            events.setdefault(rid, []).append((idx, lazy_tok))

    rng = np.random.RandomState(5)
    reqs = [eng.submit(rng.randint(0, 256, (n,)).tolist(), 7,
                       stream_cb=cb) for n in (4, 10)]
    eng.run_until_idle()
    for req in reqs:
        got = req.future.result(timeout=0).tokens
        ev = events[req.id]
        assert [i for i, _ in ev] == list(range(7))   # in order
        # lazy stream values == final result (reading syncs lazily)
        assert [int(t) for _, t in ev] == got


def test_queue_full_admission_rejects(tiny_net):
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=64,
                       max_queue=2)
    for n in (4, 5):
        eng.submit(list(range(1, 1 + n)), 2)
    with pytest.raises(QueueFull):
        eng.submit([1, 2, 3], 2)
    eng.run_until_idle()            # queue drains...
    eng.submit([1, 2, 3], 2)        # ...and admission reopens
    eng.run_until_idle()


def test_oversized_request_rejected_at_submit(tiny_net):
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=16)
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 10)), max_tokens=1000)  # > capacity
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 300)), max_tokens=1)    # > max bucket


def test_admission_waits_for_block_budget(tiny_net):
    """A request the pool cannot worst-case cover RIGHT NOW stays
    queued (FCFS) until a running request releases its reservation."""
    net, cfg = tiny_net
    # 9 usable blocks of 8 → 72 cache slots
    eng = DecodeEngine(net, max_batch=2, block_size=8, num_blocks=10)
    big1 = eng.submit(list(range(1, 17)), max_tokens=17)  # 4 blocks
    big2 = eng.submit(list(range(1, 17)), max_tokens=17)  # 4 blocks
    big3 = eng.submit(list(range(1, 17)), max_tokens=17)  # needs 4 > 1
    eng.step()
    assert eng.active_count == 2            # big3 not admitted
    assert eng.scheduler.queue_depth == 1
    eng.run_until_idle()
    assert all(r.future.done() for r in (big1, big2, big3))


def test_eos_truncates_and_frees_slot_early(tiny_net):
    """Greedy decode is deterministic: learn the sequence once, then
    re-serve with eos_id set to an emitted token — the result
    truncates at (and includes) eos and the device-side done mask
    frees the slot before max_tokens."""
    net, cfg = tiny_net
    prompt = list(range(3, 9))
    eng0 = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=64)
    full = eng0.submit(prompt, 10).future
    eng0.run_until_idle()
    toks = full.result(timeout=0).tokens
    eos = toks[4]
    cut = toks.index(eos)
    eng = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=64,
                       eos_id=eos, done_poll_interval=2)
    fut = eng.submit(prompt, 10).future
    eng.run_until_idle()
    got = fut.result(timeout=0).tokens
    assert got == toks[:cut + 1]
    assert got[-1] == eos
    assert eng.active_count == 0
    # fewer dispatches than max_tokens would have needed: the done
    # poll reclaimed the slot within done_poll_interval of the EOS
    assert eng._dispatch_count <= cut + 1 + 2


def test_server_threaded_end_to_end(tiny_net):
    net, cfg = tiny_net
    srv = LLMServer(net, max_batch=4, block_size=8, num_blocks=64,
                    auto_start=False)
    warm = srv.warmup([6, 20])
    assert warm["warmup_s"] > 0 and warm["decode_compile_s"] > 0
    srv.start()
    try:
        rng = np.random.RandomState(6)
        futs = [srv.submit(rng.randint(0, 256, (n,)).tolist(), 5)
                for n in (4, 9, 17, 3, 30, 2)]
        res = [f.result(timeout=120) for f in futs]
        assert all(len(r.tokens) == 5 for r in res)
        st = srv.stats()
        assert st["completed"] == 6
        assert st["decode_traces"] == 1
        assert st["latency_p99_s"] >= st["latency_p50_s"] >= 0
        assert "warmup" in st
    finally:
        srv.close()
    assert not srv.running


def test_server_metrics_port_serves_and_close_tears_down(tiny_net):
    """LLMServer(metrics_port=...) arms the HTTP scrape plane
    (ISSUE 10 satellite): /healthz answers, /metrics carries the
    engine's registry children, and close() tears the endpoint down
    so a scraper sees target-down, never a frozen scrape."""
    import json as _json
    import urllib.error
    import urllib.request
    net, cfg = tiny_net
    srv = LLMServer(net, max_batch=1, block_size=8, num_blocks=64,
                    auto_start=False, metrics_port=0)   # ephemeral
    port = srv.metrics_port
    assert port and port > 0
    base = f"http://127.0.0.1:{port}"
    h = _json.load(urllib.request.urlopen(base + "/healthz",
                                          timeout=5))
    assert h["status"] == "ok" and h["pid"] == os.getpid()
    text = urllib.request.urlopen(base + "/metrics",
                                  timeout=5).read().decode()
    assert "serving_queue_depth{engine=" in text
    assert "# TYPE serving_dispatches_total counter" in text
    srv.close()
    assert srv.metrics_port is None
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"{base}/healthz", timeout=2)


def test_server_close_fails_pending_futures(tiny_net):
    net, cfg = tiny_net
    srv = LLMServer(net, max_batch=1, block_size=8, num_blocks=64,
                    auto_start=False)      # pump never started
    fut = srv.submit([1, 2, 3], 4)
    srv.close()
    with pytest.raises(RuntimeError):
        fut.result(timeout=0)


def test_server_close_releases_pool_and_fails_backlog(tiny_net):
    """close() with an in-flight slot AND a reservation-blocked
    backlog: every future fails (none hang) and the pool fully
    recovers — no leaked blocks or reservations."""
    net, cfg = tiny_net
    srv = LLMServer(net, max_batch=1, block_size=8, num_blocks=10,
                    auto_start=False)
    eng = srv.engine
    mid = srv.submit(list(range(1, 17)), max_tokens=17)    # 4 blocks
    blocked = srv.submit(list(range(1, 17)), max_tokens=17)
    eng.step()                      # admit+prefill mid; backlog waits
    assert eng.active_count == 1 and eng.scheduler.queue_depth == 1
    srv.close()
    for fut in (mid, blocked):
        with pytest.raises(RuntimeError):
            fut.result(timeout=0)
    st = eng._kv.allocator.stats()
    assert st["allocated"] == 0 and st["reserved"] == 0


def test_default_buckets_floor_to_block_multiple():
    """A model whose max_position is not a block multiple must still
    construct (top bucket floors to alignment)."""
    paddle.seed(0)
    net = GPTForCausalLM(gpt_tiny(use_flash_attention=False,
                                  max_position_embeddings=100))
    net.eval()
    eng = DecodeEngine(net, max_batch=1, block_size=16, num_blocks=32)
    assert eng._buckets[-1] == 96          # 100 floored to 16-multiple
    fut = eng.submit(list(range(1, 20)), 3).future
    eng.run_until_idle()
    assert len(fut.result(timeout=0).tokens) == 3


def test_hapi_prepare_serving_export(tiny_net):
    """Model.fit machinery → LLMServer in one call, with AOT warmup."""
    net, cfg = tiny_net
    model = paddle.Model(net)
    srv = model.prepare_serving(prompt_lengths=[8],
                                max_batch=2, block_size=8,
                                num_blocks=64, start=True)
    try:
        res = srv.submit([5, 6, 7, 8], 4).result(timeout=120)
        assert len(res.tokens) == 4
        assert srv.stats()["warmup"]["buckets"] == [8]
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------
_CACHE_PROBE = """
import os, paddle_tpu, jax, jax.numpy as jnp
from paddle_tpu.framework import compile_cache
want = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert compile_cache.active_cache_dir() == want, \
    compile_cache.active_cache_dir()
# the variable alone places the cache: no code configured a directory
assert jax.config.jax_compilation_cache_dir == want
f = jax.jit(lambda x: (x @ x.T).sum() * 3)
print(float(f(jnp.ones((32, 32)))))
"""


def test_compilation_cache_reused_across_processes(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache
    and nothing else is configured.  A second process re-serves
    compiles from it: the first run writes entries, the second adds
    NONE (all keys hit)."""
    cache = str(tmp_path / "xla_cache")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache,
               PADDLE_TPU_COMPILE_CACHE="1", JAX_PLATFORMS="cpu")
    for expect_growth in (True, False):
        before = set(os.listdir(cache)) if os.path.isdir(cache) \
            else set()
        r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                           env=env, cwd=REPO, capture_output=True,
                           text=True, timeout=240)
        assert r.returncode == 0, r.stdout + r.stderr
        after = set(os.listdir(cache))
        if expect_growth:
            assert len(after - before) > 0    # entries written
        else:
            assert after == before            # pure cache hits


def test_compilation_cache_off_by_default(monkeypatch):
    """The knob is on/off only; unset (or a path, the form it no
    longer has) enables nothing."""
    from paddle_tpu.framework import compile_cache

    def boom(*a, **k):
        raise AssertionError("the cache was enabled with the knob off")
    monkeypatch.setattr(compile_cache, "enable_compilation_cache", boom)
    for val in (None, "", "0", "/some/dir"):
        if val is None:
            monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        else:
            monkeypatch.setenv(compile_cache.ENV_VAR, val)
        assert compile_cache.enable_from_env() == \
            compile_cache.active_cache_dir()


def test_done_poll_interval_auto_tunes(tiny_net):
    """Default (no explicit done_poll_interval): the engine calibrates
    the poll cadence from observed dispatch latency over the first few
    polls and freezes a bounded decision (ISSUE 7: the serving
    analogue of auto-K)."""
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=2, block_size=8, num_blocks=64,
                       eos_id=999_999)   # never emitted: pure decode
    assert eng._poll_auto and eng.done_poll_interval == 8
    for p in ([1, 2, 3], [4, 5, 6]):
        eng.submit(p, max_tokens=64)
    eng.run_until_idle()
    assert eng._poll_decision is not None
    d = eng._poll_decision
    assert 1 <= d["done_poll_interval"] <= eng._poll_tuner.max_fold
    assert eng.done_poll_interval == d["done_poll_interval"]
    assert eng.stats()["done_poll_decision"] == d


def test_done_poll_interval_explicit_stays_fixed(tiny_net):
    net, cfg = tiny_net
    eng = DecodeEngine(net, max_batch=1, block_size=8, num_blocks=64,
                       eos_id=999_999, done_poll_interval=2)
    assert not eng._poll_auto
    eng.submit([1, 2, 3], max_tokens=48)
    eng.run_until_idle()
    assert eng.done_poll_interval == 2
    assert eng._poll_decision is None

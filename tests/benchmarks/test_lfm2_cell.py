"""The cell ``lfm2-8b-a1b-ep4share-s8192`` on the CPU: it rehearses end to
end with and without a trace and reaches ``correct``, each of its checks
of values comes out wrong with the reference computed through
``float8_e4m3fn`` (the control behind its limits) or with a planted fault
and none through bfloat16, its family's counts are what hand arithmetic
gives for the published widths, its configuration keeps them and agrees
with the catalog row's numbers key by key, what ``BENCHMARK.json`` gained
for it stands together after what was there (and the pinned test it trips
runs here whole on the benchmark as it was before this PR's first entry),
and the reader of ``harness/lfm2_scopes.py`` joins a hand-made pair as
``test_scopes.py`` has ``scopes.py`` do.
"""

import importlib.util
import json
import os
import shutil
import sys

import pytest
from pytest import approx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                       # noqa: E402
from benchmarks.families import lfm2_moe as family            # noqa: E402
from benchmarks.harness import cells, lfm2_scopes, report     # noqa: E402

CELL = "lfm2-8b-a1b-ep4share-s8192"
CONFIG = "lfm2-8b-a1b"
CONFIG_FILE = "benchmarks/configs/lfm2-8b-a1b.json"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = (
    "lf_conv_ms_per_step", "lf_conv_proj_ms_per_step",
    "lf_attn_proj_ms_per_step", "lf_gqa_core_ms_per_step",
    "lf_dense_mlp_ms_per_step", "lf_moe_route_ms_per_step",
    "lf_moe_experts_ms_per_step", "lf_moe_expert_imbalance",
    "lf_conv_roofline", "lf_moe_experts_roofline", "lf_gqa_core_roofline")
CONV, ATTENTION = ["conv"], ["full_attention"]
# the catalog row of the model-configs guide (architectures.jsonl,
# LFM2-8B-A1B), its ``config`` as it stands
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": (CONV * 2 + ATTENTION + CONV * 3 + ATTENTION) + (
        CONV * 3 + ATTENTION) * 3 + CONV * 2 + ATTENTION + CONV * 2,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def _copy_benchmark(dst):
    """A traced run replaces <checkout>/.bench_traces/<cell>: run from a
    copy."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


@pytest.fixture(scope="module")
def config():
    return cells.load_json(os.path.join(ROOT, CONFIG_FILE))


# --------------------------------------------------------------------------
# the rehearsal
# --------------------------------------------------------------------------
@pytest.mark.parametrize("trace", [False, True])
def test_the_cell_rehearses_end_to_end(trace, monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    cell = cells.load_cell(CELL, _copy_benchmark(tmp_path))
    options = report.RunOptions(seed=3_000_000_019, seconds=0.5, trace=trace,
                                rehearse=True)
    obj = bench_run.run_cell(cell, options)
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert last.startswith(bench_run.REHEARSAL_PREFIX)
    assert json.loads(last[len(bench_run.REHEARSAL_PREFIX):]) == obj
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 2
    for letter in "abcdefghi":
        assert f"ok: ({letter})" in out, letter
    assert "WRONG" not in out
    assert "layers conv_dense attention_moe conv_moe, 2 of 8 experts" in out
    assert "the step recomputes the layers the file names ([0]: by kind " \
        "{'conv_dense': 1, 'attention_moe': 0, 'conv_moe': 0})" in out
    assert "none dropped" in out
    assert "balanced the routers' biases over 3 forward passes" in out
    assert "given the experts the step chose" in out
    assert "the jitted step gained 0 for it" in out
    # the line of counters says them all
    counters = next(line for line in out.splitlines()
                    if line.startswith("counters: "))
    for name in ("short_conv_calls {'forward': ", "'backward': ",
                 "short_conv_bytes {'1': 720896.0, '3': 720896.0}",
                 "recompute_layers {'conv_dense': 1", "flash_tiles {'square'",
                 "moe_pairs ", "moe_expert_tokens_max ",
                 "moe_expert_tokens_mean "):
        assert name in counters, name
    assert 128 * 2 * 256 * 2 * 11 // 2 == 720896       # one sequence a call
    assert "the program counted moe_pairs_total" in out
    if trace:
        # a CPU has no device plane: the device metrics are left out
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert set(NEW_METRICS) & set(obj["metrics"]) == {
            "lf_moe_expert_imbalance"}
        assert obj["metrics"]["lf_moe_expert_imbalance"]["value"] >= 1.0
        assert "attention_ms_per_step" not in obj["metrics"]
        assert "tokens_per_s" not in obj["metrics"]
    else:
        assert obj["metrics"]["tokens_per_s"]["value"] > 0
        assert obj["metrics"]["setup_s"]["value"] > 0


# --------------------------------------------------------------------------
# the control: every check comes out wrong where it should
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def rehearsed():
    """The cell's runner at the rehearsal size after two steps, as
    ``run`` has it when the checks begin."""
    import jax
    from benchmarks.drivers import train_lfm2_lm as driver
    from benchmarks.harness import traffic
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        cell = cells.load_cell(CELL, ROOT)
        config, mix = (cells.sized(x, True)
                       for x in (cell.config, cell.traffic))
        runner = driver.build_runner(config, 7, jax.devices()[:1],
                                     mix["batch"] * mix["seq_len"])
        ring = traffic.token_batches(mix, config["vocab_size"], 7)
        observed = driver.Observed(runner, mix["sync_every"])
        for batch in ring[:2]:
            observed.train_step(*batch)
        yield driver, observed, config, mix, ring


def _run_checks(rehearsed, fam, step=3, letters="acdf"):
    driver, observed, toy, mix, ring = rehearsed
    seq = mix["seq_len"]
    said = []
    checks = {}
    for letter, run in (
            ("a", lambda c: driver.check_forward(c, observed.runner, fam, toy,
                                                 seq, 7)),
            ("c", lambda c: driver.check_operator(c, fam, toy, seq, 7)),
            ("d", lambda c: driver.check_attention(c, fam, toy, seq, 7,
                                                   True)),
            ("f", lambda c: driver.check_step(c, observed, fam, toy, ring[0],
                                              step))):
        if letter in letters:
            checks[letter] = driver.Checks(said.append)
            run(checks[letter])
    return checks, said


def test_every_limit_refuses_the_reference_through_float8(rehearsed):
    """The limits of (a)-(d) and (f) lie between the program's readings
    and what the same reference reads when it is computed in the nearest
    precision below the configuration's bf16: every weight it reads, the
    operator's ``[B | C | x]`` and taps and attention's q, k and v rounded
    through ``float8_e4m3fn``.  Each check alone makes such a run not
    ``correct``; through bfloat16, which the program's weights and those
    inputs are in already, every check passes as it does in the
    rehearsal."""
    import jax.numpy as jnp
    driver = rehearsed[0]
    fine, said = _run_checks(rehearsed, family.rounded_through(jnp.bfloat16))
    assert not [w for c in fine.values() for w in c.failed], said
    coarse, said = _run_checks(
        rehearsed, family.rounded_through(jnp.float8_e4m3fn), step=4)
    # (a) given the program's routing, (b) routing for itself; the pairs
    # of (e) are counted from the routing given and stay right
    wrong = coarse["a"].failed
    assert len(wrong) == 2 and wrong[0].startswith("(a)") \
        and wrong[1].startswith("(b)"), said
    assert f"< {driver.LOGITS_RTOL}" in wrong[0]
    assert f"< {driver.OWN_CHOICE_RTOL}" in wrong[1]
    # (c): y and both gradients; (d): out and every gradient that the
    # rounded inputs reach
    assert len(coarse["c"].failed) == 3 and len(coarse["d"].failed) >= 3
    # (f): the gradients, by the limit they have; the update is arithmetic
    # on the step's own gradient, which the reference's precision leaves
    wrong, = coarse["f"].failed
    assert "agree with jax.grad of the float32 reference" in wrong
    assert f"< {driver.GRADS_RTOL}" in wrong


def test_a_step_at_another_learning_rate_is_refused(rehearsed, monkeypatch):
    """The planted fault for (f)'s second half: the check reads twice the
    learning rate the schedule gave the step, so every leaf moved half as
    far as AdamW would have it.  The gradients still agree."""
    driver, observed, *_ = rehearsed
    optimizer = observed.runner.optimizer
    real = optimizer.get_lr
    calls = []

    def doubled():
        # the check asks first; the step itself, after it, gets the rate
        calls.append(1)
        return real() * (2 if len(calls) == 1 else 1)

    monkeypatch.setattr(optimizer, "get_lr", doubled)
    checks, said = _run_checks(rehearsed, family, step=5, letters="f")
    wrong, = checks["f"].failed
    assert "change of their float32 weights" in wrong
    assert f"< {driver.UPDATE_RTOL}" in wrong
    # half the way: 0.5 of the reference's change on every leaf
    assert wrong.count(" 5.0e-01") >= 21 and driver.UPDATE_RTOL < 0.5


def test_gates_left_out_of_the_operators_backward_pass_are_refused(
        rehearsed, monkeypatch):
    """The planted fault for (c): a backward pass that hands the gate C no
    gradient.  y and the taps' gradient still agree; the gradient by
    ``[B | C | x]`` is found wrong."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import short_conv
    forgetful = jax.custom_vjp(short_conv._forward)

    def backward(kept, dy):
        d_bcx, d_taps = short_conv._bwd(kept, dy)
        d_b, d_c, d_x = jnp.split(d_bcx, 3, -1)
        return jnp.concatenate([d_b, jnp.zeros_like(d_c), d_x], -1), d_taps

    forgetful.defvjp(short_conv._fwd, backward)
    monkeypatch.setattr(short_conv, "_gated_short_conv", forgetful)
    checks, said = _run_checks(rehearsed, family, letters="c")
    wrong, = checks["c"].failed
    assert "gated_short_conv dbcx" in wrong, said
    assert [line.split()[3] for line in said if "ok: (c)" in line] == [
        "y", "dweight"]


# --------------------------------------------------------------------------
# what the benchmark declares
# --------------------------------------------------------------------------
def test_the_cell_declares_its_metrics_and_reads_the_block_metrics():
    cell = cells.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "pretrain-b1-s8192"
    assert cell.config["mesh"] == {}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    # the block metrics and the eight of the host half list no cells and
    # read this one as it is
    everywhere = {
        "attention_ms_per_step", "mlp_ms_per_step", "lmhead_loss_ms_per_step",
        "optimizer_ms_per_step", "unscoped_ms_per_step", "flash_ms_per_step",
        "xla_ops_ms_per_step", "device_idle_share", "step_hbm_gb",
        "host_dispatch_ms_per_step", "compiles_in_window", "first_step_s",
        "second_step_s", "step_launch_ms_per_step",
        "step_host_own_ms_per_step", "idle_own_host_ms_per_step",
        "device_programs_per_step", "host_gc_ms_per_step",
        "step_trace_lower_s", "step_compile_s", "step_programs_built"}
    assert names == everywhere | set(NEW_METRICS)
    bench = cells.load_benchmark(ROOT)
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert not [n for n in everywhere if "workloads" in declared[n]]
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "mfu", "peak_hbm_gb", "setup_s"}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS), other
    assert len(bench["workloads"]) == len(
        {(w["config"], w["traffic"]) for w in bench["workloads"]})
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    for name in NEW_METRICS:
        roofline = name.endswith("_roofline")
        assert declared[name] == {
            "name": name, "moves": "tokens_per_s", "workloads": [CELL],
            "source": "program_counter" if name.endswith("imbalance")
            else "device_trace",
            "unit": "%" if roofline else "ratio"
            if name.endswith("imbalance") else "ms",
            "better": "higher" if roofline else "lower",
            "layer": "kernels" if roofline else "model"}
    for m in cell.per_layer:
        assert callable(report.load_reader(ROOT, m["name"]))
    for folder, key in (("drivers", "driver"), ("families", "family")):
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", folder, cell.config[key] + ".py"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert "1/4 of its EP4 load" in entry["why"] and len(entry["why"]) <= 200
    config, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config["why"]) <= 200
    # no trace, no table: every reader returns None and raises not, as on
    # a program that has none of these scopes or counters (the parent)
    obs = {"trace": None, "chips": 1, "config": cell.config, "family": None,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        assert report.load_reader(ROOT, name)(obs) is None


def test_the_benchmark_gained_entries_after_what_was_there(monkeypatch):
    """``test_sambay_cell.py`` takes what was there to be everything that
    is not PR 38's, so an entry appended after PR 38's trips it, and it is
    expected to fail since this cell was appended (``tests/conftest.py``
    says why it may not be edited here; the chain of such pins is four
    deep, ROADMAP D16).  Nothing it holds is let go meanwhile: it fails as
    it stands, and its whole body, the three older pins inside it, runs
    here on the benchmark as it was: ``BENCHMARK.json``'s lists cut before
    this PR's first entry, by position, and the metric directory's listing
    less the files of every metric at or after that position.  So nothing
    it reads changes when a later PR appends, and no fifth stand-in is
    needed for this one.  Of this PR's entries it holds only that they
    stand together, in order, after that position, and that no entry
    before them names the new cell, configuration or metrics."""
    bench = cells.load_benchmark(ROOT)
    mine = {"configs": (CONFIG,), "workloads": (CELL,),
            "per_layer": NEW_METRICS}
    had = dict(bench)
    for key, names in mine.items():
        listed = [e["name"] for e in bench[key]]
        at = listed.index(names[0])
        assert tuple(listed[at:at + len(names)]) == names, key
        had[key] = bench[key][:at]
    later = {m["name"] for m in bench["per_layer"][len(had["per_layer"]):]}
    was = json.dumps(had)
    assert CELL not in was and CONFIG not in was
    assert not [n for n in NEW_METRICS if n in was]

    spec = importlib.util.spec_from_file_location(
        "the_sambay_cells_tests", os.path.join(HERE, "test_sambay_cell.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    pinned = (theirs.
              test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest)
    with pytest.raises(AssertionError):     # entries after PR 38's
        pinned(monkeypatch)
    metrics_dir = os.path.join(ROOT, "benchmarks", "layer_metrics")
    listdir = os.listdir

    def as_it_was(path):
        names = listdir(path)
        if os.path.abspath(path) == metrics_dir:
            names = [n for n in names if n[:-3] not in later]
        return names

    monkeypatch.setattr(os, "listdir", as_it_was)
    monkeypatch.setattr(cells, "load_benchmark", lambda root=ROOT: had)
    pinned(monkeypatch)


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    reduced = {"num_hidden_layers": 5, "num_dense_layers": 1,
               "num_experts": 8, "vocab_size": 16384}
    assert config["reduced"] == list(reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config[key] == reduced[key], key
            assert config["published"][key] == value, key
        elif key == "layer_types":
            # the published layers 1-5
            assert config[key] == value[1:6] == CONV + ATTENTION + CONV * 3
            assert config["published"][key] == value
        else:
            assert config[key] == value and type(config[key]) is type(value), \
                key
    assert set(config["changed"]) >= set(reduced)
    assert 16384 * 4 == 65536 and config["experts_held"] == [0, 8]
    assert config["layers_held"] == [1, 5]
    assert family.kinds(config) == ("conv_dense", "attention_moe", "conv_moe",
                                    "conv_moe", "conv_moe")
    assert family.router_width(config) == 32
    for key in ("tie", "final norm", "RMSNorm", "rotary", "operator's layout",
                "initialisation", "conv weight's start", "router",
                "first loss", "optimizer"):
        assert config["assumed"][key], key
    assert "8.34 B" in config["assumed"]["tie"]
    assert "1e-6" in config["assumed"]["router"]
    assert "1 : 3" in config["changed"]["num_hidden_layers"]
    assert "6 : 16" in config["changed"]["num_hidden_layers"]
    assert "2.4 times" in config["changed"]["num_hidden_layers"]
    # the compiled step has more than a tenth of the limit to spare with
    # nothing recomputed (tests/test_chip_compile.py compiles it)
    assert config["recompute"] == []
    assert config["mesh"] == {} and config["initializer_range"] == 0.02
    assert config["tie_word_embeddings"] is True
    assert config["optimizer"] == {"name": "AdamW", "learning_rate": 1e-4,
                                   "warmup_steps": 500}
    assert config["router_bias"] == {"update_rate": 0.001, "passes": 100}
    assert config["step_bytes_limit"] == 15_600_000_000
    for key in ("changed", "assumed", "deployment", "notes"):
        assert config[key], key
    assert "507 820 160 parameters" in config["notes"]["a layer here"]
    assert "4 pipeline stages of 6 layers" in config["deployment"]
    assert "4 chips share each stage" in config["deployment"]
    assert "routed experts 4 ways" in config["deployment"]
    assert "4 ways by rows" in config["deployment"]
    # the toy size keeps the three kinds and fills a lane group a call
    toy = cells.sized(config, True)
    assert family.kinds(toy) == ("conv_dense", "attention_moe", "conv_moe")
    assert toy["hidden_size"] // toy["num_attention_heads"] == 64
    assert toy["num_experts"] == toy["experts_held"][1]
    from benchmarks.drivers import train_lfm2_lm as driver
    for sized in (config, toy):
        program = driver.program_config(sized)
        assert program.kinds == family.kinds(sized)
        assert program.vocab_rows_held == sized["vocab_size"]
    with pytest.raises(ValueError, match="what is held here"):
        driver.program_config({**config, "num_dense_layers": 2})
    entry, = [c for c in cells.load_benchmark(ROOT)["configs"]
              if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == CONFIG_FILE


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    assert w == {"conv": 2048 * 6144 + 2048 * 3 + 2048 * 2048,
                 "attention": 2 * 2048 * 2048 + 2 * 2048 * 512,
                 "dense": 3 * 2048 * 7168, "router": 2048 * 32,
                 "expert": 3 * 2048 * 1792}
    # the issue's arithmetic
    assert w["conv"] == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    assert w["attention"] + 128 == 10_485_888
    assert (w["dense"], 8 * w["expert"], w["router"]) == (
        44_040_192, 88_080_384, 65_536)
    per = family.layer_params(config)
    assert (per["conv_dense"], per["attention_moe"], per["conv_moe"]) == (
        60_827_648, 98_635_904, 104_933_376)
    assert family.param_count(config) == 60_827_648 + 98_635_904 \
        + 3 * 104_933_376 + 33_554_432 + 2_048 == 507_820_160
    assert family.pairs_per_token(config) == 4 * 8 / 32 == 1.0
    # 6 for each weight a token multiplies, the expected pair a token on
    # the held experts, the causal half square once
    weights = 4 * w["conv"] + w["attention"] + w["dense"] \
        + 4 * (w["router"] + 1.0 * w["expert"]) + 16384 * 2048
    square = 3 * 4 * 2048 * 8193 / 2
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + square, rel=1e-12)
    # 10.63 TFLOP a step, 54 ms at the chip's peak
    assert 8192 * family.flops_per_token(config, 8192) == approx(
        1.0632e13, rel=1e-3)
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    conv = family.conv_cost(config, 1, 8192)
    assert conv["flops"] == 4 * 8192 * 6 * w["conv"]
    assert conv["bytes"] == 4 * (8192 * 17 * 2048 * 2 + w["conv"] * 2 * 3)
    least, bound = cells.least_seconds(conv["flops"], conv["bytes"], peaks)
    # 3.30 TFLOP: 16.7 ms at the peak; the bytes would take 3.3
    assert bound == "operations" and 1e3 * least == approx(16.75, rel=1e-3)
    gqa = family.gqa_cost(config, 1, 8192)
    assert gqa["flops"] == 8192 * square
    assert gqa["bytes"] == 6 * 2 * 8192 * (2048 + 512)
    least, bound = cells.least_seconds(gqa["flops"], gqa["bytes"], peaks)
    # 0.825 TFLOP in the causal triangle: 4.19 ms at the peak
    assert bound == "operations" and 1e3 * least == approx(4.187, rel=1e-3)
    pairs = 4 * 8192 * 1.0
    experts = family.experts_cost(config, pairs)
    assert experts["flops"] == 6 * w["expert"] * pairs
    assert experts["bytes"] == 4 * 8 * w["expert"] * 2 * 3 \
        + pairs * 3 * 2 * (2 * 2048 + 3 * 1792)
    least, bound = cells.least_seconds(experts["flops"], experts["bytes"],
                                       peaks)
    # 2.16 TFLOP, 10.99 ms at the peak; the bytes would take 4.9
    assert bound == "operations" and 1e3 * least == approx(10.99, rel=1e-3)


# --------------------------------------------------------------------------
# the reader on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope():
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_lfm2_scoped.step.txt``, the same step with this family's
    sub-scopes in its ``op_name``s.  Microseconds a step, device 0 first |
    second run, device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   short_conv and, by one member, router: mixed
        fusion.3      10 |  8   experts, recomputed in the backward pass;
                                the optimizer's part has no sub-scope
        flash_fwd.2   20 | 22   gqa_core: 21 and 18, 19.5
        fusion.4       6 |  6   dense_mlp
        all-reduce.6  10 | 10   attn_proj, backward
        copy.8         4 |  4   conv_proj
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step.  The mixed row counts for neither the operator's
    metric nor the routing's; the operator's roofline takes it with
    ``conv_proj``'s row, so that no fusion leaves its denominator."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA, "hand_made_lfm2_scoped.step.txt")) as f:
        text = f.read()
    said = []
    table = lfm2_scopes.reader.block_table(data, text, say=said.append)
    assert table is not None, said
    us = {r.name: 1e6 * r.seconds for r in table.rows}
    assert us == approx({"short_conv+router": 10.0, "experts": 9.0,
                         "gqa_core": 19.5, "dense_mlp": 6.0,
                         "attn_proj": 10.0, "conv_proj": 4.0, "unscoped": 2.0,
                         "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)

    class Family:
        conv_cost = staticmethod(lambda c, b, s: {"flops": 197e12 * 2.8e-6,
                                                  "bytes": 0.0})
        gqa_cost = staticmethod(lambda c, b, s: {"flops": 197e12 * 3.9e-6,
                                                 "bytes": 0.0})
        experts_cost = staticmethod(lambda c, pairs: {
            "flops": 0.0, "bytes": 819e9 * 1e-9 * pairs})

    obs = {"trace": object(), "chips": 1, "config": {}, "family": Family,
           "traffic": {"batch": 1, "seq_len": 8},
           "peaks": cells.load_peaks("TPU v5 lite", ROOT),
           "counters": {
               "before": {"observed": 1, "moe_pairs": 100},
               "after": {"observed": 3, "moe_pairs": 3700, "program": {
                   "moe_expert_tokens_max": [12, 30],
                   "moe_expert_tokens_mean": [10, 20]}}},
           lfm2_scopes.TABLE: {"trace": True, "scopes": table}}
    read = {name: report.load_reader(ROOT, name)(obs) for name in NEW_METRICS}
    assert read == approx({
        "lf_conv_ms_per_step": 0.0,               # the mixed row is no one's
        "lf_conv_proj_ms_per_step": 0.004,
        "lf_attn_proj_ms_per_step": 0.010,
        "lf_gqa_core_ms_per_step": 0.0195,
        "lf_dense_mlp_ms_per_step": 0.006,
        "lf_moe_route_ms_per_step": 0.0,
        "lf_moe_experts_ms_per_step": 0.009,
        "lf_moe_expert_imbalance": (1.2 + 1.5) / 2,
        # 2.8 us of products over the 4 of conv_proj and the mixed row's 10
        "lf_conv_roofline": 100 * 2.8 / 14.0,
        # 1800 pairs a step, 1 ns of bytes a pair, over 9 us
        "lf_moe_experts_roofline": 100 * 1.8 / 9.0,
        "lf_gqa_core_roofline": 100 * 3.9 / 19.5})
    assert lfm2_scopes.ms_per_step(obs, __file__, ("router",),
                                   mixed=True) == approx(0.010)
    # the readers before it are untouched by this copy
    from benchmarks.harness import (hybrid_moe_scopes, sambay_scopes, scopes,
                                    subscopes)
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    for other in (subscopes, hybrid_moe_scopes, sambay_scopes):
        assert "short_conv" not in other.reader.SCOPES
    assert lfm2_scopes.reader.SCOPES == lfm2_scopes.SUBSCOPES
    assert len(lfm2_scopes.SUBSCOPES) == 9
    # a step without any of these scopes (the parent's): one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        plain = f.read()
    assert lfm2_scopes.reader.block_table(
        data, plain, say=said.append) is None
    assert "carries any of the scopes conv_proj" in said[-1]

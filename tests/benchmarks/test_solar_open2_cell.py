"""The cell ``solar-open2-kda-rank0-s8192`` on the CPU: it rehearses end to
end with and without a trace and reaches ``correct``, each of its checks
of values comes out wrong with the reference computed through
``float8_e4m3fn`` (the control behind its limits) or with a planted fault
and none through bfloat16, its family's counts are what hand arithmetic
gives for the published widths, its configuration keeps them and agrees
with the catalog row's numbers key by key, what ``BENCHMARK.json`` gained
for it stands together after what was there, and the reader of
``harness/solar_scopes.py`` joins a hand-made pair as ``test_scopes.py``
has ``scopes.py`` do.
"""

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
from benchmarks.families import solar_open2 as family         # noqa: E402
from benchmarks.harness import (cells, report, solar_scopes,  # noqa: E402
                                ssm_scopes)

CELL = "solar-open2-kda-rank0-s8192"
CONFIG = "solar-open2-250b"
CONFIG_FILE = "benchmarks/configs/solar-open2-250b.json"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW_METRICS = (
    "so_kda_core_ms_per_step", "so_kda_proj_ms_per_step",
    "so_kda_conv_gate_ms_per_step", "so_gqa_core_ms_per_step",
    "so_moe_route_ms_per_step", "so_moe_experts_ms_per_step",
    "so_moe_shared_expert_ms_per_step", "so_moe_expert_imbalance",
    "so_recompute_ms_per_step", "so_kda_core_roofline")
# the published catalog row (architectures.jsonl,
# Solar-Open2-250B), its ``config`` as it stands
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


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
    assert "layers gqa kda kda kda, 2 heads of 4, 4 of 16 experts" in out
    assert "the step recomputes the mixers the file names ([0, 1]: by " \
        "kind {'gqa': 1, 'kda': 1})" in out
    assert "none dropped" in out
    assert "balanced the routers' biases over 3 forward passes" in out
    assert "given the experts the step chose" in out
    assert "the jitted step gained 0 for it" in out
    counters = next(line for line in out.splitlines()
                    if line.startswith("counters: "))
    # the registry is the process's: other tests on this worker may have
    # traced the rule before, so the count is said and not pinned
    for name in ("delta_rule_calls ", "recompute_layers {'gqa': 1, 'kda': 1}",
                 "flash_tiles {'square'", "moe_pairs ",
                 "moe_expert_tokens_max ", "moe_expert_tokens_mean "):
        assert name in counters, name
    assert "the program counted moe_pairs_total" in out
    if trace:
        # a CPU has no device plane: the device metrics are left out
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert set(NEW_METRICS) & set(obj["metrics"]) == {
            "so_moe_expert_imbalance"}
        assert obj["metrics"]["so_moe_expert_imbalance"]["value"] >= 1.0
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
    from benchmarks.drivers import train_solar_lm as driver
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


def _run_checks(rehearsed, fam, letters="acdf"):
    """The checks as ``run`` makes them; (f) runs the runner's next step,
    whichever tests of this module ran on the fixture before."""
    driver, observed, toy, mix, ring = rehearsed
    seq = mix["seq_len"]
    step = len(observed.expert_tokens) + 1
    said = []
    checks = {}
    for letter, run in (
            ("a", lambda c: driver.check_forward(c, observed.runner, fam, toy,
                                                 seq, 7)),
            ("c", lambda c: driver.check_rule(c, fam, toy, seq, 7)),
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
    delta rule's q, k, v, log alpha and beta and attention's q, k and v
    rounded through ``float8_e4m3fn``.  Each check alone makes such a run
    not ``correct``; through bfloat16 every check passes as it does in the
    rehearsal."""
    import jax.numpy as jnp
    driver = rehearsed[0]
    fine, said = _run_checks(rehearsed, family.rounded_through(jnp.bfloat16))
    assert not [w for c in fine.values() for w in c.failed], said
    coarse, said = _run_checks(
        rehearsed, family.rounded_through(jnp.float8_e4m3fn))
    # (a) given the program's routing, (b) routing for itself; the pairs
    # of (e) are counted from the routing given and stay right
    wrong = coarse["a"].failed
    assert len(wrong) == 2 and wrong[0].startswith("(a)") \
        and wrong[1].startswith("(b)"), said
    assert f"< {driver.LOGITS_RTOL}" in wrong[0]
    assert f"< {driver.OWN_CHOICE_RTOL}" in wrong[1]
    # (c): o and every gradient; (d): out and every gradient that the
    # rounded inputs reach
    assert len(coarse["c"].failed) >= 5 and len(coarse["d"].failed) >= 3
    assert all(f"< {driver.RULE_RTOL}" in w for w in coarse["c"].failed)
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
    checks, said = _run_checks(rehearsed, family, letters="f")
    wrong, = checks["f"].failed
    assert "change of their float32 weights" in wrong
    assert f"< {driver.UPDATE_RTOL}" in wrong
    assert wrong.count(" 5.0e-01") >= 30 and driver.UPDATE_RTOL < 0.5


def test_a_backward_pass_that_forgets_beta_is_refused(rehearsed,
                                                      monkeypatch):
    """The planted fault for (c): a backward pass that hands beta no
    gradient.  o and the other four gradients still agree; the gradient
    by beta is found wrong."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import delta_rule
    rule = delta_rule.gated_delta_rule

    def forward(q, k, v, a, b, chunk):
        return jax.vjp(lambda *xs: rule(*xs, chunk), q, k, v, a, b)

    def backward(chunk, back, dy):
        grads = back(dy)
        return grads[:4] + (jnp.zeros_like(grads[4]),)

    forgetful = jax.custom_vjp(rule, nondiff_argnums=(5,))
    forgetful.defvjp(forward, backward)
    monkeypatch.setattr(delta_rule, "gated_delta_rule", forgetful)
    checks, said = _run_checks(rehearsed, family, letters="c")
    wrong, = checks["c"].failed
    assert "gated_delta_rule dbeta" in wrong, said
    assert [line.split()[3] for line in said if "ok: (c)" in line] == [
        "o", "dq", "dk", "dv", "dlog_alpha"]


# --------------------------------------------------------------------------
# what the benchmark declares
# --------------------------------------------------------------------------
def test_the_cell_declares_its_metrics_and_reads_the_block_metrics():
    cell = cells.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "pretrain-b1-s8192"
    assert cell.config["mesh"] == {}
    names = {m["name"] for m in cell.per_layer}
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
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "mfu", "peak_hbm_gb", "setup_s"}
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS), other
    assert len(bench["workloads"]) == len(
        {(w["config"], w["traffic"]) for w in bench["workloads"]})
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
    assert "1/40 of its EP40 load" in entry["why"] and len(entry["why"]) <= 200
    config, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config["why"]) <= 200
    # no trace, no table: every reader returns None and raises not, as on
    # a program that has none of these scopes or counters (the parent)
    obs = {"trace": None, "chips": 1, "config": cell.config, "family": None,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        assert report.load_reader(ROOT, name)(obs) is None


def test_the_benchmark_gained_entries_at_its_end():
    """This PR's configuration, cell and ten metrics were appended: they
    stand together, in order, and no entry before them names them, so a
    test that cuts the benchmark by position before an earlier PR's
    entries reads the same benchmark as before.  Where they stand is found
    by the first of them, so nothing this reads moves when a later PR
    appends."""
    bench = cells.load_benchmark(ROOT)
    mine = {"configs": (CONFIG,), "workloads": (CELL,),
            "per_layer": NEW_METRICS}
    for key, names in mine.items():
        listed = [e["name"] for e in bench[key]]
        at = listed.index(names[0])
        assert tuple(listed[at:at + len(names)]) == names, key
        had = json.dumps(bench[key][:at])
        assert CELL not in had and CONFIG not in had
        assert not [n for n in NEW_METRICS if n in had]
    files = set(os.listdir(os.path.join(ROOT, "benchmarks", "layer_metrics")))
    assert {n + ".py" for n in NEW_METRICS} <= files


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    reduced = {"num_hidden_layers": 4, "n_routed_experts": 8,
               "num_attention_heads": 8, "num_key_value_heads": 1,
               "vocab_size": 24576}
    assert config["reduced"] == list(reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config[key] == reduced[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), \
                key
    assert set(config["changed"]) >= set(reduced)
    assert 24576 * 8 == 196608 and 8 * 40 == 320 and 8 * 8 == 64
    assert config["layers_held"] == [0, 4]
    assert config["experts_held"] == [0, 8]
    assert config["heads_held"] == [0, 8]
    assert family.kinds(config) == ("gqa", "kda", "kda", "kda")
    assert family.router_width(config) == 320
    for key in ("KDA", "decay gate", "beta", "output gate", "GQA gate",
                "RMSNorm", "router", "experts", "initialisation",
                "first loss", "chunk", "optimizer"):
        assert config["assumed"][key], key
    assert "1e-20" in config["assumed"]["router"]
    assert "1 : 3" in config["changed"]["num_hidden_layers"]
    # every mixer recomputed, the expert half never
    assert config["recompute"] == [0, 1, 2, 3]
    assert config["mesh"] == {} and config["initializer_range"] == 0.02
    assert config["optimizer"] == {"name": "AdamW", "learning_rate": 1e-4,
                                   "warmup_steps": 500}
    assert config["router_bias"] == {"update_rate": 0.001, "passes": 100}
    assert config["step_bytes_limit"] == 15_600_000_000
    for key in ("changed", "assumed", "deployment", "notes"):
        assert config[key], key
    assert "840 874 392 parameters" in config["notes"]["parameters"]
    for said in ("12 pipeline stages", "40 chips share each stage",
                 "routed experts 40 ways", "heads 8 ways",
                 "8 ways by rows"):
        assert said in config["deployment"], said
    # the toy size keeps the kinds and fills a lane group a call
    toy = cells.sized(config, True)
    assert family.kinds(toy) == ("gqa", "kda", "kda", "kda")
    assert toy["num_attention_heads"] * toy["head_dim"] == 128
    assert toy["n_routed_experts"] == toy["experts_held"][1]
    from benchmarks.drivers import train_solar_lm as driver
    for sized in (config, toy):
        program = driver.program_config(sized)
        assert program.kinds == family.kinds(sized)
        assert program.vocab_rows_held == sized["vocab_size"]
        assert program.kv_heads_held[1] == sized["num_key_value_heads"]
    with pytest.raises(ValueError, match="what is held here"):
        driver.program_config({**config, "num_key_value_heads": 8})
    entry, = [c for c in cells.load_benchmark(ROOT)["configs"]
              if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == CONFIG_FILE


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    assert w == {"kda": 4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024)
                 + 4096 * 8 + 3 * 1024 * 4,
                 "gqa": 3 * 4096 * 1024 + 2 * 4096 * 128,
                 "router": 4096 * 320, "expert": 3 * 4096 * 1280,
                 "shared": 3 * 4096 * 1280}
    # by hand: 18.1 M a KDA layer, 13.6 M the GQA layer
    assert w["kda"] == 12_582_912 + 4_194_304 + 1_310_720 + 32_768 + 12_288
    assert w["gqa"] == 13_631_488
    per = family.layer_params(config)
    assert per == {"kda": 18_135_176 + 142_876_672,
                   "gqa": 13_631_488 + 142_876_672}
    assert 8 * w["expert"] + w["shared"] + w["router"] + 2 * 4096 \
        == 142_876_672
    assert family.param_count(config) == 156_508_160 + 3 * 161_011_848 \
        + 201_326_592 + 4096 == 840_874_392
    # 14 bytes a parameter: 11.77e9 bytes of arguments
    assert 14 * 840_874_392 == approx(11.772e9, rel=1e-3)
    assert family.pairs_per_token(config) == 8 * 8 / 320 == 0.2
    kda = family.kda_cost(config, 1, 8192)
    # a chunk of 64 and a head of 128: 4 C^2 d + 2 C^2 d + 6 C d^2 + 2 C^2 d
    chunk = 4 * 64 * 64 * 128 + 2 * 64 * 64 * 128 + 6 * 64 * 128 * 128 \
        + 2 * 64 * 64 * 128
    assert chunk == 10_485_760
    assert kda["flops"] == 3 * chunk * (3 * 8 * 8192 / 64)
    # a position of a head: q, k, log alpha, beta float32, v bf16 read
    inputs = 4 * 128 + 4 * 128 + 2 * 128 + 4 * 128 + 4
    assert inputs == 1796
    # forward: the inputs and o; backward: the inputs and do, and their
    # gradients
    assert kda["bytes"] == 3 * 8 * 8192 * (inputs + 256 + inputs + 256
                                           + inputs)
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    least, bound = cells.least_seconds(kda["flops"], kda["bytes"], peaks)
    # 96.6 GFLOP, 0.49 ms at the peak; 1.16 GB, 1.42 ms of bytes
    assert bound == "bytes" and 1e3 * least == approx(1.4165, rel=1e-3)
    weights = 3 * w["kda"] + w["gqa"] + 4 * (
        w["router"] + w["shared"] + 0.2 * w["expert"]) + 24576 * 4096
    square = 3 * 4 * 1024 * 8193 / 2
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + square + kda["flops"] / 8192, rel=1e-12)
    # about 1.56 GFLOP a token, 12.8 TFLOP a step: 65 ms at the peak
    assert family.flops_per_token(config, 8192) == approx(1.5587e9, rel=1e-3)


# --------------------------------------------------------------------------
# the reader on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope(monkeypatch):
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_solar_scoped.step.txt``, the same step with this family's
    sub-scopes in its ``op_name``s.  Microseconds a step, device 0 first |
    second run, device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   kda_core and, by one member, router: mixed
        fusion.3      10 |  8   experts, recomputed in the backward pass;
                                the optimizer's part has no sub-scope
        flash_fwd.2   20 | 22   gqa_core, recomputed: 21 and 18, 19.5
        fusion.4       6 |  6   shared_expert
        all-reduce.6  10 | 10   kda_proj, backward
        copy.8         4 |  4   kda_conv_gate
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step.  The mixed row counts for neither the rule's metric
    nor the routing's."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA, "hand_made_solar_scoped.step.txt")) as f:
        text = f.read()
    said = []
    table = solar_scopes.reader.block_table(data, text, say=said.append)
    assert table is not None, said
    us = {r.name: 1e6 * r.seconds for r in table.rows}
    assert us == approx({"kda_core+router": 10.0, "experts": 9.0,
                         "gqa_core": 19.5, "shared_expert": 6.0,
                         "kda_proj": 10.0, "kda_conv_gate": 4.0,
                         "unscoped": 2.0, "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)
    again = ssm_scopes.readers["recompute_scopes"].block_table(
        data, text, say=said.append)

    class Family:
        kda_cost = staticmethod(lambda c, b, s: {"flops": 0.0,
                                                 "bytes": 819e9 * 1.2e-6})

    obs = {"trace": object(), "chips": 1, "config": {}, "family": Family,
           "traffic": {"batch": 1, "seq_len": 8},
           "peaks": cells.load_peaks("TPU v5 lite", ROOT),
           "counters": {
               "before": {"observed": 1, "moe_pairs": 100},
               "after": {"observed": 3, "moe_pairs": 3700, "program": {
                   "moe_expert_tokens_max": [12, 30],
                   "moe_expert_tokens_mean": [10, 20]}}},
           solar_scopes.TABLE: {"trace": True, "scopes": table},
           "recompute_scopes": {"trace": True, "scopes": again}}
    read = {name: report.load_reader(ROOT, name)(obs) for name in NEW_METRICS}
    assert read == approx({
        "so_kda_core_ms_per_step": 0.0,           # the mixed row is no one's
        "so_kda_proj_ms_per_step": 0.010,
        "so_kda_conv_gate_ms_per_step": 0.004,
        "so_gqa_core_ms_per_step": 0.0195,
        "so_moe_route_ms_per_step": 0.0,
        "so_moe_experts_ms_per_step": 0.009,
        "so_moe_shared_expert_ms_per_step": 0.006,
        "so_moe_expert_imbalance": (1.2 + 1.5) / 2,
        "so_recompute_ms_per_step": 0.0195,
        # no row is kda_core's alone: no share is read
        "so_kda_core_roofline": None})
    # were 10 us of the rule's own: 1.2 us of bytes over them
    monkeypatch.setattr(solar_scopes, "ms_per_step",
                        lambda obs, metric_file, names: 0.010)
    assert report.load_reader(ROOT, "so_kda_core_roofline")(obs) == approx(
        100 * 1.2 / 10.0)
    monkeypatch.undo()
    # the readers before it are untouched by this copy
    from benchmarks.harness import lfm2_scopes, scopes, subscopes
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    for other in (subscopes, lfm2_scopes):
        assert "kda_core" not in other.reader.SCOPES
    assert solar_scopes.reader.SCOPES == solar_scopes.SUBSCOPES
    assert len(solar_scopes.SUBSCOPES) == 9
    # a step without any of these scopes (the parent's): one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        plain = f.read()
    assert solar_scopes.reader.block_table(
        data, plain, say=said.append) is None
    assert "carries any of the scopes kda_proj" in said[-1]

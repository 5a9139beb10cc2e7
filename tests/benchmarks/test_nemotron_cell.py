"""The cell ``nemotron3-nano-ep16stage0-s8192`` on the CPU: it rehearses
end to end with and without a trace and reaches ``correct``, its family's
counts are what hand arithmetic gives for the published widths, its
configuration keeps them and agrees with the catalog row's numbers, what
``BENCHMARK.json`` gained for it is appended and nothing else, and the
reader of ``harness/hybrid_moe_scopes.py`` joins a hand-made pair as
``test_scopes.py`` has ``scopes.py`` do.
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
from benchmarks.families import nemotron_h as family          # noqa: E402
from benchmarks.harness import (cells, hybrid_moe_scopes,     # noqa: E402
                                report, ssm_scopes)

CELL = "nemotron3-nano-ep16stage0-s8192"
CONFIG = "nemotron-3-nano-30b-a3b"
CONFIG_FILE = "benchmarks/configs/nemotron-3-nano-30b-a3b.json"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW_METRICS = (
    "nh_ssm_scan_ms_per_step", "nh_ssm_conv_proj_norm_ms_per_step",
    "nh_gqa_core_ms_per_step", "nh_moe_route_ms_per_step",
    "nh_moe_experts_ms_per_step", "nh_moe_shared_expert_ms_per_step",
    "nh_moe_expert_imbalance", "nh_recompute_ms_per_step",
    "nh_ssm_scan_roofline", "nh_moe_experts_roofline")
# the catalog row of the model-configs guide (architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), its ``config`` as it stands
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


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
    assert "the step recomputes blocks [0, 1] of 4 (1 mamba, 1 moe, 0 " \
        "attention)" in out
    assert "1 Mamba-2, 2 expert and 1 attention block(s), 2 of 8 experts" \
        in out
    assert "none dropped" in out and "ssm_scan_kernel_visits_total" in out
    assert "balanced the routers' biases over 3 forward passes" in out
    if trace:
        # a CPU has no device plane: the device metrics are left out
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert set(NEW_METRICS) & set(obj["metrics"]) == {
            "nh_moe_expert_imbalance"}
        assert obj["metrics"]["nh_moe_expert_imbalance"]["value"] >= 1.0
        assert "attention_ms_per_step" not in obj["metrics"]
        assert "tokens_per_s" not in obj["metrics"]
    else:
        assert obj["metrics"]["tokens_per_s"]["value"] > 0
        assert obj["metrics"]["setup_s"]["value"] > 0


def test_the_cell_declares_its_metrics_and_reads_the_block_metrics():
    cell = cells.load_cell(CELL, ROOT)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "pretrain-b1-s8192"
    assert cell.config["mesh"] == {}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"attention_ms_per_step", "mlp_ms_per_step",
            "lmhead_loss_ms_per_step", "optimizer_ms_per_step",
            "unscoped_ms_per_step", "flash_ms_per_step", "xla_ops_ms_per_step",
            "device_idle_share", "step_hbm_gb"} <= names
    # no other cell's own metrics: the sub-scopes' readers list their cells
    assert not {n for n in names if n.startswith(("dsa_", "moe_", "ssm_"))}
    assert "flash_roofline" not in names
    assert {m["name"] for m in cell.end_to_end} >= {"tokens_per_s", "setup_s"}
    for other in ("gpt2m-pretrain-s1024", "keye2-lm-ep8share-s8192",
                  "granite4h-micro-stage0-s8192"):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS)
    bench = cells.load_benchmark(ROOT)
    assert len(bench["workloads"]) == len(
        {(w["config"], w["traffic"]) for w in bench["workloads"]})
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["moves"] == "tokens_per_s"
        assert (declared[name]["unit"] == "%") == name.endswith("_roofline")
    for m in cell.per_layer:
        assert callable(report.load_reader(ROOT, m["name"]))
    for folder, key in (("drivers", "driver"), ("families", "family")):
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", folder, cell.config[key] + ".py"))
    # no trace, no table: every reader returns None and raises not, as on
    # a program that has none of these scopes or counters
    obs = {"trace": None, "chips": 1, "config": cell.config, "family": None,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        assert report.load_reader(ROOT, name)(obs) is None


def test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest(
        monkeypatch):
    """``test_granite_cell.py`` pins the benchmark at five cells inside its
    test of what its own cell declares, so that test is expected to fail
    on the count since this cell was appended (``tests/conftest.py`` says
    why it may not be edited here).  Nothing it holds is let go
    meanwhile: its whole body runs here on ``BENCHMARK.json`` less what
    this cell's PR appended, the count and the one four-chip cell
    included, and what was appended is held to be appended and no more."""
    import importlib.util
    bench = cells.load_benchmark(ROOT)
    mine = {"configs": {CONFIG}, "workloads": {CELL},
            "per_layer": set(NEW_METRICS)}
    had = dict(bench)
    for key, names in mine.items():
        had[key] = [e for e in bench[key] if e["name"] not in names]
        # appended: in the file's order, after everything that was there
        assert bench[key][:len(had[key])] == had[key], key
        assert {e["name"] for e in bench[key][len(had[key]):]} == names
    assert (len(had["configs"]), len(had["workloads"]),
            len(had["end_to_end"]), len(had["per_layer"])) == (4, 5, 4, 29)
    assert had["end_to_end"] == bench["end_to_end"]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    # no entry that was there names the new cell: no list was extended
    assert CELL not in json.dumps(had) and CONFIG not in json.dumps(had)
    spec = importlib.util.spec_from_file_location(
        "the_granite_cells_tests", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "test_granite_cell.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    pinned = theirs.test_the_cell_declares_its_metrics_and_reads_the_block_metrics
    with pytest.raises(AssertionError):     # six cells: the count, alone
        pinned()
    monkeypatch.setattr(cells, "load_benchmark", lambda root=ROOT: had)
    pinned()


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    reduced = {"num_hidden_layers": 9, "n_routed_experts": 8,
               "vocab_size": 16384}
    assert config["reduced"] == list(reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config[key] == reduced[key], key
            assert config["published"][key] == value, key
        elif key == "hybrid_override_pattern":
            # the first nine blocks of the published pattern
            assert config[key] == value[:9] == "MEMEM*EME"
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert set(config["changed"]) >= set(reduced)
    assert 16384 * 8 == 131072 and config["experts_held"] == [0, 8]
    assert config["blocks_held"] == [0, 9]
    kinds = family.kinds(config)
    assert (kinds.count("mamba"), kinds.count("moe"),
            kinds.count("attention")) == (4, 4, 1)
    # which blocks are recomputed: Mamba-2 blocks, by their index
    assert config["recompute"] and all(
        kinds[i] == "mamba" for i in config["recompute"])
    assert config["mesh"] == {} and config["initializer_range"] == 0.02
    assert config["embedding_range"] == 1.0
    assert config["router_bias"] == {"update_rate": 0.001, "passes": 100}
    assert config["optimizer"] == {"name": "AdamW", "learning_rate": 1e-4,
                                   "warmup_steps": 500}
    assert config["step_bytes_limit"] == 15_600_000_000
    for key in ("changed", "assumed", "deployment", "notes"):
        assert config[key], key
    for key in ("initialisation", "rescale_prenorm_residual", "embedding",
                "positions", "router", "optimizer", "gated norm",
                "first loss"):
        assert config["assumed"][key], key
    assert "6 pipeline stages" in config["deployment"]
    assert "16 chips share each stage" in config["deployment"]
    assert "routed experts 16 ways" in config["deployment"]
    assert "8 ways by rows" in config["deployment"]
    toy = config["rehearsal"]
    assert len(toy["hybrid_override_pattern"]) == toy["num_hidden_layers"]
    assert toy["n_routed_experts"] == toy["experts_held"][1]
    entry, = [c for c in cells.load_benchmark(ROOT)["configs"]
              if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == CONFIG_FILE


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    assert w == {
        # in_proj 2688 x (4096 + 6144 + 64), the convolution's 6144 x 4,
        # out_proj 4096 x 2688
        "mamba": 2688 * 10304 + 6144 * 4 + 4096 * 2688,
        "attention": 2 * 2688 * 4096 + 2 * 2688 * 256,
        "router": 2688 * 128, "shared": 2 * 2688 * 3712,
        "expert": 2 * 2688 * 1856}
    mamba = 27_697_152 + 30_720 + 192 + 4_096 + 11_010_048 + 2_688
    attention = 11_010_048 * 2 + 1_376_256 + 2_688
    moe = 344_064 + 8 * 9_977_856 + 19_955_712 + 2_688
    assert (mamba, attention, moe) == (38_744_896, 23_399_040, 100_125_312)
    assert family.param_count(config) == 4 * mamba + attention + 4 * moe \
        + 2 * 44_040_192 + 2_688 == 666_962_944
    assert family.pairs_per_token(config) == 6 * 8 / 128
    # the scan forward, a token and block: (128 + 1) / 2 pairs of C . B
    # (2 x 8 x 128) and of a [Q, Q] x [Q, 64] row a head (2 x 64 x 64),
    # and 2 x 2 x 64 x 64 x 128 for the chunk's state and what it adds
    scan = 64.5 * (2 * 1024 + 2 * 4096) + 4 * 64 * 64 * 128
    assert family.scan_flops_per_token(config) == scan == 2_757_632
    weights = 4 * (w["mamba"]) + w["attention"] + 4 * (
        344_064 + 19_955_712 + 0.375 * 9_977_856) + 16384 * 2688
    attention_square = 3 * 4 * 4096 * 8193 / 2
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + attention_square + 4 * 3 * scan, rel=1e-12)
    # 1.76e13 a step, 89 ms at the chip's peak
    assert 8192 * family.flops_per_token(config, 8192) == approx(
        1.758e13, rel=1e-3)
    cost = family.scan_cost(config, 1, 8192)
    assert cost["flops"] == 4 * 8192 * 3 * scan
    forward = 2 * 4096 + 2 * 2 * 1024 + 4 * 64 + 2 * 4096
    backward = 2 * 2 * 4096 + 4096 + 256 + 2 * 4096 + 4096 + 256
    assert cost["bytes"] == 4 * 8192 * (forward + backward)
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    least, bound = cells.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "bytes" and 1e3 * least == approx(2.161, rel=1e-3)
    # the held experts over the pairs a balanced router sends here:
    # 4 blocks x 8192 x 6 x 8 / 128
    pairs = 4 * 8192 * 0.375
    experts = family.experts_cost(config, pairs)
    assert experts["flops"] == 6 * 9_977_856 * pairs
    assert experts["bytes"] == 4 * 8 * 9_977_856 * 2 * 3 \
        + pairs * 3 * 2 * (2 * 2688 + 2 * 1856)
    least, bound = cells.least_seconds(experts["flops"], experts["bytes"],
                                       peaks)
    # 0.736 TFLOP, 3.73 ms at the peak; the bytes would take 3.16
    assert bound == "operations" and 1e3 * least == approx(3.734, rel=1e-3)


# --------------------------------------------------------------------------
# the reader on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope():
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_hybrid_moe_scoped.step.txt``, the same step with this
    family's sub-scopes in its ``op_name``s.  Microseconds a step, device 0
    first | second run, device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   ssm_scan and, by one member, router: mixed
        fusion.3      10 |  8   experts, recomputed in the backward pass;
                                the optimizer's part has no sub-scope
        flash_fwd.2   20 | 22   gqa_core, recomputed: 21 and 18, 19.5
        fusion.4       6 |  6   shared_expert
        all-reduce.6  10 | 10   combine, backward
        copy.8         4 |  4   dispatch
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step.  The mixed row counts for neither the scan's metric
    nor the routing's; the readers that know five or eight of the ten
    names give it to the part they know."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA,
                           "hand_made_hybrid_moe_scoped.step.txt")) as f:
        text = f.read()
    said = []
    table = hybrid_moe_scopes.reader.block_table(data, text, say=said.append)
    assert table is not None, said
    rows = {r.name: r for r in table.rows}
    us = {name: 1e6 * r.seconds for name, r in rows.items()}
    assert us == approx({"ssm_scan+router": 10.0, "experts": 9.0,
                         "gqa_core": 19.5, "shared_expert": 6.0,
                         "combine": 10.0, "dispatch": 4.0, "unscoped": 2.0,
                         "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)
    assert 1e6 * rows["experts"].backward_s == approx(9.0)
    assert 1e6 * rows["combine"].backward_s == approx(10.0)

    def ms(table_, names):        # what the metric files ask of a table
        names = frozenset(names)
        return table_.ms_per_step(lambda b: bool(b) and b <= names)

    assert ms(table, ("ssm_scan",)) == 0.0
    assert ms(table, ("ssm_proj", "ssm_conv", "ssm_norm")) == 0.0
    assert ms(table, ("router", "dispatch", "combine")) == approx(0.014)
    assert ms(table, ("experts",)) == approx(0.009)
    assert ms(table, ("shared_expert",)) == approx(0.006)
    assert ms(table, ("gqa_core",)) == approx(0.0195)
    # the readers of five and of eight names see the mixed row as their own
    five = ssm_scopes.readers["ssm_scopes"].block_table(
        data, text, say=said.append)
    assert ms(five, ("ssm_scan",)) == approx(0.010)
    from benchmarks.harness import scopes, subscopes
    eight = subscopes.reader.block_table(data, text, say=said.append)
    assert ms(eight, ("router", "dispatch", "combine")) == approx(0.024)
    # what is recomputed, by the second reader of ssm_scopes.py: the
    # kernel; fusion.3 holds the optimizer's part too
    again = ssm_scopes.readers["recompute_scopes"].block_table(
        data, text, say=said.append)
    assert ms(again, ssm_scopes.RECOMPUTED[:1]) == approx(0.0195)
    # the readers before it are untouched by this copy
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    assert "shared_expert" not in subscopes.reader.SCOPES
    assert hybrid_moe_scopes.reader.SCOPES == hybrid_moe_scopes.SUBSCOPES
    assert len(hybrid_moe_scopes.SUBSCOPES) == 10
    # a step without any of these scopes: one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        plain = f.read()
    assert hybrid_moe_scopes.reader.block_table(
        data, plain, say=said.append) is None
    assert "carries any of the scopes ssm_proj" in said[-1]


# --------------------------------------------------------------------------
# check (h) sees what it is there to see
# --------------------------------------------------------------------------
def test_the_gradient_check_sees_a_backward_pass_that_forgets_the_gates(
        monkeypatch, config):
    """The step's gradients against the reference's at the toy size: they
    agree; with the experts' hand-written backward pass returning no
    gradient for the gates, the router's matrix is found wrong and the
    others are not."""
    import jax
    import jax.numpy as jnp
    from benchmarks.drivers import train_lm, train_nemotron_lm as driver
    from benchmarks.harness import traffic
    from paddle_tpu.incubate.distributed.models.moe import grouped
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    toy = cells.sized(config, True)
    mix = cells.sized(cells.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "pretrain-b1-s8192.json")), True)
    runner = driver.build_runner(toy, 11, jax.devices()[:1])
    batch = traffic.token_batches(mix, toy["vocab_size"], 11)[0]

    def checked():
        said = []
        check = train_lm.Checks(said.append)
        driver.check_gradients(check, runner, family, toy, batch)
        return check.failed, said[-1]

    failed, line = checked()
    assert not failed and "ok: (h)" in line, line
    assert "over the first 128 of 128 positions" in line
    # the loss over a sequence's first positions only, as at the timed
    # size: the reference runs on those alone
    monkeypatch.setattr(driver, "GRADS_POSITIONS", 48)
    failed, line = checked()
    assert not failed and "over the first 48 of 128 positions" in line, line
    whole = grouped._window_backward

    def forgetful(w, kept, gates, weights, g):
        d_y, d_gates, d_weights = whole(w, kept, gates, weights, g)
        return d_y, jnp.zeros_like(d_gates), d_weights

    monkeypatch.setattr(grouped, "_window_backward", forgetful)
    failed, line = checked()
    assert failed and "WRONG: (h)" in line, line
    by_parameter = line.split("by parameter ")[1].split()
    errs = dict(zip(by_parameter[::2], map(float, by_parameter[1::2])))
    assert all((e > driver.GRADS_RTOL) == name.endswith("gate.weight")
               for name, e in errs.items()), errs

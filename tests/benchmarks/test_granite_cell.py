"""The cell ``granite4h-micro-stage0-s8192`` on the CPU: it rehearses end
to end with and without a trace, its family's counts are what hand
arithmetic gives for the published widths, its configuration keeps them,
and the two readers of ``harness/ssm_scopes.py`` join a hand-made pair as
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
from benchmarks.families import granite_hybrid as family      # noqa: E402
from benchmarks.harness import cells, report, ssm_scopes      # noqa: E402

CELL = "granite4h-micro-stage0-s8192"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW_METRICS = ("ssm_scan_ms_per_step", "ssm_conv_ms_per_step",
               "ssm_proj_norm_ms_per_step", "gqa_core_ms_per_step",
               "recompute_ms_per_step", "ssm_scan_roofline")


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
    return cells.load_cell(CELL, ROOT).config


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
    for letter in "abcd":
        assert f"ok: ({letter})" in out, letter
    assert "WRONG" not in out
    assert "the step recomputes 3 of 3 layers" in out
    assert "2 Mamba-2 layers and 1 attention layer(s)" in out
    if trace:
        # a CPU has no device plane: the device metrics are left out
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert not set(NEW_METRICS) & set(obj["metrics"])
        assert "tokens_per_s" not in obj["metrics"]
    else:
        assert obj["metrics"]["tokens_per_s"]["value"] > 0
        assert obj["metrics"]["setup_s"]["value"] > 0


def test_the_cell_declares_its_metrics_and_reads_the_block_metrics():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"attention_ms_per_step", "mlp_ms_per_step",
            "lmhead_loss_ms_per_step", "optimizer_ms_per_step",
            "unscoped_ms_per_step", "flash_ms_per_step"} <= names
    assert "flash_roofline" not in names and cell.chips == 1
    assert not {n for n in names if n.startswith(("dsa_", "moe_"))}
    assert cell.traffic_name == "pretrain-b1-s8192"
    for other in ("gpt2m-pretrain-s1024", "keye2-lm-ep8share-s8192"):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS)
    bench = cells.load_benchmark(ROOT)
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    assert len(bench["workloads"]) == 5
    # no trace, no table: every reader returns None and raises not, as on
    # a program that has none of these scopes
    obs = {"trace": None, "chips": 1, "config": cell.config, "family": None,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        assert report.load_reader(ROOT, name)(obs) is None


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "shared_intermediate_size": 8192, "num_attention_heads": 32,
        "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_chunk_size": 256,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "rope_scaling": None,
        "max_position_embeddings": 131072, "num_local_experts": 0,
        "num_experts_per_tok": 0, "position_embedding_type": "nope",
        "tie_word_embeddings": True, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "attention_bias": False,
        "hidden_act": "silu", "normalization_function": "rmsnorm",
        "model_type": "granitemoehybrid"}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["vocab_size"]) == \
        (10, 100352 // 8) and 12544 == 98 * 128
    # one whole period of the published pattern, nine to one
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert config["recompute"] is True and config["mesh"] == {}
    assert config["step_bytes_limit"] == 15_600_000_000
    for key in ("changed", "assumed", "deployment", "notes"):
        assert config[key], key
    assert "8 chips share each stage" in config["deployment"]
    toy = config["rehearsal"]
    assert toy["layer_types"] == ["mamba", "attention", "mamba"]
    assert toy["mamba_n_heads"] * toy["mamba_d_head"] == \
        toy["mamba_expand"] * toy["hidden_size"]


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    assert w == {
        # in_proj 2048 x (4096 + 4352 + 64), the convolution's 4352 x 4,
        # out_proj 4096 x 2048
        "mamba": 2048 * 8512 + 4352 * 4 + 4096 * 2048,
        "attention": 2 * 2048 * 2048 + 2 * 2048 * 512,      # 10.49 M
        "mlp": 2048 * 16384 + 8192 * 2048}                  # 50.33 M
    mamba_layer = 17_432_576 + 17_408 + 8_388_608 \
        + 4352 + 3 * 64 + 4096 + 50_331_648 + 2 * 2048
    attention_layer = 10_485_760 + 50_331_648 + 2 * 2048
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    assert family.param_count(config) == 9 * mamba_layer + attention_layer \
        + 12544 * 2048 + 2048 == 772_160_448
    # the scan forward, a token and layer: (256 + 1) / 2 pairs of C . B
    # (2 x 128) and of a [Q, Q] x [Q, 64] row a head (2 x 64 x 64), and
    # 2 x 2 x 64 x 64 x 128 for the chunk's state and what it adds
    scan = 128.5 * (2 * 128 + 2 * 4096) + 4 * 64 * 64 * 128
    assert family.scan_flops_per_token(config) == scan == 3_182_720
    weights = 9 * (25_838_592 + 50_331_648) + 10_485_760 + 50_331_648 \
        + 12544 * 2048
    attention = 3 * 4 * 2048 * 8193 / 2
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + attention + 9 * 3 * scan, rel=1e-12)
    # 3.95e13 a step, 200 ms at the chip's peak
    assert 8192 * family.flops_per_token(config, 8192) == approx(
        3.948e13, rel=1e-3)
    cost = family.scan_cost(config, 1, 8192)
    assert cost["flops"] == 9 * 8192 * 3 * scan
    forward = 2 * 4096 + 2 * 2 * 128 + 4 * 64 + 2 * 4096
    backward = 2 * 2 * 4096 + 512 + 256 + 2 * 4096 + 512 + 256
    assert cost["bytes"] == 9 * 8192 * (forward + backward) == 3_189_768_192
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    least, bound = cells.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "bytes" and 1e3 * least == approx(3.895, rel=1e-3)


# --------------------------------------------------------------------------
# the two readers on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope():
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_ssm_scoped.step.txt``, the same step with this family's
    sub-scopes in its ``op_name``s.  Microseconds a step, device 0 first
    | second run, device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   ssm_scan (a member with no sub-scope and
                                a constant lend nothing)
        fusion.3      10 |  8   ssm_scan, recomputed in the backward pass;
                                the optimizer's part has no sub-scope
        flash_fwd.2   20 | 22   gqa_core, recomputed: 21 and 18, 19.5
        fusion.4       6 |  6   ssm_norm
        all-reduce.6  10 | 10   ssm_proj, backward
        copy.8         4 |  4   ssm_conv
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step.  By the second reader: the kernel is the
    recomputation's, 19.5; fusion.3 holds the optimizer's part too and is
    ``rematted_computation+optimizer``, 9; everything else found is
    unscoped there."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA, "hand_made_ssm_scoped.step.txt")) as f:
        text = f.read()
    said = []
    table = ssm_scopes.readers["ssm_scopes"].block_table(
        data, text, say=said.append)
    assert table is not None, said
    rows = {r.name: r for r in table.rows}
    us = {name: 1e6 * r.seconds for name, r in rows.items()}
    assert us == approx({"ssm_scan": 19.0, "gqa_core": 19.5,
                         "ssm_norm": 6.0, "ssm_proj": 10.0,
                         "ssm_conv": 4.0, "unscoped": 2.0,
                         "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)
    assert 1e6 * rows["ssm_scan"].backward_s == approx(9.0)
    assert 1e6 * rows["gqa_core"].backward_s == approx(19.5)

    def ms(table_, names):        # what the metric files ask of a table
        names = frozenset(names)
        return table_.ms_per_step(lambda b: bool(b) and b <= names)

    assert ms(table, ("ssm_scan",)) == approx(0.019)
    assert ms(table, ("ssm_proj", "ssm_norm")) == approx(0.016)
    assert ms(table, ("ssm_conv",)) == approx(0.004)
    assert ms(table, ("gqa_core",)) == approx(0.0195)
    again = ssm_scopes.readers["recompute_scopes"].block_table(
        data, text, say=said.append)
    assert {r.name: 1e6 * r.seconds for r in again.rows} == approx(
        {"rematted_computation": 19.5, "rematted_computation+optimizer": 9.0,
         "unscoped": 32.0, "not found": 1.0})
    assert ms(again, ssm_scopes.RECOMPUTED[:1]) == approx(0.0195)
    # the readers before it are untouched by these copies
    from benchmarks.harness import scopes, subscopes
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    assert "sparse_core" in subscopes.reader.SCOPES
    assert scopes.block_table(data, text, say=said.append) is not None
    # a step without any of these scopes: one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        plain = f.read()
    assert ssm_scopes.readers["ssm_scopes"].block_table(
        data, plain, say=said.append) is None
    assert "carries any of the scopes ssm_proj" in said[-1]
    # ... and one that recomputes nothing has no such row
    none = ssm_scopes.readers["recompute_scopes"].block_table(
        data, plain, say=said.append)
    assert ms(none, ssm_scopes.RECOMPUTED[:1]) == 0.0
    assert {r.name for r in none.rows} == {"optimizer", "unscoped",
                                           "not found"}

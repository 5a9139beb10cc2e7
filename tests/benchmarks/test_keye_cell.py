"""The cell ``keye2-lm-ep8share-s8192`` on the CPU: it rehearses end to
end with and without a trace, its family's counts are what hand
arithmetic gives for the published widths, its configuration keeps
them, and the sub-scope reader joins a hand-made pair as
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
from benchmarks.families import keye_lm as family             # noqa: E402
from benchmarks.harness import cells, report, subscopes       # noqa: E402

CELL = "keye2-lm-ep8share-s8192"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
NEW_METRICS = ("dsa_indexer_ms_per_step", "dsa_core_ms_per_step",
               "moe_route_ms_per_step", "moe_experts_ms_per_step",
               "moe_expert_imbalance", "dsa_core_roofline",
               "moe_experts_roofline")


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
    options = report.RunOptions(seed=3_000_000_011, seconds=0.5, trace=trace,
                                rehearse=True)
    obj = bench_run.run_cell(cell, options)
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert last.startswith(bench_run.REHEARSAL_PREFIX)
    assert json.loads(last[len(bench_run.REHEARSAL_PREFIX):]) == obj
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 2
    for letter in "abcdef":
        assert f"ok: ({letter})" in out or f"({letter}) the" in out, letter
    assert "WRONG" not in out
    if trace:
        # a CPU has no device plane: the device metrics are left out, the
        # program's counters are read
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert obj["metrics"]["moe_expert_imbalance"]["value"] >= 1.0
        assert "dsa_core_ms_per_step" not in obj["metrics"]
        assert "tokens_per_s" not in obj["metrics"]
    else:
        assert obj["metrics"]["tokens_per_s"]["value"] > 0
        assert obj["metrics"]["setup_s"]["value"] > 0


def test_the_cell_declares_its_seven_metrics_and_reads_the_block_metrics():
    cell = cells.load_cell(CELL, ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW_METRICS) <= names
    assert {"attention_ms_per_step", "mlp_ms_per_step",
            "lmhead_loss_ms_per_step", "optimizer_ms_per_step",
            "unscoped_ms_per_step", "flash_ms_per_step"} <= names
    assert "flash_roofline" not in names and cell.chips == 1
    for other in ("gpt2m-pretrain-s1024", "gpt3xl-dp2mp2-s2048"):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS)
    # no trace, no table: every device reader returns None and raises not
    obs = {"trace": None, "chips": 1, "config": cell.config,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        if name != "moe_expert_imbalance":
            assert report.load_reader(ROOT, name)(obs) is None


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "num_local_experts": 128,
        "intermediate_size": 6144, "rope_theta": 10000000,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 262144,
        "norm_topk_prob": True, "tie_word_embeddings": False}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 151936 // 8)
    assert config["experts_held"] == [0, 16] and config["mesh"] == {}
    assert "8 chips share each layer" in config["deployment"]
    # the rehearsal selects: its topk is below its toy sequence
    toy = cells.sized(cells.load_cell(CELL, ROOT).traffic, True)
    assert config["rehearsal"]["sa_config"]["topk"] < toy["seq_len"]


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    assert w == {"attention": 2048 * 4096 * 2 + 2 * 2048 * 512,    # 18.87 M
                 "indexer": 2048 * 1024 + 2048 * 64 + 2048 * 16,   # 2.26 M
                 "router": 2048 * 128, "expert": 3 * 2048 * 768}   # 4.72 M
    layer = 18_874_368 + 2_260_992 + 262_144 + 16 * 4_718_592 \
        + 2 * 2048 + 2 * 128 + 2 * 64
    assert family.param_count(config) == \
        5 * layer + 2 * 18992 * 2048 + 2048 == 562_290_560
    # 2048 rows that see all their causal keys, 6144 that see 2048
    pairs = 2048 * 2049 // 2 + 6144 * 2048
    assert family.selected_pairs(8192, 2048) == pairs == 14_681_088
    assert family.selected_pairs(1024, 2048) == 1024 * 1025 // 2
    assert family.pairs_per_token(config) == 1.0      # 8 x 16 / 128
    weights = 5 * (18_874_368 + 2_260_992 + 262_144 + 4_718_592) \
        + 18992 * 2048
    core = 3 * 4 * 32 * 128 * pairs / 8192
    indexer = 3 * 2 * 16 * 64 * 8193 / 2
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + 5 * (core + indexer), rel=1e-12)
    # 1.3e13 a step, 66 ms at the chip's peak
    assert 8192 * family.flops_per_token(config, 8192) == approx(
        1.297e13, rel=1e-3)
    cost = family.sparse_core_cost(config, 1, 8192)
    assert cost["flops"] == 5 * 6 * 2 * 32 * 128 * pairs
    assert cost["bytes"] == 5 * 6 * 2 * 8192 * 128 * 36
    experts = family.experts_cost(config, 5 * 8192)
    assert experts["flops"] == 6 * 4_718_592 * 5 * 8192
    assert experts["bytes"] == 5 * 16 * 4_718_592 * 2 * 3 \
        + 5 * 8192 * 3 * 2 * (2 * 2048 + 3 * 768)
    # the least times: operations bound both
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    assert cells.least_seconds(cost["flops"], cost["bytes"], peaks) == (
        approx(cost["flops"] / 197e12), "operations")


# --------------------------------------------------------------------------
# the sub-scope reader on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope():
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_subscoped.step.txt``, the same step with the sub-scopes in
    its ``op_name``s.  Microseconds a step, device 0 first | second run,
    device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   experts (a member with no sub-scope and a
                                constant lend nothing)
        fusion.3      10 |  8   experts, backward: the optimizer's part of
                                the fusion has no sub-scope
        flash_fwd.2   20 | 22   sparse_core: 21 and 18, 19.5
        fusion.4       6 |  6   indexer_kl (its ``head`` member has none)
        all-reduce.6  10 | 10   indexer, backward
        copy.8         4 |  4   dispatch
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step, as the six-scope table of the same trace."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA, "hand_made_subscoped.step.txt")) as f:
        text = f.read()
    said = []
    table = subscopes.reader.block_table(data, text, say=said.append)
    assert table is not None, said
    rows = {r.name: r for r in table.rows}
    us = {name: 1e6 * r.seconds for name, r in rows.items()}
    assert us == approx({"experts": 19.0, "sparse_core": 19.5,
                         "indexer_kl": 6.0, "indexer": 10.0,
                         "dispatch": 4.0, "unscoped": 2.0,
                         "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)
    assert 1e6 * rows["experts"].backward_s == approx(9.0)
    assert 1e6 * rows["indexer"].backward_s == approx(10.0)
    # what the metric files ask of the table
    def ms(names):
        names = frozenset(names)
        return table.ms_per_step(lambda b: bool(b) and b <= names)
    assert ms(("indexer", "select", "indexer_kl")) == approx(0.016)
    assert ms(("sparse_core",)) == approx(0.0195)
    assert ms(("router", "dispatch", "combine")) == approx(0.004)
    assert ms(("experts",)) == approx(0.019)
    # the six-scope reader is untouched by its second copy
    from benchmarks.harness import scopes
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    assert scopes.block_table(data, text, say=said.append) is not None
    # a step without any of these scopes: one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        assert subscopes.reader.block_table(data, f.read(),
                                            say=said.append) is None
    assert "carries any of the scopes indexer" in said[-1]

"""The cell ``phi4flash-depth6-s8192`` on the CPU: it rehearses end to
end with and without a trace and reaches ``correct``, each of its checks comes out wrong with the reference
computed through ``float8_e4m3fn`` (the control behind its limits) or with
a planted fault, its family's counts
are what hand arithmetic gives for the published widths, its
configuration keeps them and agrees with the catalog row's numbers key by
key, what ``BENCHMARK.json`` gained for it is appended and nothing else
(and the pinned test it trips runs here whole on the benchmark less this
PR's entries), and the reader of ``harness/sambay_scopes.py`` joins a
hand-made pair as ``test_scopes.py`` has ``scopes.py`` do.
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
from benchmarks.families import sambay as family              # noqa: E402
from benchmarks.harness import (cells, report,                # noqa: E402
                                sambay_scopes, ssm_scopes)

CELL = "phi4flash-depth6-s8192"
CONFIG = "phi-4-mini-flash-reasoning"
CONFIG_FILE = "benchmarks/configs/phi-4-mini-flash-reasoning.json"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
HERE = os.path.dirname(os.path.abspath(__file__))
NEW_METRICS = (
    "sy_s6_scan_ms_per_step", "sy_ssm_conv_proj_ms_per_step",
    "sy_gmu_ms_per_step", "sy_swa_core_ms_per_step",
    "sy_full_core_ms_per_step", "sy_diff_combine_ms_per_step",
    "sy_recompute_ms_per_step", "sy_s6_scan_roofline",
    "sy_swa_core_roofline", "sy_full_core_roofline")
# the catalog row of the model-configs guide (architectures.jsonl,
# Phi-4-mini-flash-reasoning), its ``config`` as it stands
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


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
    options = report.RunOptions(seed=3_000_000_017, seconds=0.5, trace=trace,
                                rehearse=True)
    obj = bench_run.run_cell(cell, options)
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert last.startswith(bench_run.REHEARSAL_PREFIX)
    assert json.loads(last[len(bench_run.REHEARSAL_PREFIX):]) == obj
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] >= 2
    for letter in "abcdef":
        assert f"ok: ({letter})" in out, letter
    assert "WRONG" not in out
    assert "layers mamba swa mamba_memory full_kv gmu cross" in out
    assert "the step recomputes the layers the file names ([0, 1, 2, 4]: " \
        "by kind {'mamba': 1, 'swa': 1, 'mamba_memory': 1, 'full_kv': 0, " \
        "'gmu': 1, 'cross': 0})" in out
    # the line of counters says them all
    counters = next(line for line in out.splitlines()
                    if line.startswith("counters: "))
    for name in ("s6_scan_chunks {'0': ", "'2': ",
                 "s6_scan_state_bytes", "yoco_shared_kv_bytes 131072",
                 "gmu_memory_bytes 262144", "recompute_layers {'mamba': 1",
                 "flash_tiles {'square'"):
        assert name in counters, name
    # two heads of 64 a call fill a lane group: the packed kernels walk
    # the toy band (one tile of 128 a head and call)
    assert "3 calls x 2 heads x the band's 1 tiles of 128 x 128 = 6" in out
    if trace:
        # a CPU has no device plane: the device metrics are left out
        assert obj["metrics"]["compiles_in_window"]["value"] == 0.0
        assert not set(NEW_METRICS) & set(obj["metrics"])
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
    from benchmarks.drivers import train_sambay_lm as driver
    from benchmarks.harness import traffic
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
        cell = cells.load_cell(CELL, ROOT)
        config, mix = (cells.sized(x, True)
                       for x in (cell.config, cell.traffic))
        runner = driver.build_runner(config, 7, jax.devices()[:1])
        ring = traffic.token_batches(mix, config["vocab_size"], 7)
        for batch in ring[:2]:
            runner.train_step(*batch)
        yield driver, runner, config, mix, ring


def _run_checks(rehearsed, fam, config=None, step=3):
    from benchmarks.drivers import train_granite_lm
    driver, runner, toy, mix, ring = rehearsed
    said = []
    checks = {}
    for letter, run in (
            ("a", lambda c: train_granite_lm.check_logits(
                c, runner, fam, toy, mix["seq_len"], 7)),
            ("b", lambda c: driver.check_scan(c, fam, toy, mix["seq_len"],
                                              7)),
            ("c", lambda c: driver.check_attention(c, fam, toy,
                                                   mix["seq_len"], 7)),
            ("d", lambda c: driver.check_step(c, runner, fam, config or toy,
                                              ring[0], step))):
        checks[letter] = driver.Checks(said.append)
        run(checks[letter])
    return checks, said


def test_every_limit_refuses_the_reference_through_float8(rehearsed):
    """The limits of (a)-(d) lie between the program's readings and what
    the same reference reads when it is computed in the nearest precision
    below the configuration's bf16: every weight it reads, a scan's x, B
    and C and attention's q, k and v rounded through ``float8_e4m3fn``.
    Each check alone makes such a run not ``correct``; through bfloat16,
    which the program's weights and those inputs are in already, every
    check passes as it does in the rehearsal."""
    import jax.numpy as jnp
    driver = rehearsed[0]
    fine, said = _run_checks(rehearsed, family.rounded_through(jnp.bfloat16))
    assert not [w for c in fine.values() for w in c.failed], said
    coarse, said = _run_checks(
        rehearsed, family.rounded_through(jnp.float8_e4m3fn), step=4)
    for letter in "abc":
        assert coarse[letter].failed, (letter, said)
    # (b), (c): y or out and every gradient that the rounded inputs reach
    assert len(coarse["b"].failed) >= 5 and len(coarse["c"].failed) >= 3
    # (d): the gradients, by the limit they have; the update is arithmetic
    # on the step's own gradient, which the reference's precision leaves
    wrong, = coarse["d"].failed
    assert "agree with jax.grad of the float32 reference" in wrong
    assert f"< {driver.GRADS_RTOL}" in wrong


def test_a_step_at_another_learning_rate_is_refused(rehearsed):
    """The planted fault for (d)'s second half: the configuration says
    twice the learning rate the optimizer was built with, so every leaf
    moved half as far as AdamW would have it.  The gradients still
    agree."""
    driver, _, toy, *_ = rehearsed
    lr = toy["optimizer"]["learning_rate"]
    twice = {**toy, "optimizer": {**toy["optimizer"],
                                  "learning_rate": 2 * lr}}
    checks, said = _run_checks(rehearsed, family, config=twice, step=5)
    wrong, = checks["d"].failed
    assert "change of their float32 weights" in wrong
    assert f"< {driver.UPDATE_RTOL}" in wrong
    # half the way: 0.5 of the reference's change on every leaf
    assert wrong.count(" 5.0e-01") >= 30
    assert not [w for k in "abc" for w in checks[k].failed], said


def test_the_familys_adamw_is_the_papers():
    """Two steps by hand on one weight: m, v, their corrections, the
    decoupled decay."""
    import numpy as np
    w, g = np.float32([0.5]), np.float32([0.2])
    zero = np.zeros(1, np.float32)
    lr = 0.1
    one = family.reference_adamw(w, zero, zero, g, 1, lr)
    # m^ = g, v^ = g^2: a whole learning rate against the sign
    assert one == approx(0.5 - lr * (0.2 / (0.2 + 1e-8) + 0.01 * 0.5))
    m, v = np.float32([0.1 * 0.2]), np.float32([0.001 * 0.04])
    g2 = np.float32([-0.1])
    m2, v2 = 0.9 * m + 0.1 * g2, 0.999 * v + 0.001 * g2 * g2
    two = family.reference_adamw(one.astype(np.float32), m, v, g2, 2, lr)
    assert two == approx(one - lr * (
        (m2 / (1 - 0.9 ** 2)) / (np.sqrt(v2 / (1 - 0.999 ** 2)) + 1e-8)
        + 0.01 * one))
    assert family.ADAMW == {"beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
                            "weight_decay": 0.01}


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
            "device_idle_share", "step_hbm_gb", "step_launch_ms_per_step",
            "step_compile_s"} <= names
    # no other cell's own metrics: the sub-scopes' readers list their cells
    assert not {n for n in names
                if n.startswith(("dsa_", "moe_", "ssm_", "nh_", "gqa_"))}
    assert "flash_roofline" not in names
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "mfu", "peak_hbm_gb", "setup_s"}
    bench = cells.load_benchmark(ROOT)
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        theirs = {m["name"] for m in cells.load_cell(other, ROOT).per_layer}
        assert not theirs & set(NEW_METRICS), other
    assert len(bench["workloads"]) == len(
        {(w["config"], w["traffic"]) for w in bench["workloads"]})
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name] == {
            "name": name, "source": "device_trace", "moves": "tokens_per_s",
            "workloads": [CELL],
            "unit": "%" if name.endswith("_roofline") else "ms",
            "better": "higher" if name.endswith("_roofline") else "lower",
            "layer": "kernels" if name.endswith("_roofline") else "model"}
    for m in cell.per_layer:
        assert callable(report.load_reader(ROOT, m["name"]))
    for folder, key in (("drivers", "driver"), ("families", "family")):
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", folder, cell.config[key] + ".py"))
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert "1:1:1:1:1:1" in entry["why"] and "8:8:1:1:7:7" in entry["why"]
    assert len(entry["why"]) <= 200
    # no trace, no table: every reader returns None and raises not, as on
    # a program that has none of these scopes (the parent commit)
    obs = {"trace": None, "chips": 1, "config": cell.config, "family": None,
           "counters": {"before": {}, "after": {}}}
    for name in NEW_METRICS:
        assert report.load_reader(ROOT, name)(obs) is None


def test_the_benchmark_gained_entries_at_the_end_and_kept_the_rest(
        monkeypatch):
    """``test_host_half.py`` holds the benchmark less PR 36's eight to (5,
    6, 4, 39) with nothing after those eight, and the metric directory's
    listing to what is declared, so it is expected to fail since this
    cell was appended (``tests/conftest.py`` says why it may not be
    edited here; the chain of such pins is now three deep, ROADMAP D16).
    Nothing it holds is let go meanwhile: it fails as it stands, and its
    whole body, the two older pins inside it, runs here on
    ``BENCHMARK.json`` less this PR's entries, with the metric
    directory's listing less this PR's files.  Of this PR's entries it
    holds only that they came after what was there, in order: no size of
    the benchmark and no last place, so that the next PR appends without
    a fourth stand-in."""
    bench = cells.load_benchmark(ROOT)
    mine = {"configs": (CONFIG,), "workloads": (CELL,),
            "per_layer": NEW_METRICS}
    had = dict(bench)
    for key, names in mine.items():
        had[key] = [e for e in bench[key] if e["name"] not in names]
        # present, in this order, after everything that was there before
        at = [[e["name"] for e in bench[key]].index(n) for n in names]
        assert at == sorted(at) and at[0] >= len(had[key]), key
    # no entry that was there names the new cell or configuration: no
    # ``workloads`` list that was there was extended
    was = json.dumps(had)
    assert CELL not in was and CONFIG not in was
    assert not [n for n in NEW_METRICS if n in was]

    spec = importlib.util.spec_from_file_location(
        "the_host_halfs_tests", os.path.join(HERE, "test_host_half.py"))
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    pinned = (theirs.
              test_the_benchmark_gained_eight_metrics_at_its_end_and_kept_the_rest)
    with pytest.raises(AssertionError):     # entries after PR 36's eight
        pinned(monkeypatch)
    metrics_dir = os.path.join(ROOT, "benchmarks", "layer_metrics")
    listdir = os.listdir

    def less_mine(path):
        names = listdir(path)
        if os.path.abspath(path) == metrics_dir:
            names = [n for n in names if n[:-3] not in NEW_METRICS]
        return names

    monkeypatch.setattr(os, "listdir", less_mine)
    monkeypatch.setattr(cells, "load_benchmark", lambda root=ROOT: had)
    pinned(monkeypatch)


# --------------------------------------------------------------------------
# the configuration and the family's counts, by hand
# --------------------------------------------------------------------------
def test_the_configuration_keeps_every_published_width(config):
    reduced = {"num_hidden_layers": 6, "vocab_size": 25008}
    assert config["reduced"] == list(reduced)
    for key, value in CATALOG.items():
        if key in reduced:
            assert config[key] == reduced[key], key
            assert config["published"][key] == value, key
        else:
            assert config[key] == value and type(config[key]) is type(value), \
                key
    assert set(config["changed"]) >= set(reduced)
    assert 25008 * 8 == 200064
    assert config["layers"] == {"n_self": 2, "n_cross": 2}
    assert config["published"]["layers"] == {"n_self": 16, "n_cross": 14}
    assert 16 + 2 + 14 == 32 and 2 + 2 + 2 == config["num_hidden_layers"]
    assert family.kinds(config) == ("mamba", "swa", "mamba_memory",
                                    "full_kv", "gmu", "cross")
    # the assumed sizes, each with its reason
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
                16, 4, 2, 2560 // 16)
    for key in ("no positions", "layout", "Mamba-1 sizes", "Mamba-1 start",
                "differential attention", "lambda0's l", "gated memory unit",
                "initialisation", "optimizer"):
        assert config["assumed"][key], key
    assert "1 : 1 : 1 : 1 : 1 : 1" in config["changed"]["num_hidden_layers"]
    assert "8 : 8 : 1 : 1 : 7 : 7" in config["changed"]["num_hidden_layers"]
    assert "0-5" in config["assumed"]["lambda0's l"]
    for key in ("the mix", "lambda0", "why six and not eight", "a layer here"):
        assert config["notes"][key], key
    # the fewest layers after which the compiled step has a tenth of the
    # limit to spare (tests/test_chip_compile.py compiles it): the K/V
    # producer and the cross layer keep their activations
    assert config["recompute"] == [0, 1, 2, 4]
    assert config["mesh"] == {} and config["initializer_range"] == 0.02
    assert config["optimizer"] == {"name": "AdamW", "learning_rate": 1e-4}
    assert config["step_bytes_limit"] == 15_600_000_000
    for key in ("changed", "assumed", "deployment", "notes"):
        assert config[key], key
    # how the scan walks the sequence is no key of the file: ops/ssm.py
    # takes the chunk from the sequence's length
    assert not [k for k in (*config, *config["assumed"],
                            *config["rehearsal"]) if "chunk" in k]
    assert "8 chips a stage" in config["deployment"]
    assert "8 ways by rows" in config["deployment"]
    assert "26 layers left out lie on further stages" in config["deployment"]
    # the toy size keeps the kinds and fills a lane group a call
    toy = cells.sized(config, True)
    assert family.kinds(toy) == family.kinds(config)
    assert toy["hidden_size"] // toy["num_attention_heads"] == 64
    entry, = [c for c in cells.load_benchmark(ROOT)["configs"]
              if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == CONFIG_FILE


def test_the_familys_counts_are_hand_arithmetic(config):
    w = family.layer_weights(config)
    mamba = (2560 * 10240 + 5120 * 4 + 5120 * 192 + 160 * 5120
             + 5120 * 2560)
    assert w == {"mamba": mamba, "mamba_memory": mamba,
                 "swa": 2560 * 5120 + 2560 * 2560,
                 "full_kv": 2560 * 5120 + 2560 * 2560,
                 "cross": 2 * 2560 * 2560, "gmu": 2 * 2560 * 5120,
                 "mlp": 2560 * 20480 + 10240 * 2560}
    per = family.layer_params(config)
    # the issue's arithmetic, a kind of layer
    assert per == {"mamba": 119_895_040, "mamba_memory": 119_895_040,
                   "swa": 98_322_304, "full_kv": 98_322_304,
                   "gmu": 104_867_840, "cross": 91_766_144}
    assert sum(per.values()) == 633_068_672
    assert family.param_count(config) == 633_068_672 + 25008 * 2560 + 5120 \
        == 697_094_272
    # the band: sum of min(t + 1, 512); the triangle; 12 % of it
    band, triangle = family.band_pairs(8192, 512), family.band_pairs(8192)
    assert band == sum(min(t + 1, 512) for t in range(8192)) == 4_063_488
    assert triangle == 8192 * 8193 // 2 == 33_558_528
    assert band / triangle == approx(0.121, abs=1e-3)
    assert family.band_pairs(256, 512) == family.band_pairs(256)
    # a (query, key) pair of a layer: 20 head pairs x 2 maps x (q . k over
    # 64 and a V of 128), once forward and twice backward; a streaming
    # kernel's seven products count the scores again
    assert family.attention_flops_per_pair(config, 3) == \
        20 * 2 * 3 * (2 * 64 + 2 * 128) == 46_080
    assert family.attention_flops_per_pair(config, 7) == \
        20 * 2 * ((128 + 256) + (3 * 128 + 2 * 256)) == 51_200
    scan = 7 * 5120 * 16
    assert family.scan_flops_per_token(config) == scan == 573_440
    weights = 2 * mamba + 2 * w["swa"] + w["cross"] + w["gmu"] \
        + 6 * w["mlp"] + 25008 * 2560
    assert family.flops_per_token(config, 8192) == approx(
        6 * weights + (band + 2 * triangle) / 8192 * 46_080 + 2 * 3 * scan,
        rel=1e-12)
    # 4.58 GFLOP a token, 3.76e13 a step: 191 ms at the chip's peak
    assert family.flops_per_token(config, 8192) == approx(4.585e9, rel=1e-3)
    cost = family.scan_cost(config, 1, 8192)
    assert cost["flops"] == 2 * 8192 * 3 * scan
    forward = 2 * 5120 + 2 * 2 * 16 + 4 * 5120 + 2 * 5120
    backward = 2 * 2 * 5120 + 64 + 4 * 5120 + 2 * 5120 + 64 + 4 * 5120
    assert cost["bytes"] == 2 * 8192 * (forward + backward)
    peaks = cells.load_peaks("TPU v5 lite", ROOT)
    least, bound = cells.least_seconds(cost["flops"], cost["bytes"], peaks)
    assert bound == "bytes" and 1e3 * least == approx(2.256, rel=1e-3)
    window = family.window_cost(config, 1, 8192)
    assert window["flops"] == band * 51_200
    row = 2 * (2560 + 2 * 1280)
    assert window["bytes"] == 8192 * ((row + 5120) + (row + 10240) + row)
    least, bound = cells.least_seconds(window["flops"], window["bytes"],
                                       peaks)
    # 0.208 TFLOP in the band: 1.06 ms at the peak
    assert bound == "operations" and 1e3 * least == approx(1.056, rel=1e-3)
    # the K/V producer and the cross layer: the whole triangle, twice
    full = family.full_cost(config, 1, 8192)
    assert full["flops"] == 2 * triangle * 51_200
    assert full["bytes"] == 2 * window["bytes"]
    least, bound = cells.least_seconds(full["flops"], full["bytes"], peaks)
    # 3.44 TFLOP: 17.4 ms at the peak
    assert bound == "operations" and 1e3 * least == approx(17.44, rel=1e-3)


# --------------------------------------------------------------------------
# the reader on a hand-made pair
# --------------------------------------------------------------------------
def test_hand_made_table_by_sub_scope():
    """``hand_made_scoped.xspace.txt`` (test_scopes.py has its times) beside
    ``hand_made_sambay_scoped.step.txt``, the same step with this family's
    sub-scopes in its ``op_name``s.  Microseconds a step, device 0 first |
    second run, device 1 the same but for the kernel (18 | 18):

        fusion.1      10 | 10   s6_scan and, by one member, gmu: mixed
        fusion.3      10 |  8   full_core, recomputed in the backward pass;
                                the optimizer's part has no sub-scope
        flash_fwd.2   20 | 22   swa_core, recomputed: 21 and 18, 19.5
        fusion.4       6 |  6   diff_combine
        all-reduce.6  10 | 10   attn_proj, backward
        copy.8         4 |  4   ssm_conv
        fusion.5       2 |  2   unscoped
        fusion.7       1 |  1   not found

    61.5 busy a step.  The mixed row counts for neither the scan's metric
    nor the gated memory unit's; every new metric reads its rows."""
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        data = ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))
    with open(os.path.join(TESTDATA,
                           "hand_made_sambay_scoped.step.txt")) as f:
        text = f.read()
    said = []
    table = sambay_scopes.reader.block_table(data, text, say=said.append)
    assert table is not None, said
    rows = {r.name: r for r in table.rows}
    us = {name: 1e6 * r.seconds for name, r in rows.items()}
    assert us == approx({"s6_scan+gmu": 10.0, "full_core": 9.0,
                         "swa_core": 19.5, "diff_combine": 6.0,
                         "attn_proj": 10.0, "ssm_conv": 4.0, "unscoped": 2.0,
                         "not found": 1.0})
    assert 1e6 * table.busy_s == approx(61.5)

    # every new metric through its own file, on this table and (the
    # recomputation's) on the second reader of ssm_scopes.py
    class Family:
        scan_cost = staticmethod(lambda c, b, s: {"flops": 0.0,
                                                  "bytes": 819e9 * 1e-6})
        window_cost = staticmethod(lambda c, b, s: {"flops": 197e12 * 3.9e-6,
                                                    "bytes": 0.0})
        full_cost = staticmethod(lambda c, b, s: {"flops": 197e12 * 1.8e-6,
                                                  "bytes": 0.0})

    again = ssm_scopes.readers["recompute_scopes"].block_table(
        data, text, say=said.append)
    obs = {"trace": object(), "chips": 1, "config": {}, "family": Family,
           "traffic": {"batch": 1, "seq_len": 8},
           "peaks": cells.load_peaks("TPU v5 lite", ROOT),
           sambay_scopes.TABLE: {"trace": True, "scopes": table},
           "recompute_scopes": {"trace": True, "scopes": again}}
    read = {name: report.load_reader(ROOT, name)(obs) for name in NEW_METRICS}
    assert read == approx({
        "sy_s6_scan_ms_per_step": 0.0,            # the mixed row is no one's
        "sy_ssm_conv_proj_ms_per_step": 0.004,
        "sy_gmu_ms_per_step": 0.0,
        "sy_swa_core_ms_per_step": 0.0195,
        "sy_full_core_ms_per_step": 0.009,
        "sy_diff_combine_ms_per_step": 0.006,
        "sy_recompute_ms_per_step": 0.0195,       # the kernel, run again
        "sy_s6_scan_roofline": None,              # no time, no share
        "sy_swa_core_roofline": 100 * 3.9 / 19.5,
        "sy_full_core_roofline": 100 * 1.8 / 9.0})
    # a table whose scan has a row of its own: 1 us of bytes over 10 us
    alone = sambay_scopes.reader.block_table(
        data, text.replace("jit(step)/jvp(attn)/gmu/add",
                           "jit(step)/jvp(attn)/s6_scan/add"),
        say=said.append)
    obs[sambay_scopes.TABLE] = {"trace": True, "scopes": alone}
    assert report.load_reader(ROOT, "sy_s6_scan_ms_per_step")(obs) == \
        approx(0.010)
    assert report.load_reader(ROOT, "sy_s6_scan_roofline")(obs) == \
        approx(10.0)
    # the readers before it are untouched by this copy
    from benchmarks.harness import hybrid_moe_scopes, scopes, subscopes
    assert scopes.SCOPES == ("embed", "attn", "mlp", "head", "loss",
                             "optimizer")
    assert "s6_scan" not in subscopes.reader.SCOPES
    assert "s6_scan" not in hybrid_moe_scopes.reader.SCOPES
    assert sambay_scopes.reader.SCOPES == sambay_scopes.SUBSCOPES
    assert len(sambay_scopes.SUBSCOPES) == 9
    # a step without any of these scopes (the parent's): one line, and None
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        plain = f.read()
    assert sambay_scopes.reader.block_table(
        data, plain, say=said.append) is None
    assert "carries any of the scopes ssm_proj" in said[-1]

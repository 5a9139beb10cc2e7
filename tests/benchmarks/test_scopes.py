"""The program's six named scopes and ``benchmarks/harness/scopes.py``,
which joins them to a device trace, on the CPU.

``hand_made_scoped.xspace.txt`` and ``hand_made_scoped.step.txt`` are
written by hand so that every row can be worked out on paper;
``toy_gpt_scoped_1chip.xplane.pb.gz`` was recorded on a TPU v5e and
holds its program's module, ``.step.txt.gz`` is what
``compiled.as_text()`` gave beside it.
"""

import gzip
import os
import re
import sys

import pytest
from pytest import approx

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, report, scopes, spans  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
WORKLOADS = [w["name"] for w in cells.load_benchmark(ROOT)["workloads"]]
NEW_METRICS = ("attention_ms_per_step", "mlp_ms_per_step",
               "lmhead_loss_ms_per_step", "optimizer_ms_per_step",
               "unscoped_ms_per_step")


# --------------------------------------------------------------------------
# the scopes in the program
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def toy_step_names():
    """Every name the lowered train step of a toy GPT gives an operation."""
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.distributed import collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    paddle.seed(7)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=16, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=net.parameters(),
                          multi_precision=True)
    amp.decorate(net, opt, level="O2", dtype="bfloat16")
    mesh = collective.build_mesh({}, devices=jax.devices()[:1])
    prev = collective.get_mesh()
    try:
        runner = DistributedRunner(net, opt, GPTPretrainingCriterion(),
                                   mesh=mesh)
        ids = np.random.default_rng(0).integers(0, 128, (2, 16),
                                                dtype=np.int64)
        text = runner.lower_step([ids], [ids]).as_text(debug_info=True)
    finally:
        collective.set_mesh(prev)
    return set(re.findall(r'loc\("([^"]*)"', text))


@pytest.mark.parametrize("scope,forms", [
    ("embed", ("jvp({})", "transpose(jvp({}))")),
    ("attn", ("jvp({})", "transpose(jvp({}))")),
    ("mlp", ("jvp({})", "transpose(jvp({}))")),
    ("head", ("jvp({})", "transpose(jvp({}))")),
    ("loss", ("jvp({})", "transpose(jvp({}))")),
    ("optimizer", ("{}",)),         # outside the differentiated function
])
def test_the_train_step_names_its_blocks(toy_step_names, scope, forms):
    for form in forms:
        part = "/" + form.format(scope) + "/"
        assert any(part in name for name in toy_step_names), part
    # ... and the reader's pattern finds the scope in each of them
    named = [n for n in toy_step_names if scope in scopes.SCOPE.findall(n)]
    assert len(named) >= len(forms)


# --------------------------------------------------------------------------
# the hand-made pair: every number worked out on paper
# --------------------------------------------------------------------------
def _hand_made_text():
    with open(os.path.join(TESTDATA, "hand_made_scoped.step.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def hand_made_trace():
    from jax.profiler import ProfileData
    with open(os.path.join(TESTDATA, "hand_made_scoped.xspace.txt")) as f:
        return ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(f.read()))


def test_hand_made_table(hand_made_trace):
    """Microseconds.  A run of ``jit_step`` on device 0, first | second:

        fusion.1      10 | 10   mlp: a matmul, a residual add with no
                                scope, and a constant named ``embed``
        flash_fwd.2   20 | 22   attn (the kernel; 18 | 18 on device 1)
        fusion.3      10 |  8   mlp+optimizer: a weight gradient of the
                                backward pass fused with its update
        fusion.4       6 |  6   head+loss
        fusion.5       2 |  2   unscoped
        all-reduce.6  10 | 10   attn, backward: a collective is a row's
        fusion.7       1 |  1   the text has it as f32[8,16] and the
                                trace as bf16[4]: not found
        copy.8         4 |  4   embed

    63 | 63 busy on device 0, 61 | 59 on device 1: 61.5 a step.
    ``convert.9`` runs in another program between the two runs and
    counts for nothing."""
    said = []
    table = scopes.block_table(hand_made_trace, _hand_made_text(),
                               say=said.append)
    assert said == []
    assert (table.module, table.steps) == ("jit_step", 2)
    assert table.busy_s == approx(61.5e-6)
    rows = {r.name: r for r in table.rows}
    assert {n: r.seconds for n, r in rows.items()} == approx({
        "attn": (31 + 28) / 2 * 1e-6, "mlp": 10e-6, "mlp+optimizer": 9e-6,
        "head+loss": 6e-6, "embed": 4e-6, "unscoped": 2e-6,
        "not found": 1e-6})
    assert [r.name for r in table.rows][:3] == ["attn", "mlp",
                                                "mlp+optimizer"]
    # rule 3: one partition, a backward share, the longest groups
    assert sum(r.seconds for r in table.rows) == approx(table.busy_s)
    assert {n: r.backward_s for n, r in rows.items() if r.backward_s} == \
        approx({"attn": 10e-6, "mlp+optimizer": 9e-6})
    assert rows["attn"].longest(3) == [
        ("flash_fwd custom-call -> (bf16[4,256,256], f32[4,256,256])",
         approx(19.5e-6)),
        ("all-reduce -> f32[8,16]", approx(10e-6))]
    assert rows["mlp+optimizer"].blocks == {"mlp", "optimizer"}
    assert rows["unscoped"].blocks == rows["not found"].blocks == set()
    lines = table.lines()
    assert len(lines) == 1 + len(table.rows)
    assert "2 runs of jit_step" in lines[0]
    assert "ms busy a step; 0.001 ms of it not found" in lines[0]
    # the first device alone, as a one-chip cell reads a four-chip host
    one = scopes.block_table(hand_made_trace, _hand_made_text(), chips=1)
    assert one.busy_s == approx(63e-6)
    assert one.ms_per_step(lambda blocks: blocks == {"attn"}) == approx(31e-3)


def test_nested_events_give_their_time_to_the_innermost():
    """A loop from 0 to 10 around two bodies, then an event of its own."""
    assert sorted(scopes.exclusive([
        (0.0, 10.0, "while"), (1.0, 4.0, "body.1"), (4.0, 9.0, "body.2"),
        (5.0, 6.0, "inner"), (12.0, 13.0, "after")])) == [
            ("after", 1.0), ("body.1", 3.0), ("body.2", 4.0),
            ("inner", 1.0), ("while", 2.0)]


@pytest.mark.parametrize("spoil,says", [
    # an executable from before the scopes: the same text with none
    (lambda t: re.sub(r"\((%s)\)|/optimizer/" % "|".join(scopes.SCOPES[:5]),
                      lambda m: "()" if m.group(1) else "/", t),
     "no instruction of the program's text carries any of the scopes"),
    # another executable ran: the kernel has another result there, and
    # 20.5 of 61.5 microseconds are not found
    (lambda t: t.replace("%flash_fwd.2 = (bf16[4,256,256]",
                         "%flash_fwd.2 = (bf16[8,256,256]"),
     "another executable ran"),
    # a run of another program only
    (lambda t: t.replace("HloModule jit_step", "HloModule jit_other"),
     "the trace holds no run of jit_other"),
    # no text at all
    (lambda t: None, "no device time by block: "),
])
def test_refusals_say_one_line_and_return_none(hand_made_trace, spoil, says):
    said = []
    assert scopes.block_table(hand_made_trace, spoil(_hand_made_text()),
                              say=said.append) is None
    assert len(said) == 1 and says in said[0]


# --------------------------------------------------------------------------
# the pair recorded on the chip
# --------------------------------------------------------------------------
def _recorded_toy(folder):
    path = folder / "toy.xplane.pb"
    path.parent.mkdir(parents=True, exist_ok=True)
    with gzip.open(os.path.join(
            TESTDATA, "toy_gpt_scoped_1chip.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def test_recorded_one_chip_toy(tmp_path):
    """Two steps of a 2-layer toy GPT (b4 x s256, 4 heads of 64) on one
    v5e chip with the scopes in it (PR 25)."""
    path = _recorded_toy(tmp_path)
    said = []
    table = scopes.read(path, chips=1, say=said.append)
    assert said == [] and table.steps == 2
    assert table.not_found_s == 0.0
    assert sum(r.seconds for r in table.rows) == approx(table.busy_s)
    rows = {r.name: r for r in table.rows}
    assert set(rows) >= {"attn", "mlp", "optimizer", "unscoped"}
    # the 6 Mosaic kernels of a step are attention's, two of three in
    # the backward pass
    kernels = [g for g, _ in rows["attn"].longest(99) if "custom-call" in g]
    assert len(kernels) == 3
    assert 0.0 < rows["attn"].backward_s < rows["attn"].seconds
    # the text compiled.as_text() gave for the step beside the run names
    # every instruction as the trace's own module does, though it spells
    # asynchronous slices otherwise (rule 1)
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(
            TESTDATA, "toy_gpt_scoped_1chip.step.txt.gz"), "rt") as f:
        beside = scopes.block_table(ProfileData.from_file(path), f.read(),
                                    chips=1, say=said.append)
    assert said == [] and beside.lines() == table.lines()
    # the same trace reduces through trace_reduce to one busy time
    from benchmarks.harness import trace_reduce
    summary = trace_reduce.reduce(path, chips=1)
    programs = table.busy_s * table.steps
    assert programs <= summary.busy_s
    assert programs == approx(summary.busy_s, rel=0.02)


def test_a_metric_reads_the_newest_trace_of_its_checkout_once(tmp_path,
                                                               capsys):
    metric = str(tmp_path / "benchmarks" / "layer_metrics" / "x.py")
    obs = {"trace": object(), "chips": 1}
    # no trace on disk: nothing to read, said by nobody
    assert scopes.ms_per_step(obs, metric, lambda blocks: True) is None
    assert capsys.readouterr().out == ""
    _recorded_toy(tmp_path / ".bench_traces" / "some-cell" / "plugins"
                  / "profile" / "2026_09_28")
    obs = {"trace": object(), "chips": 1}
    everything = scopes.ms_per_step(obs, metric, lambda blocks: True)
    assert everything == approx(1e3 * obs["scopes"].busy_s)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("device time by block: 2 runs of jit_step")
    assert len(out) == 2 + len(obs["scopes"].rows)
    # the second metric of the run reads the table the first one left
    assert 0.0 < scopes.ms_per_step(
        obs, metric, lambda blocks: blocks == {"attn"}) < everything
    assert capsys.readouterr().out == ""
    # a run without a trace, or with one of no device (a CPU)
    assert scopes.ms_per_step({"trace": None}, metric, bool) is None


# --------------------------------------------------------------------------
# the five metrics
# --------------------------------------------------------------------------
def _table(**rows):
    return scopes.BlockTable("jit_step", 1, sum(rows.values()), [
        scopes.Row(name.replace("_", "+"), frozenset(
            () if name in ("unscoped", "not found")
            else name.split("_")), seconds)
        for name, seconds in rows.items()])


def test_each_row_is_counted_by_one_metric_at_most():
    table = _table(attn=5e-3, embed_attn=1e-3, attn_mlp=2e-3,
                   attn_optimizer=3e-3, mlp=7e-3, mlp_optimizer=4e-3,
                   head=1e-3, loss=2e-3, head_loss=3e-3, optimizer=0.5e-3,
                   unscoped=6e-3, embed=1e-3, embed_loss=0.25e-3)
    table.rows.append(scopes.Row(scopes.NOT_FOUND, frozenset(), 0.125e-3))
    got = {m: report.load_reader(ROOT, m)({"trace": object(),
                                           "scopes": table})
           for m in NEW_METRICS}
    assert got == approx({
        "attention_ms_per_step": 8.0, "mlp_ms_per_step": 7.0,
        "lmhead_loss_ms_per_step": 6.0, "optimizer_ms_per_step": 7.5,
        "unscoped_ms_per_step": 6.0})
    # embed, embed+loss and what was not found are in the table only
    assert sum(got.values()) + 1.0 + 0.25 + 0.125 == approx(
        1e3 * sum(r.seconds for r in table.rows))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_declares_the_five_and_reads_nothing_without_a_table(
        workload):
    cell = cells.load_cell(workload, ROOT)
    declared = {m["name"]: m for m in cell.per_layer}
    assert set(NEW_METRICS) <= set(declared)
    for name in NEW_METRICS:
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "device_trace", "tokens_per_s")
        read = report.load_reader(ROOT, name)
        # a run without a trace; a refused join leaves None
        assert read({"trace": None}) is None
        assert read({"trace": object(), "scopes": None}) is None
    # a run without a table leaves them out and keeps the others
    obs = {"trace": None, "scopes": None,
           "counters": {"before": {"step_programs": 1, "retraces": 0},
                        "after": {"step_programs": 1, "retraces": 0}},
           "setup": {"first_step_s": 1.0, "second_step_s": 0.5},
           "compiled_step": {"step_bytes": 2e9},
           "window": {"start_s": 0.0, "end_s": 1.0},
           "spans": spans.Spans()}
    values = report.per_layer_metrics(cell, obs)
    assert not set(NEW_METRICS) & set(values)
    assert {"compiles_in_window", "first_step_s", "step_hbm_gb"} <= \
        set(values)

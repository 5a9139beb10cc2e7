"""The benchmark's harness, on the CPU: the rehearsal runs a toy cell end
to end, the measuring path refuses a CPU, ``BENCHMARK.json`` and the
data files agree, and a later PR's files are found without an edit.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run                      # noqa: E402
from benchmarks.harness import cells, report                 # noqa: E402

BENCH = cells.load_benchmark(ROOT)
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _copy_benchmark(dst):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


# --------------------------------------------------------------------------
# the rehearsal: the whole control flow at a toy size
# --------------------------------------------------------------------------
def _rehearse(workload, trace, monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    # from a copy: a traced run replaces <checkout>/.bench_traces/<cell>,
    # and the checkout's own may hold a chip's trace
    cell = cells.load_cell(workload, _copy_benchmark(tmp_path))
    options = report.RunOptions(seed=3, seconds=0.5, trace=trace,
                                rehearse=True)
    obj = bench_run.run_cell(cell, options)
    lines = capsys.readouterr().out.strip().splitlines()
    return cell, obj, lines


@pytest.mark.parametrize("workload,trace", [
    ("gpt2m-pretrain-s1024", False),     # one device, kernels interpreted
    ("gpt2m-short-s128", True),          # one device, composed attention
    ("gpt3xl-dp2mp2-s2048", False),      # dp2 x mp2 on four virtual devices
    ("gpt3xl-dp2mp2-s2048", True),
])
def test_rehearsal_runs_a_toy_cell_end_to_end(workload, trace, monkeypatch,
                                              capsys, tmp_path):
    cell, obj, lines = _rehearse(workload, trace, monkeypatch, capsys,
                                 tmp_path)
    last = lines[-1]
    # never a result line: the prefix keeps any reader from taking it for one
    assert last.startswith(bench_run.REHEARSAL_PREFIX)
    with pytest.raises(ValueError):
        json.loads(last)
    line = json.loads(last[len(bench_run.REHEARSAL_PREFIX):])
    assert line == obj
    # a traced run on a CPU has no device plane, hence no breakdown
    assert set(line) == CONTRACT_KEYS
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell.chips
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 2
    declared = {m["name"]: m["unit"] for m in
                (cell.per_layer if trace else cell.end_to_end)}
    assert line["metrics"], "a run reports something"
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == declared[name]
        assert isinstance(m["value"], float)
    if trace:
        assert line["metrics"]["compiles_in_window"]["value"] == 0.0
        assert "tokens_per_s" not in line["metrics"]
        # device metrics are read from a device trace or not at all
        assert "device_idle_share" not in line["metrics"]
        assert "busy_s" not in line["device"]
    else:
        # the rate of the whole window: every completed step's tokens over
        # the time between the first and the last sync
        steps, tokens, seconds = re.search(
            r"window: (\d+) steps of (\d+) tokens in ([0-9.]+) s",
            "\n".join(lines)).groups()
        assert int(steps) == line["attempted"] - line["failed"]
        assert line["metrics"]["tokens_per_s"]["value"] == pytest.approx(
            int(steps) * int(tokens) / float(seconds), rel=1e-5)
        assert line["metrics"]["setup_s"]["value"] > 0
        # a share of a TPU's peak and a TPU's memory: not on a CPU
        assert "mfu" not in line["metrics"]
        assert "peak_hbm_gb" not in line["metrics"]


# --------------------------------------------------------------------------
# the measuring path never falls back
# --------------------------------------------------------------------------
def _measure(cwd, extra_env, workload="gpt2m-pretrain-s1024"):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PADDLE_TPU_")}
    env.update({"JAX_PLATFORMS": "cpu"}, **extra_env)
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload,
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result_line(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


@pytest.mark.parametrize("extra_env,says", [
    ({}, "no TPU"),
    ({"PADDLE_TPU_DISABLE_PALLAS": "1"}, "PADDLE_TPU_DISABLE_PALLAS"),
    ({"PADDLE_TPU_PALLAS_INTERPRET": "1"}, "PADDLE_TPU_PALLAS_INTERPRET"),
    ({"GRAFT_BENCH_FORCE_CPU": "1"}, "GRAFT_BENCH_FORCE_CPU"),
])
def test_measuring_path_refuses_without_the_chip(extra_env, says):
    proc = _measure(ROOT, extra_env)
    _no_result_line(proc)
    assert says in proc.stderr


def test_unknown_workload_is_refused():
    proc = _measure(ROOT, {}, workload="no-such-cell")
    _no_result_line(proc)
    assert "no-such-cell" in proc.stderr


def test_benchmark_alone_in_a_directory_gives_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program."""
    proc = _measure(_copy_benchmark(tmp_path), {})
    _no_result_line(proc)


@pytest.mark.parametrize("setup_peak,held,reserved,peak", [
    # gpt2m-pretrain-s1024 as the chip's runtime read it (PERF.md section 6)
    ([5241037312], [5320310272], [5803623424], 11123933696),
    # the fullest of four devices, not the sum of the largest readings
    ([9, 2, 2, 2], [5, 6, 6, 6], [3, 1, 3, 3], 9),
    # a transient of set-up above the step sets the peak
    ([20, 2], [5, 5], [3, 3], 20),
    # a CPU reports nothing
    ([None], [None], [None], None),
])
def test_peak_is_the_fullest_device_by_the_runtime(setup_peak, held,
                                                   reserved, peak):
    from benchmarks.drivers import train_lm
    assert train_lm.fullest_device_peak(setup_peak, held, reserved) == peak


@pytest.mark.parametrize("grew,count", [
    ({}, 0),
    ({"step_programs": 1}, 1),                 # the step compiled again
    ({"step_programs": 1, "retraces": 2.0}, 3),
    ({"built": 4}, 0),     # jax's own count is said on a line, not counted
])
def test_compiles_in_window_reads_the_programs_two_counters(grew, count):
    before = {"step_programs": 2, "retraces": 5.0, "built": 70}
    after = {k: v + grew.get(k, 0) for k, v in before.items()}
    read = report.load_reader(ROOT, "compiles_in_window")
    assert read({"counters": {"before": before, "after": after}}) == count


def test_unknown_device_kind_is_an_error():
    with pytest.raises(cells.BenchmarkError, match="TPU v9"):
        cells.load_peaks("TPU v9", ROOT)
    assert cells.load_peaks("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12


# --------------------------------------------------------------------------
# BENCHMARK.json and the data files agree
# --------------------------------------------------------------------------
def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(tuple(BENCH["paths"]))
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in BENCH[key]]
    assert len(names) == len(set(names)), "a name is used once"
    assert all(cells.NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200
               for e in BENCH["configs"] + BENCH["workloads"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}, "every config has a cell"
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_names_files_that_exist(workload):
    cell = cells.load_cell(workload, ROOT)
    declared = next(c for c in BENCH["configs"]
                    if c["name"] == cell.config_name)
    assert declared["file"].startswith("benchmarks/configs/")
    assert cell.config["source"] == declared["source"]
    assert cell.config["reduced"] == declared["reduced"]
    for key in ("changed", "assumed", "reduced", "deployment", "family",
                "driver", "mesh", "rehearsal"):
        assert key in cell.config, key
    # no width is cut: what `reduced` names is no width
    for key in cell.config["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in (
            "n_embd", "n_inner", "n_head")
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "families", cell.config["family"] + ".py"))
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "drivers", cell.config["driver"] + ".py"))
    for key in ("batch", "seq_len", "ring", "tokens", "sync_every",
                "kernels", "rehearsal"):
        assert key in cell.traffic, key
    assert cell.traffic["kernels"] in ("required", "any")
    mesh = 1
    for degree in cell.config["mesh"].values():
        mesh *= degree
    assert mesh == cell.chips
    # every cell reports setup_s, another end-to-end metric and a per-layer one
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(report.load_reader(ROOT, m["name"]))


def test_every_reader_is_declared():
    declared = {m["name"] for m in BENCH["per_layer"]}
    folder = os.path.join(ROOT, "benchmarks", "layer_metrics")
    readers = {f[:-3] for f in os.listdir(folder) if f.endswith(".py")}
    assert readers == declared


def test_same_seed_same_batches():
    from benchmarks.harness import traffic
    mix = cells.sized(cells.load_cell(WORKLOADS[0], ROOT).traffic, True)
    a, b, c = (traffic.token_batches(mix, 1000, s) for s in (7, 7, 8))
    assert len(a) == mix["ring"]
    for (xa, ya), (xb, yb), (xc, _) in zip(a, b, c):
        assert xa[0].shape == (mix["batch"], mix["seq_len"])
        assert (xa[0] == xb[0]).all() and (ya[0] == yb[0]).all()
        assert (xa[0] != xc[0]).any()
        assert (ya[0][:, :-1] == xa[0][:, 1:]).all()    # shifted by one
        assert xa[0].min() >= 0 and xa[0].max() < 1000
    with pytest.raises(ValueError, match="zipf"):
        traffic.token_batches({**mix, "tokens": {"distribution": "zipf"}},
                              1000, 0)


# --------------------------------------------------------------------------
# driven by data: a later PR adds files and entries, and edits nothing
# --------------------------------------------------------------------------
def test_new_cell_config_traffic_and_metric_are_found_without_an_edit(
        tmp_path):
    root = _copy_benchmark(tmp_path)
    bench = cells.load_benchmark(root)
    # a second configuration and a second traffic mix: two data files
    config = cells.load_json(os.path.join(
        root, "benchmarks", "configs", "gpt2-medium.json"))
    config.update(n_layer=36, n_embd=1280, n_head=20, n_inner=5120)
    with open(os.path.join(root, "benchmarks", "configs",
                           "gpt2-large.json"), "w") as f:
        json.dump(config, f)
    mix = cells.load_json(os.path.join(
        root, "benchmarks", "traffic", "pretrain-b8-s1024.json"))
    mix.update(batch=8, seq_len=128)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "host-bound-b8-s128.json"), "w") as f:
        json.dump(mix, f)
    # a per-layer metric: one small reader
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "steps_in_window.py"), "w") as f:
        f.write("def read(obs):\n    return obs['window']['steps']\n")
    with open(os.path.join(root, "benchmarks", "layer_metrics",
                           "nothing_to_read.py"), "w") as f:
        f.write("def read(obs):\n    return None\n")
    # ... and one entry each
    bench["configs"].append({
        "name": "gpt2-large", "source": config["source"],
        "file": "benchmarks/configs/gpt2-large.json", "reduced": [],
        "why": "a test's"})
    bench["workloads"].append({
        "name": "gpt2l-host-bound", "config": "gpt2-large",
        "traffic": "host-bound-b8-s128", "chips": 1, "why": "a test's"})
    for name in ("steps_in_window", "nothing_to_read"):
        bench["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "step_engine",
            "moves": "tokens_per_s", "workloads": ["gpt2l-host-bound"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = cells.load_cell("gpt2l-host-bound", root)
    assert cell.config["n_embd"] == 1280 and cell.traffic["seq_len"] == 128
    names = {m["name"] for m in cell.per_layer}
    assert {"steps_in_window", "nothing_to_read"} <= names
    assert "flash_roofline" not in names        # lists its own cells
    only_new = cells.Cell(**{**cell.__dict__, "per_layer": [
        m for m in cell.per_layer if "workloads" in m]})
    got = report.per_layer_metrics(only_new, {"window": {"steps": 40}})
    assert got == {"steps_in_window": 40.0}     # None is left out
    # the cells that were there do not report the new metrics
    old = cells.load_cell(WORKLOADS[0], root)
    assert "steps_in_window" not in {m["name"] for m in old.per_layer}


def test_a_declared_metric_without_a_reader_is_an_error(tmp_path):
    root = _copy_benchmark(tmp_path)
    os.remove(os.path.join(root, "benchmarks", "layer_metrics",
                           "step_hbm_gb.py"))
    with pytest.raises(cells.BenchmarkError, match="step_hbm_gb"):
        report.load_reader(root, "step_hbm_gb")

"""Runs one cell of the benchmark once, in this process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
without a trace, its per-layer metrics with one), ``device`` and, with a
trace, ``breakdown``.  Earlier lines say what the run did.

It measures on a TPU and nowhere else.  No TPU, fewer chips than the
cell asks for, a device kind with no published peaks, or one of the
switches that take the kernels off the device set in the environment:
a non-zero exit and no result line.  It never falls back.

``--rehearse-cpu`` runs the same control flow at the toy size each data
file carries, on the CPU, with the kernels in the Pallas interpreter and
virtual devices for a four-chip cell.  It is asked for, never detected.
Its last line carries the prefix ``REHEARSAL``, so nothing can read it
as a result: no number of a rehearsal is a device number.
"""

import time

T0 = time.perf_counter()     # set-up is counted from here

import argparse              # noqa: E402
import importlib             # noqa: E402
import json                  # noqa: E402
import os                    # noqa: E402
import sys                   # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:     # a script has its own directory there
    sys.path.insert(0, ROOT)

from benchmarks.harness import cells, report   # noqa: E402  (no jax yet)

# switches of the program that would keep the kernels off the device, or
# the run off the chip, while everything still "works"
REFUSED_ENV = ("PADDLE_TPU_PALLAS_INTERPRET", "PADDLE_TPU_DISABLE_PALLAS",
               "GRAFT_BENCH_FORCE_CPU")
REHEARSAL_PREFIX = "REHEARSAL (CPU, toy size, not a result) "


def say(msg: str):
    print(msg, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on the CPU, kernels interpreted; prints "
                         "no result line")
    return ap.parse_args(argv)


def configure_environment(cell: cells.Cell, rehearse: bool):
    """Everything that must be decided before jax is imported."""
    # The persistent compile cache lives inside the checkout at a fixed
    # path (the path is part of its key), whatever the machine says:
    # two checkouts that are compared must share nothing.  The program
    # (framework/compile_cache.py) follows this variable.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        cell.root, ".jax_compile_cache")
    # ... and it evicts nothing: one step's executable is some 64 MB, and
    # a machine's limit for its own shared directory (192 MiB on the chip
    # tool's) would turn a later cell's warm runs into compiles
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else: logs in /tmp
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
        return
    bad = [k for k in REFUSED_ENV if os.environ.get(k)]
    if bad:
        sys.exit(f"benchmark: refusing to run with {', '.join(bad)} set: a "
                 "cell measures the kernels on the chip")


def check_devices(jax, cell: cells.Cell, rehearse: bool):
    devices = jax.devices()
    say(f"jax {jax.__version__}; devices: {len(devices)} x "
        f"{devices[0].device_kind} (platform {devices[0].platform})")
    if rehearse:
        say("REHEARSAL on the CPU at a toy size, kernels interpreted: not "
            "a chip run, and no number below is a device number")
    elif devices[0].platform != "tpu":
        sys.exit(f"benchmark: jax found no TPU (platform "
                 f"{devices[0].platform!r}); a cell is measured on the chip "
                 "only")
    if len(devices) < cell.chips:
        sys.exit(f"benchmark: cell {cell.name!r} needs {cell.chips} chips, "
                 f"jax reports {len(devices)}")


def run_cell(cell: cells.Cell, options: report.RunOptions) -> dict:
    """Runs the cell in this process, whose jax is already set up for it,
    prints the last line and returns its object."""
    driver = importlib.import_module(
        f"benchmarks.drivers.{cell.config['driver']}")
    record = driver.run(cell, options, say)
    setup_s = record.window_start_s - T0
    say(f"set-up took {setup_s:.1f} s")
    obj = report.result(cell, options, record, setup_s)
    say((REHEARSAL_PREFIX if options.rehearse else "")
        + json.dumps(obj, allow_nan=False))
    return obj


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cells.load_cell(args.workload, ROOT)
    options = report.RunOptions(seed=args.seed, seconds=args.seconds,
                                trace=bool(args.trace),
                                rehearse=args.rehearse_cpu)
    configure_environment(cell, options.rehearse)

    import jax
    check_devices(jax, cell, options.rehearse)
    from paddle_tpu.framework import compile_cache
    cache = compile_cache.enable_compilation_cache()
    say(f"jax and the program imported, devices found: "
        f"{time.perf_counter() - T0:.1f} s since the process started")
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, {cell.chips} chip(s), seed {options.seed}, "
        f"{options.seconds:g} s, trace {int(options.trace)}")
    say(f"compile cache: {cache}")
    obj = run_cell(cell, options)
    return 0 if obj["correct"] or not options.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

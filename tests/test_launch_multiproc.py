"""Two-process launch integration (upstream collective tests spawn real
subprocess pods — SURVEY.md §4; VERDICT r3 next #6): launch/main.py
spawns 2 local ranks, they rendezvous through
``jax.distributed.initialize`` (CPU backend) via the paddle env
contract, run one cross-process collective, and the watchdog tears the
pod down cleanly with workerlog.N files in place."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.dist

WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu.distributed import init_parallel_env
    from paddle_tpu.distributed.parallel import ParallelEnv

    env = init_parallel_env()          # jax.distributed.initialize
    assert jax.process_count() == 2, jax.process_count()
    rank = env.rank

    # one real cross-process collective: global sum over a mesh that
    # spans both processes
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("x",))
    local = jax.device_put(np.array([float(rank + 1)], np.float32),
                           jax.local_devices()[0])
    arr = jax.make_array_from_single_device_arrays(
        (2,), NamedSharding(mesh, P("x")), [local])
    total = jax.jit(jnp.sum,
                    out_shardings=NamedSharding(mesh, P()))(arr)
    val = float(total)
    assert val == 3.0, val

    # object collectives over the control plane (upstream *_object_*
    # forms): broadcast a config dict, allgather per-rank payloads
    from paddle_tpu.distributed import (broadcast_object_list,
                                        all_gather_object)
    cfg = [{"lr": 0.1, "name": "from-rank0"}] if rank == 0 else [None]
    broadcast_object_list(cfg, src=0)
    assert cfg[0]["name"] == "from-rank0", cfg

    objs = []
    all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    assert [o["rank"] for o in objs] == [0, 1], objs
    assert objs[1]["tag"] == "xx"
    print(f"RANK-{rank}-COLLECTIVE-OK sum={val} objs={len(objs)}",
          flush=True)
""")


def test_launch_two_ranks_rendezvous_and_collective(tmp_path):
    from conftest import require_cpu_multiprocess
    require_cpu_multiprocess()
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    log_dir = tmp_path / "log"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    # the workers must see exactly ONE local CPU device each so the
    # global mesh is 2 devices = 2 processes
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restart", "0",
         "--log_dir", str(log_dir),
         "--job_id", "it2p", str(script)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=240)
    # --max_restart 0 (not the default 3): restarts are incidental
    # here (the watchdog test owns that path) and a healthy backend
    # rendezvous succeeds on incarnation 1; on a container whose
    # jaxlib lacks CPU multiprocess (the known drift failure) the
    # default burned 4 incarnations x 2 workers of jax imports
    # against the tier-1 wall clock before failing identically
    logs = {}
    for r in (0, 1):
        p = log_dir / f"workerlog.{r}"
        assert p.exists(), (
            f"missing workerlog.{r}; launcher stderr:\n{proc.stderr}")
        logs[r] = p.read_text()
    assert proc.returncode == 0, (
        f"launcher rc={proc.returncode}\nstderr:\n{proc.stderr}\n"
        f"workerlog.0:\n{logs[0]}\nworkerlog.1:\n{logs[1]}")
    assert "finished OK" in proc.stdout
    assert "RANK-0-COLLECTIVE-OK sum=3.0" in logs[0]
    assert "RANK-1-COLLECTIVE-OK sum=3.0" in logs[1]


def test_launch_watchdog_kills_pod_on_rank_death(tmp_path):
    """One rank exits nonzero → watchdog kills the survivor and the
    launcher reports failure (retries exhausted)."""
    script = tmp_path / "crash.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        rank = int(os.environ["PADDLE_TRAINER_ID"])
        if rank == 1:
            sys.exit(7)
        time.sleep(120)   # rank 0 would hang forever without the watchdog
    """))
    log_dir = tmp_path / "log"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restart", "0",
         "--log_dir", str(log_dir), str(script)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert (log_dir / "workerlog.0").exists()
    assert (log_dir / "workerlog.1").exists()


TRAIN_WORKER = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.distributed import init_parallel_env, collective
    from paddle_tpu.distributed.runner import DistributedRunner
    from paddle_tpu.models import (gpt_tiny, GPTForCausalLM,
                                   GPTPretrainingCriterion)

    env = init_parallel_env()
    rank = env.rank
    assert jax.process_count() == 2
    assert jax.device_count() == 2      # global view: 1 CPU dev/proc

    # global dp=2 mesh spanning both processes
    mesh = collective.build_mesh({"dp": 2})
    collective.set_mesh(mesh)
    paddle.seed(0)
    cfg = gpt_tiny()
    net = GPTForCausalLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-3,
                          parameters=net.parameters())
    runner = DistributedRunner(net, opt, GPTPretrainingCriterion(),
                               mesh=mesh)
    rng = np.random.RandomState(0)      # same data on both ranks;
    x = rng.randint(0, cfg.vocab_size, (8, 32)).astype(np.int64)
    y = np.roll(x, -1, axis=1)
    l1 = float(runner.train_step([x], [y]))
    l2 = float(runner.train_step([x], [y]))
    assert np.isfinite(l1) and np.isfinite(l2), (l1, l2)
    assert l2 < l1, (l1, l2)
    print(f"RANK-{rank}-TRAIN-OK {l1:.6f} {l2:.6f}", flush=True)
""")


def test_launch_two_process_training_step(tmp_path):
    """Multi-HOST control plane end-to-end: 2 launch-spawned processes
    rendezvous, build one global dp=2 mesh (1 local device each), and
    run a COMPILED GPT train step whose gradient all-reduce crosses
    the process boundary; losses agree bit-for-bit across ranks."""
    from conftest import require_cpu_multiprocess
    require_cpu_multiprocess()
    script = tmp_path / "train_worker.py"
    script.write_text(TRAIN_WORKER)
    log_dir = tmp_path / "log"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restart", "0",
         "--log_dir", str(log_dir),
         str(script)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=420)
    # --max_restart 0: same rationale as the rendezvous test above
    logs = {r: (log_dir / f"workerlog.{r}").read_text()
            for r in (0, 1)
            if (log_dir / f"workerlog.{r}").exists()}
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstderr:\n{proc.stderr}\n"
        + "\n".join(f"log{r}:\n{t}" for r, t in logs.items()))
    lines = {r: [l for l in t.splitlines()
                 if l.startswith(f"RANK-{r}-TRAIN-OK")]
             for r, t in logs.items()}
    assert lines[0] and lines[1], logs
    # identical program + identical global batch → identical losses
    assert lines[0][0].split()[1:] == lines[1][0].split()[1:]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_imports_initialise_no_backend():
    """One process for each chip: a parent that has touched a jax
    backend holds the chip and its children cannot have it.  The
    launcher's parent imports the package, the launch controller and
    serving; none of those may initialise a backend."""
    code = ("import paddle_tpu, paddle_tpu.distributed.launch.controller, "
            "paddle_tpu.inference.serving\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_chip_smoke_refuses_without_a_chip():
    """chip_smoke.py on a machine where jax finds no TPU: non-zero
    exit and no result line — it never falls back to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0, r.stdout + r.stderr
    assert '"ok": true' not in r.stdout + r.stderr
    assert "no TPU" in r.stderr

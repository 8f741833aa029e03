"""Elastic restart end-to-end: train on a (2, 2) mesh, checkpoint, lose
half the devices, restore + reshard onto (1, 2), continue training.
Runs in a subprocess so it can force 4 host devices without polluting the
main test process (smoke tests must see 1 device)."""
import json
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import reduced_config
    from repro.checkpoint.manager import CheckpointManager
    from repro.data.pipeline import PackedBatchIterator, SyntheticTokenSource
    from repro.ft.monitor import plan_remesh
    from repro.models import init_params
    from repro.parallel import sharding as shd
    from repro.training.optimizer import init_opt_state
    from repro.training.train_loop import TrainConfig, make_train_step

    ckpt_dir = sys.argv[1]
    cfg = reduced_config("smollm-360m")
    data = PackedBatchIterator(SyntheticTokenSource(cfg.vocab_size, seed=3),
                               batch=8, seq_len=32)
    step_fn = make_train_step(cfg, TrainConfig())

    # phase 1: big mesh (2 data x 2 model)
    from repro.runtime import make_mesh
    mesh1 = make_mesh((2, 2), ("data", "model"))
    sh1 = shd.param_shardings(cfg, mesh1)
    with mesh1:
        params = jax.device_put(init_params(cfg, jax.random.PRNGKey(0)), sh1)
        opt = init_opt_state(params)
        for _ in range(3):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            params, opt, m = step_fn(params, opt, batch)
    loss1 = float(m["loss"])
    mgr = CheckpointManager(ckpt_dir, async_save=False)
    mgr.save(3, {"params": params, "opt": opt})

    # phase 2: half the devices "fail" -> remesh (1 data x 2 model)
    plan = plan_remesh(2, model_parallel=2, pods=1)
    mesh2 = make_mesh((plan.data, plan.model), ("data", "model"))
    sh2 = {"params": shd.param_shardings(cfg, mesh2),
           "opt": {"m": shd.param_shardings(cfg, mesh2),
                   "v": shd.param_shardings(cfg, mesh2),
                   "step": NamedSharding(mesh2, P())}}
    step2, state, _ = mgr.restore(shardings=sh2)
    params2, opt2 = state["params"], state["opt"]
    with mesh2:
        for _ in range(2):
            batch = {k: jnp.asarray(v) for k, v in next(data).items()}
            params2, opt2, m2 = step_fn(params2, opt2, batch)
    data.close()
    print(json.dumps({"ok": True, "restored_step": step2,
                      "loss1": loss1, "loss2": float(m2["loss"]),
                      "devices": jax.device_count()}))
""")


def test_elastic_reshard_subprocess():
    with tempfile.TemporaryDirectory() as d:
        script = Path(d) / "elastic.py"
        script.write_text(SCRIPT)
        repo = Path(__file__).resolve().parents[1]
        out = subprocess.run(
            [sys.executable, str(script), d], capture_output=True,
            text=True, timeout=900,
            env={"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
                 "HOME": "/root"},
            cwd=str(repo))
        assert out.returncode == 0, out.stderr[-2000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["ok"] and res["restored_step"] == 3
        assert res["devices"] == 4
        assert res["loss2"] > 0 and res["loss1"] > 0

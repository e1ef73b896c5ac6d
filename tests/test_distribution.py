"""Distribution tests that need >1 device: run as subprocesses with
xla_force_host_platform_device_count set before jax imports.

Covers: sharding rules divisibility, int8-wire compressed all-reduce with
error feedback, GPipe pipeline parallelism, and a sharded end-to-end train
step on an 8-device host mesh."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path


SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_subprocess(body: str, devices: int = 8) -> dict:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("RESULT:" + json.dumps(result))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT in stdout: {out.stdout[-2000:]}")


def test_sharding_rules_divisibility():
    """_fit drops non-dividing axes (whisper 20 heads on 16-way model)."""
    res = run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import _fit
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        ok = _fit(("data", "model"), (8, 12), mesh)          # both divide
        dropped = _fit(("data", "model"), (8, 10), mesh)     # 10 % 4 != 0
        both = _fit((("data", "model"), None), (16, 3), mesh)
        result = {"ok": str(ok), "dropped": str(dropped), "both": str(both)}
    """, devices=8)
    assert res["ok"] == "PartitionSpec('data', 'model')"
    assert res["dropped"] == "PartitionSpec('data', None)"
    assert "'data', 'model'" in res["both"] or "('data', 'model')" in res["both"]


def test_compressed_allreduce_error_feedback():
    """int8-wire mean-reduce == fp32 mean within quant error; error feedback
    makes the BIAS vanish across steps (sum of deq errors -> 0)."""
    res = run_subprocess("""
        from jax.sharding import PartitionSpec as P
        from repro.dist.compress import compressed_psum_mean
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g_global = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)),
                               jnp.float32)

        def step(g, r):
            out, r2 = compressed_psum_mean({"w": g[0]}, {"w": r[0]}, "data")
            return out["w"][None], r2["w"][None]

        f = jax.shard_map(step, mesh=mesh, in_specs=(P("data"), P("data")),
                          out_specs=(P("data"), P("data")), check_vma=False)
        r = jnp.zeros((8, 64), jnp.float32)
        true_mean = g_global.mean(0)
        errs, acc = [], jnp.zeros((8, 64))
        for _ in range(6):
            out, r = f(g_global, r)
            errs.append(float(jnp.abs(out[0] - true_mean).max()))
            acc = acc + out
        # with error feedback the time-average converges to the true mean
        avg_err = float(jnp.abs(acc[0]/6 - true_mean).max())
        result = {"first_err": errs[0], "avg_err": avg_err}
    """, devices=8)
    assert res["first_err"] < 0.05            # one-step quant error is small
    assert res["avg_err"] < res["first_err"]  # feedback kills the bias


def test_pipeline_parallel_gpipe():
    """4-stage pipeline over 4 devices == sequential composition."""
    res = run_subprocess("""
        from repro.dist.pipeline import make_pipeline_fn
        mesh = jax.make_mesh((4,), ("pipe",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.standard_normal((4, 16, 16)) * 0.3, jnp.float32)

        def stage(w, x):
            return jnp.tanh(x @ w)

        pipe = make_pipeline_fn(stage, mesh, "pipe", n_micro=6)
        xs = jnp.asarray(rng.standard_normal((6, 2, 16)), jnp.float32)
        out = pipe(Ws, xs)
        ref = xs
        for s in range(4):
            ref = jnp.tanh(ref @ Ws[s])
        result = {"max_err": float(jnp.abs(out - ref).max())}
    """, devices=4)
    assert res["max_err"] < 1e-5


def test_sharded_train_step_8dev():
    """End-to-end: reduced llama3.2 train step on a (4 data x 2 model) host
    mesh with the production sharding rules; loss finite, grads sharded."""
    res = run_subprocess("""
        import dataclasses
        from repro.configs.base import (ParallelConfig, RunConfig, ShapeConfig,
                                        get_config, reduced_config)
        from repro.dist import sharding as shd
        from repro.models import io_spec, lm
        from repro.optim import make_optimizer
        from repro.train.train_state import TrainState, make_train_step

        cfg = reduced_config(get_config("llama3.2-1b"))
        shape = ShapeConfig("t", 64, 8, "train")
        parallel = ParallelConfig(remat="block", fsdp=True, seq_parallel=True,
                                  vocab_chunking=2)
        run = RunConfig(model=cfg, shape=shape, parallel=parallel,
                        optimizer="adamw", learning_rate=1e-3, warmup_steps=1)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        opt = make_optimizer("adamw", 1e-3, 0.1)
        with mesh:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            pspecs = shd.param_specs(params, mesh, parallel)
            params = jax.tree_util.tree_map(jax.device_put, params, pspecs)
            ostate = opt.init(params)
            state = TrainState(params, ostate, jnp.zeros((), jnp.int32))
            batch = io_spec.materialize(io_spec.train_batch_spec(cfg, shape))
            bspecs = shd.batch_specs(batch, mesh, parallel)
            batch = jax.tree_util.tree_map(jax.device_put, batch, bspecs)
            step_fn = jax.jit(make_train_step(run, opt))
            with shd.activation_rules(mesh, parallel):
                state2, metrics = step_fn(state, batch)
            loss1 = float(metrics["loss"])
            state3, metrics2 = step_fn(state2, batch)
        w = jax.tree_util.tree_leaves(state3.params)[0]
        result = {"loss1": loss1, "loss2": float(metrics2["loss"]),
                  "finite": bool(np.isfinite(loss1)),
                  "n_shards": len(w.sharding.device_set)}
    """, devices=8)
    assert res["finite"]
    assert res["loss2"] <= res["loss1"] + 0.5
    assert res["n_shards"] == 8

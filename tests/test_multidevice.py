"""Multi-device SPMD correctness, run in a subprocess with 8 fake CPU
devices (the parent test process must keep its 1-device view for the other
tests — jax pins device count at first init).  The child is pinned to the
CPU platform, so it never probes for an accelerator's runtime."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(body: str, n: int = 8) -> str:
    src = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", src],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(REPO)),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600, cwd=str(REPO))
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_brick_decode_attention_matches_oracle():
    run_with_devices("""
        from repro.configs.registry import reduced_config
        from repro.launch.mesh import make_mesh_of
        from repro.models import model_zoo
        from repro.parallel.sharding import Sharder
        import dataclasses

        # force the brick path: no window, big-enough cache, 4-way model axis
        cfg = reduced_config("qwen3-32b")
        mesh = make_mesh_of((2, 4), ("data", "model"))
        shd = Sharder(cfg, mesh)
        model = model_zoo.build_model(cfg)
        params = model.table.init(jax.random.key(0))

        from repro.core import brick_attention as brick
        W = 8192  # > 4096 threshold, divisible by 4
        assert brick.brick_active(cfg, shd, W)

        cache = model.init_cache(shd, 4, W)
        from repro.train import steps as steps_lib
        dec, _ = steps_lib.make_decode_step(cfg, model, mesh)
        tok = jnp.ones((4, 1), jnp.int32)
        logits = []
        c = cache
        jd = jax.jit(dec)
        for i in range(3):
            lg, c = jd(params, c, {"tokens": tok + i})
            logits.append(np.asarray(lg, np.float32))

        # oracle: same model decoded on a 1x1 mesh (non-brick path)
        cfg1 = dataclasses.replace(cfg, decode_cache_seq_shard=False)
        mesh1 = make_mesh_of((1, 1), ("data", "model"))
        shd1 = Sharder(cfg1, mesh1)
        model1 = model_zoo.build_model(cfg1)
        c1 = model1.init_cache(shd1, 4, W)
        dec1, _ = steps_lib.make_decode_step(cfg1, model1, mesh1)
        jd1 = jax.jit(dec1)
        for i in range(3):
            lg1, c1 = jd1(params, c1, {"tokens": tok + i})
            np.testing.assert_allclose(logits[i], np.asarray(lg1, np.float32),
                                       rtol=2e-4, atol=2e-4)
        print("BRICK ATTENTION OK")
    """)


def test_train_step_invariant_to_mesh():
    """The same train step on (1,1) and (2,4) meshes gives the same loss —
    sharding must not change the math."""
    run_with_devices("""
        from repro.configs.registry import reduced_config
        from repro.launch.mesh import make_mesh_of
        from repro.models import model_zoo
        from repro.optim.adamw import AdamW, init_opt_state
        from repro.parallel.sharding import Sharder
        from repro.train import steps as steps_lib

        cfg = reduced_config("qwen3-14b", microbatches=2)
        model = model_zoo.build_model(cfg)
        params = model.table.init(jax.random.key(0))
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                         cfg.vocab_size, jnp.int32),
            "labels": jax.random.randint(jax.random.key(2), (8, 32), 0,
                                         cfg.vocab_size, jnp.int32),
        }
        losses = []
        for shape in ((1, 1), (2, 4)):
            mesh = make_mesh_of(shape, ("data", "model"))
            step_fn, shd = steps_lib.make_train_step(cfg, model, mesh)
            p = jax.device_put(params, model.table.shardings(shd))
            o = init_opt_state(p, AdamW())
            _, _, m = jax.jit(step_fn)(p, o, batch)
            losses.append(float(m["loss"]))
        assert abs(losses[0] - losses[1]) < 1e-3, losses
        print("MESH INVARIANCE OK", losses)
    """)


def test_spmd_query_matches_host_jse():
    """The SPMD grid-brick query job over a sharded event store equals the
    host-level JSE result (the paper's dataflow, two realizations)."""
    run_with_devices("""
        from repro.configs.geps_events import reduced
        from repro.core import events as ev
        from repro.core.brick import create_store, gather_store, shard_to_mesh
        from repro.core.catalog import MetadataCatalog
        from repro.core.jse import JobSubmissionEngine, spmd_query_step
        from repro.launch.mesh import make_mesh_of

        cfgE = reduced()
        schema = ev.EventSchema.from_config(cfgE)
        store = create_store(schema, n_events=128, n_nodes=8,
                             events_per_brick=16, replication=2, seed=3)
        batch = gather_store(store)
        mesh = make_mesh_of((8, 1), ("data", "model"))
        sharded = shard_to_mesh(batch, mesh)
        expr = "e_total > 40 && count(pt > 15) >= 1"
        step = jax.jit(spmd_query_step(expr, schema, calib_iters=2))
        out = step(sharded)

        cat = MetadataCatalog(8)
        jse = JobSubmissionEngine(cat, store)
        jid = jse.submit(expr, calib_iters=2)
        merged, _ = jse.run_job_simulated(jid)
        assert int(out["n_selected"]) == merged.n_selected
        assert abs(float(out["sum_var"]) - merged.sum_var) < 1e-2
        print("SPMD QUERY OK", int(out["n_selected"]))
    """)

"""Elastic scaling: reshard a live training state between meshes and keep
training (the preemption-resize path), exercised in an 8-device subprocess."""
import json
import subprocess
import sys
import textwrap

SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    import numpy as np
    from repro.configs import get_tiny
    from repro.configs.base import MeshConfig, ShapeSpec, TrainConfig
    from repro.launch.mesh import make_mesh
    from repro.data.synthetic import make_batch
    from repro.runtime.steps import init_train_state, make_train_step
    from repro.runtime.elastic import (relower_train_step, reshard_state,
                                       state_shardings)

    cfg = get_tiny("llama3-8b")
    tcfg = TrainConfig(remat="none")
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    batch = make_batch(cfg, ShapeSpec("t", 64, 8, "train"))
    step = make_train_step(cfg, tcfg)

    # phase 1: 2x4 mesh
    mesh_a = make_mesh(MeshConfig((2, 4), ("data", "model")))
    state = reshard_state(state, mesh_a, cfg)
    batch_shape = jax.eval_shape(lambda b: b, batch)
    with jax.set_mesh(mesh_a):
        st_a = relower_train_step(step, state, batch_shape, mesh_a, cfg)
        state, m1 = st_a(state, batch)
        l1 = float(m1["loss"])

    # elastic resize: "lose half the pod" -> 4x2 mesh, reshard live state
    mesh_b = make_mesh(MeshConfig((4, 2), ("data", "model")))
    state = reshard_state(state, mesh_b, cfg)
    with jax.set_mesh(mesh_b):
        st_b = relower_train_step(step, state, batch_shape, mesh_b, cfg)
        state, m2 = st_b(state, batch)
        l2 = float(m2["loss"])

    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1 + 1.0
    print(json.dumps({"l1": l1, "l2": l2}))
""")


def test_elastic_reshard_between_meshes():
    r = subprocess.run([sys.executable, "-c", SNIPPET],
                       capture_output=True, text=True, timeout=900,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # second step continues improving on the new mesh
    assert out["l2"] <= out["l1"]

"""Elastic scaling: a checkpoint written under one mesh restores onto a
different mesh (different device count / sharding) with identical values.
Runs in a subprocess with 8 fake host devices."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.ckpt import restore, save

    ckpt_dir = sys.argv[1]
    auto = jax.sharding.AxisType.Auto
    mesh_a = jax.make_mesh((8,), ("model",), axis_types=(auto,))
    mesh_b = jax.make_mesh((2, 4), ("data", "model"), axis_types=(auto,) * 2)

    # "train" on mesh A: params sharded 8-way on the last dim
    w = jnp.arange(16 * 64, dtype=jnp.float32).reshape(16, 64)
    wa = jax.device_put(w, NamedSharding(mesh_a, P(None, "model")))
    tree = {"w": wa, "step": jnp.int32(7)}
    save(ckpt_dir, 7, tree)

    # "restart" on mesh B with a different layout (elastic rescale)
    shard_b = {"w": NamedSharding(mesh_b, P("data", "model")),
               "step": NamedSharding(mesh_b, P())}
    out = restore(ckpt_dir, tree, shardings=shard_b)
    assert out["w"].sharding == shard_b["w"], out["w"].sharding
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(w))
    assert int(out["step"]) == 7
    print("ELASTIC_OK")
""")


def test_elastic_mesh_to_mesh_restore(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                       env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ELASTIC_OK" in r.stdout

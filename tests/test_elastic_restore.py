"""Elastic scaling: a checkpoint written under one topology restores onto a
different mesh (reshard-on-load), in a subprocess with fake devices."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_checkpoint_reshards_onto_new_mesh(tmp_path):
    code = f"""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ckpt

        # phase 1: "old fleet" — save unsharded-logical from host arrays
        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                 "b": jnp.ones((8,), jnp.float32)}}
        ckpt.save(r"{tmp_path}", 3, tree, extra={{"next_step": 3}})

        # phase 2: "new fleet" — restore sharded onto a 2x4 mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        sh = {{"w": NamedSharding(mesh, P("data", "model")),
              "b": NamedSharding(mesh, P("model"))}}
        restored, extra = ckpt.restore(r"{tmp_path}", tree, shardings=sh)
        assert extra["next_step"] == 3
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))
        # really sharded on the new mesh
        assert restored["w"].sharding == sh["w"]
        assert len(restored["w"].addressable_shards) == 8
        print("OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout

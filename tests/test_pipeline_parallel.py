"""GPipe pipeline parallelism == sequential reference (fwd + grads),
in a subprocess with fake devices."""
import os
import subprocess
import sys
import textwrap


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_pipeline_matches_sequential_and_differentiates():
    out = run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.train.pipeline import (pipeline_apply,
                                          sequential_reference)

        mesh = make_mesh((4,), ("pipe",))
        L, D = 8, 16          # 8 layers -> 4 stages x 2 layers
        key = jax.random.key(0)
        W = jax.random.normal(key, (L, D, D)) * (0.5 / np.sqrt(D))

        def stage_fn(w_stack, h):
            def body(hc, w):
                return jnp.tanh(hc @ w), None
            h, _ = jax.lax.scan(body, h, w_stack)
            return h

        x = jax.random.normal(jax.random.key(1), (8, D))
        ref = sequential_reference(stage_fn, W, x, 4)
        got = pipeline_apply(stage_fn, W, x, mesh, n_microbatches=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

        # differentiable: grads flow through the ppermute chain
        def loss(w, fn):
            return jnp.sum(fn(w) ** 2)
        g_ref = jax.grad(lambda w: jnp.sum(
            sequential_reference(stage_fn, w, x, 4) ** 2))(W)
        g_pipe = jax.grad(lambda w: jnp.sum(pipeline_apply(
            stage_fn, w, x, mesh, n_microbatches=4) ** 2))(W)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                                   rtol=5e-4, atol=5e-5)
        print("OK")
    """)
    assert "OK" in out

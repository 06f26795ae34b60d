"""Multi-device sharding semantics, run in subprocesses with
xla_force_host_platform_device_count (the main test process must keep the
default 1-device view, per the brief)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    env.pop("DRYRUN_DEVICES", None)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    return r.stdout


def test_main_process_sees_one_device():
    import jax
    assert jax.device_count() == 1


def test_param_specs_and_divisibility():
    out = run_py("""
        import jax, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke_config
        from repro.models import abstract_params
        from repro.sharding import param_specs
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("chatglm3_6b")   # kv=2 < model=4
        ap = abstract_params(cfg)
        specs = param_specs(ap, cfg, mesh, fsdp=True)
        flat = jax.tree_util.tree_flatten_with_path(specs)[0]
        flat_p = jax.tree_util.tree_flatten_with_path(ap)[0]
        by = {"/".join(str(getattr(k, 'key', getattr(k, 'idx', k)))
              for k in path): s for path, s in flat}
        shp = {"/".join(str(getattr(k, 'key', getattr(k, 'idx', k)))
               for k in path): l.shape for path, l in flat_p}
        # every spec respects divisibility
        for k, s in by.items():
            for dim, ax in zip(shp[k], tuple(s)):
                if ax is not None:
                    n = mesh.shape[ax] if isinstance(ax, str) else \
                        int(np.prod([mesh.shape[a] for a in ax]))
                    assert dim % n == 0, (k, s, shp[k])
        # kv heads (2) not divisible by model (4) -> replicated on model
        kv = [s for k, s in by.items() if k.endswith("attn/wk")][0]
        assert "model" not in tuple(kv), kv
        # q heads sharded over model
        q = [s for k, s in by.items() if k.endswith("attn/wq")][0]
        assert "model" in tuple(q), q
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_matches_dense_dispatch():
    """shard_map EP == single-shard MoE (same math, distributed)."""
    out = run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke_config
        from repro.models import moe as moe_lib
        from repro.models import transformer as tfm
        cfg = get_smoke_config("deepseek_moe_16b")
        mesh = make_mesh((2, 4), ("data", "model"))
        key = jax.random.key(0)
        p = moe_lib.moe_init(key, cfg)
        x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                              jnp.float32) * 0.1
        T_loc = 2 * 16 // 8
        cap = moe_lib.capacity_of(cfg, T_loc)
        dense = moe_lib.moe_apply(p, x, cfg, capacity=8 * cap)
        ep = moe_lib.moe_apply_ep(p, x, cfg, mesh, capacity=cap)
        # EP shards tokens before gating; with ample capacity both keep
        # every token-expert pair -> identical outputs
        np.testing.assert_allclose(np.asarray(ep), np.asarray(dense),
                                   rtol=2e-4, atol=2e-4)
        print("OK")
    """)
    assert "OK" in out


def test_distributed_cmax_matches_local():
    """The sharded batch path at the paper's camera: each device runs its
    slots alone, so the compiled step holds no collective, and the
    estimates match the single-device path."""
    out = run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core import CmaxConfig
        from repro.core.distributed import estimate_batch_sharded
        from repro.core.pipeline import estimate_windows_parallel
        from repro.data import events as ev
        spec = ev.SequenceSpec(name="t", n_windows=4,
                               events_per_window=1024, n_features=50,
                               seed=1, window_dt=0.03)
        wins, om_true, _ = ev.make_sequence(spec)
        cfg = CmaxConfig(camera=spec.camera)
        om0 = om_true + 0.1
        mesh = make_mesh((4, 2), ("data", "model"))
        hlo = jax.jit(lambda w, o: estimate_batch_sharded(
            w, o, cfg, mesh)).lower(wins, om0).compile().as_text()
        for op in ("all-gather", "all-reduce", "all-to-all",
                   "collective-permute", "reduce-scatter"):
            assert op not in hlo, op
        dist = estimate_batch_sharded(wins, om0, cfg, mesh)
        loc = estimate_windows_parallel(wins, om0, cfg)
        # a device's program may fuse differently from the single-device
        # one; a window sitting exactly on the gain threshold can then take
        # one extra/fewer adaptive iteration, so compare estimates loosely
        # (they converge to the same optimum)
        np.testing.assert_allclose(np.asarray(dist.omega),
                                   np.asarray(loc.omega), rtol=0.05,
                                   atol=0.05)
        print("OK")
    """)
    assert "OK" in out


def test_shard_map_cmax_batch_and_streams_match_local():
    """The shard_map-backed serving paths (DESIGN.md §4) agree with the
    local paths on 8 fake devices, for both the (B, N) batch and the
    (S, K, N) warm-start-chained stream layouts."""
    out = run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core import CmaxConfig, StageConfig
        from repro.core.types import Camera, EventWindow
        from repro.core.pipeline import (estimate_streams,
                                         estimate_windows_parallel)
        from repro.core.distributed import (estimate_batch_sharded,
                                            estimate_streams_sharded)
        from repro.data import events as ev
        cam = Camera(width=64, height=48, fx=53.0, fy=53.0,
                     cx=32.0, cy=24.0)
        cfg = CmaxConfig(camera=cam, stages=(
            StageConfig(scale=0.5, tau=4e-4, max_iters=3, blur_taps=3,
                        blur_sigma=0.5, keep_ratio=0.5),
            StageConfig(scale=1.0, tau=1.5e-4, max_iters=3, blur_taps=5,
                        blur_sigma=1.0, keep_ratio=1.0)))
        spec = ev.SequenceSpec(name="t", n_windows=8,
                               events_per_window=256, n_features=30,
                               seed=5, window_dt=0.03, camera=cam)
        wins, om_true, _ = ev.make_sequence(spec)
        mesh = make_mesh((4, 2), ("data", "model"))
        om0 = jnp.zeros((8, 3))
        res = estimate_batch_sharded(wins, om0, cfg, mesh)
        ref = estimate_windows_parallel(wins, om0, cfg)
        np.testing.assert_allclose(np.asarray(res.omega),
                                   np.asarray(ref.omega),
                                   rtol=0.05, atol=0.05)
        # streams: 4 identical 2-window streams sharded over data
        sw = EventWindow(*(jnp.stack([a[:2]] * 4)
                           for a in (wins.x, wins.y, wins.t, wins.p,
                                     wins.valid)))
        oms, _ = estimate_streams_sharded(sw, jnp.zeros((4, 3)), cfg, mesh)
        oms_ref, _ = estimate_streams(sw, jnp.zeros((4, 3)), cfg)
        np.testing.assert_allclose(np.asarray(oms), np.asarray(oms_ref),
                                   rtol=0.05, atol=0.05)
        # indivisible batch is rejected with a clear error
        try:
            estimate_batch_sharded(
                EventWindow(*(a[:3] for a in (wins.x, wins.y, wins.t,
                                              wins.p, wins.valid))),
                jnp.zeros((3, 3)), cfg, mesh)
        except ValueError as e:
            assert "divisible" in str(e)
        else:
            raise AssertionError("expected ValueError")
        print("OK")
    """)
    assert "OK" in out


def test_train_step_lowers_on_mesh():
    """A small train step lowers+compiles with full sharding on 8 fake
    devices — the same path dryrun.py uses at 512."""
    out = run_py("""
        import os
        os.environ["DRYRUN_DEVICES"] = "8"
        import jax
        from repro.launch.dryrun import build_cell
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        # monkeypatch the shape table to a tiny cell
        from repro.models import model as M
        M.SHAPES["tiny"] = M.ShapeSpec("tiny", 64, 8, "train")
        fn, args, meta = build_cell("llama3_2_1b", "tiny", mesh)
        compiled = fn.lower(*args).compile()
        cost = compiled.cost_analysis()
        assert cost["flops"] > 0
        print("OK")
    """, devices=8)
    assert "OK" in out

"""Core datatypes for the CMAX-CAMEL pipeline.

Everything is a frozen dataclass of static metadata or a pytree of arrays,
so the whole pipeline stays jit/vmap-friendly.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Camera:
    """Pinhole camera intrinsics for a DVS sensor (DAVIS240C by default)."""

    width: int = 240
    height: int = 180
    fx: float = 199.0
    fy: float = 199.0
    cx: float = 120.0
    cy: float = 90.0

    def scaled(self, s: float) -> "Camera":
        """Intrinsics are *not* scaled: the paper scales warped pixel
        coordinates by s after warping (Alg. 2 line 7), keeping the camera
        model at native resolution. This helper only exists to report the
        scaled grid size."""
        return self

    def grid(self, s: float) -> Tuple[int, int]:
        """(H_s, W_s) = (ceil(s*H), ceil(s*W)) per the paper."""
        import math

        return (int(math.ceil(s * self.height)), int(math.ceil(s * self.width)))


@jax.tree_util.register_pytree_node_class
class EventWindow:
    """A fixed-size window of N events: x, y, t, p (+ validity mask).

    Arrays all have shape (N,). `valid` marks real events (windows shorter
    than N are padded; padding has valid=False and contributes nothing).
    """

    def __init__(self, x, y, t, p, valid=None):
        self.x = x
        self.y = y
        self.t = t
        self.p = p
        self.valid = valid if valid is not None else jnp.ones_like(x, dtype=bool)

    @property
    def n(self) -> int:
        return self.x.shape[-1]

    @property
    def t_ref(self):
        """Reference time = first valid timestamp (min over valid)."""
        big = jnp.where(self.valid, self.t, jnp.inf)
        return jnp.min(big, axis=-1)

    def tree_flatten(self):
        return (self.x, self.y, self.t, self.p, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def __repr__(self):
        return f"EventWindow(n={self.x.shape})"


@dataclasses.dataclass(frozen=True)
class StageConfig:
    """One coarse-to-fine stage (paper §2-3)."""

    scale: float            # s in {1/4, 1/2, 1}
    tau: float              # variance-gain threshold tau_s (Alg. 1)
    max_iters: int          # hard cap on stage residence (HW watchdog)
    blur_taps: int          # 3 / 5 / 9 per paper §4
    blur_sigma: float       # Gaussian sigma at this stage
    keep_ratio: float       # rho_s = s (paper §2); 1.0 disables subsampling
    step_scale: float = 1.0  # CG-PR step multiplier (coarse stages step big)

    def grid(self, cam: Camera) -> Tuple[int, int]:
        return cam.grid(self.scale)


#: Selectable engine-pass backends (CmaxConfig.engine):
#:   "reference"      — the pure-jnp scatter + blur_separable datapath (the
#:                      correctness oracle; XLA fuses it reasonably on CPU)
#:   "pallas"         — per-window fused Pallas kernels (iwe_accum +
#:                      blur_stats); a batch maps over its windows
#:   "pallas_batched" — the batched megakernel: one (batch, slab)-grid
#:                      pallas_call per engine pass for the WHOLE batch
#:                      (kernels/megakernel.py); the hot loop runs windows
#:                      in masked lockstep
ENGINES = ("reference", "pallas", "pallas_batched")


@dataclasses.dataclass(frozen=True)
class CmaxConfig:
    """Full pipeline configuration (paper-faithful defaults).

    The default three-stage schedule matches §3: scales {1/4, 1/2, 1} with
    3/5/9-tap Gaussian kernels, keep-ratio rho_s = s, and empirically chosen
    thresholds. `adaptive=False` reproduces the fixed-schedule baseline
    (each stage runs exactly `fixed_iters` iterations).

    `engine` selects the engine-pass backend (see ENGINES); it threads
    through make_engine_pass / estimate_window / estimate_batch* so the
    serving layer (launch/serve.py) and the sharded twins
    (core/distributed.py) pick the backend up with zero call-site changes.
    The remaining engine_* fields are kernel knobs: `engine_capacity` is
    the per-(window, slab) tap budget of the batched megakernel (and the
    per-tile budget of the per-window kernels), `engine_rb` the row-slab
    height. Whether the kernels compile or are interpreted follows the
    backend (kernels/backend.py): a TPU compiles, the CPU interprets.
    """

    camera: Camera = Camera()
    stages: Tuple[StageConfig, ...] = (
        StageConfig(scale=0.25, tau=1e-3, max_iters=40, blur_taps=3,
                    blur_sigma=0.5, keep_ratio=0.25, step_scale=2.0),
        StageConfig(scale=0.5, tau=4e-4, max_iters=40, blur_taps=5,
                    blur_sigma=0.75, keep_ratio=0.5, step_scale=1.4),
        StageConfig(scale=1.0, tau=1.5e-4, max_iters=40, blur_taps=9,
                    blur_sigma=1.0, keep_ratio=1.0, step_scale=1.0),
    )
    adaptive: bool = True
    fixed_iters: Tuple[int, ...] = (10, 10, 15)   # fixed-schedule baseline
    step_size: float = 0.08                       # CG-PR step scale
    use_cgpr: bool = True                         # False -> plain grad ascent
    dtype: jnp.dtype = jnp.float32
    engine: str = "reference"                     # one of ENGINES
    engine_capacity: int = 4096                   # per-(window, slab) taps
    engine_rb: int = 8                            # megakernel row-slab height

    def __post_init__(self):
        if self.engine not in ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; "
                             f"expected one of {ENGINES}")

    @property
    def n_stages(self) -> int:
        return len(self.stages)


def full_resolution_config(camera: Camera = Camera(), max_iters: int = 60,
                           tau: float = 3e-5) -> CmaxConfig:
    """Conventional full-resolution CMAX (no coarse-to-fine): one stage at
    s=1, no subsampling — the paper's 'Full-resolution CMAX' reference."""
    return CmaxConfig(
        camera=camera,
        stages=(StageConfig(scale=1.0, tau=tau, max_iters=max_iters,
                            blur_taps=9, blur_sigma=1.0, keep_ratio=1.0),),
        adaptive=True,
        fixed_iters=(max_iters,),
    )


def fixed_schedule_config(camera: Camera = Camera(),
                          iters: Tuple[int, ...] = (10, 10, 15)) -> CmaxConfig:
    """Fixed-schedule coarse-to-fine CMAX (the paper's baseline policy)."""
    return dataclasses.replace(CmaxConfig(camera=camera), adaptive=False,
                               fixed_iters=iters)

"""Plain reference of rotational contrast maximisation, the semantics that
the `cmax240-*` configurations serve.

Written from the CMAX-CAMEL paper's description and the configuration
files beside this one, in straightforward `jax.numpy`, and independent of
the code under test: it imports nothing of the program and takes nothing
it made. One window at a time (`jax.vmap` batches windows):

  warp (Alg. 2)     each event moves back to the window's first timestamp
                    along the rotational flow of the hypothesis omega; the
                    stage scales the warped coordinate by s
  sort (Alg. 3)     events whose four bilinear taps land on the stage grid,
                    grouped by their pixel at the stage's entry omega; in
                    each group, in event order, every round(1/rho_s)-th
                    event is kept (rank % stride == 0)
  vote (Eq. 2, 6)   each kept event adds polarity x bilinear weight to the
                    image of warped events (IWE) and the weights' omega
                    derivatives to the three derivative images
  blur (Eq. 3-5)    separable normalised Gaussian, zero padding
  stats (Eq. 12)    S1 = sum I, S2 = sum I^2, G_j = sum I D_j, T_j = sum D_j;
                    variance S2/P - (S1/P)^2, gradient 2/P (G - S1 T / P)
  controller        per stage, from the stage's entry omega: Polak-Ribiere
                    conjugate-gradient ascent (PR+ beta, restart when the
                    direction is not an ascent direction), a normalised step
                    alpha = step_size * step_scale; a proposal that raises
                    the variance is accepted, one that does not is rejected
                    and alpha halves; the stage ends when an accepted step's
                    relative gain falls under tau_s, when alpha falls under
                    alpha_0 / 64 after a rejection, or after max_iters
                    proposals (Alg. 1, adaptive residence)

`vote_dtype` is the precision of the vote, blur and statistics. The
configuration states float32; the control of the benchmark's comparison
runs the same code with bfloat16 there. The warp and the sort stay in
float32 in both, as coordinates in bfloat16 would be off by pixels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def warp(x, y, t, valid, omega, cam, scale):
    """Warped, stage-scaled coordinates and their omega Jacobians."""
    t_ref = jnp.min(jnp.where(valid, t, jnp.inf))
    dt = t - t_ref
    xn = (x - cam["cx"]) / cam["fx"]
    yn = (y - cam["cy"]) / cam["fy"]
    u = cam["fx"] * (xn * yn * omega[0] - (1 + xn * xn) * omega[1]
                     + yn * omega[2])
    v = cam["fy"] * ((1 + yn * yn) * omega[0] - xn * yn * omega[1]
                     - xn * omega[2])
    xw = scale * (x - dt * u)
    yw = scale * (y - dt * v)
    # d xw / d omega and d yw / d omega
    sdt = scale * dt
    jx = -sdt[:, None] * cam["fx"] * jnp.stack([xn * yn, -(1 + xn * xn), yn],
                                                axis=-1)
    jy = -sdt[:, None] * cam["fy"] * jnp.stack([1 + yn * yn, -xn * yn, -xn],
                                                axis=-1)
    return xw, yw, jx, jy


def grid(cam, scale):
    import math
    return math.ceil(scale * cam["height"]), math.ceil(scale * cam["width"])


def on_grid(xw, yw, valid, hs, ws):
    """Pixel (x0, y0) of the top-left tap, and whether all four taps land
    on the grid."""
    x0 = jnp.floor(xw).astype(jnp.int32)
    y0 = jnp.floor(yw).astype(jnp.int32)
    ok = valid & (x0 >= 0) & (x0 <= ws - 2) & (y0 >= 0) & (y0 <= hs - 2)
    return x0, y0, ok


def kept(x, y, t, valid, omega_entry, cam, stage):
    """1.0 for each event the stage keeps, else 0.0 (Alg. 3)."""
    hs, ws = grid(cam, stage["scale"])
    n = x.shape[0]
    xw, yw, _, _ = warp(x, y, t, valid, omega_entry, cam, stage["scale"])
    x0, y0, ok = on_grid(xw, yw, valid, hs, ws)
    group = jnp.where(ok, y0 * ws + x0, hs * ws)
    order = jnp.argsort(group, stable=True)
    g_sorted = group[order]
    first = jnp.searchsorted(g_sorted, g_sorted, side="left")
    rank = jnp.arange(n) - first
    stride = max(1, round(1.0 / stage["keep_ratio"]))
    keep_sorted = (g_sorted < hs * ws) & (rank % stride == 0)
    return jnp.zeros((n,), jnp.float32).at[order].set(
        keep_sorted.astype(jnp.float32))


def gaussian(taps, sigma, dtype):
    xs = jnp.arange(taps, dtype=jnp.float32) - taps // 2
    g = jnp.exp(-0.5 * (xs / sigma) ** 2)
    return (g / jnp.sum(g)).astype(dtype)


def blur(img, fir):
    """Separable 'same' convolution of a (C, H, W) stack, zero padding."""
    k = fir.shape[0]
    h = k // 2

    def along(a, axis):
        pad = [(0, 0)] * a.ndim
        pad[axis] = (h, h)
        ap = jnp.pad(a, pad)
        n = a.shape[axis]
        return sum(fir[i] * jax.lax.slice_in_dim(ap, i, i + n, axis=axis)
                   for i in range(k))

    return along(along(img, 2), 1)


def stats(x, y, t, p, valid, weight, omega, cam, stage, vote_dtype):
    """The eight Eq. 12 sums of one engine pass at `omega`."""
    hs, ws = grid(cam, stage["scale"])
    xw, yw, jx, jy = warp(x, y, t, valid, omega, cam, stage["scale"])
    x0, y0, ok = on_grid(xw, yw, valid, hs, ws)
    ax, ay = xw - x0, yw - y0
    amp = jnp.where(ok, p * weight, 0.0)
    x0c = jnp.clip(x0, 0, ws - 2)
    y0c = jnp.clip(y0, 0, hs - 2)
    img = jnp.zeros((4, hs, ws), vote_dtype)
    # taps (dy, dx): bilinear weight and its omega derivative, using
    # d ax / d omega = jx and d ay / d omega = jy
    for dy, dx in ((0, 0), (0, 1), (1, 0), (1, 1)):
        wx = ax if dx else 1 - ax
        wy = ay if dy else 1 - ay
        sx = 1.0 if dx else -1.0
        sy = 1.0 if dy else -1.0
        dw = (sx * wy)[:, None] * jx + (sy * wx)[:, None] * jy     # (N, 3)
        vals = amp[:, None] * jnp.concatenate([(wx * wy)[:, None], dw], 1)
        img = img.at[:, y0c + dy, x0c + dx].add(vals.T.astype(vote_dtype))
    b = blur(img, gaussian(stage["blur_taps"], stage["blur_sigma"],
                           vote_dtype))
    i_img, d_img = b[0], b[1:]
    out = jnp.stack([jnp.sum(i_img), jnp.sum(i_img * i_img),
                     *jnp.sum(i_img[None] * d_img, axis=(1, 2)),
                     *jnp.sum(d_img, axis=(1, 2))])
    return out.astype(jnp.float32)


def objective(s, n_pixels):
    """Variance and its omega gradient from the eight sums."""
    P = float(n_pixels)
    var = s[1] / P - (s[0] / P) ** 2
    grad = 2.0 / P * (s[2:5] - s[0] * s[5:8] / P)
    return var, grad


def stage_replay(x, y, t, p, valid, omega_entry, omega_exit, cam, stage,
                 step_size, vote_dtype):
    """For one window and one stage: the variance at the program's entry
    and exit omegas (both on the events kept at the entry omega), and the
    stage run again from the entry omega by the reference controller:
    (v_entry, v_exit, iterations, exit omega)."""
    hs, ws = grid(cam, stage["scale"])
    weight = kept(x, y, t, valid, omega_entry, cam, stage)

    def engine(om):
        return objective(stats(x, y, t, p, valid, weight, om, cam, stage,
                               vote_dtype), hs * ws)

    v0, g0 = engine(omega_entry)
    v_exit, _ = engine(omega_exit)
    alpha0 = jnp.float32(step_size * stage["step_scale"])

    def cond(c):
        return (~c["done"]) & (c["it"] < stage["max_iters"])

    def body(c):
        # Polak-Ribiere direction (PR+), steepest ascent on the first step
        g, gp, dp = c["g"], c["g_prev"], c["d_prev"]
        beta = jnp.dot(g, g - gp) / jnp.maximum(jnp.dot(gp, gp), 1e-24)
        beta = jnp.where(c["first"], 0.0, jnp.maximum(beta, 0.0))
        d = g + beta * dp
        d = jnp.where(jnp.dot(d, g) > 0.0, d, g)
        om_p = c["om"] + c["alpha"] * d / (jnp.linalg.norm(d) + 1e-12)
        v_p, g_p = engine(om_p)
        up = v_p > c["v"]
        gain = (v_p - c["v"]) / jnp.maximum(jnp.abs(c["v"]), 1e-12)
        alpha = jnp.where(up, c["alpha"], c["alpha"] * 0.5)
        done = (up & (gain < stage["tau"])) | (~up & (alpha < alpha0 / 64))
        pick = lambda a, b: jnp.where(up, a, b)
        return dict(om=pick(om_p, c["om"]), v=pick(v_p, c["v"]),
                    g=pick(g_p, g), g_prev=pick(g, gp), d_prev=pick(d, dp),
                    first=pick(jnp.bool_(False), c["first"]), alpha=alpha,
                    it=c["it"] + 1, done=done)

    z = jnp.zeros((3,), jnp.float32)
    out = jax.lax.while_loop(cond, body, dict(
        om=omega_entry, v=v0, g=g0, g_prev=z, d_prev=z,
        first=jnp.bool_(True), alpha=alpha0, it=jnp.int32(0),
        done=jnp.bool_(False)))
    return v0, v_exit, out["it"], out["om"]


@functools.lru_cache(maxsize=None)
def batched_stage_replay(cam_items, stage_items, step_size, vote_dtype):
    """jit(vmap(stage_replay)) over a block of windows, for one stage."""
    cam, stage = dict(cam_items), dict(stage_items)

    def one(x, y, t, p, valid, om_in, om_out):
        with jax.default_matmul_precision("highest"):
            return stage_replay(x, y, t, p, valid, om_in, om_out, cam,
                                stage, step_size, vote_dtype)

    return jax.jit(jax.vmap(one))

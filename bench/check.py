"""Whether a run served the right answers: the comparison that decides
`correct`.

What the timed path produced is what is compared: each response the
service handed out, and the per-stage trace its batch returned (the
`WindowResult` of the program's compiled batch function, recorded as the
service harvested it). Three layers are held to the configuration's plain
reference (`bench/configs/<reference>.py`) and to the traffic:

  service     misrouted: responses that are not the next window of their
              camera, or that no one submitted; chain_breaks: windows
              whose stage-0 entry omega is not the camera's previous
              served omega (or the first window's hint), whose stage
              s + 1 does not start where stage s ended, or whose response
              differs from its batch's result; compiles_in_window: XLA
              compiles while the window ran. Over every served window.
  engine      engine_rel_err: on a sample of served windows drawn from the
              seed, every stage: the variance the program reported at the
              stage's entry and exit omegas against the reference's at the
              same omegas, relative.
  controller  controller_mismatch: on the same sample, every stage run
              again by the reference from the program's entry omega; the
              share of window-stages whose iteration count differs, or
              whose exit omega differs by more than OMEGA_TOL.

The limits are the configuration file's `check_limits`; the readings they
were set from are in PERF.md. `control` computes the same two numbers for
the control: the reference with its vote, blur and sums in bfloat16, put
in the program's place at the same entry omegas.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

#: rad/s. A stage run again with the same decisions ends within about
#: 1e-6 rad/s of the program (float32 sums in another order); one
#: decision the other way moves the exit by a step, at least
#: step_size * step_scale / 64 = 1.25e-3 rad/s.
OMEGA_TOL = 1e-4
#: served windows compared with the reference in each run, and the
#: number the reference takes at once
SAMPLE = 48
BLOCK = 16


def _reference(cfg: dict):
    return importlib.import_module(f"bench.configs.{cfg['reference']}")


def pair(drv, results, slots) -> list:
    """(response, submission, (batch result, slot)) for every ok response,
    in the order the service handed them out."""
    ok = [r for r in drv.responses if r.status == "ok"]
    if len(ok) != len(slots):
        raise RuntimeError(f"{len(ok)} ok responses but {len(slots)} "
                           f"harvested slots")
    return [(r, drv.sub.get((r.stream_id, r.seq)), (results[b], i))
            for r, (b, i) in zip(ok, slots)]


def service_numbers(drv, paired) -> Dict[str, int]:
    misrouted, chain = 0, 0
    expect: Dict[str, int] = {}
    served: Dict[tuple, np.ndarray] = {}
    for r, s, _ in paired:
        if s is None or r.seq != expect.get(r.stream_id, 0):
            misrouted += 1
        expect[r.stream_id] = r.seq + 1
        served[(r.stream_id, r.seq)] = np.asarray(r.omega, np.float32)
    for r, s, (res, i) in paired:
        if s is None:
            continue
        st = res.stages
        want = (np.asarray(s.hint, np.float32) if s.seq == 0
                else served.get((r.stream_id, r.seq - 1)))
        ok = want is not None and np.array_equal(st[0].omega_entry[i], want)
        for a, b in zip(st[:-1], st[1:]):
            ok &= np.array_equal(a.omega_exit[i], b.omega_entry[i])
        ok &= np.array_equal(st[-1].omega_exit[i], res.omega[i])
        ok &= np.array_equal(np.asarray(r.omega, np.float32), res.omega[i])
        ok &= tuple(r.iters) == tuple(int(t.iters[i]) for t in st)
        chain += 0 if ok else 1
    return {"misrouted": misrouted, "chain_breaks": chain}


def sample(paired, seed: int) -> list:
    """Served windows to compare, drawn from the seed, with the window
    that iterated most among them."""
    n = len(paired)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    idx = list(rng.choice(n, size=min(SAMPLE, n), replace=False))
    busiest = max(range(n), key=lambda j: sum(paired[j][0].iters))
    if busiest not in idx:
        idx[0] = busiest
    return [paired[j] for j in sorted(idx)]


def replay(cfg: dict, traffic, picked, vote_dtype) -> List[dict]:
    """Per stage, the reference's (v_entry, v_exit, iters, omega_exit) for
    the picked windows, at the program's entry and exit omegas."""
    import jax.numpy as jnp
    ref = _reference(cfg)
    ev = {f: [] for f in ("x", "y", "t", "p", "valid")}
    for _, s, _ in picked:
        cam = traffic.cameras[s.camera]
        for f in ev:
            ev[f].append(getattr(cam, f)[s.window])
    n = len(picked)
    pad = (-n) % BLOCK
    ev = {f: np.stack(v + [v[-1]] * pad) for f, v in ev.items()}
    cam_items = tuple(sorted(cfg["camera"].items()))
    out = []
    for si, stage in enumerate(cfg["stages"]):
        om_in = np.stack([res.stages[si].omega_entry[i]
                          for _, _, (res, i) in picked])
        om_out = np.stack([res.stages[si].omega_exit[i]
                           for _, _, (res, i) in picked])
        om_in = np.concatenate([om_in, np.repeat(om_in[-1:], pad, 0)])
        om_out = np.concatenate([om_out, np.repeat(om_out[-1:], pad, 0)])
        fn = ref.batched_stage_replay(cam_items, tuple(sorted(stage.items())),
                                      float(cfg["step_size"]), vote_dtype)
        parts = []
        for b in range(0, n + pad, BLOCK):
            sl = slice(b, b + BLOCK)
            parts.append([np.asarray(a) for a in fn(
                *(jnp.asarray(ev[f][sl]) for f in
                  ("x", "y", "t", "p", "valid")),
                jnp.asarray(om_in[sl]), jnp.asarray(om_out[sl]))])
        v0, v1, it, om = (np.concatenate(c)[:n] for c in zip(*parts))
        out.append({"v_entry": v0, "v_exit": v1, "iters": it, "omega": om})
    return out


def compare(prog: List[dict], ref: List[dict]) -> Dict[str, float]:
    """engine_rel_err and controller_mismatch of `prog` against `ref`,
    both lists of per-stage dicts as `replay` returns them."""
    err, miss, total = 0.0, 0, 0
    for p, r in zip(prog, ref):
        for key in ("v_entry", "v_exit"):
            rel = np.abs(np.asarray(p[key], np.float64) - r[key]) / \
                np.maximum(np.abs(np.asarray(r[key], np.float64)), 1e-30)
            err = max(err, float(np.max(rel)))
        bad = (np.asarray(p["iters"]) != r["iters"]) | (
            np.max(np.abs(np.asarray(p["omega"], np.float64) - r["omega"]),
                   axis=1) > OMEGA_TOL)
        miss += int(np.sum(bad))
        total += bad.size
    return {"engine_rel_err": err, "controller_mismatch": miss / total}


def served_trace(cfg: dict, picked) -> List[dict]:
    """The program's own per-stage numbers for the picked windows."""
    out = []
    for si in range(len(cfg["stages"])):
        col = lambda f: np.stack([getattr(res.stages[si], f)[i]
                                  for _, _, (res, i) in picked])
        out.append({"v_entry": col("v_entry"), "v_exit": col("v_final"),
                    "iters": col("iters"), "omega": col("omega_exit")})
    return out


def _with_limits(cfg: dict, values: Dict[str, float], prefix: str = ""
                 ) -> Dict[str, dict]:
    lim = cfg["check_limits"]
    return {prefix + k: {"value": v, "limit": lim[k]}
            for k, v in values.items()}


def check(cfg: dict, traffic, drv, results, slots, seed: int,
          compiles_in_window: int) -> Dict[str, dict]:
    import jax.numpy as jnp
    paired = pair(drv, results, slots)
    values: Dict[str, float] = dict(service_numbers(drv, paired))
    values["compiles_in_window"] = compiles_in_window
    if paired:
        picked = sample(paired, seed)
        ref = replay(cfg, traffic, picked, jnp.float32)
        values.update(compare(served_trace(cfg, picked), ref))
    else:
        values.update(engine_rel_err=float("inf"),
                      controller_mismatch=float("inf"))
    return _with_limits(cfg, values)


def control(cfg: dict, traffic, drv, results, slots, seed: int
            ) -> Dict[str, dict]:
    """The control's readings: the reference in bfloat16 in the program's
    place, on the same sampled windows and entry omegas."""
    import jax.numpy as jnp
    picked = sample(pair(drv, results, slots), seed)
    ref = replay(cfg, traffic, picked, jnp.float32)
    low = replay(cfg, traffic, picked, jnp.bfloat16)
    return _with_limits(cfg, compare(low, ref), prefix="control.")

"""Roofline analytics: the analytic FLOPs model must track XLA's
cost_analysis when no scan undercounting is involved (single-period
models), and the three-term structure must behave sanely."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.model import SHAPES, ShapeSpec
from repro.models import transformer as tfm
from repro.roofline.analysis import (HW, analytic_flops, roofline_terms)


def _flops_of(fn, *args):
    c = jax.jit(fn).lower(*args).compile().cost_analysis()
    return float(c["flops"])


@pytest.mark.parametrize("profile", ["no_such_profile", "paper_fpga_45nm"])
def test_unreadable_roofline_profile_raises(profile):
    """No peak is ever assumed: a profile that does not exist, or has no
    [roofline] section, is an error."""
    from repro.costmodel.profiles import ProfileError
    with pytest.raises(ProfileError):
        HW.from_profile(profile)


def test_analytic_forward_matches_xla_dense():
    """2-layer dense model, scan period == depth (body counted once is the
    whole depth): analytic fwd within 25% of XLA."""
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=256,
                      n_heads=4, n_kv_heads=2, d_ff=1024, vocab_size=512,
                      block_pattern=("attn", "attn"), dtype="float32")
    B, S = 2, 256
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((B, S), jnp.int32)
    xla = _flops_of(lambda p, t: tfm.forward(p, cfg, t, remat_scan=False),
                    params, toks)
    shape = ShapeSpec("x", S, B, "prefill")
    ours = analytic_flops(cfg, shape)["forward"]
    assert abs(ours - xla) / xla < 0.25, (ours, xla)


def test_analytic_forward_matches_xla_moe():
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=128,
                      n_heads=4, n_kv_heads=4, d_ff=256, vocab_size=512,
                      block_pattern=("moe",), n_experts=8,
                      experts_per_token=2, n_shared_experts=1, moe_d_ff=64,
                      dtype="float32")
    B, S = 2, 128
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((B, S), jnp.int32)
    xla = _flops_of(lambda p, t: tfm.forward(p, cfg, t, remat_scan=False),
                    params, toks)
    shape = ShapeSpec("x", S, B, "prefill")
    ours = analytic_flops(cfg, shape)["forward"]
    # capacity-padded expert matmuls make XLA a bit higher; stay in 2x
    assert 0.5 < ours / xla < 2.0, (ours, xla)


def test_train_total_is_4x_forward():
    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128)
    fl = analytic_flops(cfg, SHAPES["train_4k"])
    assert fl["total"] == pytest.approx(4 * fl["forward"])


def test_decode_flops_linear_in_cache():
    """Decode FLOPs grow ~linearly with KV length (per-token attention is
    O(S), never O(S^2))."""
    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128)
    s1 = ShapeSpec("d", 1024, 8, "decode")
    s2 = ShapeSpec("d", 2048, 8, "decode")
    f1 = analytic_flops(cfg, s1)["attn"]
    f2 = analytic_flops(cfg, s2)["attn"]
    assert 1.5 < f2 / f1 < 2.1


def test_local_window_caps_attention():
    cfg = ModelConfig(name="t", family="hybrid", n_layers=3, d_model=64,
                      n_heads=4, n_kv_heads=1, d_ff=128, vocab_size=128,
                      block_pattern=("rglru", "rglru", "local"),
                      local_window=512, supports_long_context=True)
    f_short = analytic_flops(cfg, ShapeSpec("d", 2048, 1, "decode"))
    f_long = analytic_flops(cfg, ShapeSpec("d", 524288, 1, "decode"))
    # attention flops identical once S >> window; rnn flops equal
    assert f_long["attn"] == pytest.approx(f_short["attn"], rel=0.01)


def test_roofline_terms_dominance():
    cfg = ModelConfig(name="t", family="dense", n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128)
    # huge collective bytes -> collective-dominant
    t = roofline_terms(cfg, SHAPES["train_4k"], 256, 1e15)
    assert t["dominant"] == "collective"
    t2 = roofline_terms(cfg, SHAPES["train_4k"], 256, 0.0)
    assert t2["dominant"] in ("compute", "memory")
    assert t2["t_collective"] == 0.0


def test_useful_ratio_below_one_for_train():
    from repro.configs import get_config
    cfg = get_config("deepseek_67b")
    t = roofline_terms(cfg, SHAPES["train_4k"], 256, 0.0)
    assert 0.5 < t["useful_ratio"] < 1.0


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %ag = bf16[4,1024]{1,0} all-gather(%x), replica_groups={}
      %ar = f32[256]{0} all-reduce(%y), to_apply=%sum
      %rs = f32[2,128]{1,0} reduce-scatter(%z)
      %cp = bf16[8]{0} collective-permute(%w)
      %a2a = f32[16,16]{1,0} all-to-all(%v)
    """
    got = collective_bytes(hlo)
    assert got["all-gather"] == 4 * 1024 * 2
    assert got["all-reduce"] == 256 * 4
    assert got["reduce-scatter"] == 2 * 128 * 4
    assert got["collective-permute"] == 8 * 2
    assert got["all-to-all"] == 16 * 16 * 4
    assert got["total"] == sum(v for k, v in got.items() if k != "total")

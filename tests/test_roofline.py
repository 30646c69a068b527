"""Roofline analysis tests: HLO collective parsing, the compositional
cost assembly validated against a no-scan compile, and analytic
recurrence costs cross-checked against an unrolled lowering."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs.base import get_config
from repro.configs.shapes import InputShape
from repro.roofline.analysis import (collective_bytes, model_flops,
                                     roofline_terms)

HLO_SAMPLE = """
  %p = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %p), replica_groups={}
  %ag = bf16[64,512]{1,0} all-gather(bf16[64,32]{1,0} %x), dimensions={1}
  %rs = f32[8,16]{1,0} reduce-scatter(f32[128,16]{1,0} %y), dimensions={0}
  %tup = (f32[4,4]{1,0}, f32[4,4]{1,0}) all-to-all(f32[4,4]{1,0} %a, f32[4,4]{1,0} %b)
  %cp = u8[1024]{0} collective-permute(u8[1024]{0} %z), source_target_pairs={{0,1}}
  %dot = f32[128,256]{1,0} dot(f32[128,64]{1,0} %q, f32[64,256]{1,0} %w)
"""


def test_collective_parser_kinds_and_bytes():
    weighted, kinds = collective_bytes(HLO_SAMPLE)
    assert kinds["all-reduce"] == 128 * 256 * 4
    assert kinds["all-gather"] == 64 * 512 * 2
    assert kinds["reduce-scatter"] == 8 * 16 * 4
    assert kinds["all-to-all"] == 2 * 4 * 4 * 4
    assert kinds["collective-permute"] == 1024
    expect = (2 * 128 * 256 * 4 + 64 * 512 * 2 + 8 * 16 * 4 +
              2 * 4 * 4 * 4 + 1024)
    assert weighted == expect


def test_collective_parser_ignores_dots():
    _, kinds = collective_bytes("%d = f32[8,8]{1,0} dot(f32[8,8] %a)")
    assert kinds == {}


def test_roofline_terms_dominance():
    r = roofline_terms(1e15, 1e9, "")          # huge flops, few bytes
    assert r.dominant == "compute"
    r2 = roofline_terms(1e9, 1e12, "")
    assert r2.dominant == "memory"


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the device-kind table; a chip without published
    peaks is an error, never a silent v5e default."""
    r = roofline_terms(197e12, 0.0, "", device_kind="TPU v5 lite")
    assert r.compute_s == pytest.approx(1.0)
    with pytest.raises(KeyError, match="no published peaks"):
        roofline_terms(1e12, 1e9, "", device_kind="cpu")


def test_model_flops_modes():
    cfg = get_config("qwen3-0.6b")
    train = InputShape("t", 1024, 8, "train")
    dec = InputShape("d", 1024, 8, "decode")
    n = cfg.active_param_count()
    assert model_flops(cfg, train) == 6.0 * n * 8 * 1024
    assert model_flops(cfg, dec) == 2.0 * n * 8


def test_moe_active_params_lower():
    dbrx = get_config("dbrx-132b")
    assert dbrx.active_param_count() < 0.5 * dbrx.param_count()


@pytest.mark.slow
def test_compositional_assembly_matches_unscanned_compile():
    """A 2-layer model has ONE scan iteration, so its full-compile
    cost_analysis is exact — the compositional assembly (head + 2 x layer)
    must agree on FLOPs within fusion noise (the method's validation)."""
    from repro.launch.dryrun import assemble_cost, lower_step, _cost
    from repro.models.api import Model

    cfg = dataclasses.replace(
        get_config("qwen3-0.6b").reduced(), num_layers=2, dtype="float32")
    model = Model(cfg)
    shape = InputShape("tiny_train", 64, 4, "train")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    compiled, _ = lower_step(model, shape, mesh, "tp")
    full = _cost(compiled)
    asm = assemble_cost(model, shape, mesh, "tp")
    composed = asm["per_device"]["flops"]
    # The composition is a mild UPPER bound: XLA fuses/CSEs across layer
    # boundaries in the full program, and the per-layer probe adds its own
    # reduction.  Measured bias ~ +40% on this config; require <= +50% and
    # the same magnitude (the table reports dominance, not microseconds).
    assert composed == pytest.approx(full["flops"], rel=0.5), \
        (composed, full["flops"])
    assert composed >= 0.8 * full["flops"]          # never an undercount


@pytest.mark.slow
def test_recurrence_analytic_vs_unrolled():
    """ssm.recurrence_cost against cost_analysis of a python-unrolled
    (scan-free) recurrence: within 3x (constant-factor model)."""
    from repro.models import ssm as ssm_mod

    cfg = get_config("jamba-1.5-large-398b").reduced()
    b, s = 2, 32
    di, n = ssm_mod.d_inner(cfg), cfg.ssm_state_dim

    def unrolled(dt, bm, cm, xc):
        h = jnp.zeros((b, di, n))
        a = -jnp.ones((di, n))
        ys = []
        for t in range(s):
            decay = jnp.exp(dt[:, t][..., None] * a[None])
            h = decay * h + (dt[:, t] * xc[:, t])[..., None] * bm[:, t][:, None, :]
            ys.append(jnp.einsum("bdn,bn->bd", h, cm[:, t]))
        return jnp.stack(ys, 1)

    args = (jnp.ones((b, s, 1)), jnp.ones((b, s, n)), jnp.ones((b, s, n)),
            jnp.ones((b, s, di)))
    compiled = jax.jit(unrolled).lower(*args).compile()
    from repro.launch.dryrun import _cost

    hlo_flops = _cost(compiled)["flops"]
    analytic, _ = ssm_mod.recurrence_cost(cfg, b, s)
    assert analytic == pytest.approx(hlo_flops, rel=2.0), \
        (analytic, hlo_flops)

"""Sharding rules: divisibility fallbacks, param/spec tree congruence, and
the jaxpr cost counter's calibration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced_config
from repro.models import abstract_params
from repro.parallel import sharding as shd
from repro.runtime import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_spec_drops_nondivisible(mesh):
    rules = {"a": "model", "b": ("pod", "data")}
    # 'model' size 1 divides everything -> kept
    assert shd.spec_for((7, 4), ("a", "b"), rules, mesh) == P(None, "data") \
        or shd.spec_for((7, 4), ("a", "b"), rules, mesh) == P("model",
                                                              ("data",))


def test_param_sharding_tree_matches(mesh):
    for arch in ("smollm-360m", "deepseek-v2-lite-16b", "hymba-1.5b",
                 "seamless-m4t-large-v2"):
        cfg = get_config(arch)
        params = abstract_params(cfg)
        sh = shd.param_shardings(cfg, mesh)
        # identical tree structure
        jax.tree.map(lambda a, b: None, params, sh)


def test_head_divisibility_rules():
    """q/kv head sharding only when head count divides TP degree."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    cfg = get_config("smollm-360m")       # 15 heads, kv 5
    rules = shd.logical_rules(cfg, FakeMesh())
    assert rules["q_proj"] is None and rules["kv_proj"] is None
    cfg = get_config("qwen3-32b")         # 64 heads, kv 8
    rules = shd.logical_rules(cfg, FakeMesh())
    assert rules["q_proj"] == "model" and rules["kv_proj"] is None
    cfg = get_config("deepseek-v2-lite-16b")   # 64 experts -> EP
    rules = shd.logical_rules(cfg, FakeMesh())
    assert rules["experts"] == "model"
    cfg = get_config("grok-1-314b")            # 8 experts -> internal TP
    rules = shd.logical_rules(cfg, FakeMesh())
    assert rules["experts"] is None and rules["expert_mlp"] == "model"


def test_jaxpr_counter_calibration():
    from repro.launch.counting import jaxpr_costs
    L, B, D = 4, 32, 64

    def f(x, ws):
        def body(h, w):
            return h @ w, None
        out, _ = jax.lax.scan(body, x, ws)
        return out.sum()

    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    c = jaxpr_costs(f, x, ws)
    expect = 2 * L * B * D * D
    assert abs(c["dot_flops"] - expect) / expect < 0.01
    g = jaxpr_costs(jax.grad(f, argnums=1), x, ws)
    assert abs(g["dot_flops"] - 3 * expect) / (3 * expect) < 0.01


def test_batch_sharding_nondivisible(mesh):
    s = shd.batch_sharding(mesh, (3, 5))
    assert s.spec == P(("data",), None) or s.spec == P()

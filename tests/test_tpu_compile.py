"""Compile for a described TPU v5e chip, with no chip attached.

Interpret mode runs a Pallas kernel body in Python and accepts layouts
the chip's compiler refuses; these tests hand the real compiler the main
path's kernels at published widths, the one-chip smoke's whole train
step and the four-chip smoke's executor steps (``chip_smoke.py``), so
such a refusal fails here instead of on the chip.  Nothing runs: a
passing compile says nothing about results or times.

The topology is described only inside the ``topo`` fixture, never while
a module is imported: one process at a time may load the TPU library, so
only the test worker that is given this file loads it.  Where no v5e can
be described the fixture skips.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.models import init_pipeline_params
from repro.train import init_train_state

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    """chip_smoke.py as a module; importing it does no JAX work."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


smoke = _load_smoke()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "glm4-9b"])
def test_flash_attention_compiles_at_published_widths(one_chip, arch):
    cfg = get_config(arch)
    S, hd = 2048, cfg.resolved_head_dim
    q = jax.ShapeDtypeStruct((1, S, cfg.num_heads, hd), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, cfg.num_kv_heads, hd), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(flash_attention_fwd).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [2048, 250])
def test_rmsnorm_compiles_for_any_row_count(one_chip, rows):
    d = get_config("phi4-mini-3.8b").d_model
    x = jax.ShapeDtypeStruct((rows, d), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(rmsnorm_fwd).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_smoke_train_step_fits_one_chip(one_chip):
    _, step, state, batch = smoke.train_step_program()
    compiled = step.lower(_on(one_chip, state), _on(one_chip, batch)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0, "the train state is not donated"
    assert smoke.device_bytes(mem) < smoke.HBM_BYTES, (
        f"{smoke.device_bytes(mem)} B do not fit one v5e chip")


@pytest.mark.parametrize(
    "kind,fact,microbatches", smoke.FOUR_CHIP_CASES,
    ids=[f"{k}-{'x'.join(map(str, f))}" for k, f, _ in smoke.FOUR_CHIP_CASES])
def test_four_chip_hybrid_step_compiles(topo, kind, fact, microbatches):
    """``chip_smoke.py --chips 4``'s executor steps over the described 2x2
    host: each compiles, and the collectives its live axes need are in
    the program (ring hops for pipe/ctx/model, the AllToAll for ep)."""
    from jax.sharding import NamedSharding, PartitionSpec

    cfg = smoke.golden_configs()[0][kind]
    mesh, opt, specs, step = smoke.hybrid_program(cfg, fact, microbatches,
                                                  devices=topo.devices)
    assert mesh.devices.size == 4
    rep = NamedSharding(mesh, PartitionSpec())
    params = jax.eval_shape(
        lambda k: init_pipeline_params(cfg, k, mesh.shape["pipe"]),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    state = jax.eval_shape(lambda p: init_train_state(cfg, p, opt), params)
    state = {k: _on(rep, v) for k, v in state.items()}
    state["params"] = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        params, specs)
    tok = jax.ShapeDtypeStruct((16, 16), jnp.int32, sharding=rep)
    with jax.default_matmul_precision("highest"):
        compiled = step.lower(state, {"tokens": tok, "labels": tok}).compile()
    hlo = compiled.as_text()
    dp, S, cp, tp, ep = fact
    if S > 1 or cp > 1 or tp > 1:
        assert "collective-permute" in hlo
    if dp > 1:
        assert "all-reduce" in hlo
    if ep > 1:
        assert "all-to-all" in hlo

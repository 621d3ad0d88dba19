"""Context parallelism (ring attention) on 8 real devices (DESIGN §6).

Covers the PR's acceptance bar: KVRingShift passes the generic Eq. 13
harness on 1-D and 4-D meshes; ring attention matches blockwise attention
in forward AND vjp; the (dp, pp, cp, tp) = (2, 1, 2, 2) and (1, 1, 4, 2)
hybrid steps match the single-device fp32 reference in loss AND every
parameter gradient; cp=1 byte-equals the PR 3 hybrid path; S not divisible
by cp raises at trace time; GQA with num_kv_heads below the TP degree
still ring-rotates correctly; and the compiled CP train step contains NO
sequence-dim all-gather (the SP->TP gather the ring eliminates) while the
SP baseline does.

The whole file is marked ``slow`` (about 530 s on 8 emulated devices):
the CI slow leg runs it, and the tier-1 md subprocess keeps (2, 1, 2, 2)
CP coverage through ``test_golden.py``'s ``hybrid_cp_2x1x2x2``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import ModelConfig
from repro.core import linop, primitives as prim
from repro.core.linop import check_adjoint
from repro.core.pipeline import make_schedule, pipeline_value_and_grad
from repro.core.ring_attention import ring_attention, ring_attention_gspmd
from repro.launch.mesh import make_hybrid_mesh
from repro.models import init_pipeline_params, pipeline_fns, pipeline_param_parts
from repro.models.attention import blockwise_attention
from repro.sharding import Partitioned, Policy
from repro.train import cross_entropy

from test_hybrid import (CFG, _assert_matches_reference, _assert_trees_close,
                         _data)

from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.slow


def _need8():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 host devices")


# ---------------------------------------------------------------------------
# KVRingShift: the operator itself (acceptance: Eq. 13 on 1-D and 4-D).
# ---------------------------------------------------------------------------

class TestKVRingShiftAdjoint:
    def test_eq13_on_1d_mesh(self):
        _need8()
        mesh = compat.make_mesh((8,), ("ctx",))
        for off in (-3, -1, 1, 2):
            r = check_adjoint(linop.KVRingShift("ctx", off), mesh, (16, 4))
            assert r.passed, r

    def test_eq13_on_4d_mesh(self):
        _need8()
        mesh = compat.make_mesh((2, 1, 2, 2), ("data", "pipe", "ctx", "model"))
        for off in (-1, 1):
            r = check_adjoint(linop.KVRingShift("ctx", off), mesh, (8, 4))
            assert r.passed, r

    def test_full_ring_is_identity(self):
        """k cyclic hops of offset 1 compose to the identity permutation."""
        _need8()
        mesh = compat.make_mesh((8,), ("ctx",))
        chain = linop.KVRingShift("ctx", 1)
        for _ in range(7):
            chain = linop.KVRingShift("ctx", 1) @ chain
        F = linop.lift(chain, mesh, 2)
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 3))
        np.testing.assert_array_equal(np.asarray(F(x)), np.asarray(x))

    def test_structural_adjoint_registry(self):
        assert linop.KVRingShift("ctx", 1).T == linop.KVRingShift("ctx", -1)
        assert linop.KVRingShift("ctx", -2).T.T == linop.KVRingShift("ctx", -2)


# ---------------------------------------------------------------------------
# ring_attention vs blockwise_attention: forward AND vjp.
# ---------------------------------------------------------------------------

class TestRingMatchesBlockwise:
    @pytest.mark.parametrize("KH,causal", [(8, True), (2, True), (1, True),
                                           (4, False)])
    def test_fwd_and_grads(self, KH, causal):
        _need8()
        mesh = compat.make_mesh((8,), ("ctx",))
        B, S, H, hd = 2, 64, 8, 16
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KH, hd),
                              jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KH, hd),
                              jnp.float32)
        f = prim.smap(
            lambda q, k, v: ring_attention(q, k, v, "ctx", chunk=16,
                                           causal=causal),
            mesh, (P(None, "ctx"),) * 3, P(None, "ctx"))
        out, vjp = jax.vjp(f, q, k, v)
        ref, vjp_ref = jax.vjp(
            lambda q, k, v: blockwise_attention(q, k, v, chunk=16,
                                                causal=causal), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g = jax.random.normal(jax.random.fold_in(key, 3), out.shape)
        for got, want, name in zip(vjp(g), vjp_ref(g), "qkv"):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=5e-4, atol=5e-5, err_msg=name)


# ---------------------------------------------------------------------------
# The hybrid executor with a live ctx axis (acceptance factorizations).
# ---------------------------------------------------------------------------

def _cp_loss_and_grads(mesh, M, *, explicit_tp=True, pparams=None,
                       schedule_name="1f1b"):
    """test_hybrid's executor driver, ctx-aware: microbatch rows ride the
    data axis AND sequence positions the ctx axis at the region boundary."""
    S = dict(zip(mesh.axis_names, mesh.devices.shape)).get("pipe", 1)
    pol = Policy.for_mesh(mesh, explicit_tp=explicit_tp)
    if pparams is None:
        pparams = init_pipeline_params(CFG, jax.random.PRNGKey(0), S)
    xs, ys = _data(M, 4 * M, 16)
    pre_fn, stage_fn, logits_fn = pipeline_fns(CFG, pol)

    def post_fn(p_post, y, labels):
        return cross_entropy(logits_fn(p_post, y), labels)[0]

    mb_part = Partitioned(None, "data", "ctx")
    f = pipeline_value_and_grad(
        pre_fn, stage_fn, post_fn, pol, make_schedule(schedule_name, M, S),
        params_parts=pipeline_param_parts(CFG, pol, pparams),
        x_parts={"tokens": mb_part}, y_parts=mb_part,
        pre_psum_axes=(pol.model_axis,) if explicit_tp else ())
    loss, grads = f(pparams, xs, ys)
    return pparams, xs, ys, loss, grads


class TestCPMatchesReference:
    def test_2dp_1stage_2cp_2tp(self):
        """Acceptance: (dp, pp, cp, tp) = (2, 1, 2, 2) vs the fp32
        single-device loss and EVERY parameter gradient."""
        _need8()
        _assert_matches_reference(
            *_cp_loss_and_grads(make_hybrid_mesh(2, 1, 2, 2), M=4))

    def test_1dp_1stage_4cp_2tp(self):
        """Acceptance: (1, 1, 4, 2) — a deeper ring, same reference."""
        _need8()
        _assert_matches_reference(
            *_cp_loss_and_grads(make_hybrid_mesh(1, 1, 4, 2), M=4))

    def test_cp_without_tp(self):
        """(2, 1, 4, 1): the non-explicit stage-body branch also rings."""
        _need8()
        _assert_matches_reference(
            *_cp_loss_and_grads(make_hybrid_mesh(2, 1, 4, 1), M=4,
                                explicit_tp=False))

    def test_cp_composes_with_pipe(self):
        """(1, 2, 2, 2): ctx rings inside pipeline stage bodies."""
        _need8()
        _assert_matches_reference(
            *_cp_loss_and_grads(make_hybrid_mesh(1, 2, 2, 2), M=4))


class TestDegenerateCP:
    def test_cp1_returns_the_3d_mesh(self):
        """make_hybrid_mesh(cp=1) IS the PR 3 mesh — the cp=1 program is
        byte-identical to the 3-D hybrid path by construction."""
        _need8()
        mesh = make_hybrid_mesh(2, 2, 1, tp=2)
        assert mesh.axis_names == ("data", "pipe", "model")

    def test_size1_ctx_axis_deactivates(self):
        """A literal size-1 ctx axis also degenerates: active_ctx_axis is
        None (a 1-hop ring would still trace its ppermutes), logical
        "ctx"/"seq" resolve as today, and the executor matches the 3-D
        path step for step."""
        _need8()
        m4 = compat.make_mesh((2, 2, 1, 2), ("data", "pipe", "ctx", "model"))
        pol = Policy.for_mesh(m4, explicit_tp=True)
        assert pol.active_ctx_axis is None and pol.ctx_size == 1
        assert pol.phys("ctx") is None

        pparams = init_pipeline_params(CFG, jax.random.PRNGKey(0), 2)
        *_, loss4, grads4 = _cp_loss_and_grads(m4, M=4, pparams=pparams)
        *_, loss3, grads3 = _cp_loss_and_grads(
            make_hybrid_mesh(2, 2, 1, tp=2), M=4, pparams=pparams)
        np.testing.assert_allclose(float(loss4), float(loss3), rtol=1e-6)
        _assert_trees_close(grads4, grads3)

    def test_seq_not_divisible_raises_executor(self):
        _need8()
        from repro.optim import make_optimizer
        from repro.train import build_hybrid_train_step, init_train_state

        pol = Policy.for_mesh(make_hybrid_mesh(1, 1, 4, 2), explicit_tp=True)
        opt = make_optimizer("adamw", total_steps=10)
        step = build_hybrid_train_step(CFG, pol, opt, num_microbatches=2)
        params = init_pipeline_params(CFG, jax.random.PRNGKey(0), 1)
        state = init_train_state(CFG, params, opt)
        bad = {"tokens": jnp.zeros((8, 18), jnp.int32),
               "labels": jnp.zeros((8, 18), jnp.int32)}
        with pytest.raises(ValueError, match="not divisible"):
            step(state, bad)

    def test_seq_not_divisible_raises_gspmd(self):
        _need8()
        mesh = compat.make_mesh((1, 8, 1), ("data", "ctx", "model"))
        pol = Policy(mesh=mesh, ctx_axis="ctx")
        q = jnp.zeros((2, 20, 4, 8), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention_gspmd(q, q, q, pol, chunk=8)


class TestFusedTPRing:
    def test_gspmd_explicit_tp_with_ctx(self):
        """forward() on a (data, ctx, model) mesh with explicit_tp: the
        fused dist_jit sublayer keeps the seq dim ctx-sharded at its
        boundary and rings inside — loss and every grad match policy=None."""
        _need8()
        from repro.models import forward, init_params

        key = jax.random.PRNGKey(3)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0, 128),
                 "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                              (8, 32), 0, 128)}
        params = init_params(CFG, jax.random.PRNGKey(0))

        def loss_fn(pol):
            def f(p):
                logits, _, _ = forward(p, batch, CFG, pol, mode="train")
                return cross_entropy(logits, batch["labels"])[0]
            return f

        l0, g0 = jax.value_and_grad(loss_fn(None))(params)
        mesh = compat.make_mesh((2, 2, 2), ("data", "ctx", "model"))
        pol = Policy(mesh=mesh, ctx_axis="ctx", explicit_tp=True)
        l1, g1 = jax.jit(jax.value_and_grad(loss_fn(pol)))(params)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
        flat0 = dict(jax.tree_util.tree_leaves_with_path(g0))
        for path, leaf in jax.tree_util.tree_leaves_with_path(g1):
            np.testing.assert_allclose(np.asarray(leaf),
                                       np.asarray(flat0[path]),
                                       rtol=5e-4, atol=5e-4,
                                       err_msg=str(path))


class TestGQARotation:
    def test_kv_heads_below_tp_degree(self):
        """GQA with num_kv_heads < tp: KV heads cannot shard the model
        axis, so the GSPMD dispatch repeats them to the full query-head
        count before the ring — forward and vjp still match blockwise."""
        _need8()
        mesh = compat.make_mesh((1, 2, 4), ("data", "ctx", "model"))
        pol = Policy(mesh=mesh, ctx_axis="ctx")
        assert pol.model_size == 4
        B, S, H, KH, hd = 2, 32, 8, 2, 16     # KH=2 < tp=4
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KH, hd),
                              jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KH, hd),
                              jnp.float32)
        out, vjp = jax.vjp(
            lambda q, k, v: ring_attention_gspmd(q, k, v, pol, chunk=8),
            q, k, v)
        ref, vjp_ref = jax.vjp(
            lambda q, k, v: blockwise_attention(q, k, v, chunk=8), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g = jax.random.normal(jax.random.fold_in(key, 3), out.shape)
        for got, want, name in zip(vjp(g), vjp_ref(g), "qkv"):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=5e-4, atol=5e-5, err_msg=name)


# ---------------------------------------------------------------------------
# Perf evidence: the sequence all-gather is GONE from the compiled HLO.
# ---------------------------------------------------------------------------

class TestCompiledHLO:
    def test_no_seq_allgather_under_cp(self):
        """The SP baseline's compiled train step all-gathers the sequence
        dim in the attention region; the CP program must not — and its
        largest activation shrinks ~cp-fold (structural stand-ins for the
        TPU memory win; see roofline/hlo_profile.py)."""
        _need8()
        from repro.models import init_params
        from repro.optim import make_optimizer
        from repro.roofline.hlo_profile import (collective_inventory,
                                                peak_activation_bytes,
                                                seq_dim_allgather_bytes)
        from repro.train import build_train_step, init_train_state

        # S chosen distinct from every other global dim (d_model, vocab,
        # d_ff) so the structural scan cannot alias.
        cfg = ModelConfig(name="hlo", family="dense", num_layers=2,
                          d_model=64, num_heads=8, num_kv_heads=4,
                          head_dim=8, d_ff=128, vocab_size=256,
                          dtype="float32", remat=False, attn_chunk=24)
        B, S, cp = 8, 96, 4
        key = jax.random.PRNGKey(3)
        batch = {"tokens": jax.random.randint(key, (B, S), 0, 256),
                 "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                              (B, S), 0, 256)}
        opt = make_optimizer("adamw", total_steps=10)
        params = init_params(cfg, jax.random.PRNGKey(0))

        def compiled(pol):
            step = jax.jit(build_train_step(cfg, pol, opt))
            state = init_train_state(cfg, params, opt)
            comp = step.lower(state, batch).compile()
            _, m = step(state, batch)
            return comp.as_text(), float(m["loss"])

        hlo_sp, loss_sp = compiled(
            Policy(mesh=compat.make_mesh((1, 8), ("data", "model"))))
        hlo_cp, loss_cp = compiled(
            Policy(mesh=compat.make_mesh((1, cp, 2), ("data", "ctx", "model")),
                   ctx_axis="ctx"))
        np.testing.assert_allclose(loss_cp, loss_sp, rtol=1e-4)

        assert seq_dim_allgather_bytes(hlo_sp, S) > 0, \
            "baseline lost its SP->TP gather; the comparison is vacuous"
        assert seq_dim_allgather_bytes(hlo_cp, S) == 0, \
            collective_inventory(hlo_cp)
        assert collective_inventory(hlo_cp).get(
            "collective-permute", (0, 0))[0] > 0   # the ring is really there
        peak_sp, peak_cp = (peak_activation_bytes(hlo_sp),
                            peak_activation_bytes(hlo_cp))
        assert peak_cp * (cp // 2) <= peak_sp, (peak_sp, peak_cp)


class TestCPSmoke:
    def test_2x1x2x2_two_steps(self):
        """The default-md-run smoke: the (2, 1, 2, 2) hybrid CP step runs,
        learns on a repeated batch, and reports finite metrics."""
        _need8()
        from repro.optim import make_optimizer
        from repro.train import build_hybrid_train_step, init_train_state

        pol = Policy.for_mesh(make_hybrid_mesh(2, 1, 2, 2), explicit_tp=True)
        assert pol.active_ctx_axis == "ctx" and pol.ctx_size == 2
        key = jax.random.PRNGKey(3)
        batch = {"tokens": jax.random.randint(key, (16, 16), 0, 128),
                 "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                              (16, 16), 0, 128)}
        opt = make_optimizer("adamw", total_steps=10)
        step = jax.jit(build_hybrid_train_step(CFG, pol, opt,
                                               num_microbatches=4))
        params = init_pipeline_params(CFG, jax.random.PRNGKey(0), 1)
        state = init_train_state(CFG, params, opt)
        state, m1 = step(state, batch)
        state, m2 = step(state, batch)
        assert int(state["step"]) == 2
        assert np.isfinite(float(m1["loss"]))
        assert float(m2["loss"]) < float(m1["loss"])

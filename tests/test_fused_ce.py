"""Fused Pallas cross-entropy (ops/fused_ce.py): value + gradient parity
against the XLA logsumexp path, shard_map composition, and the
transformer integration pinned against the unsharded golden model.

Runs the kernels interpreted on the CPU mesh (same shapes the TPU path
tiles); the real-chip numbers live in BENCH (transformer_train_v1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models import transformer as T
from mmlspark_tpu.ops.fused_ce import fused_ce_available, fused_softmax_xent
from mmlspark_tpu.parallel.topology import MeshSpec, build_mesh


def submesh(shape):
    n = int(np.prod(list(shape.values())))
    return build_mesh(MeshSpec.from_dict(shape), devices=jax.devices()[:n])


def _ref_ce(h, w, labels):
    logits = h @ w
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


class TestFusedCE:

    @pytest.mark.parametrize("t,d,v", [
        (64, 128, 512),      # tile-aligned-ish
        (96, 128, 300),      # unaligned T and V (pad + mask paths)
        (512, 256, 1024),
    ])
    def test_value_and_grads_match_xla(self, rng, t, d, v):
        h = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32) * 0.1)
        lbl = jnp.asarray(rng.integers(0, v, t).astype(np.int32))
        mask = jnp.asarray((rng.uniform(size=t) > 0.2).astype(np.float32))

        def loss(fn):
            def f(h_, w_):
                ce = fn(h_, w_)
                return jnp.sum(ce * mask) / jnp.sum(mask)
            return f

        l0, g0 = jax.value_and_grad(
            loss(lambda a, b: _ref_ce(a, b, lbl)), argnums=(0, 1))(h, w)
        l1, g1 = jax.value_and_grad(
            loss(lambda a, b: fused_softmax_xent(a, b, lbl,
                                                 interpret=True)),
            argnums=(0, 1))(h, w)
        np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g0[0]),
                                   atol=3e-5)
        np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g0[1]),
                                   atol=3e-5)

    def test_bf16_compute_dtype(self, rng):
        """bf16 matmul inputs + stored logits: values track the f32
        reference within bf16 tolerance, grads keep the right scale."""
        t, d, v = 128, 128, 512
        h = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32) * 0.1)
        lbl = jnp.asarray(rng.integers(0, v, t).astype(np.int32))
        ce_ref = _ref_ce(h, w, lbl)
        ce = fused_softmax_xent(h, w, lbl, compute_dtype=jnp.bfloat16,
                                interpret=True)
        assert ce.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(ce), np.asarray(ce_ref),
                                   rtol=0.05, atol=0.05)

    @pytest.mark.skipif(
        not hasattr(jax, "typeof"),
        reason="fused kernels target the VMA-era jax API (jax.typeof, "
               "ShapeDtypeStruct(vma=...)); this jax predates it")
    def test_bf16_grads_track_f32_reference(self, rng):
        """value_and_grad through the bf16 compute-dtype path vs the f32
        reference (ADVICE r5): the backward rebuilds softmax
        probabilities from logits STORED in bf16, so its gradients carry
        bf16 rounding the XLA path does not — this pins the error
        magnitude of that stored-logits tradeoff so a regression (e.g.
        accidentally dropping to fp16 accumulation, or re-materializing
        in the wrong dtype) is caught, not silent."""
        t, d, v = 128, 128, 512
        h = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32) * 0.1)
        lbl = jnp.asarray(rng.integers(0, v, t).astype(np.int32))

        def loss(fn):
            def f(h_, w_):
                return jnp.mean(fn(h_, w_))
            return f

        l0, (gh0, gw0) = jax.value_and_grad(
            loss(lambda a, b: _ref_ce(a, b, lbl)), argnums=(0, 1))(h, w)
        l1, (gh1, gw1) = jax.value_and_grad(
            loss(lambda a, b: fused_softmax_xent(
                a, b, lbl, compute_dtype=jnp.bfloat16, interpret=True)),
            argnums=(0, 1))(h, w)
        assert gh1.dtype == gw1.dtype == jnp.float32
        np.testing.assert_allclose(float(l1), float(l0), rtol=0.02)
        # calibrated against bf16's ~8-bit mantissa: probabilities
        # carry ~4e-3 relative rounding, so the worst grad element
        # lands ~0.5% of the reference grad's PEAK (measured 0.47%
        # for dh, 0.63% for dW at this seed; the f32 path sits at
        # ~1e-7). The bound is peak-RELATIVE — the mean reduction
        # scales every grad by 1/t, so any absolute atol here either
        # goes vacuous (atol > peak: even zero grads pass) or
        # over-tightens the moment t changes. 2% = 3-4x margin over
        # the measured bf16 error while a precision regression
        # (fp16 accumulation, wrong-dtype rematerialization) or a
        # broken backward (zero grads err at 100% of peak) is far
        # outside it.
        for got, ref in ((gh1, gh0), (gw1, gw0)):
            peak = float(jnp.abs(ref).max())
            assert peak > 0.0
            err = float(jnp.abs(got - ref).max())
            assert err <= 0.02 * peak, (err, peak)

    def test_inside_shard_map(self, rng):
        """Composes under VMA-checked shard_map: varying dh, psum'd
        (invariant) dW for the replicated head weight."""
        mesh = submesh({"data": 4})
        t, d, v = 64, 128, 300
        h = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(d, v)).astype(np.float32) * 0.1)
        lbl = jnp.asarray(rng.integers(0, v, t).astype(np.int32))
        from jax.sharding import PartitionSpec as P

        def local(h_, w_, lbl_):
            ce = fused_softmax_xent(h_, w_, lbl_, interpret=True)
            return jax.lax.psum(jnp.sum(ce), "data") / t

        # check_vma=False: interpret-mode kernels cannot be re-typed
        # by the HLO interpreter's vma pass (see ops/fused_ce.py); the
        # replicated-weight grad psum is still inserted by the
        # shard_map transpose, which this test pins
        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(P("data"), P(), P("data")),
                          out_specs=P(), check_vma=False)
        loss, (dh, dw) = jax.value_and_grad(
            lambda a, b: f(a, b, lbl), argnums=(0, 1))(h, w)
        l0, (dh0, dw0) = jax.value_and_grad(
            lambda a, b: jnp.mean(_ref_ce(a, b, lbl)), argnums=(0, 1))(h, w)
        np.testing.assert_allclose(float(loss), float(l0), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(dh0),
                                   atol=3e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(dw0),
                                   atol=3e-5)

    def test_availability_gate(self):
        on_tpu = jax.default_backend() == "tpu"
        assert fused_ce_available(8192, 512, 32768) == on_tpu
        assert not fused_ce_available(8192, 200, 32768)  # d not lane-aligned
        # wide models exceed the kernels' VMEM budget (they block-load
        # all of d): auto must fall back to xla, not fail the compile
        assert not fused_ce_available(8192, 2048, 32768)
        # tiny local token counts would pad 8x past the XLA cost
        assert not fused_ce_available(64, 512, 32768)


class TestTransformerFusedCE:

    _CFG = dict(vocab=256, d_model=128, n_heads=2, d_head=16, d_ff=64,
                layers_per_stage=1)

    @pytest.mark.slow
    def test_train_step_matches_golden_single_device(self):
        """ce_impl='fused_interpret' inside the SPMD step reproduces the
        unsharded reference_loss update exactly — params included
        (VERDICT r4 #1: grad parity pinned against
        models/transformer.reference_loss). Single-device mesh: the one
        place check_vma=False is sound (see build_spmd_train_step)."""
        cfg = T.TransformerConfig(**self._CFG, ce_impl="fused_interpret")
        mesh = submesh({"data": 1})
        params = T.init_params(cfg, seed=0)
        rng = np.random.default_rng(1)
        tokens, labels, mask = T.make_batch(rng, cfg, 4, 16)

        ref_p, ref_v = params, jax.tree.map(jnp.zeros_like, params)
        for _ in range(2):
            loss_ref, g = jax.value_and_grad(T.reference_loss)(
                ref_p, tokens, labels, mask, cfg)
            ref_v = jax.tree.map(lambda v, gr: 0.9 * v + gr, ref_v, g)
            ref_p = jax.tree.map(lambda p, v: p - 0.1 * v, ref_p, ref_v)

        step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9, donate=False,
                                       check_vma=False)
        sp = T.shard_params(params, cfg, mesh)
        sv = T.shard_params(jax.tree.map(jnp.zeros_like, params), cfg, mesh)
        for _ in range(2):
            sp, sv, loss_sh = step(sp, sv, tokens, labels, mask)
        assert abs(float(loss_ref) - float(loss_sh)) < 2e-5
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             jax.device_get(sp), jax.device_get(ref_p))
        assert max(jax.tree_util.tree_leaves(diffs)) < 5e-5

    @pytest.mark.slow
    def test_sharded_local_loss_grads_match_xla(self):
        """On a real multi-axis mesh, the fused kernel's local_loss
        gradients equal the XLA CE path's exactly (same psum structure,
        same cotangents) — the sharded half of the golden pin above."""
        import dataclasses
        from jax.sharding import PartitionSpec as P
        from mmlspark_tpu.models.transformer import (
            _Axes, local_loss, param_specs)

        cfg_f = T.TransformerConfig(**self._CFG, ce_impl="fused_interpret")
        cfg_x = dataclasses.replace(cfg_f, ce_impl="xla")
        params = T.init_params(cfg_f, seed=0)
        rng = np.random.default_rng(1)
        tokens, labels, mask = T.make_batch(rng, cfg_f, 4, 16)
        mesh = submesh({"data": 2, "seq": 2})
        ax = _Axes.of(mesh)
        specs = param_specs(cfg_f, mesh)
        data_spec = P(ax.data, ax.seq)

        def grads(cfg):
            def local(p, tok, lab, m):
                return jax.value_and_grad(local_loss)(
                    p, tok, lab, m, cfg, ax)
            f = jax.shard_map(
                local, mesh=mesh,
                in_specs=(specs, data_spec, data_spec, data_spec),
                out_specs=(P(), specs), check_vma=False)
            return f(params, tokens, labels, mask)

        lx, gx = grads(cfg_x)
        lf, gf = grads(cfg_f)
        assert abs(float(lx) - float(lf)) < 1e-6
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             jax.device_get(gx), jax.device_get(gf))
        assert max(jax.tree_util.tree_leaves(diffs)) < 1e-6

    @pytest.mark.tpu
    def test_check_vma_true_multishard_grad_parity(self):
        """ADVICE r5 (medium): the compiled ``check_vma=True`` VMA/pcast
        contract in ops/fused_ce.py (pcast-to-union inputs,
        psum-via-pvary-transpose for the replicated head/embed grads)
        had zero automated coverage — and the guard test below shows the
        failure mode is silently under-reduced gradients. This runs the
        fused path under ``check_vma=True`` (the production default) on
        a multi-shard mesh and pins the 2-step momentum-SGD update
        against the unsharded golden model, head/embed included.

        The kernels run compiled, on real chips (``JAX_PLATFORMS=tpu
        pytest -m tpu``): interpret-mode Pallas cannot be re-typed under
        vma on this jax (which is what forces check_vma=False in the
        CPU tests), so elsewhere this skips at once.

        Run twice. At ``highest`` matmul precision on both sides, for
        the tight bound: the MXU's default for f32 is one bf16 pass,
        whose rounding alone moves the updated params by 2.2e-4
        (measured, v5e, PR 21). And at the DEFAULT precision — what
        production compiles — at the bound that measurement allows
        (5e-4): an under-reduced replicated grad (~1e-3 here) still
        fails it."""
        if jax.default_backend() != "tpu":
            pytest.skip("the compiled check_vma=True contract needs TPU "
                        "chips: JAX_PLATFORMS=tpu pytest -m tpu")
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices for a multi-shard mesh")
        # batch 16 x seq 16 -> 128 local tokens per shard: meets the
        # fused kernels' T_TILE on the compiled path
        cfg = T.TransformerConfig(**self._CFG, ce_impl="fused")
        mesh = submesh({"data": 2})
        params = T.init_params(cfg, seed=0)
        rng = np.random.default_rng(1)
        tokens, labels, mask = T.make_batch(rng, cfg, 16, 16)

        def two_steps():
            """(loss diff, per-leaf max param diff) of the sharded step
            against the unsharded golden model, both traced under the
            caller's matmul precision."""
            ref_p, ref_v = params, jax.tree.map(jnp.zeros_like, params)
            for _ in range(2):
                loss_ref, g = jax.value_and_grad(T.reference_loss)(
                    ref_p, tokens, labels, mask, cfg)
                ref_v = jax.tree.map(lambda v, gr: 0.9 * v + gr, ref_v, g)
                ref_p = jax.tree.map(lambda p, v: p - 0.1 * v, ref_p,
                                     ref_v)
            # check_vma=True is build_spmd_train_step's default —
            # exactly the production composition
            step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9,
                                           donate=False)
            sp = T.shard_params(params, cfg, mesh)
            sv = T.shard_params(jax.tree.map(jnp.zeros_like, params),
                                cfg, mesh)
            for _ in range(2):
                sp, sv, loss_sh = step(sp, sv, tokens, labels, mask)
            diffs = jax.tree.map(
                lambda a, b: float(jnp.abs(a - b).max()),
                jax.device_get(sp), jax.device_get(ref_p))
            return abs(float(loss_ref) - float(loss_sh)), diffs

        with jax.default_matmul_precision("highest"):
            loss_diff, diffs = two_steps()
        assert loss_diff < 2e-5
        assert max(jax.tree_util.tree_leaves(diffs)) < 5e-5, diffs
        # the guarded failure mode, asserted by name: replicated-param
        # grads (embed/head) must arrive fully psum'd across shards
        assert diffs["head"] < 5e-5 and diffs["embed"] < 5e-5, diffs

        loss_diff, diffs = two_steps()          # production precision
        assert loss_diff < 1e-4
        assert max(jax.tree_util.tree_leaves(diffs)) < 5e-4, diffs

    def test_check_vma_false_multishard_guard(self):
        """Documents the boundary: check_vma=False on a multi-shard mesh
        under-reduces replicated-param grads (embed/head) — the reason
        the flag is test-only. If this ever starts passing, shard_map
        grew the missing transpose psums and the caveat can go."""
        cfg = T.TransformerConfig(**self._CFG, ce_impl="xla")
        mesh = submesh({"data": 2})
        params = T.init_params(cfg, seed=0)
        rng = np.random.default_rng(1)
        tokens, labels, mask = T.make_batch(rng, cfg, 4, 16)
        _, g = jax.value_and_grad(T.reference_loss)(
            params, tokens, labels, mask, cfg)
        ref_p = jax.tree.map(lambda p, gr: p - 0.1 * gr, params, g)
        step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.0, donate=False,
                                       check_vma=False)
        sp = T.shard_params(params, cfg, mesh)
        sv = T.shard_params(jax.tree.map(jnp.zeros_like, params),
                            cfg, mesh)
        sp, sv, _ = step(sp, sv, tokens, labels, mask)
        head_diff = float(jnp.abs(sp["head"] - ref_p["head"]).max())
        assert head_diff > 1e-4  # under-reduced (missing psum)

"""SPMD transformer: every parallelism axis verified against an
unsharded golden model (the multi-device story of SURVEY.md §4.5, run on
the virtual 8-CPU mesh — identical code to a pod)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models import transformer as T
from mmlspark_tpu.parallel.ring_attention import (
    dense_attention, ring_attention, ring_attention_local)
from mmlspark_tpu.parallel.topology import MeshSpec, build_mesh


def submesh(shape):
    n = int(np.prod(list(shape.values())))
    return build_mesh(MeshSpec.from_dict(shape), devices=jax.devices()[:n])


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, rng, causal):
        mesh = submesh({"data": 2, "seq": 4})
        q, k, v = (jnp.asarray(
            rng.normal(size=(4, 32, 2, 8)).astype(np.float32))
            for _ in range(3))
        out = ring_attention(q, k, v, mesh, causal=causal)
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)

    @pytest.mark.parametrize("causal", [True, False])
    def test_flash_block_matches_dense(self, rng, causal):
        """Full ring with the Pallas flash block kernel (interpret mode)."""
        mesh = submesh({"seq": 4})
        q, k, v = (jnp.asarray(
            rng.normal(size=(2, 32, 2, 8)).astype(np.float32))
            for _ in range(3))
        out = ring_attention(q, k, v, mesh, causal=causal,
                             block_impl="flash_interpret")
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    def test_folded_block_matches_dense(self, rng, causal):
        """Full ring with the FOLDED (feature-major) block kernel —
        s_local=384 tiles to 128, a 3x3 grid per ring step, so the
        cross-tile rescale runs under every visibility (full / diagonal
        / none)."""
        mesh = submesh({"seq": 2})
        q, k, v = (jnp.asarray(
            rng.normal(size=(1, 768, 2, 8)).astype(np.float32))
            for _ in range(3))
        out = ring_attention(q, k, v, mesh, causal=causal,
                             block_impl="folded_interpret")
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_folded_ring_is_differentiable(self, rng, causal):
        """block_impl='folded' is TRAINING-grade: a custom VJP over the
        whole ring (backward = a second ring pass with (dk, dv)
        accumulators traveling with their kv block) must match the
        dense ring in value AND gradients."""
        from jax.sharding import PartitionSpec as P
        from mmlspark_tpu.parallel.collectives import shard_map_fn
        mesh = submesh({"seq": 2})
        q, k, v = (jnp.asarray(
            rng.normal(size=(1, 768, 2, 8)).astype(np.float32))
            for _ in range(3))
        w = jnp.asarray(rng.normal(size=(1, 768, 2, 8)).astype(np.float32))
        spec = P(None, "seq")

        def attn(impl):
            return shard_map_fn(
                lambda q_, k_, v_: ring_attention_local(
                    q_, k_, v_, "seq", causal, block_impl=impl),
                mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)

        out_d = attn("dense")(q, k, v)
        out_f = attn("folded_interpret")(q, k, v)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                                   atol=2e-5)
        gd = jax.grad(lambda *a: jnp.sum(jnp.sin(attn("dense")(*a)) * w),
                      argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(
            lambda *a: jnp.sum(jnp.sin(attn("folded_interpret")(*a)) * w),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b2 in zip("qkv", gd, gf):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       atol=5e-5, err_msg=f"d{name}")

    @pytest.mark.parametrize("causal", [True, False])
    def test_folded_block_partials_match_dense_block(self, rng, causal):
        """The (m, l, o-unnormalized) partials contract itself, with
        ring-style rotated key positions (diagonal visibility)."""
        from mmlspark_tpu.parallel.ring_attention import _block_attn
        from mmlspark_tpu.parallel.pallas_attention import (
            folded_block_attn)
        B, S, H, D = 2, 128, 3, 16
        q, k, v = (jnp.asarray(
            rng.normal(size=(B, S, H, D)).astype(np.float32))
            for _ in range(3))
        q_pos = jnp.arange(S) + S          # queries are the LATER block
        k_pos = jnp.arange(S)              # keys fully visible (causal)
        scale = D ** -0.5
        rm, rl, ro = _block_attn(q, k, v, scale, q_pos, k_pos, causal)
        fm, fl, fo = folded_block_attn(q, k, v, scale, q_pos, k_pos,
                                       causal, interpret=True)
        np.testing.assert_allclose(np.asarray(fm), np.asarray(rm),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(rl),
                                   rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(fo), np.asarray(ro),
                                   rtol=1e-5, atol=2e-5)
        # the reverse visibility: every key in the queries' future ->
        # no data (m = -inf sentinel, l = 0, o = 0)
        if causal:
            fm2, fl2, fo2 = folded_block_attn(
                q, k, v, scale, k_pos, q_pos, True, interpret=True)
            assert float(jnp.max(fl2)) == 0.0
            assert float(jnp.max(jnp.abs(fo2))) == 0.0

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("relation", ["earlier", "equal", "later"])
    @pytest.mark.parametrize("sk", [128, 200, 1100])
    def test_flash_block_partials_ring_contract(self, rng, causal,
                                                relation, sk):
        """``flash_block_attn``'s (m, l, o-unnormalized) partials
        against the dense block, as a ring step calls it: the queries'
        global positions against a KV block that started earlier in the
        sequence (all visible: no tile needs the mask), at the same
        place (the diagonal) or later (nothing visible: ``l`` and ``o``
        stay 0 for every row). Key lengths that pad (200 -> 256 in one
        tile; 1100 -> 1152 in three tiles of 384) keep the padding out
        of every row."""
        from mmlspark_tpu.parallel.ring_attention import _block_attn
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_block_attn)
        B, S, H, D = 1, 200, 2, 16
        q = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))
        k, v = (jnp.asarray(
            rng.normal(size=(B, sk, H, D)).astype(np.float32))
            for _ in range(2))
        base = 4096
        q_pos = base + jnp.arange(S)
        k_pos = {"earlier": base - sk, "equal": base,
                 "later": base + S}[relation] + jnp.arange(sk)
        scale = D ** -0.5
        rm, rl, ro = _block_attn(q, k, v, scale, q_pos, k_pos, causal)
        fm, fl, fo = flash_block_attn(q, k, v, scale, q_pos, k_pos,
                                      causal, interpret=True)
        assert fo.shape == (B, S, H, D) and fl.shape == (B, H, S)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(rl),
                                   rtol=1e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(fo), np.asarray(ro),
                                   rtol=1e-5, atol=2e-5)
        seen = np.asarray(rl) > 0
        np.testing.assert_allclose(np.asarray(fm)[seen],
                                   np.asarray(rm)[seen], atol=2e-5)
        if causal and relation == "later":
            assert float(jnp.max(fl)) == 0.0
            assert float(jnp.max(jnp.abs(fo))) == 0.0
        if causal and relation == "equal":
            # row 0 sees key 0 only; with sk > S every row still has a
            # live key, with padded keys never among them
            assert np.all(seen)


class TestFlashAttentionVJP:
    """The differentiable Pallas flash kernel (interpret mode) must match
    dense attention in value AND gradients — it is the kernel the
    single-chip train path runs on TPU (`transformer._attention`)."""

    # (B, S, H, Dh), dtype -> the tiles ``flash_tiles`` picks for it:
    # one padded 128 tile; one 384 and one 640 tile (not powers of two);
    # a 3 x 3 grid of 384 tiles with a padded remainder (52 keys of the
    # last tile are padding, three tile pairs dead under causal); a
    # 2 x 2 grid of 640 tiles; one 1024 tile; f32 at head_dim 128 (the
    # prefill's operands) in one 1024 tile
    SHAPES = {
        "s48-d16-f32": ((2, 48, 2, 16), "float32", (128, 128)),
        "s384-d64-bf16": ((1, 384, 2, 64), "bfloat16", (384, 384)),
        "s640-d16-bf16": ((1, 640, 1, 16), "bfloat16", (640, 640)),
        "s1100-d16-bf16": ((1, 1100, 1, 16), "bfloat16", (384, 384)),
        "s1280-d64-bf16": ((1, 1280, 1, 64), "bfloat16", (640, 640)),
        "s1024-d64-bf16": ((1, 1024, 1, 64), "bfloat16", (1024, 1024)),
        "s1024-d128-f32": ((1, 1024, 1, 128), "float32", (1024, 1024)),
    }

    # keys of another length than the queries, and lengths that pad (to
    # 256 x 384 and 128 x 128): ``sk`` beside the shape
    CROSS = {
        "s130x300-d64-f32": ((1, 130, 2, 64), 300, "float32"),
        "s300x130-d16-bf16": ((1, 300, 2, 16), 130, "bfloat16"),
        "s48x96-d16-f32": ((2, 48, 2, 16), 96, "float32"),
    }

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("case", list(SHAPES) + list(CROSS))
    def test_value_and_grads_match_dense(self, rng, causal, case):
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_attention, flash_tiles)
        if case in self.SHAPES:
            shape, dtype, tiles = self.SHAPES[case]
            sk = shape[1]
            assert flash_tiles(sk, sk, shape[3], dtype) == tiles
        else:
            shape, sk, dtype = self.CROSS[case]
        kv_shape = (shape[0], sk) + shape[2:]
        q = jnp.asarray(rng.normal(size=shape), dtype)
        k, v = (jnp.asarray(rng.normal(size=kv_shape), dtype)
                for _ in range(2))
        w = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        # bf16 operands: p is rounded to bf16 for P.V (and ds for the
        # gradients), 2^-9 relative an element
        atol_v, atol_g = (2e-5, 5e-5) if dtype == "float32" else (3e-2, 6e-2)

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal, None, True) * w)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=causal) * w)

        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        out_f = flash_attention(q, k, v, causal, None, True)
        out_d = dense_attention(*f32, causal=causal)
        assert out_f.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(out_f, np.float32),
                                   np.asarray(out_d), atol=atol_v)
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(*f32)
        for a, b, name in zip(gf, gd, "qkv"):
            a, b = np.asarray(a, np.float32), np.asarray(b)
            np.testing.assert_allclose(a, b, atol=atol_g,
                                       err_msg=f"d{name}")
            # no systematic error hides under the elementwise tolerance
            assert np.abs(a - b).mean() <= atol_g / 10, f"d{name}"

    # the two cells' calls, every prefill bucket of the serve cell
    # (B = 1, 16 heads x 128, f32), and shapes that pad
    TILE_SHAPES = [
        (2048, 2048, 64, "bfloat16", (1024, 1024)),     # pretrain-2k
        (16, 16, 128, "float32", (128, 128)),
        (32, 32, 128, "float32", (128, 128)),
        (64, 64, 128, "float32", (128, 128)),
        (128, 128, 128, "float32", (128, 128)),
        (256, 256, 128, "float32", (256, 256)),
        (512, 512, 128, "float32", (512, 512)),
        (1024, 1024, 128, "float32", (1024, 1024)),
        (48, 96, 16, "float32", (128, 128)),
        (130, 300, 64, "bfloat16", (256, 384)),
        (4096, 4096, 128, "bfloat16", (1024, 1024)),
        (1024, 2048, 512, "float32", (512, 1024)),      # VMEM binds
    ]

    @pytest.mark.parametrize("sq,sk,d,dtype,want", TILE_SHAPES)
    def test_flash_tiles(self, sq, sk, d, dtype, want):
        """The pure tile choice: tiles divide the lengths padded to 128
        (never further), fit the VMEM budget, and are as large as both
        allow; 128 x 128 is the floor."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        tq, tk = PA.flash_tiles(sq, sk, d, dtype)
        assert (tq, tk) == want
        assert PA._round_up(sq, 128) % tq == 0
        assert PA._round_up(sk, 128) % tk == 0
        assert (tq, tk) == (128, 128) or PA._flash_vmem_bytes(
            tq, tk, d, jnp.dtype(dtype).itemsize) <= PA._FLASH_VMEM_BUDGET

    @pytest.mark.parametrize("sq,sk,d,dtype",
                             [c[:4] for c in TILE_SHAPES])
    def test_flash_bwd_tiles(self, sq, sk, d, dtype):
        """The backward's tile choice over ``test_flash_tiles``' shapes:
        tiles divide the lengths padded to 128 (never further), fit the
        backward's own VMEM count, and no larger pair that divides
        fits; 128 x 128 is the floor."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        tq, tk = PA.flash_bwd_tiles(sq, sk, d, dtype)
        sq_p, sk_p = PA._round_up(sq, 128), PA._round_up(sk, 128)
        assert sq_p % tq == 0 and sk_p % tk == 0
        assert tq >= 128 and tk >= 128

        def fits(a, b):
            return PA._flash_bwd_vmem_bytes(
                a, b, d, jnp.dtype(dtype).itemsize, sq_p) \
                <= PA._FLASH_VMEM_BUDGET

        assert (tq, tk) == (128, 128) or fits(tq, tk)
        assert not any(fits(a, b) and a * b > tq * tk
                       for a in PA._tile_choices(sq)
                       for b in PA._tile_choices(sk))

    @pytest.mark.parametrize("tq,tk,sq,sk", [
        (1024, 1024, 2048, 2048), (512, 512, 2048, 2048),
        (512, 1024, 2048, 2048), (1024, 512, 2048, 2048),
        (256, 384, 256, 384), (128, 384, 384, 1152),
    ])
    def test_dead_causal_tiles_fetch_nothing(self, tq, tk, sq, sk):
        """The backward's index map for the blocks that walk its inner
        (q) axis, on the pure function: a dead causal tile (every key
        after every query) names the block of the kv tile's first live
        q tile, so the pipeline fetches nothing for it; a live tile
        names its own."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        for j in range(sk // tk):
            first = PA._first_live_q(j, tq, tk)
            assert (first + 1) * tq - 1 >= j * tk       # live itself
            for i in range(sq // tq):
                dead = (i + 1) * tq - 1 < j * tk
                assert max(i, first) == (first if dead else i)

    # the benchmark's three serving cells: (table entries a slot,
    # K/V heads, dtype) at pages of 16 rows of head_dim 128
    PAGED_CELLS = [(64, 16, "float32"), (192, 32, "bfloat16"),
                   (640, 8, "bfloat16")]

    @pytest.mark.parametrize("pps,h_kv,dtype", PAGED_CELLS)
    def test_paged_fetch_pages(self, pps, h_kv, dtype):
        """The pure choice of how many table entries the paged decode
        kernel copies at a time: K and V, double-buffered, fit the
        budget the module states; at least 2; no more than the cap;
        and the whole table when the table is shorter."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        p = PA.paged_fetch_pages(pps, 16, h_kv, 128, dtype)
        page = 16 * h_kv * 128 * jnp.dtype(dtype).itemsize
        assert PA._paged_page_vmem_bytes(16, h_kv, 128, dtype) >= page
        assert 2 <= p <= PA._PAGED_MAX_FETCH
        assert 4 * p * PA._paged_page_vmem_bytes(16, h_kv, 128, dtype) \
            <= PA._PAGED_VMEM_BUDGET
        # as many as fit, and the smaller the page the more of them
        assert p == PA._PAGED_MAX_FETCH or 4 * (p + 1) * page \
            > PA._PAGED_VMEM_BUDGET
        for short in (1, 2, p - 1):
            assert PA.paged_fetch_pages(short, 16, h_kv, 128, dtype) \
                == short

    @pytest.mark.parametrize("fetch", [2, 8, 32])
    def test_paged_walk_names_the_live_entries(self, fetch):
        """The walk of a slot at ``pos``, on the pure functions the
        kernel runs: ``cdiv(pos + 1, 16)`` entries in ``cdiv(walk,
        fetch)`` fetches, each entry of ``[0, walk)`` once and none
        past it, whatever the fetch; a dead entry is never named."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        for pos in list(range(0, 70)) + [16 * fetch - 1, 16 * fetch,
                                         16 * 640 - 1]:
            walk = int(PA.paged_walk(pos, 16))
            assert walk == -(-(pos + 1) // 16)
            named = [i * fetch + j for i in range(-(-walk // fetch))
                     for j in range(int(PA._paged_fetch_live(
                         walk, i, fetch)))]
            assert named == list(range(walk))

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.slow
    def test_folded_value_and_grads_match_dense(self, rng, causal):
        """The feature-major (folded) kernel — the engine the train
        bench runs at S=1024/dh=64 — against dense, value + grads."""
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_attention_folded)
        # S=384 -> tile 128, a 3x3 tile grid: the cross-tile online-
        # softmax rescale (alpha), causal tile gating, and cross-tile
        # dq/dk/dv accumulation all execute (S=256 would be one tile)
        B, S, H, D = 2, 384, 3, 24   # H*D=72 sublanes (no 128 constraint)
        q, k, v = (jnp.asarray(
            rng.normal(size=(B, S, H, D)).astype(np.float32))
            for _ in range(3))
        w = jnp.asarray(rng.normal(size=(B, S, H, D)).astype(np.float32))

        def loss_folded(q, k, v):
            return jnp.sum(
                flash_attention_folded(q, k, v, causal, None, True) * w)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=causal) * w)

        out_f = flash_attention_folded(q, k, v, causal, None, True)
        out_d = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                                   atol=2e-5)
        gf = jax.grad(loss_folded, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gd, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, err_msg=f"d{name}")

    def test_folded_availability_rules(self):
        from mmlspark_tpu.parallel.pallas_attention import folded_available
        import jax as _jax
        on_tpu = _jax.default_backend() == "tpu"
        # eligible shape: gate tracks the backend
        assert folded_available(1024, 1024, 64) == on_tpu
        assert not folded_available(1024, 512, 64)   # cross-length
        assert not folded_available(1000, 1000, 64)  # untileable S
        assert not folded_available(1024, 1024, 60)  # head not 8-aligned
        # wide-head configs (large H*Dh) exceed the folded kernels' VMEM
        # budget — auto must fall back, not fail the Mosaic compile
        assert folded_available(1024, 1024, 64, 8) == on_tpu
        assert not folded_available(1024, 1024, 96, 32)


def _compare(mesh_shape, cfg, steps=2, B=8, S=16):
    """Sharded train step must equal the unsharded golden update."""
    mesh = submesh(mesh_shape)
    params = T.init_params(cfg, seed=0)
    rng = np.random.default_rng(1)
    tokens, labels, mask = T.make_batch(rng, cfg, B, S)

    ref_p, ref_v = params, jax.tree.map(jnp.zeros_like, params)
    for _ in range(steps):
        loss_ref, g = jax.value_and_grad(T.reference_loss)(
            ref_p, tokens, labels, mask, cfg)
        ref_v = jax.tree.map(lambda v, gr: 0.9 * v + gr, ref_v, g)
        ref_p = jax.tree.map(lambda p, v: p - 0.1 * v, ref_p, ref_v)

    step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
    sp = T.shard_params(params, cfg, mesh)
    sv = T.shard_params(jax.tree.map(jnp.zeros_like, params), cfg, mesh)
    for _ in range(steps):
        sp, sv, loss_sh = step(sp, sv, tokens, labels, mask)

    assert abs(float(loss_ref) - float(loss_sh)) < 2e-5
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         jax.device_get(sp), jax.device_get(ref_p))
    assert max(jax.tree.leaves(diffs)) < 2e-4, diffs


_DENSE = dict(vocab=64, d_model=16, n_heads=4, d_head=8, d_ff=32)


class TestSpmdTrainStep:
    def test_data_parallel(self):
        _compare({"data": 2}, T.TransformerConfig(**_DENSE,
                                                  layers_per_stage=2))

    def test_tensor_parallel(self):
        _compare({"model": 2}, T.TransformerConfig(**_DENSE,
                                                   layers_per_stage=2))

    def test_sequence_parallel_ring(self):
        _compare({"seq": 4}, T.TransformerConfig(**_DENSE,
                                                 layers_per_stage=2))

    def test_pipeline_parallel(self):
        _compare({"pipe": 2}, T.TransformerConfig(
            **_DENSE, n_stages=2, microbatches=2))

    def test_expert_parallel(self):
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=2)
        _compare({"expert": 2}, cfg)

    def test_expert_parallel_capacity_dispatch(self):
        # capacity-based all_to_all dispatch must equal the dense-dispatch
        # golden when the budget is large enough that no token drops
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=2,
                                  moe_capacity_factor=4.0)
        _compare({"expert": 2}, cfg)

    @pytest.mark.parametrize("mesh_shape", [{"data": 1},
                                            {"data": 2, "expert": 2}])
    def test_dispatch_engines_agree(self, mesh_shape):
        """Counting-sort and scatter capacity engines produce IDENTICAL
        train-step results (same kept/dropped routings, same values,
        same gradients) — the sort engine's correctness pin, with a
        tight capacity so overflow drops actually occur."""
        import dataclasses
        base = T.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                   d_head=16, d_ff=64, layers_per_stage=2,
                                   n_experts=4, moe_top_k=2,
                                   moe_capacity_factor=1.1,
                                   moe_aux_weight=0.01,
                                   moe_zloss_weight=1e-3)
        mesh = submesh(mesh_shape)
        params = T.init_params(base, seed=0)
        rng = np.random.default_rng(0)
        tokens, labels, mask = T.make_batch(rng, base, 4, 16)
        outs = {}
        for mode in ("scatter", "sort"):
            cfg = dataclasses.replace(base, moe_dispatch=mode)
            step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.0,
                                           donate=False)
            sp = T.shard_params(params, cfg, mesh)
            sv = T.shard_params(
                jax.tree.map(jnp.zeros_like, params), cfg, mesh)
            sp, sv, loss = step(sp, sv, tokens, labels, mask)
            outs[mode] = (float(loss), jax.device_get(sp))
        assert outs["scatter"][0] == outs["sort"][0]
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             outs["scatter"][1], outs["sort"][1])
        assert max(jax.tree_util.tree_leaves(diffs)) == 0.0

    @pytest.mark.parametrize("capacity", [0.0, 4.0])
    @pytest.mark.slow
    def test_top2_routing_matches_golden(self, capacity):
        # Mixtral-style top-2 (renormalized weights), dense AND capacity
        # dispatch, must equal the unsharded golden on the expert mesh
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=4,
                                  moe_top_k=2, moe_capacity_factor=capacity,
                                  moe_aux_weight=0.02)
        _compare({"expert": 2}, cfg)

    @pytest.mark.parametrize("capacity", [0.0, 4.0])
    def test_load_balancing_aux_matches_golden(self, capacity):
        # the Switch aux is computed from GLOBAL (f, P) router stats —
        # pmean'd across every token-holding axis BEFORE the nonlinear
        # product — so sharded training must equal the unsharded golden
        # for both dense and capacity dispatch
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=2,
                                  moe_capacity_factor=capacity,
                                  moe_aux_weight=0.02)
        _compare({"expert": 2}, cfg)

    @pytest.mark.parametrize("capacity", [0.0, 4.0])
    def test_router_zloss_matches_golden(self, capacity):
        # the z-loss (mean logsumexp^2 of router logits — ST-MoE's
        # logit regularizer) is token-linear, so the sharded pmean must
        # equal the unsharded golden for dense and capacity dispatch;
        # run alongside the balance aux as production configs do
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=4,
                                  moe_top_k=2, moe_capacity_factor=capacity,
                                  moe_aux_weight=0.02,
                                  moe_zloss_weight=0.01)
        _compare({"expert": 2}, cfg)

    def test_zloss_shrinks_router_logits(self):
        # with a strong z-loss, training must reduce router logit scale
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=1, n_experts=4,
                                  moe_zloss_weight=1.0)
        mesh = submesh({"data": 2})
        rng = np.random.default_rng(9)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        step = T.build_spmd_train_step(cfg, mesh, 0.02, 0.9)
        p0 = T.init_params(cfg, 4)
        # scale the router up so the z-loss has something to shrink
        p0["blocks"][0]["router"] = p0["blocks"][0]["router"] * 50.0
        params = T.shard_params(p0, cfg, mesh)
        vel = T.shard_params(jax.tree.map(jnp.zeros_like, p0), cfg, mesh)

        def router_norm(p):
            host = jax.device_get(p)
            return float(np.linalg.norm(
                np.asarray(host["blocks"][0]["router"])))

        before = router_norm(params)
        for _ in range(10):
            params, vel, _ = step(params, vel, tokens, labels, mask)
        after = router_norm(params)
        # the z-loss pulls the (deliberately inflated) router weights
        # toward smaller logits; without it the CE gradient alone has no
        # such pressure at this scale
        assert after < 0.9 * before, (before, after)

    @pytest.mark.parametrize("mesh_shape,groups", [
        ({"expert": 2}, 2), ({"data": 2}, 2),
        ({"data": 2, "expert": 2}, 4),
    ])
    @pytest.mark.slow
    def test_expert_choice_matches_golden(self, mesh_shape, groups):
        """Expert-choice routing (experts pick top-C tokens — balanced
        by construction): the sharded step must equal the group-wise
        unsharded golden, where groups = the step's contiguous token
        shards (data x expert)."""
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=4,
                                  moe_router="expert_choice",
                                  moe_capacity_factor=1.0,
                                  moe_zloss_weight=0.01)
        mesh = submesh(mesh_shape)
        params = T.init_params(cfg, seed=0)
        rng = np.random.default_rng(1)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)

        ref_p = params
        ref_v = jax.tree.map(jnp.zeros_like, params)
        for _ in range(2):
            loss_ref, g = jax.value_and_grad(T.reference_loss)(
                ref_p, tokens, labels, mask, cfg, groups)
            ref_v = jax.tree.map(lambda v, gr: 0.9 * v + gr, ref_v, g)
            ref_p = jax.tree.map(lambda p, v: p - 0.1 * v, ref_p, ref_v)

        step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
        sp = T.shard_params(params, cfg, mesh)
        sv = T.shard_params(
            jax.tree.map(jnp.zeros_like, params), cfg, mesh)
        for _ in range(2):
            sp, sv, loss_sh = step(sp, sv, tokens, labels, mask)
        assert abs(float(loss_ref) - float(loss_sh)) < 2e-5
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             jax.device_get(sp), jax.device_get(ref_p))
        assert max(jax.tree.leaves(diffs)) < 2e-4, diffs

    def test_checkpoint_resume_across_meshes(self, tmp_path):
        """save_train_state / restore_train_state: resuming — even on a
        DIFFERENT mesh layout — must continue exactly where the saved
        run left off (checkpoints are mesh-independent host gathers)."""
        cfg = T.TransformerConfig(**_DENSE, layers_per_stage=2)
        rng = np.random.default_rng(2)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)

        def run(mesh, params, vel, n):
            step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
            loss = None
            for _ in range(n):
                params, vel, loss = step(params, vel, tokens, labels, mask)
            return params, vel, loss

        mesh_a = submesh({"data": 2, "model": 2})
        p0 = T.init_params(cfg, seed=0)
        sp, sv, _ = run(mesh_a, T.shard_params(p0, cfg, mesh_a),
                        T.shard_params(jax.tree.map(jnp.zeros_like, p0),
                                       cfg, mesh_a), 2)
        path = str(tmp_path / "ckpt")
        T.save_train_state(path, sp, sv, step=2)
        # the uninterrupted run: 2 more steps on mesh A
        _, _, loss_ref = run(mesh_a, sp, sv, 2)

        # resume on a DIFFERENT mesh layout
        mesh_b = submesh({"data": 4})
        rp, rv, at = T.restore_train_state(path, cfg, mesh_b)
        assert at == 2
        _, _, loss_res = run(mesh_b, rp, rv, 2)
        assert abs(float(loss_res) - float(loss_ref)) < 2e-5

    def test_restore_missing_checkpoint_raises(self, tmp_path):
        cfg = T.TransformerConfig(**_DENSE)
        with pytest.raises(FileNotFoundError):
            T.restore_train_state(str(tmp_path / "nothing"), cfg,
                                  submesh({"data": 2}))

    def test_expert_choice_needs_capacity(self):
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, n_experts=2,
                                  moe_router="expert_choice")
        mesh = submesh({"data": 2})
        rng = np.random.default_rng(0)
        tokens, labels, mask = T.make_batch(rng, cfg, 4, 8)
        step = T.build_spmd_train_step(cfg, mesh)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        vel = T.shard_params(
            jax.tree.map(jnp.zeros_like, T.init_params(cfg, 0)), cfg, mesh)
        with pytest.raises(ValueError, match="capacity"):
            step(params, vel, tokens, labels, mask)

    def test_expert_choice_trains(self):
        # EC needs no balance aux: the loss must decrease with aux off
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=1, n_experts=4,
                                  moe_router="expert_choice",
                                  moe_capacity_factor=1.0)
        mesh = submesh({"expert": 2})
        rng = np.random.default_rng(3)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        step = T.build_spmd_train_step(cfg, mesh, 0.2, 0.9)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        vel = T.shard_params(
            jax.tree.map(jnp.zeros_like, T.init_params(cfg, 0)), cfg, mesh)
        losses = []
        for _ in range(8):
            params, vel, loss = step(params, vel, tokens, labels, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_aux_balances_expert_load(self):
        # with the aux on, a few steps must reduce routing imbalance
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=4,
                                  moe_capacity_factor=1.0,
                                  moe_aux_weight=1.0)
        mesh = submesh({"data": 2})
        rng = np.random.default_rng(9)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        step = T.build_spmd_train_step(cfg, mesh, 0.3, 0.9)
        params = T.shard_params(T.init_params(cfg, 4), cfg, mesh)
        vel = T.shard_params(
            jax.tree.map(jnp.zeros_like, T.init_params(cfg, 4)), cfg, mesh)

        def max_frac(p):
            host = jax.device_get(p)
            h = np.asarray(host["embed"])[np.asarray(tokens)]
            router = np.asarray(host["blocks"][0]["router"][0])
            top = (h @ router).argmax(-1).reshape(-1)
            return float(max(np.bincount(top, minlength=4) / len(top)))

        before = max_frac(params)
        # 30 steps: the momentum transient of the first few steps is
        # formulation-sensitive (the pjit and shard_map steps are
        # parity-pinned per step, but a marginal 10-step snapshot can
        # flip on fp-level compilation differences); the aux's
        # balancing pressure is the claim, and it must have won by 30
        for _ in range(30):
            params, vel, _ = step(params, vel, tokens, labels, mask)
        after = max_frac(params)
        assert after <= before + 1e-6, (before, after)

    def test_capacity_dispatch_drops_overflow(self):
        # a tight budget must still train (dropped tokens ride the
        # residual), not crash or NaN
        cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, d_head=8,
                                  d_ff=32, layers_per_stage=2, n_experts=2,
                                  moe_capacity_factor=0.5)
        mesh = submesh({"expert": 2})
        rng = np.random.default_rng(3)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        vel = T.shard_params(
            jax.tree.map(jnp.zeros_like, T.init_params(cfg, 0)), cfg, mesh)
        losses = []
        for _ in range(4):
            params, vel, loss = step(params, vel, tokens, labels, mask)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_capacity_flops_scale_with_factor_not_experts(self):
        # the point of capacity dispatch: expert compute ~ factor, not E
        def step_flops(n_experts, factor):
            cfg = T.TransformerConfig(vocab=32, d_model=32, n_heads=2,
                                      d_head=16, d_ff=256,
                                      layers_per_stage=1,
                                      n_experts=n_experts,
                                      moe_capacity_factor=factor)
            mesh = submesh({"data": 1})
            rng = np.random.default_rng(0)
            tokens, labels, mask = T.make_batch(rng, cfg, 4, 32)
            params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
            vel = T.shard_params(
                jax.tree.map(jnp.zeros_like, T.init_params(cfg, 0)),
                cfg, mesh)
            step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
            cost = step.lower(params, vel, tokens, labels,
                              mask).compile().cost_analysis()
            if isinstance(cost, (list, tuple)):   # jax-version shape
                cost = cost[0]
            return float(cost["flops"])

        cap_2, cap_8 = step_flops(2, 1.0), step_flops(8, 1.0)
        dense_2, dense_8 = step_flops(2, 0.0), step_flops(8, 0.0)
        assert dense_8 / dense_2 > 2.0       # dense pays per expert
        assert cap_8 / cap_2 < 1.35          # capacity does not

    def test_full_composition_5axis(self):
        """tp+pp+sp+ep+dp in one mesh — the pod-shaped program."""
        cfg = T.TransformerConfig(**_DENSE, n_stages=2, n_experts=2,
                                  microbatches=2)
        _compare({"data": 1, "seq": 2, "model": 2, "expert": 1, "pipe": 2},
                 cfg)

    def test_loss_decreases(self):
        cfg = T.TransformerConfig(**_DENSE, n_stages=2, microbatches=2)
        mesh = submesh({"data": 2, "model": 2, "pipe": 2})
        rng = np.random.default_rng(3)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9)
        params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        vel = T.shard_params(
            jax.tree.map(jnp.zeros_like, T.init_params(cfg, 0)), cfg, mesh)
        losses = []
        for _ in range(5):
            params, vel, loss = step(params, vel, tokens, labels, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_mesh_validation(self):
        cfg = T.TransformerConfig(**_DENSE, n_stages=2)
        with pytest.raises(ValueError, match="pipe"):
            T.build_spmd_train_step(cfg, submesh({"data": 2}))

    def test_full_spmd_meshspec(self):
        sizes = MeshSpec.full_spmd(8).resolve(8)
        assert sizes == {"data": 1, "seq": 2, "model": 2, "expert": 1,
                         "pipe": 2}
        assert MeshSpec.full_spmd(1).resolve(1)["data"] == 1
        assert int(np.prod(list(MeshSpec.full_spmd(32).resolve(32)
                                .values()))) == 32


class TestPjitFormulation:
    """The pjit (global GSPMD) train step. ``impl="auto"`` is the
    shard_map formulation, so TestSpmdTrainStep above never reaches
    this one: these pin the selection contract and the parity of the
    two formulations. (``check_vma=False`` belongs to the shard_map
    path; its under-reduction boundary is pinned in
    tests/test_fused_ce.py::test_check_vma_false_multishard_guard.)"""

    def test_unknown_impl_refused(self):
        cfg = T.TransformerConfig(**_DENSE)
        with pytest.raises(ValueError, match="impl"):
            T.build_spmd_train_step(cfg, submesh({"data": 2}),
                                    impl="magic")

    def test_auto_is_shard_map(self):
        """``impl="auto"`` builds the manual program: a shard_map in
        the jaxpr, which the GSPMD formulation never has."""
        cfg = T.TransformerConfig(**_DENSE)
        mesh = submesh({"data": 2})
        rng = np.random.default_rng(3)
        tokens, labels, mask = T.make_batch(rng, cfg, 4, 16)
        sp = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
        for impl, expect in (("auto", True), ("pjit", False)):
            step = T.build_spmd_train_step(cfg, mesh, donate=False,
                                           impl=impl)
            jaxpr = str(jax.make_jaxpr(step)(sp, sp, tokens, labels,
                                             mask))
            assert ("shard_map" in jaxpr) is expect, impl

    @pytest.mark.parametrize("moe, shape", [
        pytest.param({}, {"data": 2, "model": 2}, id="dense"),
        # the MoE blocks run with their operands' mesh shardings (no
        # replication pin): capacity queues and expert choice must
        # still match the manual all_to_all dispatch drop-for-drop
        pytest.param(
            {"n_experts": 4, "moe_capacity_factor": 1.5, "moe_top_k": 2,
             "moe_aux_weight": 0.01, "moe_zloss_weight": 1e-3},
            {"data": 2, "expert": 2}, id="capacity",
            marks=pytest.mark.slow),
        pytest.param(
            {"n_experts": 4, "moe_capacity_factor": 1.0,
             "moe_router": "expert_choice"}, {"data": 2, "expert": 2},
            id="expert_choice", marks=pytest.mark.slow),
    ])
    def test_pjit_matches_shard_map_fixed_seed(self, moe, shape):
        """Fixed-seed parity between the two formulations."""
        cfg = T.TransformerConfig(**_DENSE, layers_per_stage=2, **moe)
        mesh = submesh(shape)
        rng = np.random.default_rng(7)
        tokens, labels, mask = T.make_batch(rng, cfg, 8, 16)
        params = T.init_params(cfg, seed=0)
        results = {}
        for impl in ("shard_map", "pjit"):
            step = T.build_spmd_train_step(cfg, mesh, 0.1, 0.9,
                                           donate=False, impl=impl)
            sp = T.shard_params(params, cfg, mesh)
            sv = T.shard_params(
                jax.tree.map(jnp.zeros_like, params), cfg, mesh)
            for _ in range(3):
                sp, sv, loss = step(sp, sv, tokens, labels, mask)
            results[impl] = (float(loss), jax.device_get(sp))
        assert abs(results["pjit"][0] - results["shard_map"][0]) < 2e-5
        diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                             results["pjit"][1], results["shard_map"][1])
        assert max(jax.tree_util.tree_leaves(diffs)) < 2e-4, diffs


def _reference_greedy(params, cfg, prompt, n_new):
    """Greedy continuation by re-running the full-context reference
    forward per token — the golden the KV-cache decode must match."""
    ctx = [int(t) for t in prompt]
    out = []
    for _ in range(n_new):
        lg = T.reference_logits(
            params, jnp.asarray(np.asarray(ctx, np.int32))[None], cfg)
        t = int(jnp.argmax(lg[0, -1]))
        out.append(t)
        ctx.append(t)
    return out


class TestSlotDecode:
    """The slot-indexed KV-cache decode path (ISSUE 9): prefill + one-
    token steps over the preallocated pool must match the full-context
    forward pass token-for-token, with a fixed compiled-shape set."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)

    def _build(self, n_slots=4, max_len=32):
        params = T.init_params(self.CFG, seed=0)
        cache = T.init_kv_cache(self.CFG, n_slots, max_len)
        prefill = T.build_prefill(self.CFG)
        step = T.build_decode_step(self.CFG, n_slots, max_len)
        return params, cache, prefill, step

    def _pad(self, prompt, bucket):
        out = np.zeros(bucket, np.int32)
        out[:len(prompt)] = prompt
        return jnp.asarray(out)

    @pytest.mark.parametrize("plen", [1, 3, 7, 8])
    @pytest.mark.slow
    def test_greedy_decode_matches_full_context(self, plen):
        params, cache, prefill, step = self._build()
        rng = np.random.default_rng(plen)
        prompt = rng.integers(0, self.CFG.vocab, size=plen
                              ).astype(np.int32)
        bucket = 1
        while bucket < plen:
            bucket *= 2
        cache, first, logits = prefill(params, cache,
                                       self._pad(prompt, bucket),
                                       np.int32(1), np.int32(plen))
        ref = T.reference_logits(params, jnp.asarray(prompt)[None],
                                 self.CFG)
        # prefill's last-position logits ARE the full forward's
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[0, -1]), atol=1e-4)
        toks = [int(first)]
        pos = np.zeros(4, np.int32)
        cur = np.zeros(4, np.int32)
        pos[1], cur[1] = plen, int(first)
        for _ in range(9):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
            t = int(np.asarray(nxt)[1])
            toks.append(t)
            pos[1] += 1
            cur[1] = t
        assert toks == _reference_greedy(params, self.CFG, prompt, 10)

    def test_slots_decode_independently(self):
        """Two prompts in different slots step TOGETHER and each
        matches its own single-request golden — the property that
        makes mid-flight joins sound."""
        params, cache, prefill, step = self._build()
        rng = np.random.default_rng(0)
        p_a = rng.integers(0, self.CFG.vocab, size=4).astype(np.int32)
        p_b = rng.integers(0, self.CFG.vocab, size=6).astype(np.int32)
        cache, first_a, _ = prefill(params, cache, self._pad(p_a, 4),
                                    np.int32(0), np.int32(4))
        cache, first_b, _ = prefill(params, cache, self._pad(p_b, 8),
                                    np.int32(2), np.int32(6))
        toks = {0: [int(first_a)], 2: [int(first_b)]}
        pos = np.zeros(4, np.int32)
        cur = np.zeros(4, np.int32)
        pos[0], cur[0] = 4, int(first_a)
        pos[2], cur[2] = 6, int(first_b)
        for _ in range(7):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
            for s in (0, 2):
                t = int(np.asarray(nxt)[s])
                toks[s].append(t)
                pos[s] += 1
                cur[s] = t
        assert toks[0] == _reference_greedy(params, self.CFG, p_a, 8)
        assert toks[2] == _reference_greedy(params, self.CFG, p_b, 8)

    def test_slot_reuse_after_release(self):
        """A freed slot's stale lane must not leak into its next
        occupant: decode request A in slot 1, then prefill request B
        into the SAME slot and decode — B matches its golden."""
        params, cache, prefill, step = self._build()
        rng = np.random.default_rng(3)
        p_a = rng.integers(0, self.CFG.vocab, size=7).astype(np.int32)
        p_b = rng.integers(0, self.CFG.vocab, size=3).astype(np.int32)
        cache, first, _ = prefill(params, cache, self._pad(p_a, 8),
                                  np.int32(1), np.int32(7))
        pos = np.zeros(4, np.int32)
        cur = np.zeros(4, np.int32)
        pos[1], cur[1] = 7, int(first)
        for _ in range(5):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
            pos[1] += 1
            cur[1] = int(np.asarray(nxt)[1])
        # release slot 1 (host-side bookkeeping only), reuse for B
        pos[1] = cur[1] = 0
        cache, first_b, _ = prefill(params, cache, self._pad(p_b, 4),
                                    np.int32(1), np.int32(3))
        toks = [int(first_b)]
        pos[1], cur[1] = 3, int(first_b)
        for _ in range(5):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
            t = int(np.asarray(nxt)[1])
            toks.append(t)
            pos[1] += 1
            cur[1] = t
        assert toks == _reference_greedy(params, self.CFG, p_b, 6)

    def test_decode_step_compiles_once(self):
        """The step's shape set is closed by construction: any
        join/leave churn reuses ONE executable (the zero-retrace
        pillar of continuous batching)."""
        params, cache, prefill, step = self._build()
        pos = np.zeros(4, np.int32)
        cur = np.zeros(4, np.int32)
        for i in range(6):
            pos[i % 4] = i          # churn the occupancy pattern
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
        assert step._cache_size() == 1

    def test_expert_choice_decode_unsupported(self):
        """Expert-choice routing couples slots (experts pick tokens
        ACROSS the batch) — the one MoE form decode refuses."""
        cfg = T.TransformerConfig(**_DENSE, layers_per_stage=1,
                                  n_experts=2,
                                  moe_router="expert_choice",
                                  moe_capacity_factor=1.0)
        with pytest.raises(NotImplementedError, match="expert-choice"):
            T.init_kv_cache(cfg, 2, 16)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_moe_decode_matches_reference(self, top_k):
        """Token-choice MoE decode (dense dispatch at single-token
        batches) matches the full-context MoE forward token-for-token
        — the deliberate NotImplementedError is gone."""
        cfg = T.TransformerConfig(**_DENSE, layers_per_stage=2,
                                  n_experts=4, moe_top_k=top_k)
        params = T.init_params(cfg, seed=1)
        cache = T.init_kv_cache(cfg, 2, 32)
        prefill = T.build_prefill(cfg)
        step = T.build_decode_step(cfg, 2, 32)
        rng = np.random.default_rng(top_k)
        prompt = rng.integers(0, cfg.vocab, size=5).astype(np.int32)
        pad = np.zeros(8, np.int32)
        pad[:5] = prompt
        cache, first, _ = prefill(params, cache, jnp.asarray(pad),
                                  np.int32(0), np.int32(5))
        toks = [int(first)]
        pos = np.zeros(2, np.int32)
        cur = np.zeros(2, np.int32)
        pos[0], cur[0] = 5, int(first)
        for _ in range(7):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos))
            t = int(np.asarray(nxt)[0])
            toks.append(t)
            pos[0] += 1
            cur[0] = t
        assert toks == _reference_greedy(params, cfg, prompt, 8)


class TestPagedDecode:
    """The block-table KV layout (ISSUE 11): prefill/step through a
    per-slot page table over one shared page pool must match the
    full-context reference token-for-token — on scrambled,
    non-contiguous pages, through sub-page prompt buckets, with one
    executable per shape."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)
    PS, PPS, SLOTS = 8, 4, 3            # 8-row pages, 32-row lanes

    def _build(self, n_pages=None):
        params = T.init_params(self.CFG, seed=0)
        n_pages = n_pages or 1 + self.SLOTS * self.PPS
        cache = T.init_paged_kv_cache(self.CFG, n_pages, self.PS)
        prefill = T.build_paged_prefill(self.CFG, self.PS, self.PPS)
        step = T.build_paged_decode_step(self.CFG, self.SLOTS,
                                         self.PS, self.PPS)
        return params, cache, prefill, step

    def _pad(self, prompt, bucket):
        out = np.zeros(bucket, np.int32)
        out[:len(prompt)] = prompt
        return jnp.asarray(out)

    @pytest.mark.parametrize("plen", [1, 3, 7, 8])
    def test_paged_greedy_matches_full_context(self, plen):
        """Four prompt lengths (sub-page and page-aligned buckets)
        decode on deliberately scrambled page tables and match the
        dense reference exactly — the layout is invisible to the
        math."""
        params, cache, prefill, step = self._build()
        rng = np.random.default_rng(plen)
        prompt = rng.integers(0, self.CFG.vocab,
                              size=plen).astype(np.int32)
        bucket = 1
        while bucket < plen:
            bucket *= 2
        tables = np.zeros((self.SLOTS, self.PPS), np.int32)
        tables[1] = [7, 2, 11, 5]       # non-contiguous on purpose
        cache, first, logits = prefill(
            params, cache, self._pad(prompt, bucket),
            jnp.asarray(tables[1]), np.int32(plen))
        ref = T.reference_logits(params, jnp.asarray(prompt)[None],
                                 self.CFG)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[0, -1]), atol=1e-4)
        toks = [int(first)]
        pos = np.zeros(self.SLOTS, np.int32)
        cur = np.zeros(self.SLOTS, np.int32)
        pos[1], cur[1] = plen, int(first)
        for _ in range(9):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos), jnp.asarray(tables))
            t = int(np.asarray(nxt)[1])
            toks.append(t)
            pos[1] += 1
            cur[1] = t
        assert toks == _reference_greedy(params, self.CFG, prompt, 10)

    def test_page_reuse_after_release(self):
        """Pages handed from a finished slot to a new one carry no
        stale rows into the next occupant's decode (the page analogue
        of slot reuse)."""
        params, cache, prefill, step = self._build()
        rng = np.random.default_rng(3)
        p_a = rng.integers(0, self.CFG.vocab, size=7).astype(np.int32)
        p_b = rng.integers(0, self.CFG.vocab, size=3).astype(np.int32)
        tables = np.zeros((self.SLOTS, self.PPS), np.int32)
        tables[0] = [4, 9, 1, 3]
        cache, first, _ = prefill(params, cache, self._pad(p_a, 8),
                                  jnp.asarray(tables[0]), np.int32(7))
        pos = np.zeros(self.SLOTS, np.int32)
        cur = np.zeros(self.SLOTS, np.int32)
        pos[0], cur[0] = 7, int(first)
        for _ in range(5):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos), jnp.asarray(tables))
            pos[0] += 1
            cur[0] = int(np.asarray(nxt)[0])
        # "release" slot 0's pages and hand page 9 to slot 2
        pos[0] = cur[0] = 0
        tables[0] = 0
        tables[2] = [9, 4, 0, 0]
        cache, first_b, _ = prefill(params, cache, self._pad(p_b, 4),
                                    jnp.asarray(tables[2]),
                                    np.int32(3))
        toks = [int(first_b)]
        pos[2], cur[2] = 3, int(first_b)
        for _ in range(5):
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos), jnp.asarray(tables))
            t = int(np.asarray(nxt)[2])
            toks.append(t)
            pos[2] += 1
            cur[2] = t
        assert toks == _reference_greedy(params, self.CFG, p_b, 6)

    def test_paged_step_compiles_once_under_table_churn(self):
        """Page tables are DATA, not shapes: churning table contents
        and occupancy reuses one executable."""
        params, cache, prefill, step = self._build()
        pos = np.zeros(self.SLOTS, np.int32)
        cur = np.zeros(self.SLOTS, np.int32)
        tables = np.zeros((self.SLOTS, self.PPS), np.int32)
        for i in range(5):
            tables[i % self.SLOTS] = (i + 1) % (self.SLOTS * self.PPS)
            pos[i % self.SLOTS] = i
            cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                 jnp.asarray(pos), jnp.asarray(tables))
        assert step._cache_size() == 1


def _big_outputs(jaxpr, n_elems, out=None):
    """Primitive names of every equation, nested jaxprs included,
    that produces ``n_elems`` elements or more."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if hasattr(v, "eqns") or hasattr(v, "jaxpr")]
        for sub in subs:
            _big_outputs(getattr(sub, "jaxpr", sub), n_elems, out)
        if not subs and any(int(np.prod(v.aval.shape)) >= n_elems
                            for v in eqn.outvars):
            out.add(eqn.primitive.name)
    return out


class TestPagedPoolLayout:
    """The paged pool is ONE array a layer (ISSUE 30): a program hands
    a layer's array whole to its kernel and writes the rows or pages
    it names in place, so every leaf's buffer stays where it was
    through every donated call, and no program holds an operation
    that produces a pool-sized value other than the in-place write."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=3)
    PS, PPS, SLOTS, W = 8, 4, 3, 3
    N_PAGES = 1 + SLOTS * PPS

    def _cache(self):
        return T.init_paged_kv_cache(self.CFG, self.N_PAGES, self.PS)

    def _call(self, program, impl, donate=True):
        """``(jitted program, the arguments after params and cache)``"""
        cfg, ps, pps = self.CFG, self.PS, self.PPS
        tables = np.zeros((self.SLOTS, pps), np.int32)
        tables[1] = [7, 2, 11, 5]
        ints = lambda *a: jnp.asarray(np.array(a, np.int32))  # noqa: E731
        kw = dict(donate=donate, attn_impl=impl)
        if program.startswith("prefill_"):
            bucket = int(program.split("_")[1])
            return (T.build_paged_prefill(cfg, ps, pps, **kw),
                    (jnp.ones(bucket, jnp.int32), ints(*tables[1]),
                     np.int32(bucket)))
        if program.startswith("prefix_"):
            bucket = int(program.split("_")[1])
            return (T.build_paged_prefix_prefill(cfg, ps, pps, **kw),
                    (jnp.ones(bucket, jnp.int32), ints(*tables[1]),
                     np.int32(ps + bucket), np.int32(ps)))
        if program == "step":
            return (T.build_paged_decode_step(cfg, self.SLOTS, ps, pps,
                                              **kw),
                    (ints(0, 5, 0), ints(0, 9, 0), jnp.asarray(tables)))
        assert program == "verify"
        return (T.build_paged_verify_step(cfg, self.SLOTS, self.W, ps,
                                          pps, donate=donate),
                (jnp.ones((self.SLOTS, self.W), jnp.int32),
                 ints(0, 9, 0), jnp.asarray(tables)))

    def test_pool_is_one_array_a_layer(self):
        cache = self._cache()
        assert sorted(cache) == ["k", "v"]
        for name in ("k", "v"):
            assert isinstance(cache[name], list)
            assert len(cache[name]) == self.CFG.n_layers == 3
            for leaf in cache[name]:
                assert leaf.shape == (self.N_PAGES, self.PS,
                                      self.CFG.n_heads, self.CFG.d_head)
                assert leaf.dtype == jnp.float32
        ptrs = [x.unsafe_buffer_pointer() for x in jax.tree.leaves(cache)]
        assert len(set(ptrs)) == 2 * self.CFG.n_layers

    @pytest.mark.parametrize("program,impl", [
        ("prefill_4", "dense"), ("prefill_8", "dense"),
        ("prefill_16", "dense"), ("prefill_8", "pallas_interpret"),
        ("prefix_4", "dense"), ("prefix_8", "dense"),
        ("prefix_16", "dense"), ("prefix_8", "pallas_interpret"),
        ("step", "dense"), ("step", "pallas_interpret"),
        ("verify", "dense")])
    def test_every_layers_buffer_stays_in_place(self, program, impl):
        """Donation holds leaf by leaf: after a prefill (a part of a
        page, one page, several), an offset prefill, a step and a
        verify each layer's buffer is the one it was, and the tree
        comes back in the layout it went in."""
        from mmlspark_tpu.testing.decode_load import (
            cache_buffer_pointers)
        params = T.init_params(self.CFG, seed=0)
        fn, args = self._call(program, impl)
        cache = self._cache()
        before = cache_buffer_pointers(cache)
        struct = jax.tree.structure(cache)
        for _ in range(2):
            cache = fn(params, cache, *args)[0]
            assert jax.tree.structure(cache) == struct
            assert cache_buffer_pointers(cache) == before
        assert fn._cache_size() == 1

    @pytest.mark.parametrize("program", [
        "prefill_4", "prefill_8", "prefill_16",
        "prefix_4", "prefix_8", "prefix_16", "step"])
    def test_kernel_programs_produce_no_pool_sized_value(self, program):
        """With the kernels on the path (``attn_impl="pallas"``, traced
        and not run) the only operations whose result is as large as a
        layer's pool are the in-place writes and the kernel call that
        takes the pool whole: no slice, gather, copy or transpose of a
        pool. The stacked layout's ``ck[l]`` was such a slice."""
        params = T.init_params(self.CFG, seed=0)
        fn, args = self._call(program, "pallas", donate=False)
        n_pages = 4097                  # a pool larger than any activation
        cache = jax.eval_shape(
            lambda: T.init_paged_kv_cache(self.CFG, n_pages, self.PS))
        jaxpr = jax.make_jaxpr(fn)(params, cache, *args).jaxpr
        layer_pool = n_pages * self.PS * self.CFG.n_heads * self.CFG.d_head
        assert _big_outputs(jaxpr, layer_pool) == {
            "step": {"scatter"}, "prefill_16": {"scatter"},
            "prefix_16": {"scatter"}}.get(
                program, {"dynamic_update_slice"})

    @pytest.mark.parametrize("bucket", [4, 8, 16])
    def test_cold_prefill_writes_only_its_pages(self, bucket):
        """A bucket under, at and over the page size: the rows land in
        the pages the table names, in order, and every other page of
        every layer is as it was (the bucket equal to the page size is
        one ``dynamic_update_slice``, not a one-chunk scatter)."""
        params = T.init_params(self.CFG, seed=0)
        fn, args = self._call(f"prefill_{bucket}", "dense",
                              donate=False)
        cache = jax.tree.map(lambda x: x + 7.0, self._cache())
        out = fn(params, cache, *args)[0]
        named = [7, 2, 11, 5][:max(bucket // self.PS, 1)]
        for name in ("k", "v"):
            for l in range(self.CFG.n_layers):
                got = np.asarray(out[name][l])
                rows = got[named].reshape(-1, *got.shape[2:])
                assert not np.any(rows[:bucket] == 7.0)
                np.testing.assert_array_equal(rows[bucket:], 7.0)
                others = np.delete(got, named, axis=0)
                np.testing.assert_array_equal(others, 7.0)


class TestSpeculativeSteps:
    """The propose/verify machinery (ISSUE 11): with the target as
    its own draft, every proposal must verify (acceptance is exactly
    1.0) and the emitted stream must equal the reference greedy
    continuation — the round invariant that rejected-position cache
    rows are repaired by later writes, proven by construction."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)

    def test_self_draft_full_acceptance_matches_reference(self):
        cfg = self.CFG
        W, slots, ps, pps = 4, 2, 8, 4
        params = T.init_params(cfg, seed=0)
        cache = T.init_paged_kv_cache(cfg, 1 + slots * pps, ps)
        prefill = T.build_paged_prefill(cfg, ps, pps)
        verify = T.build_paged_verify_step(cfg, slots, W, ps, pps)
        dcache = T.init_kv_cache(cfg, slots, pps * ps)
        dprefill = T.build_prefill(cfg)
        propose = T.build_draft_propose(cfg, slots, pps * ps, W)
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, cfg.vocab, size=4).astype(np.int32)
        pad = np.zeros(4, np.int32)
        pad[:4] = prompt
        tables = np.zeros((slots, pps), np.int32)
        tables[0] = [3, 6, 1, 2]
        cache, first, _ = prefill(params, cache, jnp.asarray(pad),
                                  jnp.asarray(tables[0]), np.int32(4))
        dcache, _, _ = dprefill(params, dcache, jnp.asarray(pad),
                                np.int32(0), np.int32(4))
        golden = _reference_greedy(params, cfg, prompt, 17)
        emitted = [int(first)]
        pos = np.zeros(slots, np.int32)
        cur = np.zeros(slots, np.int32)
        pos[0], cur[0] = 4, int(first)
        for _ in range(4):
            dcache, props = propose(params, dcache, jnp.asarray(cur),
                                    jnp.asarray(pos))
            props = np.asarray(props)
            ver_in = np.concatenate([cur[:, None], props[:, :W - 1]],
                                    axis=1).astype(np.int32)
            cache, vtok, _ = verify(params, cache,
                                    jnp.asarray(ver_in),
                                    jnp.asarray(pos),
                                    jnp.asarray(tables))
            vtok = np.asarray(vtok)
            # a model drafting for itself agrees with itself
            assert [int(t) for t in props[0]] == \
                [int(t) for t in vtok[0]]
            for j in range(W):
                emitted.append(int(vtok[0, j]))
            pos[0] += W
            cur[0] = emitted[-1]
        assert emitted == golden

    def test_layer_truncated_draft_shares_leaves(self):
        cfg = self.CFG
        params = T.init_params(cfg, seed=0)
        dp, dcfg = T.layer_truncated_draft(params, cfg, 1)
        assert dcfg.n_layers == 1
        assert dp["embed"] is params["embed"]       # aliased, no copy
        assert dp["blocks"][0] is params["blocks"][0]
        with pytest.raises(ValueError, match="draft layers"):
            T.layer_truncated_draft(params, cfg, 5)


class TestPagedAttnKernel:
    """The fused Pallas paged-attention gather (ISSUE 13): the
    block-table kernel (scalar-prefetched page tables aiming each page
    DMA, streaming softmax in VMEM) must be token-for-token equal to
    the dense materialized-lane gather on EVERY prompt bucket,
    on scrambled non-contiguous tables, across decode steps."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)
    PS, PPS, SLOTS = 8, 4, 3

    @pytest.mark.parametrize("plens", [(1, 3, 7), (8, 13, 16),
                                       (2, 16, 31)])
    def test_token_for_token_parity_every_bucket(self, plens):
        cfg = self.CFG
        n_pages = 1 + self.SLOTS * self.PPS
        prefill = T.build_paged_prefill(cfg, self.PS, self.PPS)
        params = T.init_params(cfg, seed=0)
        steps = {
            "dense": T.build_paged_decode_step(
                cfg, self.SLOTS, self.PS, self.PPS),
            "pallas": T.build_paged_decode_step(
                cfg, self.SLOTS, self.PS, self.PPS,
                attn_impl="pallas_interpret"),
        }
        rng = np.random.default_rng(sum(plens))
        perm = rng.permutation(np.arange(1, n_pages))
        tables = perm.reshape(self.SLOTS, self.PPS).astype(np.int32)
        prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
                   for n in plens]
        toks = {}
        pos = np.array([len(p) for p in prompts], np.int32)
        for name, step in steps.items():
            cache = T.init_paged_kv_cache(cfg, n_pages, self.PS)
            first = np.zeros(self.SLOTS, np.int32)
            for s, pr in enumerate(prompts):
                bucket = 1
                while bucket < len(pr):
                    bucket *= 2
                pad = np.zeros(bucket, np.int32)
                pad[:len(pr)] = pr
                cache, nxt, _ = prefill(params, cache,
                                        jnp.asarray(pad),
                                        jnp.asarray(tables[s]),
                                        np.int32(len(pr)))
                first[s] = int(nxt)
            seq = [first.copy()]
            cur, p = first.copy(), pos.copy()
            for _ in range(6):
                cache, nxt, _ = step(params, cache, jnp.asarray(cur),
                                     jnp.asarray(p),
                                     jnp.asarray(tables))
                cur = np.asarray(nxt)
                seq.append(cur.copy())
                p = p + 1
            toks[name] = np.stack(seq)
        np.testing.assert_array_equal(toks["dense"], toks["pallas"])

    def test_unknown_impl_refused(self):
        with pytest.raises(ValueError, match="attn_impl"):
            T.build_paged_decode_step(self.CFG, 2, 8, 4,
                                      attn_impl="cuda")

    def test_kernel_numerics_close_to_dense(self):
        """Beyond argmax equality: the streaming-softmax output itself
        sits at fp tolerance from the materialized-lane softmax."""
        from mmlspark_tpu.parallel.pallas_attention import (
            paged_decode_attention)
        rng = np.random.default_rng(0)
        n, h, d, ps, pps = 3, 4, 8, 8, 4
        n_pages = 1 + n * pps
        kp = jnp.asarray(rng.normal(size=(n_pages, ps, h, d)),
                         jnp.float32)
        vp = jnp.asarray(rng.normal(size=(n_pages, ps, h, d)),
                         jnp.float32)
        q = jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
        tables = rng.permutation(np.arange(1, n_pages)) \
            .reshape(n, pps).astype(np.int32)
        pos = np.array([5, 17, 30], np.int32)
        out = paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                     jnp.asarray(pos),
                                     scale=d ** -0.5, page_size=ps,
                                     interpret=True)
        # dense reference: gather the virtual lane, masked softmax
        lane_k = np.asarray(kp)[tables].reshape(n, pps * ps, h, d)
        lane_v = np.asarray(vp)[tables].reshape(n, pps * ps, h, d)
        s = np.einsum("nhk,nshk->nhs", np.asarray(q), lane_k) \
            * d ** -0.5
        idx = np.arange(pps * ps)
        s = np.where(idx[None, None, :] <= pos[:, None, None],
                     s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("nhs,nshk->nhk", p, lane_v)
        np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)

    ROWS = 16                           # a page of the cells

    @classmethod
    def _pool(cls, pps, groups, dtype, seed, free_slot=True):
        """Six slots over tables of ``pps`` entries at the positions
        where the walk changes: a free slot (position 0, an all-scratch
        table; ``free_slot=False`` claims its page), the last row of a
        page and the first of the next, the last row of a fetch and the
        first of the next, the table's last row. Entries past a slot's
        walk name the scratch page. head_dim 128, so the kernel walks
        the table by its own copies (the tests above, at head_dim 8,
        are fed by the ``BlockSpec`` pipeline)."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        ps, h_kv, d = cls.ROWS, 2, 128
        fetch = PA.paged_fetch_pages(pps, ps, h_kv, d, dtype)
        assert fetch < pps                   # several fetches a slot
        pos = np.array([0, 3 * ps - 1, 3 * ps, fetch * ps - 1,
                        fetch * ps, pps * ps - 1], np.int32)
        walk = pos // ps + 1
        rng = np.random.default_rng(seed)
        n_pages = 1 + int(walk.sum())
        k, v = (jnp.asarray(rng.normal(size=(n_pages, ps, h_kv, d)),
                            jnp.float32).astype(dtype) for _ in range(2))
        q = jnp.asarray(rng.normal(size=(len(pos), groups * h_kv, d)),
                        jnp.float32).astype(dtype)
        claimed = iter(rng.permutation(np.arange(1, n_pages)))
        tables = np.zeros((len(pos), pps), np.int32)
        for t, w in zip(tables, walk):
            t[:w] = [next(claimed) for _ in range(w)]
        if free_slot:
            tables[0] = 0
        return q, k, v, tables, pos

    @pytest.mark.parametrize("pps", [64, 192, 640])
    @pytest.mark.parametrize("groups", [1, 4])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_kernel_matches_dense_twin(self, dtype, groups, pps):
        """The kernel (interpreted) against the dense twin (every
        slot's lane gathered, one masked softmax) at the three cells'
        table lengths, with and without grouped queries, in both page
        dtypes: every row ``index <= pos`` is read and no other."""
        from mmlspark_tpu.models.granite_hybrid import _lane_attention
        from mmlspark_tpu.parallel.pallas_attention import (
            paged_decode_attention)
        q, k, v, tables, pos = self._pool(pps, groups, dtype, seed=pps)
        got = paged_decode_attention(q, k, v, jnp.asarray(tables),
                                     jnp.asarray(pos), scale=0.3,
                                     page_size=self.ROWS, interpret=True)
        assert got.dtype == q.dtype and got.shape == q.shape
        f32 = jnp.float32
        want = _lane_attention(q.astype(f32), k.astype(f32), v.astype(f32),
                               jnp.asarray(tables), jnp.asarray(pos), 0.3)
        np.testing.assert_allclose(
            np.asarray(got.astype(f32)), np.asarray(want),
            atol=1e-5 if dtype == "float32" else 2e-2)

    def test_nothing_dead_reaches_the_result(self):
        """The scratch page, every page no live entry names and every
        row past ``pos`` in a slot's last page hold NaN, and the output
        is the clean pool's bit for bit: a dead entry is neither
        copied nor read, a dead row is removed, not multiplied by 0."""
        from mmlspark_tpu.parallel.pallas_attention import (
            paged_decode_attention)
        q, k, v, tables, pos = self._pool(64, 1, "float32", seed=7,
                                          free_slot=False)
        dead = np.ones(k.shape[:2], bool)             # (page, row)
        for t, p in zip(tables, pos):
            dead[t[:p // self.ROWS + 1]] = False
            dead[t[p // self.ROWS], p % self.ROWS + 1:] = True
        assert not dead[tables[np.arange(len(pos)), pos // self.ROWS],
                        pos % self.ROWS].any() and dead[0].all()
        outs = []
        for poison in (False, True):
            kk, vv = (jnp.where(dead[:, :, None, None] & poison, jnp.nan, x)
                      for x in (k, v))
            outs.append(np.asarray(paged_decode_attention(
                q, kk, vv, jnp.asarray(tables), jnp.asarray(pos),
                scale=0.3, page_size=self.ROWS, interpret=True)))
        assert np.isfinite(outs[0]).all()
        np.testing.assert_array_equal(outs[0], outs[1])


class TestVerifyScores:
    """The fused-CE verify/score path (ISSUE 13): the width-k verify
    emits per-proposal target log-probs; the fused (streaming CE) and
    XLA (logsumexp-minus-gold) engines agree, and the scores really
    are the log-probs of the proposed tokens."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)

    def _scores(self, ce_impl):
        cfg = self.CFG
        W, slots, ps, pps = 4, 2, 8, 4
        params = T.init_params(cfg, seed=0)
        cache = T.init_paged_kv_cache(cfg, 1 + slots * pps, ps)
        prefill = T.build_paged_prefill(cfg, ps, pps)
        verify = T.build_paged_verify_step(cfg, slots, W, ps, pps,
                                           with_scores=True,
                                           ce_impl=ce_impl)
        rng = np.random.default_rng(5)
        tables = (1 + np.arange(slots * pps)).reshape(slots, pps) \
            .astype(np.int32)
        pos = np.zeros(slots, np.int32)
        first = np.zeros(slots, np.int32)
        for s in range(slots):
            pr = rng.integers(1, cfg.vocab, size=3 + s) \
                .astype(np.int32)
            pad = np.zeros(4, np.int32)
            pad[:len(pr)] = pr
            cache, nxt, _ = prefill(params, cache, jnp.asarray(pad),
                                    jnp.asarray(tables[s]),
                                    np.int32(len(pr)))
            pos[s], first[s] = len(pr), int(nxt)
        toks = np.concatenate(
            [first[:, None],
             rng.integers(1, cfg.vocab, size=(slots, W - 1))],
            axis=1).astype(np.int32)
        cache, greedy, logits, scores = verify(
            params, cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(tables))
        return toks, np.asarray(greedy), np.asarray(logits), \
            np.asarray(scores)

    def test_fused_matches_xla(self):
        toks_x, g_x, l_x, s_x = self._scores("xla")
        toks_f, g_f, l_f, s_f = self._scores("fused_interpret")
        np.testing.assert_array_equal(g_x, g_f)
        np.testing.assert_allclose(s_x, s_f, atol=1e-4)

    def test_scores_are_proposal_logprobs(self):
        toks, greedy, logits, scores = self._scores("xla")
        lg = logits[:, :-1].astype(np.float64)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True))
                     .sum(-1)) + lg.max(-1)
        for n in range(toks.shape[0]):
            for j in range(toks.shape[1] - 1):
                ref = lg[n, j, toks[n, j + 1]] - lse[n, j]
                assert abs(scores[n, j] - ref) < 1e-4

    def test_unknown_ce_impl_refused(self):
        with pytest.raises(ValueError, match="ce_impl"):
            T.build_paged_verify_step(self.CFG, 2, 4, 8, 4,
                                      with_scores=True, ce_impl="tpu")

    def test_engine_resolution(self):
        # CPU backend: auto always resolves to xla (fused needs TPU)
        assert T.verify_ce_engine(self.CFG, 64, 8) == "xla"


class TestFlashPrefill:
    """The streaming-softmax Pallas prefill kernel (ISSUE 17): every
    prefill builder's flash engine must be token-for-token (and
    cache-row-for-cache-row) equal to its dense engine, including
    offset/partial prefix prefill and the scratch-page overshoot
    convention — interpret mode is the CPU parity contract."""

    CFG = T.TransformerConfig(**_DENSE, layers_per_stage=2)
    PS, PPS = 8, 4

    @pytest.mark.parametrize("s", [1, 5, 16, 63])
    def test_kernel_matches_dense_attention(self, rng, s):
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_prefill_attention)
        from mmlspark_tpu.parallel.ring_attention import dense_attention
        q, k, v = (jnp.asarray(rng.normal(size=(2, s, 3, 8)),
                               jnp.float32) for _ in range(3))
        ref = dense_attention(q, k, v, causal=True)
        got = flash_prefill_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("plen", [3, 8, 13])
    def test_cold_prefill_parity_both_layouts(self, rng, plen):
        cfg = self.CFG
        params = T.init_params(cfg, seed=0)
        prompt = rng.integers(1, cfg.vocab, size=plen).astype(np.int32)
        bucket = 1
        while bucket < plen:
            bucket *= 2
        pad = np.zeros(bucket, np.int32)
        pad[:plen] = prompt
        outs = {}
        for impl in ("dense", "pallas_interpret"):
            # slot-lane layout
            f = T.build_prefill(cfg, donate=False, attn_impl=impl)
            _, nxt, logits = f(params, T.init_kv_cache(cfg, 2, 32),
                               jnp.asarray(pad), jnp.int32(0),
                               jnp.int32(plen))
            # paged layout
            fp = T.build_paged_prefill(cfg, self.PS, self.PPS,
                                       donate=False, attn_impl=impl)
            cache, pnxt, plogits = fp(
                params, T.init_paged_kv_cache(cfg, 1 + self.PPS,
                                              self.PS),
                jnp.asarray(pad),
                jnp.arange(1, 1 + self.PPS, dtype=jnp.int32),
                jnp.int32(plen))
            outs[impl] = (int(nxt), np.asarray(logits), int(pnxt),
                          np.asarray(plogits), np.stack(cache["k"]))
        d, fl = outs["dense"], outs["pallas_interpret"]
        assert d[0] == fl[0] and d[2] == fl[2]
        np.testing.assert_allclose(fl[1], d[1], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(fl[3], d[3], atol=1e-4, rtol=1e-4)
        # the K/V the decode steps will read are identical rows
        np.testing.assert_allclose(fl[4], d[4], atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("pps,hit_pages,suffix", [
        (4, 1, 11), (4, 2, 5),
        # hit 4 pages + suffix bucket 32 reaches past the 7-page lane:
        # the overflow chunk must ride scratch page 0, never re-aim at
        # a shared page
        (7, 4, 17)])
    def test_prefix_offset_prefill_parity(self, rng, pps, hit_pages,
                                          suffix):
        """Offset prefill over shared pages, including the bucket-
        overshoot shape."""
        cfg = self.CFG
        params = T.init_params(cfg, seed=0)
        hit = hit_pages * self.PS
        length = hit + suffix
        assert length <= self.PS * pps
        prompt = rng.integers(1, cfg.vocab,
                              size=length).astype(np.int32)
        bucket = 1
        while bucket < suffix:
            bucket *= 2
        pad = np.zeros(bucket, np.int32)
        pad[:suffix] = prompt[hit:]
        table = jnp.arange(1, 1 + pps, dtype=jnp.int32)
        # shared prefix pages: a dense full prefill of the whole
        # prompt wrote them (the cache invariant: shared pages ARE a
        # previous cold prefill's output) — run as an offset prefill
        # at hit 0, which handles overshooting prompt buckets too
        cold = T.build_paged_prefix_prefill(cfg, self.PS, pps,
                                            donate=False)
        pbucket = 1
        while pbucket < length:
            pbucket *= 2
        ppad = np.zeros(pbucket, np.int32)
        ppad[:length] = prompt
        warm_cache, cold_nxt, cold_logits = cold(
            params, T.init_paged_kv_cache(cfg, 1 + pps, self.PS),
            jnp.asarray(ppad), table, jnp.int32(length), jnp.int32(0))
        outs = {}
        for impl in ("dense", "pallas_interpret"):
            f = T.build_paged_prefix_prefill(cfg, self.PS, pps,
                                             donate=False,
                                             attn_impl=impl)
            cache, nxt, logits = f(params, warm_cache,
                                   jnp.asarray(pad), table,
                                   jnp.int32(length), jnp.int32(hit))
            outs[impl] = (int(nxt), np.asarray(logits),
                          np.stack(cache["k"]))
        d, fl = outs["dense"], outs["pallas_interpret"]
        assert d[0] == fl[0]
        np.testing.assert_allclose(fl[1], d[1], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(fl[2], d[2], atol=1e-5, rtol=1e-5)
        # offset prefill is EXACT, not approximate: both engines land
        # on the cold full-prefill's next token, and neither rewrote a
        # shared prefix page (rows outside the lane rode scratch)
        assert d[0] == int(cold_nxt)
        np.testing.assert_allclose(fl[1], np.asarray(cold_logits),
                                   atol=1e-4, rtol=1e-4)
        shared = np.stack(warm_cache["k"])[:, 1:1 + hit_pages]
        np.testing.assert_array_equal(
            fl[2][:, 1:1 + hit_pages], shared)

    def test_unknown_impl_refused_on_every_builder(self):
        for build in (lambda: T.build_prefill(self.CFG,
                                              attn_impl="tensor"),
                      lambda: T.build_paged_prefill(
                          self.CFG, self.PS, self.PPS,
                          attn_impl="tensor"),
                      lambda: T.build_paged_prefix_prefill(
                          self.CFG, self.PS, self.PPS,
                          attn_impl="tensor")):
            with pytest.raises(ValueError, match="attn_impl"):
                build()

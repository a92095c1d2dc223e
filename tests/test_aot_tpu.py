"""Ahead-of-time compilation for the v5e, with no chip: the check to run
before any chip call.

``jax.experimental.topologies.get_topology_desc("v5e:2x2", "tpu")``
gives the installed libtpu's compiler a device description to target,
so Mosaic really lowers every Pallas kernel and XLA:TPU really compiles
every whole program — a kernel that overflows VMEM or a program that
cannot be partitioned is rejected here, in the sandbox, instead of on
billed chip time. Shapes are ``chip_smoke.py``'s (``FULL``); engines are
NAMED, because ``"auto"`` reads the process's default backend (the CPU
here) and a named engine does not.

Compilation is not execution: what this cannot see is exactly what
``chip_smoke.py`` exists for. Marked ``slow`` (minutes of XLA:TPU
compile on the host); run it alone::

    JAX_PLATFORMS=cpu python -m pytest tests/test_aot_tpu.py -m slow -q
"""

import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import chip_smoke  # noqa: E402
from mmlspark_tpu.models import transformer as T  # noqa: E402

pytestmark = pytest.mark.slow

FULL = chip_smoke.FULL
NAMED = dataclasses.replace(FULL, attention_impl="folded",
                            ce_impl="fused", attn_impl="pallas")
PAGE = 16                                   # TransformerDecoder's default
PAGES_PER_SLOT = FULL.max_len // PAGE
N_PAGES = 1 + FULL.n_slots * PAGES_PER_SLOT


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"no TPU compiler to target: {e}")


def _mesh(topo, shape: dict) -> Mesh:
    n = int(np.prod(list(shape.values())))
    return Mesh(np.array(topo.devices[:n]).reshape(tuple(shape.values())),
                tuple(shape))


def _abstract(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one
    sharding, or a matching tree of them)."""
    if not isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _n_mosaic(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _expert_walks(compiled) -> int:
    """Mosaic calls of ``touched_experts``, the decode step's walk over
    the experts its slots routed to (XLA names a Mosaic call after the
    jitted function it came from)."""
    return len(re.findall(r"%touched_experts\S* = .* custom-call\(",
                          compiled.as_text()))


def _dense_expert_reads(compiled, w_in_e) -> list:
    """Lines of the optimized HLO, other than the held experts' own
    parameters and the walks that take them whole, that hold a shape of
    ``w_in_e`` ([E, D, 2F]): a fused masked product over every held
    expert (the ``edf`` operand of ``td,edf->etf``) would."""
    shape = "bf16[" + ",".join(map(str, w_in_e.shape)) + "]"
    return [line for line in compiled.as_text().splitlines()
            if shape in line and " parameter(" not in line
            and "touched_experts" not in line
            and not line.startswith(("HloModule", "ENTRY"))]


def _flash_call_names(compiled):
    """Names of the Mosaic calls that are the flash forward kernel: XLA
    names the instruction after the jitted ``_flash_call``, and the
    benchmark finds the kernel in a trace by that name."""
    return re.findall(r"%(\S*_flash_call\S*) = .* custom-call\(",
                      compiled.as_text())


# ---------------------------------------------------------------------------
# each Pallas kernel family, alone, on one device


def _compile_on_one(topo, fn, *shapes):
    one = NamedSharding(_mesh(topo, {"x": 1}), P())
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


class TestKernels:
    B, S, H, DH = FULL.batch, FULL.seq, FULL.n_heads, FULL.d_head

    # folded: fwd, dq, dkv; flash: fwd and the one backward kernel
    @pytest.mark.parametrize("family,n_kernels", [("folded", 3),
                                                  ("flash", 2)])
    def test_training_attention_fwd_bwd(self, topo, family, n_kernels):
        from mmlspark_tpu.parallel import pallas_attention as PA
        attn = (PA.flash_attention_folded if family == "folded"
                else PA.flash_attention)
        qkv = ((self.B, self.S, self.H, self.DH), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(attn(q, k, v, True).astype(jnp.float32))

        c = _compile_on_one(topo, jax.grad(loss, argnums=(0, 1, 2)),
                            qkv, qkv, qkv)
        assert _n_mosaic(c) == n_kernels

    def test_fused_ce_fwd_bwd(self, topo):
        from mmlspark_tpu.ops.fused_ce import fused_softmax_xent
        t = FULL.batch * FULL.seq

        def loss(h, w, lbl):
            return jnp.sum(fused_softmax_xent(
                h, w, lbl, compute_dtype=jnp.bfloat16))

        c = _compile_on_one(
            topo, jax.grad(loss, argnums=(0, 1)),
            ((t, FULL.d_model), jnp.float32),
            ((FULL.d_model, FULL.vocab), jnp.float32), ((t,), jnp.int32))
        assert _n_mosaic(c) == 3            # fwd, dh, dW

    # the four serving cells' tables (slots, table entries a slot,
    # query heads, K/V heads, head_dim, page dtype; pages of 16 rows),
    # and the smoke test's: at head_dim 64 the pages come through the
    # ``BlockSpec`` pipeline (Mosaic slices no memref of half a lane
    # register, so the kernel cannot aim its own copies there)
    @pytest.mark.parametrize("n,pps,h,h_kv,d,dtype", [
        (8, 64, 16, 16, 128, jnp.float32),    # pythia-1.4b.chat-closed
        (8, 192, 32, 32, 128, jnp.bfloat16),  # evabyte-6.5b.doc-closed
        (16, 640, 32, 8, 128, jnp.bfloat16),  # granite-4.0-h-small.rag-closed
        (8, 40, 16, 16, 128, jnp.bfloat16),   # ouro-2.6b.reason-closed
        (FULL.n_slots, PAGES_PER_SLOT, FULL.n_heads, FULL.n_heads,
         FULL.d_head, jnp.float32),
    ])
    def test_paged_decode_attention(self, topo, n, pps, h, h_kv, d, dtype):
        """ONE Mosaic call that walks the table, named after the jitted
        function (the benchmark finds the kernel in a trace by that
        name), with the K and V pages of two fetches inside the budget
        the rule states and the whole kernel inside the v5e's default
        scoped VMEM (it asks for no limit of its own; or the compile
        fails)."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        pool = ((1 + n * pps, PAGE, h_kv, d), dtype)
        c = _compile_on_one(
            topo, functools.partial(PA.paged_decode_attention,
                                    scale=d ** -0.5, page_size=PAGE),
            ((n, h, d), dtype), pool, pool,
            ((n, pps), jnp.int32), ((n,), jnp.int32))
        assert _n_mosaic(c) == 1
        assert len(re.findall(r"%(\S*paged_decode_attention\S*) = .* "
                              r"custom-call\(", c.as_text())) == 1
        fetch = PA.paged_fetch_pages(pps, PAGE, h_kv, d, dtype)
        assert 4 * fetch * PA._paged_page_vmem_bytes(
            PAGE, h_kv, d, dtype) <= PA._PAGED_VMEM_BUDGET

    # the decode step's expert walk at the two hybrid cells' layers:
    # (slots, d_model, expert width, experts held)
    @pytest.mark.parametrize("t,d,f,e", [
        (32, 2048, 1536, 64),    # lfm2-24b-a2b.assist-closed
        (16, 4096, 768, 36),     # granite-4.0-h-small.rag-closed
    ])
    def test_touched_experts(self, topo, t, d, f, e):
        """ONE Mosaic call named after the jitted function, the experts
        left in HBM (no temporary), two chunks' copies inside the budget
        the rule states and the kernel inside the scoped VMEM it asks
        for (or the compile fails)."""
        from mmlspark_tpu.parallel import pallas_moe as PM
        bf = jnp.bfloat16
        c = _compile_on_one(
            topo, PM.touched_experts, ((t, d), bf), ((t, e), jnp.float32),
            ((e, d, 2 * f), bf), ((e, f, d), bf), ((e,), jnp.int32),
            ((), jnp.int32))
        assert _n_mosaic(c) == _expert_walks(c) == 1
        assert c.memory_analysis().temp_size_in_bytes == 0
        chunk = PM.expert_chunk(d, f, 2)
        assert 2 * 3 * d * chunk * 2 <= PM._MOE_VMEM_BUDGET
        assert d * chunk * 2 == 3 * 2**20          # copies of 3 MiB

    @pytest.mark.parametrize("s", [8, 128, FULL.max_len])
    def test_flash_prefill_attention(self, topo, s):
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_prefill_attention)
        qkv = ((1, s, self.H, self.DH), jnp.float32)
        c = _compile_on_one(topo, flash_prefill_attention, qkv, qkv, qkv)
        assert _n_mosaic(c) == 1

    # the benchmark's cells at their real shapes: Mosaic takes the
    # 64-lane blocks, the tiles ``flash_tiles`` picks fit VMEM, and the
    # instruction keeps the name the benchmark's readers look for

    def test_flash_attention_pretrain_2k(self, topo):
        """``pythia-410m.pretrain-2k``: (4, 2048, 16, 64) bf16, forward
        and gradient: the forward kernel and the one backward kernel,
        each under the name the benchmark counts it by (the forward's
        pattern must not find the backward)."""
        from mmlspark_tpu.parallel import pallas_attention as PA
        qkv = ((4, 2048, 16, 64), jnp.bfloat16)

        def loss(q, k, v):
            return jnp.sum(PA.flash_attention(
                q, k, v, True).astype(jnp.float32))

        c = _compile_on_one(topo, jax.grad(loss, argnums=(0, 1, 2)),
                            qkv, qkv, qkv)
        assert _n_mosaic(c) == 2
        assert len(_flash_call_names(c)) == 1
        bwd = re.findall(r"%(\S*_flash_bwd_call\S*) = .* custom-call\(",
                         c.as_text())
        assert len(bwd) == 1 and "_flash_call" not in bwd[0]
        tq, tk = PA.flash_bwd_tiles(2048, 2048, 64, jnp.bfloat16)
        assert f"flash.bwd_t{tq}x{tk}" in c.as_text()

    @pytest.mark.parametrize("s", [16, 128, 512, 1024])
    def test_flash_prefill_chat_closed(self, topo, s):
        """``pythia-1.4b.chat-closed``'s prefill buckets: (1, S, 16,
        128) f32."""
        from mmlspark_tpu.parallel.pallas_attention import (
            flash_prefill_attention)
        qkv = ((1, s, 16, 128), jnp.float32)
        c = _compile_on_one(topo, flash_prefill_attention, qkv, qkv, qkv)
        assert _n_mosaic(c) == 1
        assert _flash_call_names(c)

    @pytest.mark.parametrize("s", [1, 16, 128, FULL.max_len])
    def test_paged_prefix_prefill_attention(self, topo, s):
        from mmlspark_tpu.parallel.pallas_attention import (
            paged_prefix_prefill_attention)
        pool = ((N_PAGES, PAGE, self.H, self.DH), jnp.float32)
        c = _compile_on_one(
            topo, functools.partial(paged_prefix_prefill_attention,
                                    scale=self.DH ** -0.5, page_size=PAGE),
            ((s, self.H, self.DH), jnp.float32), pool, pool,
            ((PAGES_PER_SLOT,), jnp.int32), ((), jnp.int32))
        assert _n_mosaic(c) == 1

    def test_gbdt_histogram(self, topo):
        from mmlspark_tpu.gbdt import pallas_hist as PH
        n, f = FULL.fit_rows, FULL.fit_features
        n_pad = PH._round_up(n, PH.ROW_TILE)
        f_pad = PH._round_up(f, PH.F_TILE)
        c = _compile_on_one(
            topo, functools.partial(PH.build_histogram_pallas,
                                    n_features=f, n_bins=256),
            ((f_pad, n_pad), jnp.int32), ((n,), jnp.float32),
            ((n,), jnp.float32), ((n,), jnp.bool_))
        assert _n_mosaic(c) == 1


# ---------------------------------------------------------------------------
# the smoke's whole programs


def _train_compiled(topo, mesh_shape: dict, sz):
    mesh = _mesh(topo, mesh_shape)
    cfg = chip_smoke.transformer_config(sz, "bfloat16")
    step = T.build_spmd_train_step(cfg, mesh, learning_rate=0.01)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), T.param_specs(cfg, mesh),
        is_leaf=lambda s: isinstance(s, P))
    params = _abstract(jax.eval_shape(lambda: T.init_params(cfg, 0)),
                       shardings)
    data = NamedSharding(mesh, P("data", None))
    tok = jax.ShapeDtypeStruct((sz.batch, sz.seq), jnp.int32,
                               sharding=data)
    mask = jax.ShapeDtypeStruct((sz.batch, sz.seq), jnp.float32,
                                sharding=data)
    return step.lower(params, params, tok, tok, mask).compile()


class TestTrainPrograms:
    def test_one_chip_step_holds_the_kernels(self, topo):
        """``train`` at full depth: the compiled text carries exactly
        the Pallas calls ``chip_smoke.phase_train`` asserts."""
        from mmlspark_tpu.ops import fused_ce
        from mmlspark_tpu.parallel import pallas_attention as PA
        calls = chip_smoke._pallas_calls(
            _train_compiled(topo, {"data": 1}, NAMED).as_text())

        def n(fn):
            return sum(c for name, c in calls.items()
                       if f"jit({fn.__name__})" in name)

        L = NAMED.n_layers
        assert (n(PA._ffwd_call), n(PA._fbwd_call)) == (L, 2 * L), calls
        assert (n(fused_ce._fwd_call), n(fused_ce._bwd_call)) == (1, 2), \
            calls

    def test_data2_model2_step(self, topo):
        """``train4`` (depth cut to 2: every layer is the same
        program text)."""
        sz = dataclasses.replace(NAMED, n_layers=2)
        c = _train_compiled(topo, {"data": 2, "model": 2}, sz)
        assert _n_mosaic(c) == 2 * 3 + 3    # per layer fwd+2 bwd, CE 3


def _decode_programs(topo, mesh_shape, sz, cfg=None):
    """AOT-compile the decoder's program set — the step and every
    bucket of both prefills, what ``warmup()`` compiles — the way
    ``TransformerDecoder`` builds them. ``sz`` gives slots, lane and
    engine; ``cfg`` the model, where it is not the smoke's."""
    from mmlspark_tpu.parallel.sharding import bucket_ladder
    if cfg is None:
        cfg = chip_smoke.transformer_config(sz, "float32")
    pages_per_slot = sz.max_len // PAGE
    n_pages = 1 + sz.n_slots * pages_per_slot
    if mesh_shape is None:
        mesh = _mesh(topo, {"x": 1})
        repl = cache_sh = NamedSharding(mesh, P())
        param_sh = repl
        cache_sharding = None
    else:
        mesh = _mesh(topo, mesh_shape)
        repl = NamedSharding(mesh, P())
        cache_sharding = cache_sh = NamedSharding(
            mesh, T.decode_cache_spec(mesh))
        param_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s),
            T.decode_param_specs(cfg, mesh),
            is_leaf=lambda s: isinstance(s, P))
    params = _abstract(jax.eval_shape(lambda: T.init_params(cfg, 0)),
                       param_sh)
    cache = _abstract(jax.eval_shape(
        lambda: T.init_paged_kv_cache(cfg, n_pages, PAGE)), cache_sh)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    kw = dict(cache_sharding=cache_sharding, attn_impl=sz.attn_impl)
    prefill = T.build_paged_prefill(cfg, PAGE, pages_per_slot, **kw)
    prefix = T.build_paged_prefix_prefill(cfg, PAGE, pages_per_slot, **kw)
    step = T.build_paged_decode_step(cfg, sz.n_slots, PAGE,
                                     pages_per_slot, **kw)
    out = {"step": step.lower(
        params, cache, arg((sz.n_slots,)), arg((sz.n_slots,)),
        arg((sz.n_slots, pages_per_slot))).compile()}
    for s in bucket_ladder(sz.max_len):
        out[f"prefill_{s}"] = prefill.lower(
            params, cache, arg((s,)), arg((pages_per_slot,)),
            arg(())).compile()
        out[f"prefix_{s}"] = prefix.lower(
            params, cache, arg((s,)), arg((pages_per_slot,)), arg(()),
            arg(())).compile()
    return out


class TestServePrograms:
    """Depth cut to 2 (the layers repeat one program text); widths,
    pool and buckets are the smoke's."""

    SZ = dataclasses.replace(NAMED, n_layers=2)

    @pytest.mark.parametrize("mesh_shape", [None, {"model": 4}],
                             ids=["one_chip", "model4"])
    def test_decoder_programs(self, topo, mesh_shape):
        progs = _decode_programs(topo, mesh_shape, self.SZ)
        assert len(progs) == 23             # what warmup() reports
        for name, c in progs.items():
            # one attention kernel per layer in every program
            assert _n_mosaic(c) == self.SZ.n_layers, name


_POOL_MOVE = re.compile(
    r"= \(?\w+\[([\d,]+)\]\S* "
    r"(copy|copy-start|slice|dynamic-slice|transpose)\(")


def _pool_sized_moves(text: str, n_elems: int):
    """The instructions of a compiled program that copy, slice or
    transpose ``n_elems`` elements or more (one layer's page pool):
    what a program that touches only the pages it names does not hold
    (a layer sliced out of a stacked pool is such a ``slice``, under
    ``slice_bitcast_fusion``)."""
    return [line.strip()[:200] for line in text.splitlines()
            for m in [_POOL_MOVE.search(line)]
            if m and np.prod([int(d) for d in m.group(1).split(",")])
            >= n_elems]


class TestPythiaServeCell:
    """The softmax block's decode programs at the size of the cell
    ``pythia-1.4b.chat-closed`` (``benchmark/configs/pythia-1.4b.json``:
    every width, all 24 layers, page 16, lanes of 1,024): the pool is
    one array a layer, so no program's temporaries are of a pool's
    size (the stacked pool's 16-token bucket held 4.52 GiB of them at
    8 slots), nothing copies or slices a layer's pool, and the 23
    programs of ``warmup()`` compile at 16 slots too."""

    TEMP_LIMIT = 2 ** 28                    # 0.25 GiB

    @staticmethod
    def _programs(topo, n_slots):
        import json
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "pythia-1.4b.json")) as f:
            m = json.load(f)
        sv = m["serve"]
        assert (sv["n_slots"], sv["page_size"]) == (8, PAGE)
        cfg = T.TransformerConfig(
            vocab=m["vocab_size"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"], d_head=m["head_dim"],
            d_ff=m["intermediate_size"], n_stages=1,
            layers_per_stage=m["num_hidden_layers"], dtype=sv["dtype"])
        sz = dataclasses.replace(NAMED, n_slots=n_slots,
                                 max_len=sv["max_len"])
        layer_pool = ((1 + n_slots * sv["max_len"] // PAGE) * PAGE
                      * cfg.n_heads * cfg.d_head)
        return cfg, layer_pool, _decode_programs(topo, None, sz, cfg)

    @pytest.mark.parametrize("n_slots", [8, 16])
    def test_programs_touch_only_the_pages_they_name(self, topo, n_slots):
        cfg, layer_pool, progs = self._programs(topo, n_slots)
        assert len(progs) == 23             # what warmup() reports
        for name, c in progs.items():
            assert _n_mosaic(c) == cfg.n_layers, name
            assert c.memory_analysis().temp_size_in_bytes \
                < self.TEMP_LIMIT, name
            assert not _pool_sized_moves(c.as_text(), layer_pool), name
        # the benchmark finds the step's kernel by this name
        assert progs["step"].as_text().count(
            "paged_decode_attention") >= cfg.n_layers


class TestEvaBytePrograms:
    """The EVA block kind's programs at the shapes of the cell
    ``evabyte-6.5b.doc-closed`` (``benchmark/configs/evabyte-6.5b.json``:
    every width, 8 slots, 1,537 pages of 16 rows, 64 summary + 128
    window pages a slot), depth cut to 2: the layers repeat one program
    text, and each layer's pool is an array of its own. What the chip's
    compiler has to take: ``paged_decode_attention`` over bfloat16 pages,
    the flash forward kernel over [1,024 summary rows | the tile] with
    the visibility in its positions, and no temporary of a pool's size
    (the softmax block's programs copy the pool: PERF.md, fault (b))."""

    N_SLOTS, PAGE, N_PAGES = 8, 16, 1537
    SUM_PAGES, WIN_PAGES = 64, 128

    @pytest.fixture(scope="class")
    def eva(self, topo):
        from mmlspark_tpu.models import evabyte as E
        one = NamedSharding(_mesh(topo, {"x": 1}), P())
        cfg = E.EvaByteConfig(n_layers=2)
        params = _abstract(jax.eval_shape(
            lambda: E.init_params(cfg, 0)), one)
        cache = _abstract(jax.eval_shape(
            lambda: E.init_cache(cfg, self.N_PAGES, self.PAGE)), one)

        def ints(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
        return E, cfg, params, cache, ints

    @staticmethod
    def _pool_bytes(cfg, n_pages, page):
        return n_pages * page * cfg.n_heads * cfg.d_head * 2

    def test_step(self, eva):
        E, cfg, params, cache, ints = eva
        c = E.build_eva_step(cfg, self.PAGE, attn_impl="pallas").lower(
            params, cache, ints(self.N_SLOTS), ints(self.N_SLOTS),
            ints(self.N_SLOTS, self.SUM_PAGES + self.WIN_PAGES)).compile()
        assert _n_mosaic(c) == cfg.n_layers
        assert "paged_decode_attention" in c.as_text()
        assert c.memory_analysis().temp_size_in_bytes \
            < self._pool_bytes(cfg, self.N_PAGES, self.PAGE) // 4

    @pytest.mark.parametrize("tile", [128, 2048])
    def test_window_prefill(self, eva, tile):
        E, cfg, params, cache, ints = eva
        c = E.build_eva_prefill(cfg, self.PAGE, attn_impl="pallas").lower(
            params, cache, ints(tile), ints(self.SUM_PAGES),
            ints(self.WIN_PAGES), ints(), ints()).compile()
        assert _n_mosaic(c) == cfg.n_layers
        assert "_flash_call" in c.as_text()
        assert c.memory_analysis().temp_size_in_bytes \
            < self._pool_bytes(cfg, self.N_PAGES, self.PAGE)

    def test_compaction(self, eva):
        E, cfg, params, cache, ints = eva
        summ = [{"phi": b["phi"], "mu": b["mu"]} for b in params["blocks"]]
        c = E.build_eva_compact(cfg, self.PAGE).lower(
            summ, cache, ints(self.WIN_PAGES),
            ints(cfg.summaries_per_window // self.PAGE)).compile()
        assert c.memory_analysis().temp_size_in_bytes \
            < self._pool_bytes(cfg, self.N_PAGES, self.PAGE) // 4


# ---------------------------------------------------------------------------
# the Granite-hybrid block kind at the cell's shapes
# (benchmark/configs/granite-4.0-h-small.json): the two programs the
# decoder compiles, against abstract weights


class TestGraniteHybridPrograms:
    N_SLOTS, MAX_LEN, N_PAGES, TILE = 16, 10240, 10241, 512

    @pytest.fixture(scope="class")
    def shapes(self, topo):
        from mmlspark_tpu.models import granite_hybrid as GH
        cfg = GH.GraniteHybridConfig(vocab=50176,
                                     experts_held=tuple(range(36)))
        one = NamedSharding(_mesh(topo, {"x": 1}), P())
        params = _abstract(jax.eval_shape(lambda: GH.init_params(cfg, 0)),
                           one)
        cache = _abstract(jax.eval_shape(
            lambda: GH.init_cache(cfg, self.N_SLOTS, self.N_PAGES, PAGE)),
            one)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

        return GH, cfg, params, cache, i32

    @staticmethod
    def _nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    def test_step(self, shapes):
        """One token for 16 slots: the recurrent state and the K/V
        pools are updated where they lie (every cache byte aliased, no
        temporary the size of a state), the grouped-query paged kernel
        is one Mosaic call, and each of the ten expert layers one more,
        the walk over the touched experts, with no masked product over
        the 36 held left beside it (10.81 GB of arguments, 16 MB of
        temporaries)."""
        GH, cfg, params, cache, i32 = shapes
        low = GH.build_hybrid_step(cfg, PAGE, attn_impl="pallas").lower(
            params, cache, i32(self.N_SLOTS), i32(self.N_SLOTS),
            i32(self.N_SLOTS, self.MAX_LEN // PAGE))
        # the one fetch packs counters behind the tokens; the tokens
        # alone are an output too, which the loop hands to the next
        # step as it lies on the device (ISSUE 36)
        _, fetched, _, tokens = low.out_info
        assert fetched.shape == (self.N_SLOTS + len(cfg.experts_held) + 1,)
        assert (tokens.shape, tokens.dtype) == ((self.N_SLOTS,), jnp.int32)
        c = low.compile()
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes == self._nbytes(cache)
        assert mem.temp_size_in_bytes < 64 * 2**20
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 12 * 2**30
        assert mem.argument_size_in_bytes < 10.82e9
        assert _n_mosaic(c) == 1 + cfg.n_layers
        assert _expert_walks(c) == cfg.n_layers
        assert _dense_expert_reads(c, params["blocks"][0]["w_in_e"]) == []
        assert "paged_decode_attention" in c.as_text()
        # 4.76 B parameters in bfloat16 on this chip
        assert 9.4e9 < self._nbytes(params) < 9.6e9

    def test_prefill_tile(self, shapes):
        """A 512-token tile: the flash kernel over the lane so far, the
        experts as grouped products (two a layer), the state carried."""
        GH, cfg, params, cache, i32 = shapes
        c = GH.build_hybrid_prefill(cfg, PAGE, attn_impl="pallas").lower(
            params, cache, i32(self.TILE), i32(self.MAX_LEN // PAGE),
            i32(), i32(), i32()).compile()
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes == self._nbytes(cache)
        assert mem.temp_size_in_bytes < 512 * 2**20
        text = c.as_text()
        assert len(_flash_call_names(c)) == 1
        assert text.count("ragged-dot-metadata") >= 1
        assert len(re.findall(r"%ragged-dot\S* = ", text)) >= 2 * cfg.n_layers


# ---------------------------------------------------------------------------
# LFM2's layer on the hybrid block at the shapes of the cell
# lfm2-24b-a2b.assist-closed (benchmark/configs/lfm2-24b-a2b.json): the
# two programs the decoder compiles, against abstract bfloat16 weights
# and a K/V pool whose 64-lane heads are padded to 128


class TestLfm2Programs:
    HBM = 15.75e9

    @pytest.fixture(scope="class")
    def shapes(self, topo):
        import json
        from mmlspark_tpu.models import granite_hybrid as GH
        from mmlspark_tpu.parallel.pallas_attention import paged_head_lanes
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "lfm2-24b-a2b.json")) as f:
            hf = json.load(f)
        sv = hf["serve"]
        cfg = GH.GraniteHybridConfig.from_hf(hf, dtype=sv["dtype"])
        one = NamedSharding(_mesh(topo, {"x": 1}), P())
        params = _abstract(jax.eval_shape(lambda: GH.init_params(cfg, 0)),
                           one)
        lanes = paged_head_lanes(cfg.d_head, cfg.n_kv_heads)
        cache = _abstract(jax.eval_shape(lambda: GH.init_cache(
            cfg, sv["n_slots"], sv["n_pages"], sv["page_size"], lanes)), one)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

        return GH, cfg, sv, params, cache, i32

    @staticmethod
    def _nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    def _check(self, c, params, cache, temp_limit):
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes == self._nbytes(cache)
        assert mem.temp_size_in_bytes < temp_limit
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < self.HBM
        return mem

    def test_step(self, shapes):
        """One token for 32 slots: the conv tails and the padded K/V
        pools updated where they lie (12.69 GB of arguments, 11 MB of
        temporaries), ONE Mosaic call an attention layer, the walk
        over the live entries (a grid of one step a slot, not one a
        table entry), and one a MoE layer, the walk over the touched
        experts, with no masked product over all 64 left beside it."""
        GH, cfg, sv, params, cache, i32 = shapes
        # 5.27 B parameters in bfloat16 (the norms, taps, routers and
        # biases float32), a pool of 2 x 2 x 537 MB
        assert 10.5e9 < self._nbytes(params) < 10.6e9
        assert cache["k"][0].shape == (16385, 16, 8, 128)
        assert cache["tail"][0].shape == (32, 2, 2048)
        n, pps = sv["n_slots"], sv["max_len"] // sv["page_size"]
        low = GH.build_hybrid_step(cfg, sv["page_size"],
                                   attn_impl="pallas").lower(
            params, cache, i32(n), i32(n), i32(n, pps))
        _, fetched, _, tokens = low.out_info
        assert fetched.shape == (n + 64 + 1,)
        c = low.compile()
        mem = self._check(c, params, cache, 256 * 2**20)
        assert mem.argument_size_in_bytes < 12.69e9
        n_moe = cfg.n_layers - cfg.n_dense_layers
        assert _n_mosaic(c) == 2 + n_moe
        assert _expert_walks(c) == n_moe == 8
        assert _dense_expert_reads(c, params["blocks"][2]["w_in_e"]) == []
        assert "paged_decode_attention" in c.as_text()
        # a pool of 128-lane heads is what the walk takes (its grid is
        # (slots,): the kernel copies its own pages)
        from mmlspark_tpu.parallel.pallas_attention import paged_walks
        assert paged_walks(cache["k"][0].shape[-1], cfg.n_kv_heads)

    def test_prefill_tile(self, shapes):
        """A 512-token tile: the flash kernel over the lane so far (its
        64 lanes sliced off the padded pool), the experts as grouped
        products, the dense layers as plain ones, the tails carried."""
        GH, cfg, sv, params, cache, i32 = shapes
        pps = sv["max_len"] // sv["page_size"]
        c = GH.build_hybrid_prefill(cfg, sv["page_size"],
                                    attn_impl="pallas").lower(
            params, cache, i32(sv["prefill_tile"]), i32(pps), i32(), i32(),
            i32()).compile()
        self._check(c, params, cache, 1024 * 2**20)
        text = c.as_text()
        assert len(_flash_call_names(c)) == 2
        assert len(re.findall(r"%ragged-dot\S* = ", text)) >= 2 * 8


# ---------------------------------------------------------------------------
# a looped softmax stack at the shapes of the cell ouro-2.6b.reason-closed
# (benchmark/configs/ouro-2.6b.json): the five programs the decoder
# compiles, against abstract bfloat16 weights


class TestOuroPrograms:
    """48 layers run four times with one set of weights, a K/V row a
    (pass, layer, position): the passes are ONE loop in the program (a
    layer's kernel appears once, not four times), the pool of 8.08 GB is
    updated where it lies, and weights and pool together leave the chip
    room for the programs' temporaries."""

    HBM = 15.75e9

    @pytest.fixture(scope="class")
    def shapes(self, topo):
        import json
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "configs",
                               "ouro-2.6b.json")) as f:
            hf = json.load(f)
        sv = hf["serve"]
        cfg = T.TransformerConfig.from_hf(hf, dtype=sv["dtype"])
        one = NamedSharding(_mesh(topo, {"x": 1}), P())
        shapes = jax.eval_shape(lambda: T.init_params(cfg, 0))
        # the weights as the driver hands them over: matrices in the
        # configuration's dtype, norm gains and the gate float32
        params = _abstract(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, jnp.bfloat16 if x.size > cfg.d_model
                else x.dtype), shapes), one)
        pps = sv["max_len"] // sv["page_size"]
        n_pages = 1 + sv["n_slots"] * pps
        cache = _abstract(jax.eval_shape(
            lambda: T.init_paged_kv_cache(cfg, n_pages, sv["page_size"])),
            one)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

        return cfg, sv, pps, params, cache, i32

    @staticmethod
    def _nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    def _check(self, c, cfg, params, cache, temp_limit):
        mem = c.memory_analysis()
        # every pool byte aliased: the loop's carry is updated in place
        assert mem.alias_size_in_bytes == self._nbytes(cache)
        assert mem.temp_size_in_bytes < temp_limit
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < self.HBM
        layer_pool = int(np.prod(cache["k"][0].shape))
        assert not _pool_sized_moves(c.as_text(), layer_pool)
        return mem

    def test_step(self, shapes):
        cfg, sv, pps, params, cache, i32 = shapes
        # 2.668 B parameters in bfloat16, a pool four times a pass's
        assert 5.33e9 < self._nbytes(params) < 5.35e9
        assert 8.07e9 < self._nbytes(cache) < 8.09e9
        assert cache["k"][0].shape == (4 * 321, 16, 16, 128)
        n = sv["n_slots"]
        low = T.build_paged_decode_step(
            cfg, n, sv["page_size"], pps, attn_impl="pallas").lower(
            params, cache, i32(n), i32(n), i32(n, pps))
        # [tokens | exit passes] is the one fetch; the tokens alone are
        # an output too, the next step's input as it lies on the device
        _, fetched, _, tokens = low.out_info
        assert fetched.shape == (2 * n,)
        assert (tokens.shape, tokens.dtype) == ((n,), jnp.int32)
        c = low.compile()
        self._check(c, cfg, params, cache, 256 * 2**20)
        # one loop body: a layer's kernel once in the text, run 4 times
        assert _n_mosaic(c) == cfg.n_layers
        assert c.as_text().count("paged_decode_attention") >= cfg.n_layers
        assert "looped_step" in c.as_text()

    @pytest.mark.parametrize("bucket", [32, 64, 128, 256])
    def test_prefill(self, shapes, bucket):
        cfg, sv, pps, params, cache, i32 = shapes
        assert bucket in sv["prompt_buckets"]
        c = T.build_paged_prefill(
            cfg, sv["page_size"], pps, attn_impl="pallas").lower(
            params, cache, i32(bucket), i32(pps), i32()).compile()
        self._check(c, cfg, params, cache, 512 * 2**20)
        assert len(_flash_call_names(c)) == cfg.n_layers
        assert "looped_prefill" in c.as_text()


# ---------------------------------------------------------------------------
# multi-head latent attention at the shapes of the cell
# kimi-k2.6.think-closed (benchmark/configs/kimi-k2.6.json): the latent
# kernel alone, and the two programs the decoder compiles


class TestKimiPrograms:
    """Six latent-attention layers (one dense, five of 12 held experts of
    384 and a shared one) over a latent pool of 64 slots x 12,288
    positions: 8.37 GB of weights and 6.04 GB of pool, updated where it
    lies, leave the chip room for the programs' temporaries; the step
    holds one latent kernel a layer and one expert walk a MoE layer."""

    HBM = 15.75e9

    @pytest.fixture(scope="class")
    def shapes(self, topo):
        import json
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "benchmark"))
        import reference_kimi as RK
        from mmlspark_tpu.models import granite_hybrid as GH
        from mmlspark_tpu.parallel.pallas_mla import latent_pool_lanes
        with open(os.path.join(root, "benchmark", "configs",
                               "kimi-k2.6.json")) as f:
            hf = json.load(f)
        sv = hf["serve"]
        m = RK.Model.from_config(hf)
        cfg = GH.GraniteHybridConfig.from_hf(
            RK.program_hf(hf), experts_held=m.experts_held,
            dtype=sv["dtype"])
        one = NamedSharding(_mesh(topo, {"x": 1}), P())
        # the weights as the driver hands them over
        params = _abstract(jax.eval_shape(
            lambda: RK.make_params(m, 0, jnp.bfloat16)), one)
        cache = _abstract(jax.eval_shape(lambda: GH.init_cache(
            cfg, sv["n_slots"], sv["n_pages"], sv["page_size"],
            latent_lanes=latent_pool_lanes(cfg.latent_lanes))), one)

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

        return GH, cfg, sv, params, cache, i32

    @staticmethod
    def _nbytes(tree) -> int:
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree))

    def _check(self, c, cache, temp_limit):
        mem = c.memory_analysis()
        assert mem.alias_size_in_bytes == self._nbytes(cache)
        assert mem.temp_size_in_bytes < temp_limit
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < self.HBM
        assert not _pool_sized_moves(c.as_text(),
                                     int(np.prod(cache["lat"][0].shape)))
        return mem

    def test_latent_kernel(self, topo):
        """ONE Mosaic call named after the jitted function, the pool left
        in HBM (no temporary), two fetches of 32 pages of 640 lanes
        inside the budget; a 576-lane row is refused by Mosaic, which
        is why the pool keeps 640."""
        from mmlspark_tpu.parallel import pallas_mla as PM
        n, pps, h = 64, 768, 64
        lanes = PM.latent_pool_lanes(576)
        c = _compile_on_one(
            topo, functools.partial(PM.paged_latent_decode_attention,
                                    scale=0.1447, page_size=PAGE, d_lat=512),
            ((n, h, lanes), jnp.bfloat16),
            ((1 + n * pps, PAGE, lanes), jnp.bfloat16),
            ((n, pps), jnp.int32), ((n,), jnp.int32))
        assert _n_mosaic(c) == 1 and lanes == 640
        assert c.memory_analysis().temp_size_in_bytes == 0
        assert len(re.findall(r"%(\S*paged_latent_decode_attention\S*) = "
                              r".* custom-call\(", c.as_text())) == 1
        fetch = PM.latent_fetch_pages(pps, PAGE, lanes, jnp.bfloat16)
        assert fetch == 32
        assert 2 * fetch * PAGE * lanes * 2 <= PM._LATENT_VMEM_BUDGET

    def test_step(self, shapes):
        """One token for 64 slots: 14.41 GB of arguments and some 13 MB of
        temporaries, the six pools updated where they lie, six latent
        kernels and five expert walks."""
        GH, cfg, sv, params, cache, i32 = shapes
        assert 8.36e9 < self._nbytes(params) < 8.38e9
        assert cache["lat"][0].shape == (49153, 16, 640)
        assert 6.03e9 < self._nbytes(cache) < 6.05e9
        n, pps = sv["n_slots"], sv["max_len"] // sv["page_size"]
        c = GH.build_hybrid_step(cfg, sv["page_size"],
                                 attn_impl="pallas").lower(
            params, cache, i32(n), i32(n), i32(n, pps)).compile()
        self._check(c, cache, 64 * 2**20)
        text = c.as_text()
        assert len(re.findall(r"%paged_latent_decode_attention\S* = ",
                              text)) == 6
        assert _expert_walks(c) == 5

    def test_prefill_tile(self, shapes):
        """A 512-token tile: the lane expanded and scored a tile of rows
        at a time inside ONE Mosaic call a layer
        (``latent_tile_attention``; no flash kernel, no XLA loop), the
        experts as grouped products, some 265 MB of temporaries."""
        GH, cfg, sv, params, cache, i32 = shapes
        pps = sv["max_len"] // sv["page_size"]
        c = GH.build_hybrid_prefill(cfg, sv["page_size"],
                                    attn_impl="pallas").lower(
            params, cache, i32(sv["prefill_tile"]), i32(pps), i32(), i32(),
            i32()).compile()
        self._check(c, cache, 512 * 2**20)
        text = c.as_text()
        assert not _flash_call_names(c)
        assert "paged_latent_decode_attention" not in text
        assert len(re.findall(r"%latent_tile_attention\S* = .* custom-call\(",
                              text)) == 6
        assert " while(" not in text

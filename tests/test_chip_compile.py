"""The chip's compiler, kept as tests.

Every Pallas kernel on the main path is compiled here with
``interpret=False`` for a DESCRIBED ``v5e:2x2`` topology (no chip
attached) at ``gpt_small`` widths — H=12, Dh=64, bf16, 8 slots, window
1024. Interpret mode, which every other CPU test selects, accepts block
shapes and relayouts that Mosaic refuses; these cases raise exactly what
the chip would raise, at no chip time. Nothing runs, so they say nothing
about results — the interpret-mode suites pin those.

Code that asks ``jax.default_backend()`` still sees the CPU here, so each
case calls the kernel with ``interpret=False`` itself.
"""

import importlib
import json
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from pytorch_multiprocessing_distributed_tpu.ops.kv_quant import QuantizedKV
from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
    flash_attention,
    fused_sgd_apply,
    ring_all_reduce,
)

from perf.trace_reduce import MOSAIC, parse_instruction

# the module, not the same-named function ops.pallas re-exports
da = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.decode_attention")
ca = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.chunk_attention")
sc = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.short_conv")
gm = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.grouped_matmul")

B, S, H, D = 8, 1024, 12, 64      # gpt_small serving: 8 slots, window 1024
K1 = 5                             # --draft_k 4 verify block
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    """The v5e 2x2 host described once per module, compile cache off
    around the cases (a described-device executable is written to the
    persistent cache but cannot be read back without a chip — the next
    compile would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, args, sharding):
    """Lower ``fn`` on shape-only ``args`` placed by ``sharding`` and
    compile for the described chip; returns the compiled text."""
    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    text = jax.jit(fn).lower(*jax.tree.map(place, args)).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# the names ops/pallas gives its kernels; perf/layer_metrics match on them
KERNEL_NAMES = {
    "flash_attention_fwd", "flash_attention_bwd_dq",
    "flash_attention_bwd_dkv", "decode_attention",
    "paged_decode_attention",
    "paged_verify_decode_attention", "fused_sgd_update", "ring_all_reduce",
    "mla_paged_decode_attention",
    "gqa_paged_decode_attention_full", "gqa_paged_decode_attention_window",
    "gqa_chunk_attention", "short_conv", "ragged-dot-experts",
}


def _mosaic_names(text):
    """The labels ``perf/trace_reduce.py`` would give a compiled
    program's Mosaic kernels in a profiler trace of the chip (an "XLA
    Ops" event's name is the instruction's text), ``mosaic:`` taken
    off."""
    labels = (parse_instruction(line.strip().removeprefix("ROOT "))[0]
              for line in text.splitlines() if "tpu_custom_call" in line)
    return {label[len(MOSAIC):] for label in labels
            if label.startswith(MOSAIC)}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv(shape, kv_dtype):
    """A K or V operand ``[..., heads, D]``: bf16 array, or the int8 +
    f32-scale pair whose scale drops the trailing head_dim axis
    (ops/kv_quant.py)."""
    if kv_dtype == "int8":
        return QuantizedKV(_sds(shape, jnp.int8),
                           _sds(shape[:-1], jnp.float32))
    return _sds(shape, BF16)


def _pool(layers, pages, page, heads, kv_dtype):
    """A paged pool ``[L, P, ps, H * D]``: every head's row side by
    side in the lanes; the int8 pair keeps one scale a token and head,
    ``[L, P, ps, H]``."""
    shape = (layers, pages, page, heads * D)
    if kv_dtype == "int8":
        return QuantizedKV(_sds(shape, jnp.int8),
                           _sds(shape[:-1] + (heads,), jnp.float32))
    return _sds(shape, BF16)


def _decode_case(layout, kv_dtype, k1, page, slots=B, heads=H):
    """(fn, args) of one decode/verify kernel call at serving shapes."""
    q = _sds((slots, k1, heads, D), BF16)
    pos = _sds((slots,), jnp.int32)
    if layout == "dense":  # generate()'s kernel; verify is paged only
        kv = _kv((slots, S, heads, D), kv_dtype)
        return (lambda q, k, v, p: da.decode_attention(
            q, k, v, p, impl="pallas", interpret=False),
                (q, kv, kv, pos))
    n_win = S // page
    # layer 1 of a two-layer pool: the kernel reads it in place
    pages = _pool(2, slots * n_win + 1, page, heads, kv_dtype)
    tab = _sds((slots, n_win), jnp.int32)
    kern = (da.paged_decode_attention if k1 == 1
            else da.paged_verify_decode_attention)
    return (lambda q, k, v, t, p: kern(q, k, v, t, p, layer=1,
                                       impl="pallas", interpret=False),
            (q, pages, pages, tab, pos))


def _flash_case(batch, seq, grad):
    x = _sds((batch, seq, H, D), BF16)

    def fwd(q, k, v):
        # called from inside a named scope, as the models' flax modules
        # do: a transform (jvp, transpose) wraps the enclosing scope's
        # name and leaves the kernel's own alone; with nothing around
        # the call it would wrap the kernel's (jvp_flash_attention_fwd_)
        with jax.named_scope("attn"):
            return flash_attention(q, k, v, causal=True, interpret=False)

    if not grad:
        return fwd, (x, x, x)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), (x, x, x)


def _sgd_case():
    # one leaf of each kind gpt_small/resnet carry: a matmul weight, a
    # bias, a conv kernel
    leaves = {"w": _sds((768, 3072), jnp.float32),
              "b": _sds((3072,), jnp.float32),
              "conv": _sds((3, 3, 64, 64), jnp.float32)}
    return (lambda p, g, m: fused_sgd_apply(p, g, m, 0.1, interpret=False),
            (leaves, leaves, leaves))


_DECODE = [
    pytest.param(lambda lay=lay, kv=kv, k1=k1, pg=pg:
                 _decode_case(lay, kv, k1, pg),
                 id=f"{lay}-{kv}-{'verify' if k1 > 1 else 'decode'}"
                    + (f"-page{pg}" if pg else ""))
    for lay, pages in (("dense", (None,)), ("paged", (16, 128)))
    for kv in ("bf16", "int8")
    for k1 in ((1,) if lay == "dense" else (1, K1))
    for pg in pages
] + [
    # the benchmark's serving cell (gpt2-medium.serve.closed): 32 slots,
    # 16 heads, pages of 16, window 1024 -> 64 table entries a slot
    pytest.param(lambda kv=kv, k1=k1: _decode_case("paged", kv, k1, 16,
                                                   slots=32, heads=16),
                 id=f"paged-{kv}-{'verify' if k1 > 1 else 'decode'}"
                    "-page16-gpt2-medium-32slots")
    for kv in ("bf16", "int8")
    for k1 in (1, K1)
]

def _mla_case(slots=64, heads=32, rank=512, rope=128, page=16,
              window=8192, layers=5):
    """The latent decode kernel at the shapes of the cell
    ``xing4-29b-a4b.serve.closed-4k1k``: 64 slots, 32 heads, rows of
    512 latent values and the rotary key in 128 lanes, pages of 16,
    window 8,192, layer 2 of a five-layer pool."""
    n_win = window // page
    pages = slots * n_win + 1
    args = (_sds((slots, heads, rank + rope), BF16),
            _sds((layers, pages, page, rank + rope), BF16),
            _sds((slots, n_win), jnp.int32), _sds((slots,), jnp.int32))
    return (lambda q, c, t, p: da.mla_paged_decode_attention(
        q, c, t, p, layer=2, rank=rank, scale=0.1, impl="pallas",
        interpret=False), args)


def _gqa_case(reach, slots=48, heads=48, kv_heads=8, dim=128, page=16,
              s_max=9216, window=4096):
    """The grouped decode kernel at the shapes of the cell
    ``trinity-large-preview.serve.closed-8k1k``: 48 slots, 48 query
    heads on 8 key/value heads of 128, rows of 2,048 values (K then
    V), pages of 16. ``reach`` None: the one full layer's pool under
    the page table of a 9,216-column bucket; a number: layer 2 of the
    four sliding layers' ring pool, 257 pages a slot."""
    row = 2 * kv_heads * dim
    if reach is None:
        entries, layers, layer = s_max // page, 1, 0
        pages = slots * entries + 1
    else:
        entries, layers, layer = -(-window // page) + 1, 4, 2
        pages = slots * entries
    args = (_sds((slots, heads, dim), BF16),
            _sds((layers, pages, page, row), BF16),
            _sds((slots, entries), jnp.int32), _sds((slots,), jnp.int32))
    return (lambda q, c, t, p: da.gqa_paged_decode_attention(
        q, c, t, p, layer=layer, kv_heads=kv_heads, scale=dim ** -0.5,
        reach=reach, impl="pallas", interpret=False), args)


def _gqa_mimo_case(window):
    """The grouped decode kernel at the shapes of the cell
    ``mimo-v2.5.serve.closed-1k8k``: 128 slots, 64 query heads, keys of
    192 and values of 128, pages of 16. ``window`` False: layer 1 of
    the two full layers' pool (4 key/value heads, rows of 1,280) under
    the page table of a 9,216-column bucket; True: layer 3 of the five
    window layers' rings (8 key/value heads, rows of 2,560, 9 pages a
    slot) with a sink a head."""
    slots, heads, dk, dv, page = 128, 64, 192, 128, 16
    kv_heads = 8 if window else 4
    row = kv_heads * (dk + dv)
    if window:
        entries, layers, layer, pages = 9, 5, 3, slots * 9
    else:
        entries, layers, layer = 9216 // page, 2, 1
        pages = slots * entries + 1
    args = (_sds((slots, heads, dk), BF16),
            _sds((layers, pages, page, row), BF16),
            _sds((slots, entries), jnp.int32), _sds((slots,), jnp.int32),
            _sds((heads,), jnp.float32))
    return (lambda q, c, t, p, s: da.gqa_paged_decode_attention(
        q, c, t, p, layer=layer, kv_heads=kv_heads, scale=dk ** -0.5,
        reach=128 if window else None, sinks=s if window else None,
        impl="pallas", interpret=False), args)


def _chunk_case(heads, kv_heads, dk, dv, width, reach, sink, t=1024):
    """The grouped chunk-attention kernel at a cell's chunk: ``t``
    queries of ``heads`` heads against one layer's standalone cache of
    ``width`` columns (rows of ``kv_heads * (dk + dv)`` values), the
    chunk's start traced."""
    args = [_sds((t, heads, dk), BF16),
            _sds((width, kv_heads * (dk + dv)), BF16),
            _sds((), jnp.int32)] + ([_sds((heads,), jnp.float32)] if sink
                                    else [])
    return (lambda q, c, s, *sinks: ca.gqa_chunk_attention(
        q, c, s, kv_heads=kv_heads, scale=dk ** -0.5, reach=reach,
        sinks=sinks[0] if sinks else None, impl="pallas",
        interpret=False), args)


def _short_conv_case(slots=128, width=2048, page=16, layers=9):
    """The conv's decode kernel at the shapes of the cell
    ``lfm2-8b-a1b.serve.closed-4k1k``: 128 slots, projections of 3 x
    2,048, layer 4 of the nine conv layers' rings of 2 pages of 16 a
    slot (the layer an operand)."""
    args = (_sds((slots, 3 * width), BF16), _sds((3, width), BF16),
            _sds((layers, slots * 2, page, width), BF16),
            _sds((slots, 2), jnp.int32), _sds((slots,), jnp.int32),
            _sds((), jnp.int32))
    return (lambda p, t, pool, table, pos, layer: sc.short_conv(
        p, t, pool, table, pos, layer=layer, impl="pallas",
        interpret=False), args)


def _grouped_case(rows, d, f, held, gated):
    """The routed experts' grouped matmul at a cell's call: ``rows``
    sorted rows over ``held`` experts of ``D`` -> ``F`` (the gated form:
    gate and up in one call) or ``F`` -> ``D`` (down), counts traced."""
    if gated:
        args = (_sds((rows, d), BF16), _sds((held, d, f), BF16),
                _sds((held, d, f), BF16), _sds((held,), jnp.int32))
        return (lambda x, a, b, c: gm.gated_grouped_matmul(
            x, a, b, c, impl="pallas", interpret=False), args)
    args = (_sds((rows, f), BF16), _sds((held, f, d), BF16),
            _sds((held,), jnp.int32))
    return (lambda x, a, c: gm.grouped_matmul(
        x, a, c, impl="pallas", interpret=False), args)


# the routed experts of the five expert cells at a decode step's and a
# chunk's first rung of rows (held experts of D -> F): gate and up in
# one call, down in another
_GROUPED = [
    pytest.param(lambda rows=rows, d=d, f=f, held=held, gated=gated:
                 _grouped_case(rows, d, f, held, gated),
                 id=f"grouped-{'gate-up' if gated else 'down'}-{cell}")
    for cell, rows, d, f, held in (
        ("lfm2-decode", 512, 2048, 1792, 32),
        ("lfm2-chunk", 4096, 2048, 1792, 32),
        ("xing4-decode", 256, 3584, 1024, 64),
        ("xing4-chunk", 4096, 3584, 1024, 64),
        ("trinity-decode", 128, 3072, 3072, 32),
        ("trinity-chunk", 1024, 3072, 3072, 32),
        ("openpangu-decode", 128, 7680, 2048, 16),
        ("openpangu-chunk", 1024, 7680, 2048, 16),
        ("mimo-decode", 128, 4096, 2048, 16),
        ("mimo-chunk", 1024, 4096, 2048, 16))
    for gated in (True, False)
]


_CASES = _DECODE + _GROUPED + [
    # the chunk of trinity-large-preview.serve.closed-8k1k: 1,024
    # queries, a bucket of 8,192 columns, 48 heads on 8 of 128; and of
    # mimo-v2.5.serve.closed-1k8k: a bucket of 1,024, 64 heads on 4
    # (full) and on 8 (window of 128, a sink), keys 192, values 128
    pytest.param(lambda: _chunk_case(48, 8, 128, 128, 8192, None, False),
                 id="gqa-chunk-full-t1024-w8192-48on8-d128"),
    pytest.param(lambda: _chunk_case(48, 8, 128, 128, 8192, 4096, False),
                 id="gqa-chunk-window4096-t1024-w8192-48on8-d128"),
    pytest.param(lambda: _chunk_case(64, 4, 192, 128, 1024, None, False),
                 id="gqa-chunk-full-t1024-w1024-64on4-dk192-dv128"),
    pytest.param(lambda: _chunk_case(64, 8, 192, 128, 1024, 128, True),
                 id="gqa-chunk-window128-sink-t1024-w1024-64on8-dk192-dv128"),
    # the cell trinity-large-preview.serve.closed-8k1k: one body, two
    # names (the full layer's page table; a sliding layer's ring)
    pytest.param(lambda: _gqa_case(None),
                 id="gqa-paged-decode-full-48slots-w9216"),
    pytest.param(lambda: _gqa_case(4096),
                 id="gqa-paged-decode-window-48slots-ring257"),
    # the cell mimo-v2.5.serve.closed-1k8k: keys wider than values, a
    # different head count a kind, the window's sink
    pytest.param(lambda: _gqa_mimo_case(False),
                 id="gqa-paged-decode-full-dk192-dv128-128slots-w9216"),
    pytest.param(lambda: _gqa_mimo_case(True),
                 id="gqa-paged-decode-window-sink-128slots-ring9"),
    # the cell lfm2-8b-a1b.serve.closed-4k1k: heads of 64 (32 on 8),
    # the full layers' pages under a 5,120-column table; the chunk of a
    # 4,096-token prompt; the conv's ring, written in place
    pytest.param(lambda: _gqa_case(None, slots=128, heads=32, dim=64,
                                   s_max=5120),
                 id="gqa-paged-decode-full-128slots-32on8-d64-w5120"),
    pytest.param(lambda: _chunk_case(32, 8, 64, 64, 4096, None, False),
                 id="gqa-chunk-full-t1024-w4096-32on8-d64"),
    pytest.param(_short_conv_case, id="short-conv-decode-128slots-c2048"),
    pytest.param(_mla_case, id="mla-paged-decode-xing4-64slots-w8192"),
    # the cell openpangu-ultra-moe-718b.serve.closed-2k1k: 128 slots,
    # 128 heads (242 operations a byte of cache), window 4,096
    pytest.param(lambda: _mla_case(slots=128, heads=128, window=4096),
                 id="mla-paged-decode-pangu-128slots-128heads-w4096"),
    pytest.param(lambda: _flash_case(B, S, False), id="flash-fwd-s1024"),
    pytest.param(lambda: _flash_case(B, S, True), id="flash-fwdbwd-s1024"),
    pytest.param(lambda: _flash_case(1, 4096, True),
                 id="flash-fwdbwd-s4096"),
    # ragged prefill lengths the engine's bucketless admission produces
    pytest.param(lambda: _flash_case(1, 24, False), id="flash-fwd-s24"),
    pytest.param(lambda: _flash_case(1, 37, False), id="flash-fwd-s37"),
    pytest.param(lambda: _flash_case(1, 600, False), id="flash-fwd-s600"),
    pytest.param(_sgd_case, id="fused-sgd"),
]


@pytest.mark.parametrize("make", _CASES)
def test_kernel_compiles_for_v5e(topo, make):
    fn, args = make()
    text = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    # the chip's compiler names the instruction after the kernel, not
    # after whatever wrapper the call sits in
    names = _mosaic_names(text)
    assert names and names <= KERNEL_NAMES, names


def test_the_grouped_matmul_is_read_by_the_expert_metrics(topo):
    """The kernel's label in a trace of the chip is what the two
    routed-expert metrics match (``^mosaic:ragged-dot``): they read the
    grouped expert matmuls whichever kernel implements them."""
    fn, args = _grouped_case(512, 2048, 1792, 32, True)
    text = _compile(fn, args, SingleDeviceSharding(topo.devices[0]))
    assert _mosaic_names(text) == {"ragged-dot-experts"}
    for name in ("routed_expert_matmul_ms.serve",
                 "routed_expert_matmul_decode_ms.serve"):
        with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                               "layer_metrics", name + ".json")) as f:
            match = json.load(f)["args"]["match"]
        assert re.match(match, MOSAIC + "ragged-dot-experts"), name


def test_ring_all_reduce_compiles_on_four_chips(topo):
    """The RDMA ring needs the 4-device topology: one shard_map over all
    four described chips."""
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("x",))
    assert mesh.size == 4

    def fn(x):
        return jax.shard_map(
            lambda s: ring_all_reduce(s, "x", interpret=False),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            check_vma=False)(x)

    x = _sds((4 * 8, 1024), jnp.float32)
    text = _compile(fn, (x,), NamedSharding(mesh, P("x")))
    assert _mosaic_names(text) == {"ring_all_reduce"}


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_gpt_small_train_step_compiles_with_its_kernel(topo, monkeypatch,
                                                       chips):
    """The whole ``make_lm_train_step`` program of ``chip_smoke.py``'s
    train phase — gpt_small, bf16, 8x1024 a chip — for one described
    chip and for the four-chip DP mesh: it fits the chip's memory, the
    flash kernel compiles INSIDE it, and across chips the compiler put
    the all-reduces in. ~40 s each, so outside tier-1."""
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.ops import pallas
    from pytorch_multiprocessing_distributed_tpu.train.lm import (
        create_lm_train_state, make_lm_train_step)
    from pytorch_multiprocessing_distributed_tpu.train.optim import sgd

    # the kernels ask the (CPU) backend and would pick interpret mode
    monkeypatch.setattr(pallas, "default_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
    model = models.get_model("gpt_small", dtype=BF16)
    opt = sgd(learning_rate=0.01)
    state = jax.eval_shape(
        lambda: create_lm_train_state(
            model, jax.random.PRNGKey(0), jnp.zeros((2, S), jnp.int32), opt))
    replicated, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=replicated),
        state)
    tokens = jax.ShapeDtypeStruct((B * chips, S), jnp.int32, sharding=split)
    compiled = make_lm_train_step(model, opt, mesh).lower(
        state, tokens).compile()
    text = compiled.as_text()
    assert _mosaic_names(text) == {
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv"}
    assert ("all-reduce" in text) == (chips > 1)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9


@pytest.mark.parametrize("name, kwargs, slots, s_max", [
    ("xing4_29b_a4b", {}, 64, 8192),
    ("pangu_ultra_moe_718b", dict(experts_held=16, vocab_size=19200),
     128, 4096),
], ids=["xing4-64slots-w8192", "pangu-16-of-256-experts-128slots-w4096"])
def test_latent_decode_program_compiles_at_one_layer(topo, name, kwargs,
                                                     slots, s_max):
    """The decode programs of the cells ``xing4-29b-a4b.serve.closed-
    4k1k`` (64 slots, window 8,192) and ``openpangu-ultra-moe-718b.
    serve.closed-2k1k`` (128 slots, window 4,096, 16 of 256 experts
    held), pages of 16, bfloat16, at ONE expert layer of the published
    widths, through ``ServingEngine``: the latent kernel compiles
    inside it beside the grouped expert matmuls, and the donated page
    pools are written in place (the program holds no second copy of
    them: ``ROADMAP.md`` A2)."""
    from perf.rehearse import as_chip
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.serving import ServingEngine

    chip = SingleDeviceSharding(topo.devices[0])
    model = models.get_model(name, dtype=BF16, num_layers=1,
                             first_k_dense=0, **kwargs)
    params = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    with as_chip(chip):
        engine = ServingEngine(model, params, max_slots=slots, s_max=s_max,
                               kv_layout="paged", page_size=16,
                               prefill_chunk=1024)
        assert engine.decode_attn == "pallas"
        pool = engine.pool

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        args = (jax.tree.map(sds, params), sds(pool.k_pages),
                sds(pool.v_pages), sds(pool.device_table()),
                sds(pool.positions), sds(pool.last_tokens),
                sds(pool.active), sds(pool.budgets), sds(pool.eos_ids),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = engine._decode.lower(*args, window=s_max,
                                        horizon=1).compile()
    text = compiled.as_text()
    assert "mla_paged_decode_attention" in _mosaic_names(text)
    # one call a layer (one layer here); the pool it reads is the
    # program's donated argument where it lies (``pl.ANY``), never a
    # slice or a copy: the temporaries below
    assert sum("tpu_custom_call" in line
               and "mla_paged_decode_attention" in line
               for line in text.splitlines()) == 1
    # the tokens and, behind them, the expert layer's load: a column a
    # held expert, one for the assignments routed elsewhere and one for
    # the rows the grouped matmuls were given
    assert f"s32[{slots + model.n_held + 2}]" in text
    # a share of the experts: ONE conditional over the ladder's rungs
    # (every branch's grouped matmuls compile for the chip); every
    # expert held: none
    assert text.count(" conditional(") == (model.n_held < model.n_experts)
    mem = compiled.memory_analysis()
    pools = pool.k_pages.nbytes + pool.v_pages.nbytes
    assert pools == ((slots * s_max // 16 + 1) * 16 * (512 + 128) * 2
                     ) and not pool.v_pages.size
    # temporaries far below one copy of the pools: written in place
    assert mem.temp_size_in_bytes < pools // 4, mem
    assert mem.alias_size_in_bytes >= pools


def test_gpt_decode_and_insert_programs_write_the_pools_in_place(topo):
    """The decode and insert programs of the cell ``gpt2-medium.serve.
    closed`` (``gpt_medium`` widths, 32 slots, window 1,024, pages of
    16, bfloat16; depth cut to 2 layers to stay in tier-1), through
    ``ServingEngine``: the paged kernel compiles inside the decode
    program, and both programs write the donated ``[L, P, ps, H * Dh]``
    pools in place — no layer sliced out and stacked back, no padded
    second copy (``ROADMAP.md`` A2 + A3)."""
    from perf.rehearse import as_chip
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.inference.generate import (
        pref_cache_shapes)
    from pytorch_multiprocessing_distributed_tpu.serving import ServingEngine

    chip = SingleDeviceSharding(topo.devices[0])
    model = models.get_model("gpt_medium", dtype=BF16, num_layers=2)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    with as_chip(chip):
        engine = ServingEngine(model, params, max_slots=32, s_max=S,
                               kv_layout="paged", page_size=16)
        assert engine.decode_attn == "pallas"
        pool = engine.pool

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        state = (sds(pool.positions), sds(pool.last_tokens),
                 sds(pool.active), sds(pool.budgets), sds(pool.eos_ids))
        decode = engine._decode.lower(
            jax.tree.map(sds, params), sds(pool.k_pages),
            sds(pool.v_pages), sds(pool.device_table()), *state,
            jax.ShapeDtypeStruct((2,), jnp.uint32), window=S,
            horizon=1).compile()
        pref = jax.ShapeDtypeStruct(pref_cache_shapes(model, S)[0], BF16)
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        insert = engine._insert_jit.lower(
            sds(pool.k_pages), sds(pool.v_pages), *state, pref, pref,
            jax.ShapeDtypeStruct((S // 16,), jnp.int32),
            *(scalar,) * 5).compile()
    assert pool.k_pages.shape == (2, 32 * 64 + 1, 16, 16 * D)
    assert "paged_decode_attention" in _mosaic_names(decode.as_text())
    pools = pool.k_pages.nbytes + pool.v_pages.nbytes
    for program in (decode, insert):
        mem = program.memory_analysis()
        # temporaries far below one copy of the pools: written in place
        assert mem.temp_size_in_bytes < pools // 4, mem
        assert mem.alias_size_in_bytes >= pools


def test_lfm2_decode_and_insert_programs_write_both_pools_in_place(topo):
    """The decode and insert programs of the cell ``lfm2-8b-a1b.serve.
    closed-4k1k`` (published widths, 128 slots, window 5,120, pages of
    16, bfloat16; depth cut to the first three layers, two conv and one
    full, to stay in tier-1), through ``ServingEngine``: the conv kernel
    runs once a conv layer, the grouped kernel once a full layer and
    the grouped expert matmul twice an expert layer inside the decode
    program, and both programs write the donated full
    pool and conv ring in place."""
    from perf.rehearse import as_chip
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.inference.generate import (
        pref_cache_shapes)
    from pytorch_multiprocessing_distributed_tpu.serving import ServingEngine

    chip = SingleDeviceSharding(topo.devices[0])
    model = models.get_model("lfm2_8b_a1b", dtype=BF16, num_layers=3)
    params = jax.eval_shape(lambda: model._init(jax.random.PRNGKey(0)))
    slots, s_max = 128, 5120
    with as_chip(chip):
        engine = ServingEngine(model, params, max_slots=slots, s_max=s_max,
                               kv_layout="paged", page_size=16,
                               prefill_chunk=1024)
        assert engine.decode_attn == "pallas"
        pool = engine.pool

        def sds(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        state = (sds(pool.positions), sds(pool.last_tokens),
                 sds(pool.active), sds(pool.budgets), sds(pool.eos_ids))
        decode = engine._decode.lower(
            jax.tree.map(sds, params), sds(pool.k_pages),
            sds(pool.v_pages), sds(pool.device_table()), *state,
            jax.ShapeDtypeStruct((2,), jnp.uint32), window=s_max,
            horizon=1).compile()
        prefs = [jax.ShapeDtypeStruct(shape, BF16)
                 for shape in pref_cache_shapes(model, 1024)]
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        insert = engine._insert_jit.lower(
            sds(pool.k_pages), sds(pool.v_pages), *state, *prefs,
            jax.ShapeDtypeStruct((1024 // 16,), jnp.int32),
            *(scalar,) * 5).compile()
    assert pool.k_pages.shape == (1, slots * 320 + 1, 16, 1024)
    assert pool.v_pages.shape == (2, slots * 2, 16, 2048)
    text = decode.as_text()
    assert _mosaic_names(text) >= {"short_conv",
                                   "gqa_paged_decode_attention_full"}
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert sum("short_conv" in line for line in calls) == 2
    assert sum("gqa_paged_decode_attention_full" in line
               for line in calls) == 1
    # the one expert layer: gate and up in one grouped call, down in one
    assert sum("ragged-dot-experts" in line for line in calls) == 2
    pools = pool.k_pages.nbytes + pool.v_pages.nbytes
    ring = pool.v_pages.nbytes
    for program in (decode, insert):
        mem = program.memory_analysis()
        # temporaries far below one copy of the pools, and below one
        # copy of the ring: both written in place
        assert mem.temp_size_in_bytes < min(pools // 4, ring), mem
        assert mem.alias_size_in_bytes >= pools


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_gpt_decode_program_calls_the_kernel_once_a_layer(topo, kv_dtype):
    """The decode program of the cell ``gpt2-medium.serve.closed`` at
    ONE layer of the published widths (32 slots, window 1,024, pages
    of 16, bfloat16; int8 pages beside it), through ``ServingEngine``:
    one ``paged_decode_attention`` call a layer, and the pools it reads
    are the program's donated arguments where they lie (``pl.ANY``),
    never a slice or a copy: the temporaries below."""
    from perf.rehearse import as_chip
    from pytorch_multiprocessing_distributed_tpu import models
    from pytorch_multiprocessing_distributed_tpu.serving import ServingEngine

    chip = SingleDeviceSharding(topo.devices[0])
    model = models.get_model("gpt_medium", dtype=BF16, num_layers=1)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    with as_chip(chip):
        engine = ServingEngine(model, params, max_slots=32, s_max=S,
                               kv_layout="paged", page_size=16,
                               kv_dtype=kv_dtype)
        assert engine.decode_attn == "pallas"
        pool = engine.pool

        def sds(x):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)

        compiled = engine._decode.lower(
            sds(params), sds(pool.k_pages), sds(pool.v_pages),
            sds(pool.device_table()), sds(pool.positions),
            sds(pool.last_tokens), sds(pool.active), sds(pool.budgets),
            sds(pool.eos_ids), jax.ShapeDtypeStruct((2,), jnp.uint32),
            window=S, horizon=1).compile()
    text = compiled.as_text()
    assert _mosaic_names(text) == {"paged_decode_attention"}
    assert sum("tpu_custom_call" in line
               and "paged_decode_attention" in line
               for line in text.splitlines()) == 1
    pools = sum(x.nbytes for x in jax.tree.leaves((pool.k_pages,
                                                   pool.v_pages)))
    mem = compiled.memory_analysis()
    # temporaries far below one copy of the pools: written in place
    assert mem.temp_size_in_bytes < pools // 4, mem
    assert mem.alias_size_in_bytes >= pools


# (positions, a block's columns, page, table entries, heads, NaN in dead pages)
_MLA_VALUES = [
    pytest.param(positions, columns, 8, 12, 4, False,
                 id=f"columns{columns}-positions{i}")
    for columns in (512, 32)
    for i, positions in enumerate(([0, 37, 95], [95, 95, 16]))
] + [
    # a page's last and first column, in a block of two pages and in one
    # block a slot
    pytest.param([0, 15, 16], 32, 16, 8, 4, False, id="page-edges-2-pages"),
    pytest.param([0, 15, 16], 512, 16, 8, 4, False, id="page-edges-1-block"),
    # a block's last and first column; a slot of one block before one of
    # two and one of four (the halves' parity carries over the slots)
    pytest.param([31, 32, 127], 32, 16, 8, 4, False, id="block-edges"),
    pytest.param([127, 0, 127], 32, 16, 8, 4, False, id="window-end"),
    pytest.param([127, 64, 126], 64, 16, 8, 4, False,
                 id="window-end-2-blocks"),
    pytest.param([5, 100, 47], 64, 16, 8, 128, False, id="128-heads"),
    pytest.param([0, 40, 127], 32, 16, 8, 4, True, id="nan-dead-pages"),
    pytest.param([17, 99, 64], 512, 16, 8, 32, True,
                 id="nan-dead-pages-1-block-32-heads"),
]


@pytest.mark.parametrize("positions, columns, page, n_win, heads, nan",
                         _MLA_VALUES)
def test_mla_kernel_in_interpret_mode_equals_the_xla_form(
        monkeypatch, positions, columns, page, n_win, heads, nan):
    """Values, on the CPU: the kernel under the Pallas interpreter
    against the gather-and-softmax form, live pages only, in one block
    of pages a slot and in several (the online softmax across blocks,
    the two halves of the buffer across slots). ``nan``: every page
    that is live for no slot holds NaN, and the result is the clean
    pool's: no page beyond a position is ever copied."""
    monkeypatch.setattr(da, "_mla_block_pages",
                        lambda ps, entries, *_: min(columns // ps, entries))
    rng = np.random.default_rng(0)
    slots, rank, rope, layers = 3, 32, 128, 2
    pages = slots * n_win + 1

    def rand(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    table = rng.permutation(np.arange(1, pages)).reshape(slots, n_win)
    pool = rand(layers, pages, page, rank + rope)
    kernel_pool = pool
    if nan:
        live = np.zeros(pages, bool)
        for row, pos in zip(table, positions):
            live[row[:pos // page + 1]] = True
        kernel_pool = jnp.where(live[None, :, None, None], pool, jnp.nan)
    q, table = rand(slots, heads, rank + rope), jnp.asarray(table, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    want = da.mla_paged_decode_attention(
        q, pool, table, positions, layer=1, rank=rank, scale=0.2,
        window=page * n_win, impl="xla")
    got = da.mla_paged_decode_attention(
        q, kernel_pool, table, positions, layer=1, rank=rank, scale=0.2,
        impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page, n_win, heads, want", [
    (16, 512, 32, 64),     # xing4-29b-a4b.serve.closed-4k1k: 1,024 columns
    (16, 256, 128, 64),    # openpangu-ultra-moe-718b.serve.closed-2k1k
    (16, 24, 32, 24),      # no more than the window has
    (128, 64, 32, 8),      # pages of 128: the same 1,024 columns
], ids=["xing4", "pangu", "short-window", "page128"])
def test_mla_block_is_chosen_from_heads_rows_and_the_vmem_budget(
        page, n_win, heads, want):
    assert da._mla_block_pages(page, n_win, heads, 640 * 2) == want

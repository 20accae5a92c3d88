"""Every Pallas kernel carries the name the profiler trace shows.

``perf/trace_reduce.py`` labels a Mosaic kernel ``mosaic:<name>`` from
its HLO instruction's name, and the chip's compiler takes that from the
``name=`` of the ``pl.pallas_call`` (``tests/test_chip_compile.py``
reads it out of the compiled text). The benchmark's kernel metrics
(``perf/layer_metrics/flash_*_ms.train.json``,
``paged_decode_attn_ms.serve.json``) match on these names, so the
ledger can compare a kernel's time across PRs that rewrite what is
around it. Here each of the twelve call sites is traced (nothing runs)
and the name is read out of the jaxpr.
"""

import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from pytorch_multiprocessing_distributed_tpu.ops.kv_quant import QuantizedKV
from pytorch_multiprocessing_distributed_tpu.ops.pallas import (
    flash_attention, fused_sgd_apply, ring_all_reduce)

# the module, not the same-named function ops.pallas re-exports
da = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.decode_attention")
ca = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.chunk_attention")
sc = importlib.import_module(
    "pytorch_multiprocessing_distributed_tpu.ops.pallas.short_conv")

B, S, H, D, K1, PAGE = 2, 64, 2, 32, 3, 16


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kv(shape, int8):
    if int8:
        return QuantizedKV(_sds(shape, jnp.int8), _sds(shape[:-1]))
    return _sds(shape)


def _flash(grad):
    x = _sds((B, S, H, D))

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    return (jax.grad(loss, argnums=(0, 1, 2)) if grad else loss), (x, x, x)


def _decode(paged, k1, int8=False):
    q, pos = _sds((B, k1, H, D)), _sds((B,), jnp.int32)
    if not paged:  # generate()'s kernel; the verify pass is paged only
        kv = _kv((B, S, H, D), int8)
        return (lambda q, k, v, p: da.decode_attention(
            q, k, v, p, impl="pallas", interpret=True), (q, kv, kv, pos))
    # a two-layer pool [L, P, ps, H * D] (int8: + scales [L, P, ps, H])
    shape = (2, B * (S // PAGE) + 1, PAGE, H * D)
    pages = (QuantizedKV(_sds(shape, jnp.int8), _sds(shape[:-1] + (H,)))
             if int8 else _sds(shape))
    table = _sds((B, S // PAGE), jnp.int32)
    kern = (da.paged_decode_attention if k1 == 1
            else da.paged_verify_decode_attention)
    return (lambda q, k, v, t, p: kern(q, k, v, t, p, layer=1,
                                       impl="pallas", interpret=True),
            (q, pages, pages, table, pos))


def _mla():
    """The latent decode kernel over a ``[L, P, ps, R + Rw]`` pool."""
    pages = B * (S // PAGE) + 1
    args = (_sds((B, H, D + 128)), _sds((2, pages, PAGE, D + 128)),
            _sds((B, S // PAGE), jnp.int32), _sds((B,), jnp.int32))
    return (lambda q, c, t, p: da.mla_paged_decode_attention(
        q, c, t, p, layer=1, rank=D, scale=0.1, impl="pallas",
        interpret=True), args)


def _chunk():
    """The grouped chunk kernel: a chunk of S queries of 2H heads on H
    against one layer's cache [2S, H * 2D], a window and a sink."""
    args = (_sds((S, 2 * H, D)), _sds((2 * S, H * 2 * D)),
            _sds((), jnp.int32), _sds((2 * H,)))
    return (lambda q, c, s, sinks: ca.gqa_chunk_attention(
        q, c, s, kv_heads=H, scale=0.1, reach=PAGE, sinks=sinks,
        impl="pallas", interpret=True), args)


def _short_conv():
    """The conv's decode kernel over a ``[L, N * 2, ps, C]`` ring pool,
    the layer an operand."""
    args = (_sds((B, 3 * 128)), _sds((3, 128)),
            _sds((2, B * 2, PAGE, 128)), _sds((B, 2), jnp.int32),
            _sds((B,), jnp.int32))
    return (lambda p, t, pool, table, pos: sc.short_conv(
        p, t, pool, table, pos, layer=1, impl="pallas", interpret=True),
        args)


def _sgd():
    leaves = {"w": _sds((24, 40)), "b": _sds((40,))}
    return (lambda p, g, m: fused_sgd_apply(p, g, m, 0.1, interpret=True),
            (leaves, leaves, leaves))


def _ring():
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    fn = jax.shard_map(lambda v: ring_all_reduce(v, "x", interpret=True),
                       mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
    return fn, (_sds((2 * 8, 128)),)


def _pallas_names(jaxpr):
    """Names of the ``pallas_call`` equations in ``jaxpr`` and in every
    jaxpr nested in it (custom_vjp, pjit, shard_map), in order."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names.extend(_pallas_names(inner))
    return names


# (call site, how to reach it, the name that site passes)
_SITES = [
    ("flash_attention.py fwd", lambda: _flash(False),
     "flash_attention_fwd"),
    ("flash_attention.py bwd dq", lambda: _flash(True),
     "flash_attention_bwd_dq"),
    ("flash_attention.py bwd dkv", lambda: _flash(True),
     "flash_attention_bwd_dkv"),
    ("decode_attention.py dense", lambda: _decode(False, 1),
     "decode_attention"),
    ("decode_attention.py paged", lambda: _decode(True, 1),
     "paged_decode_attention"),
    ("decode_attention.py paged verify", lambda: _decode(True, K1),
     "paged_verify_decode_attention"),
    ("decode_attention.py latent paged", _mla,
     "mla_paged_decode_attention"),
    ("chunk_attention.py grouped chunk", _chunk, "gqa_chunk_attention"),
    ("short_conv.py decode", _short_conv, "short_conv"),
    ("fused_update.py", _sgd, "fused_sgd_update"),
    ("ring_allreduce.py", _ring, "ring_all_reduce"),
    # the int8 variants go through the same call sites
    ("decode_attention.py dense int8", lambda: _decode(False, 1, True),
     "decode_attention"),
    ("decode_attention.py paged int8", lambda: _decode(True, 1, True),
     "paged_decode_attention"),
    ("decode_attention.py paged verify int8",
     lambda: _decode(True, K1, True), "paged_verify_decode_attention"),
]
KERNEL_NAMES = {name for _site, _make, name in _SITES}


@pytest.mark.parametrize("make, want", [s[1:] for s in _SITES],
                         ids=[s[0].replace(" ", "-") for s in _SITES])
def test_call_site_passes_its_stable_name(make, want):
    fn, args = make()
    names = _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)
    # the backward trace holds the forward kernel too; no kernel of
    # any trace goes unnamed (the default is the body's function name)
    assert want in names and set(names) <= KERNEL_NAMES, names
    # trace_reduce.op_kind strips a trailing number off a label
    assert re.fullmatch(r"[a-z_]*[a-z]", want)


def test_kernel_metrics_match_the_names_the_kernels_carry():
    """The metric files that select by regex over ``mosaic:<name>``
    (the kernels' times and rooflines): each must pick out exactly its
    kernels."""
    assert len(KERNEL_NAMES) == 11
    labels = ["mosaic:" + name for name in KERNEL_NAMES]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    picked = {}
    for metric in ("flash_fwd_ms.train", "flash_bwd_ms.train",
                   "paged_decode_attn_ms.serve", "mla_decode_attn_ms.serve",
                   "mla_decode_attn_roofline.serve",
                   "paged_decode_attn_roofline.serve",
                   "chunk_attn_ms.serve", "short_conv_decode_ms.serve",
                   "short_conv_roofline.serve"):
        with open(os.path.join(root, "perf", "layer_metrics",
                               metric + ".json")) as fh:
            rx = re.compile(json.load(fh)["args"]["match"])
        picked[metric] = {label for label in labels if rx.search(label)}
    assert picked == {
        "flash_fwd_ms.train": {"mosaic:flash_attention_fwd"},
        "flash_bwd_ms.train": {"mosaic:flash_attention_bwd_dq",
                               "mosaic:flash_attention_bwd_dkv"},
        "paged_decode_attn_ms.serve": {"mosaic:paged_decode_attention"},
        "paged_decode_attn_roofline.serve": {
            "mosaic:paged_decode_attention"},
        "mla_decode_attn_ms.serve": {"mosaic:mla_paged_decode_attention"},
        "mla_decode_attn_roofline.serve": {
            "mosaic:mla_paged_decode_attention"},
        "chunk_attn_ms.serve": {"mosaic:gqa_chunk_attention"},
        "short_conv_decode_ms.serve": {"mosaic:short_conv"},
        "short_conv_roofline.serve": {"mosaic:short_conv"},
    }

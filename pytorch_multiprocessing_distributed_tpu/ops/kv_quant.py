"""graftquant: int8 KV-cache quantization as a pytree pair.

The serving stack's decode hot loop is bandwidth- and residency-bound:
KV pages are the dominant bytes term of every flash-decode dispatch and
the per-slot HBM term that bounds batch. Storing K/V **int8 with
per-token-per-head f32 scales** halves both at a budgeted logit cost —
the scale sidecar lives BESIDE the data with the data's own layout
untouched, so what the Pallas kernels stream is unchanged and the
scale is one multiply in the VMEM stream.

The representation is :class:`QuantizedKV`, a registered pytree node
``(data int8, scale f32)`` with one scale per head_dim group
(quantization groups over head_dim — one amax per (…, token, head)
group), so the scale carries the data's shape MINUS the trailing
head_dim axis:

* dense slot caches: data ``[L, slots, s_max, H, Dh]`` int8,
  scale ``[L, slots, s_max, H]`` f32;
* paged caches keep a token's heads side by side in the lanes: data
  ``[L, pages, page_size, H * Dh]`` int8, scale ``[L, pages,
  page_size, H]`` f32 — the same numbers, the last two data axes
  merged (a fresh ``[..., H, Dh]`` pair is reshaped on its way in).

Because it is a pytree, every existing jitted program signature,
``donate_argnums`` index, and ``out_shardings`` arity is UNCHANGED — a
quantized cache operand simply flattens to two leaves where one used to
be. Donation still reuses both buffers (int8->int8, f32->f32), scan
carries it, and ``jax.tree.map(ShapeDtypeStruct, …)`` lowers it for the
graftcheck audit. Duck-typed ``.shape``/``.dtype``/``__getitem__``
(layer indexing slices BOTH leaves) keep the generate/engine call sites
readable.

The quant formula (device and the numpy host twin used by the
prefill->decode wire path are test-pinned equal, so a transferred block
splices WITHOUT requantization):

    amax  = max(|x|) over head_dim            (per token, per head)
    scale = amax / 127        (1.0 where the group is all-zero)
    q     = clip(round(x / scale), -127, 127) as int8

Dequant is ``q * scale`` cast to the compute dtype — shared verbatim by
the Pallas kernels and the XLA fallback, so CPU tests pin the exact
math the TPU runs. Not token-exact vs the unquantized engine: the
harness pins greedy transcripts on canonical configs and budgets the
max-abs-logit delta instead (tests/test_graftquant.py).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "QuantizedKV",
    "KV_DTYPES",
    "quantize_kv",
    "dequantize_kv",
    "quantize_kv_np",
    "kv_slice_in_dim",
    "stack_kv",
    "flatten_heads",
]

# engine-facing names for the cache element layout; "model" keeps the
# historical behaviour (cache dtype == model dtype)
KV_DTYPES = ("model", "int8")

_QMAX = 127.0
# The scale formula multiplies by this precomputed reciprocal instead of
# writing ``amax / _QMAX``: XLA strength-reduces division-by-constant to
# a reciprocal multiply inside jit, so the literal division is 1 ULP off
# the numpy twin on a few percent of values. One shared constant makes
# the eager, jitted, and host paths run the SAME f32 multiply.
_INV_QMAX = np.float32(1.0 / _QMAX)


class QuantizedKV:
    """Pytree pair ``(data int8, scale f32)`` for a quantized KV cache.

    ``scale.shape == data.shape[:-1]`` — one scale per head_dim group
    (a paged pool merges data's ``(H, Dh)`` into ``H * Dh`` lanes:
    ``scale.shape == data.shape[:-1] + (H,)``).
    Registered as a pytree node so jit/scan/donation/sharding treat it
    as two ordinary leaves; duck-typed just enough (``shape``/``dtype``
    delegate to ``data``, ``__getitem__`` indexes both leaves) that
    cache-shaped code reads the same in both modes."""

    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    # ---- array duck typing (reads delegate to the int8 payload)
    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes) + int(self.scale.nbytes)

    def __getitem__(self, idx):
        # leading-axis indexing only (layer/page selection): the
        # trailing head_dim axis exists on data alone, so an index
        # touching it would desynchronize the pair
        return QuantizedKV(self.data[idx], self.scale[idx])

    def __repr__(self):
        return (f"QuantizedKV(data={self.data.shape}:{self.data.dtype}, "
                f"scale={self.scale.shape}:{self.scale.dtype})")

    def tree_flatten(self):
        return (self.data, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node(
    QuantizedKV,
    lambda kv: kv.tree_flatten(),
    QuantizedKV.tree_unflatten,
)


def quantize_kv(x) -> QuantizedKV:
    """Symmetric per-(…, token, head) int8 quantization over the
    trailing head_dim axis. f32 math regardless of the input dtype so
    the device formula and the numpy host twin agree bit-exactly."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0.0, amax * _INV_QMAX, 1.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -_QMAX, _QMAX)
    return QuantizedKV(q.astype(jnp.int8), scale.astype(jnp.float32))


def dequantize_kv(kv: QuantizedKV, dtype):
    """``data * scale`` in f32, cast to the compute ``dtype`` — the ONE
    dequant expression, shared by the Pallas kernels (in the VMEM
    stream) and the XLA fallbacks (before the reference einsum)."""
    return (kv.data.astype(jnp.float32)
            * kv.scale[..., None]).astype(dtype)


def quantize_kv_np(x):
    """Host (numpy) twin of :func:`quantize_kv` for the prefill->decode
    PageTransfer path: the prefill replica quantizes OFF the device hot
    path and the block splices into the decode pool without
    requantization. Returns ``(data int8, scale f32)`` ndarrays,
    test-pinned bit-equal to the device formula."""
    xf = np.asarray(x).astype(np.float32)
    amax = np.max(np.abs(xf), axis=-1)
    scale = np.where(amax > 0.0, amax * _INV_QMAX,
                     np.float32(1.0)).astype(np.float32)
    q = np.clip(np.round(xf / scale[..., None]), -_QMAX, _QMAX)
    return q.astype(np.int8), scale


def kv_slice_in_dim(kv, start, size, axis: int):
    """``lax.slice_in_dim`` over a cache that may be quantized. The
    sliced axis must precede the trailing head_dim axis (windowing
    slices tokens, never lanes), so the SAME axis index is valid on
    both leaves."""
    if isinstance(kv, QuantizedKV):
        return QuantizedKV(
            jax.lax.slice_in_dim(kv.data, start, size, axis=axis),
            jax.lax.slice_in_dim(kv.scale, start, size, axis=axis))
    return jax.lax.slice_in_dim(kv, start, size, axis=axis)


def stack_kv(leaves):
    """``jnp.stack`` over per-layer cache slices that may be quantized
    pairs — rebuilds the ``[L, …]`` leading axis on BOTH leaves."""
    if leaves and isinstance(leaves[0], QuantizedKV):
        return QuantizedKV(jnp.stack([kv.data for kv in leaves]),
                           jnp.stack([kv.scale for kv in leaves]))
    return jnp.stack(leaves)


def flatten_heads(kv):
    """``[..., H, Dh]`` K/V rows (array or quantized pair) as the paged
    pools keep them: a token's heads side by side, ``[..., H * Dh]``.
    A pair's ``[..., H]`` scales are already that row's sidecar."""
    if isinstance(kv, QuantizedKV):
        return QuantizedKV(flatten_heads(kv.data), kv.scale)
    return kv.reshape(kv.shape[:-2] + (-1,))

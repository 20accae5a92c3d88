"""Single-pass fused SGD(momentum, nesterov, weight-decay) update kernel.

The optimizer update is pure HBM-bandwidth work: per parameter element it
reads (param, grad, buf) and writes (param, buf). This kernel does the
whole torch-exact update rule (``..train.optim`` docstring,
reference ``main.py:51-55``) in ONE pass with the outputs aliased onto
the inputs — params and momentum buffers are updated in place in HBM,
nothing else is allocated. XLA usually fuses the elementwise chain too;
the kernel makes the schedule explicit, guarantees 3-reads/2-writes, and
is the template for fancier fused updates (LAMB phase-2, EMA).

Exact rule (matching :func:`..train.optim.sgd`):
  g    = grad + wd * param
  buf  = init * momentum * buf + g      (init = 0.0 on the first step)
  d    = g + momentum * buf  (nesterov) | buf (classical)
  param -= lr * d
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
_BLOCK_ROWS = 1024  # 1024x128 f32 = 512 KiB per operand block in VMEM


def _kernel(scalars_ref, p_ref, g_ref, b_ref, new_p_ref, new_b_ref, *,
            momentum, weight_decay, nesterov):
    lr = scalars_ref[0]
    init = scalars_ref[1]  # 0.0 first step (torch lazy buf init), else 1.0
    p = p_ref[:]
    g = g_ref[:] + weight_decay * p
    buf = init * momentum * b_ref[:] + g
    d = g + momentum * buf if nesterov else buf
    new_p_ref[:] = p - lr * d
    new_b_ref[:] = buf


def _fused_leaf(p, g, buf, scalars, *, momentum, weight_decay, nesterov,
                interpret):
    """Apply the kernel to one flattened/padded [rows, 128] leaf."""
    orig_shape, orig_dtype = p.shape, p.dtype
    n = p.size
    rows = -(-n // _LANE)
    pad = rows * _LANE - n

    def prep(x):
        flat = x.astype(jnp.float32).reshape(-1)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(rows, _LANE)

    p2, g2, b2 = prep(p), prep(g), prep(buf)
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    kernel = functools.partial(
        _kernel, momentum=momentum, weight_decay=weight_decay,
        nesterov=nesterov,
    )
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    new_p, new_b = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (lr, init) scalars
            spec, spec, spec,
        ],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
        ],
        input_output_aliases={1: 0, 3: 1},  # param->new_param, buf->new_buf
        interpret=interpret,
        name="fused_sgd_update",
    )(scalars, p2, g2, b2)

    def unprep(x):
        return x.reshape(-1)[:n].reshape(orig_shape).astype(orig_dtype)

    return unprep(new_p), unprep(new_b)


def fused_sgd_apply(
    params: Any,
    grads: Any,
    momentum_bufs: Any,
    lr,
    *,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    nesterov: bool = True,
    initialized=True,
    interpret: Optional[bool] = None,
):
    """In-place-fused SGD over a whole parameter pytree.

    Returns ``(new_params, new_momentum_bufs)``. ``lr`` and
    ``initialized`` may be traced scalars (schedule / first-step flag).
    """
    if interpret is None:
        from . import default_interpret

        interpret = default_interpret()
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(initialized, jnp.float32),
    ])
    leaf = functools.partial(
        _fused_leaf, scalars=scalars, momentum=momentum,
        weight_decay=weight_decay, nesterov=nesterov, interpret=interpret,
    )
    pairs = jax.tree.map(leaf, params, grads, momentum_bufs)
    is_pair = lambda t: isinstance(t, tuple) and len(t) == 2  # noqa: E731
    new_params = jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair)
    new_bufs = jax.tree.map(lambda t: t[1], pairs, is_leaf=is_pair)
    return new_params, new_bufs


def sgd_pallas(
    learning_rate=0.1,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    nesterov: bool = True,
    interpret: Optional[bool] = None,
):
    """Drop-in :class:`..train.optim.Transform` whose update runs the
    fused kernel. Same trajectory as :func:`..train.optim.sgd` (pinned by
    ``tests/test_pallas_kernels.py``)."""
    from ...train.optim import OptState, Transform

    def init(params) -> OptState:
        zeros = jax.tree.map(jnp.zeros_like, params)
        return OptState(
            momentum=zeros,
            count=jnp.zeros((), jnp.int32),
            initialized=jnp.zeros((), jnp.bool_),
        )

    def apply(grads, state: OptState, params, lr_step=None):
        """Fused path: returns (new_params, new_state) directly."""
        lr = (
            learning_rate(lr_step) if callable(learning_rate)
            else jnp.asarray(learning_rate, jnp.float32)
        )
        new_params, new_bufs = fused_sgd_apply(
            params, grads, state.momentum, lr,
            momentum=momentum, weight_decay=weight_decay,
            nesterov=nesterov,
            initialized=state.initialized.astype(jnp.float32),
            interpret=interpret,
        )
        new_state = OptState(
            momentum=new_bufs,
            count=state.count + 1,
            initialized=jnp.ones((), jnp.bool_),
        )
        return new_params, new_state

    def update(grads, state: OptState, params, lr_step=None):
        """updates-contract shim (adds one extra param pass vs ``apply``)."""
        new_params, new_state = apply(grads, state, params, lr_step=lr_step)
        updates = jax.tree.map(lambda np_, p: np_ - p, new_params, params)
        return updates, new_state

    return Transform(init, update, apply)

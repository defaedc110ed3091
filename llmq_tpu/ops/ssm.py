"""Mamba-2 state-space operations in plain JAX: the convolution window,
the one-token state update (decode rows) and the chunked scan (prompt
slices and the prefill program).

The recurrence, a head (``X_t`` its P inputs, ``B_t`` / ``C_t`` the N
input and output maps of the ONE group all heads share)::

    H_t = exp(dt_t A) H_{t-1} + dt_t X_t (x) B_t      # (P, N)
    Y_t = H_t C_t + D X_t

**The state's layout.** A row's state of one layer is held as
``(N, H * P)`` float32 — the state dimension on the sublanes, heads and
their inputs side by side on the lanes — and a family's leaf stacks it
``(layers, rows, N, H * P)``. Both products of the update then broadcast
a ROW (``dt X`` and the decay, one value a lane) down the sublanes and
reduce over the sublanes (``H_t C_t``: a sum of rows): nothing crosses
lanes, which is what the in-place kernel (``ops/pallas/ssm_update.py``)
and XLA's fusion of :func:`ssm_update` both want. ``(H, P, N)``, the
reference's order, would reduce over the lanes once a (head, input).

Everything here computes in float32 whatever the activations' type: the
state is float32 (a family may hold it narrower — ``state.dtype`` is
what is stored), and a product that feeds the state asks for
``Precision.HIGHEST`` so that the matrix unit does not round its float32
operands to bfloat16 on the way in.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def conv_step(window: jnp.ndarray, x: jnp.ndarray, w: jnp.ndarray,
              b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the depthwise causal convolution. ``window``
    (B, K-1, C): the row's last K-1 inputs, oldest first; ``x`` (B, C)
    the new input; ``w`` (C, K), ``b`` (C,). Returns ``(silu(conv)
    (B, C) float32, the window moved on by one)``."""
    full = jnp.concatenate([window, x[:, None].astype(window.dtype)], axis=1)
    y = jnp.einsum("bkc,ck->bc", full.astype(jnp.float32),
                   w.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) + b.astype(jnp.float32)
    return jax.nn.silu(y), full[:, 1:]


def conv_slices(window: jnp.ndarray, x: jnp.ndarray, lengths: jnp.ndarray,
                w: jnp.ndarray, b: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The same convolution over S slices of T inputs that continue
    their rows' windows. ``window`` (S, K-1, C), ``x`` (S, T, C),
    ``lengths`` (S,) the valid inputs of each slice. Returns ``(silu(
    conv) (S, T, C) float32, the windows behind each slice's LAST VALID
    input)``: what lies past a slice's length never enters a window."""
    K, T = w.shape[1], x.shape[1]
    full = jnp.concatenate([window, x.astype(window.dtype)], axis=1)
    wf = w.astype(jnp.float32)
    y = b.astype(jnp.float32)
    for j in range(K):
        y = y + full[:, j:j + T].astype(jnp.float32) * wf[:, j]
    new = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(f, n, K - 1, 0))(
        full, lengths)
    return jax.nn.silu(y), new


def _lane_inputs(x, dt, a):
    """``(x float32 (B, H, P), exp(dt A) and dt X (B, H*P))``: the decay
    and the input of the update, each head's value over its lanes."""
    B, H, P = x.shape
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    decay = jnp.repeat(jnp.exp(dt * a.astype(f32)), P, axis=1)
    return xf, decay, (dt[:, :, None] * xf).reshape(B, H * P)


def ssm_update(state: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
               a: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
               d: jnp.ndarray, active: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row. ``state`` (B, N, H*P); ``x`` (B, H, P); ``dt``
    (B, H) after its softplus; ``a`` (H,) negative; ``bm``, ``cm``
    (B, N); ``d`` (H,). Returns ``(y (B, H, P) float32, the new state in
    ``state.dtype``)``; a row that is not ``active`` keeps its state
    (its ``y`` is of no use). Elementwise products and a sum over N,
    all float32: no matrix product, so nothing is rounded on the way."""
    B, H, P = x.shape
    f32 = jnp.float32
    xf, decay, dtx = _lane_inputs(x, dt, a)
    new = (state.astype(f32) * decay[:, None, :]
           + bm.astype(f32)[:, :, None] * dtx[:, None, :])
    y = jnp.sum(new * cm.astype(f32)[:, :, None], axis=1).reshape(B, H, P)
    y = y + d.astype(f32)[None, :, None] * xf
    new = new.astype(state.dtype)
    if active is not None:
        new = jnp.where(active[:, None, None], new, state)
    return y, new


def ssm_scan(state: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
             a: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
             d: jnp.ndarray, lengths: jnp.ndarray, chunk: int
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over S slices of T tokens, ``chunk`` tokens at a
    time (the state-space duality's form: inside a chunk the outputs are
    a masked matrix product, between chunks the state is carried).
    ``state`` (S, N, H*P) each slice's state BEFORE its first token;
    ``x`` (S, T, H, P); ``dt`` (S, T, H) after its softplus; ``bm``,
    ``cm`` (S, T, N); ``lengths`` (S,). Returns ``(y (S, T, H, P)
    float32, the state behind each slice's last VALID token in
    ``state.dtype``)``: a token past its slice's length has ``dt`` 0,
    so it neither decays the state nor feeds it."""
    S, T, H, P = x.shape
    N = bm.shape[-1]
    f32 = jnp.float32
    hi = lax.Precision.HIGHEST
    Q = min(chunk, T)
    pad = -T % Q
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    dt = jnp.where(valid[:, :, None], dt.astype(f32), 0.0)
    xf, bf, cf = x.astype(f32), bm.astype(f32), cm.astype(f32)
    if pad:
        dt, xf, bf, cf = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (
            v.ndim - 2)) for v in (dt, xf, bf, cf))
    c = (T + pad) // Q
    dt = dt.reshape(S, c, Q, H)
    xf = xf.reshape(S, c, Q, H, P)
    bf, cf = bf.reshape(S, c, Q, N), cf.reshape(S, c, Q, N)
    # (lax's: jnp.cumsum is a jit of its own, and a named scope shows
    # twice in the names of what runs inside one)
    acs = lax.cumsum(dt * a.astype(f32), axis=2)               # (S,c,Q,H)
    acs_h = jnp.moveaxis(acs, 3, 2)                            # (S,c,H,Q)
    # inside a chunk: token i reads token j <= i through exp(sum of the
    # decays between them)
    seg = acs_h[..., :, None] - acs_h[..., None, :]            # (S,c,H,i,j)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    mix = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    mix = mix * jnp.einsum("scin,scjn->scij", cf, bf)[:, :, None]
    mix = mix * jnp.moveaxis(dt, 3, 2)[..., None, :]           # dt_j
    y = jnp.einsum("schij,scjhp->scihp", mix, xf)
    # what each chunk adds to the state at its own end
    to_end = jnp.exp(acs[:, :, -1:, :] - acs) * dt             # (S,c,Q,H)
    adds = jnp.einsum("scjn,scjhp->scnhp", bf, xf * to_end[..., None],
                      precision=hi)
    whole = jnp.exp(acs[:, :, -1, :])                          # (S,c,H)

    def carry(h, step):
        add, dec = step
        return h * dec[:, None, :, None] + add, h

    last, before = lax.scan(
        carry, state.astype(f32).reshape(S, N, H, P),
        (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(whole, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                        # (S,c,N,H,P)
    y = y + (jnp.einsum("scin,scnhp->scihp", cf, before, precision=hi)
             * jnp.exp(acs)[..., None])
    y = y + d.astype(f32)[:, None] * xf
    return (y.reshape(S, T + pad, H, P)[:, :T],
            last.reshape(S, N, H * P).astype(state.dtype))


def update_route(n_state: int, width: int, state_dtype, *,
                 enabled: bool = True) -> Tuple[bool, bool]:
    """``(use the in-place kernel, in interpret mode)`` for a state of
    ``(n_state, width)`` a row: ``ops/attention._kernel_route``'s policy
    (``LLMQ_PALLAS``, the backend, the caller's ``enabled``), and a
    float32 state of whole tiles."""
    from llmq_tpu.ops.attention import _kernel_route
    from llmq_tpu.ops.pallas.ssm_update import ssm_update_viable
    return _kernel_route(
        width, enabled=enabled,
        extra_ok=(jnp.dtype(state_dtype) == jnp.float32
                  and ssm_update_viable(n_state, width)))


def _leaf_route(pool: jnp.ndarray, enabled: bool) -> Tuple[bool, bool]:
    """:func:`update_route` of a stacked leaf ``(L, R, N, W)``; a leaf
    of another rank (the convolution's windows) is XLA's."""
    if pool.ndim != 4:
        return False, False
    return update_route(pool.shape[2], pool.shape[3], pool.dtype,
                        enabled=enabled)


def decode_walk(active: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The in-place kernel's walk of a decode step's rows: ``(rows (B,)
    int32, n_live)`` — the rows that are ``active`` first, in ascending
    order (what follows them is never read). The same for every layer
    of a step, so a program makes it once."""
    rows = jnp.nonzero(active, size=active.shape[0], fill_value=0)[0]
    return rows.astype(jnp.int32), jnp.sum(active, dtype=jnp.int32)


def ssm_update_layer(pool: jnp.ndarray, layer, x: jnp.ndarray,
                     dt: jnp.ndarray, a: jnp.ndarray, bm: jnp.ndarray,
                     cm: jnp.ndarray, d: jnp.ndarray, active: jnp.ndarray,
                     *, walk=None, enabled: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`ssm_update` on the first B rows of layer ``layer`` of the
    stacked leaf ``pool`` (L, R, N, H*P), R >= B: the in-place kernel
    where :func:`update_route` takes it (over ``walk``, the step's
    :func:`decode_walk`; made here if a caller has none), else XLA's
    fusion of the same and a ``dynamic_update_slice`` of the rows.
    Returns ``(y (B, H, P) float32, pool)``: a row that is not
    ``active`` keeps its state, and its ``y`` is of no use."""
    B, H, P = x.shape
    use_kernel, interpret = _leaf_route(pool, enabled)
    if not use_kernel:
        y, new = ssm_update(pool[layer, :B], x, dt, a, bm, cm, d, active)
        return y, pool.at[layer, :B].set(new)
    from llmq_tpu.ops.pallas.ssm_update import ssm_update_pallas
    xf, decay, dtx = _lane_inputs(x, dt, a)
    if walk is None:
        walk = decode_walk(active)
    y, pool = ssm_update_pallas(pool, layer, decay, dtx, bm, cm, *walk,
                                interpret=interpret)
    return (y.reshape(B, H, P)
            + d.astype(jnp.float32)[None, :, None] * xf), pool


def rows_read(pool: jnp.ndarray, layer, rows: jnp.ndarray, *,
              enabled: bool = True) -> jnp.ndarray:
    """``pool[layer, rows]`` of a stacked leaf ``(L, R, ...)``: the
    states the slices of a program continue, each ``rows[s]`` a row of
    the leaf. Through ``ops/pallas/ssm_update.state_rows_read`` where
    :func:`update_route` takes the leaf (its docstring has why a copy is
    a kernel), else XLA's gather."""
    use_kernel, interpret = _leaf_route(pool, enabled)
    if not use_kernel:
        return pool[layer, rows]
    from llmq_tpu.ops.pallas.ssm_update import state_rows_read
    return state_rows_read(pool, layer, rows, interpret=interpret)


def rows_write(pool: jnp.ndarray, layer, rows: jnp.ndarray,
               new: jnp.ndarray, *, enabled: bool = True) -> jnp.ndarray:
    """``pool[layer, rows[s]] = new[s]`` for each slice ``s``, in place
    on a donated leaf: :func:`rows_read`'s routes; XLA's is one
    ``dynamic_update_slice`` a slice (a scatter over ``rows`` made XLA
    copy the whole leaf twice a layer)."""
    use_kernel, interpret = _leaf_route(pool, enabled)
    if use_kernel:
        from llmq_tpu.ops.pallas.ssm_update import state_rows_write
        return state_rows_write(pool, layer, rows, new, interpret=interpret)
    for s in range(new.shape[0]):
        at = (layer, rows[s]) + (0,) * (pool.ndim - 2)
        pool = lax.dynamic_update_slice(
            pool, new[s][None, None].astype(pool.dtype), at)
    return pool


def own_rows(init, batch: int, row_state, rows):
    """``(row_state, rows)`` for a forward function of a row-state
    family: a caller without row state (a test, a plain prefill) gets a
    zero one of its batch's size (``init(batch)``), row ``b`` for
    sequence ``b``."""
    if row_state is None:
        row_state = init(batch)
    if rows is None:
        rows = jnp.arange(batch, dtype=jnp.int32)
    return row_state, rows

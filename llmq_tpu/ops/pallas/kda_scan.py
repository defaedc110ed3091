"""Pallas TPU kernel: the delta rule's chunked scan (``ops/kda.py``)
over prompt slices, the state in VMEM from a slice's first chunk to its
last.

The recurrence and the state's layout are ``ops/kda.py``'s. A grid step
is ``CHUNK`` tokens of ``heads`` heads of one slice; the chunk axis is
the grid's last and runs in order, the state's block keeps its index
over it, so a (slice, head block)'s state comes into VMEM once, is
moved on in place a chunk at a time and goes back to HBM once. A chunk
wholly past its slice's length is not run (``lengths`` is a scalar
prefetch; the index maps hold such a step at the slice's last live
chunk, so nothing is fetched for it either) and its outputs are zeros;
a slice of length 0 hands its state back to the bit.

**Two levels inside a chunk.** With ``G`` the log-decays summed from the
chunk's start, token ``i`` reads token ``j <= i`` through
``exp(G_i - G_j)`` a channel, and ``exp(G_i) / exp(G_j)`` is no way to
compute that (``ops/kda.py``). So:

- inside a BLOCK of ``block`` tokens (``cfg.kda_chunk``, 16) the
  differences are taken exactly, a source token at a time: one
  ``(block, d_k)`` tile of ``exp(G_i - G_j)``, two lane sums (``k_i``'s
  and ``q_i``'s) — the vector units' work, as ``kda.kda_scan`` does it;
- a block BELOW the diagonal is an MXU product through a reference
  point, the cumulative decay ``c_n`` at the start of the READING block:
  ``(k_i * exp(G_i - c_n)) . (k_j b_j * exp(c_n - G_j))``, both factors in
  (0, 1] because ``G`` only falls — a factor that underflows is of a term
  whose true value underflows as well.

The chunk's unit-lower system ``(I + M) W = V - (K * exp(G)) S_0`` is
then solved whole: ``T = (I + M_diag)^-1`` of the diagonal blocks by the
product form (``kda._inverse_unit_lower``'s arithmetic, the blocks side
by side in one ``(CHUNK, CHUNK)`` matrix), and with ``N = T M_below``,
which is nilpotent over the blocks, ``W = prod (I + (-N)^(2^i)) T rhs``
— every product ``CHUNK`` rows tall. Everything is float32 and every
product asks for ``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Tokens a grid step.
CHUNK = 64
#: Heads a grid step (a program's blocks are ``128 * HEADS`` lanes).
HEADS = 4


def kda_scan_heads(n_heads: int) -> int:
    """Heads a grid step takes of ``n_heads``: ``HEADS`` where it
    divides them, else the largest power of two under it that does."""
    n = HEADS
    while n_heads % n:
        n //= 2
    return n


def kda_scan_viable(d_k: int, n_heads: int, d_v: int, T: int,
                    chunk: int) -> bool:
    """Whether the kernel takes slices of ``T`` tokens worked in exact
    blocks of ``chunk``: a head's keys and its values one 128-lane tile
    each, whole ``CHUNK``-token steps, and blocks of whole sublane
    tiles, a power of two of them, that tile a step."""
    return (d_k == 128 and d_v == 128 and n_heads >= 1 and T >= CHUNK
            and T % CHUNK == 0 and chunk % 8 == 0 and CHUNK % chunk == 0
            and chunk & (chunk - 1) == 0)


def _dot(a, b, dims=((1,), (0,))):
    return lax.dot_general(a, b, (dims, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _head(n, q, k, g, v, beta, s0, block: int):
    """One head's chunk: ``q``, ``k``, ``g`` (C, d_k), ``v`` (C, d_v),
    ``beta`` (C, 1), ``s0`` (d_k, d_v), the first ``n`` tokens valid.
    Returns ``(o (C, d_v), the state behind token min(n, C))`` — as a
    generator's value: it yields between its stages, so that a caller
    who steps several heads in turn (:func:`_in_step`) lays each stage's
    product of every head side by side in the program."""
    C, dk = k.shape
    nb = C // block
    f32 = jnp.float32
    shift = block.bit_length() - 1
    g = jnp.where(lax.broadcasted_iota(jnp.int32, (C, dk), 0) < n, g, 0.0)
    beta = jnp.where(lax.broadcasted_iota(jnp.int32, (C, 1), 0) < n, beta,
                     0.0)
    kb = k * beta
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same = (ri >> shift) == (ci >> shift)
    # the log-decays summed from each BLOCK's start to a token, and from
    # behind the token to the block's end (sums, never differences of
    # sums: what is small stays exact)
    both = _dot(jnp.concatenate([jnp.where(same & (ci <= ri), 1.0, 0.0),
                                 jnp.where(same & (ci > ri), 1.0, 0.0)],
                                0).astype(f32), g)
    yield
    gl, left = both[:C], both[C:]
    blocks = [slice(b * block, (b + 1) * block) for b in range(nb)]
    ends = [gl[rows][block - 1:block] for rows in blocks]   # a block's sum

    def span(lo, hi):                   # blocks lo .. hi - 1 summed
        return sum(ends[lo:hi], jnp.zeros((1, dk), f32))

    # ... from the chunk's start to a token, and from it to the chunk's end
    G = jnp.concatenate([gl[rows] + span(0, b)
                         for b, rows in enumerate(blocks)], 0)
    to_end = jnp.concatenate([left[rows] + span(b + 1, nb)
                              for b, rows in enumerate(blocks)], 0)

    # the diagonal blocks, exactly: a source token j at a time
    lane = lax.broadcasted_iota(jnp.int32, (block, C), 1)
    m_rows, b_rows = [], []
    for b, rows in enumerate(blocks):
        gb, kk, qq, kbb = gl[rows], k[rows], q[rows], kb[rows]
        m_acc = jnp.zeros((block, C), f32)
        b_acc = jnp.zeros((block, C), f32)
        for j in range(block):
            lo = j // 8 * 8            # the sublane tiles above j: masked
            # (a token i above j sees exp of a sum of up to block - 1
            # tokens' decays with the sign turned: masked as a column's
            # entry below, where a select drops it whatever it is)
            i = lax.broadcasted_iota(jnp.int32, (block - lo, 1), 0) + lo
            z = jnp.exp(gb[lo:] - gb[j:j + 1]) * kbb[j:j + 1]
            a_col = jnp.where(i > j, jnp.sum(z * kk[lo:], axis=1,
                                             keepdims=True), 0.0)
            b_col = jnp.where(i >= j, jnp.sum(z * qq[lo:], axis=1,
                                              keepdims=True), 0.0)
            if lo:
                pad = jnp.zeros((lo, 1), f32)
                a_col = jnp.concatenate([pad, a_col], 0)
                b_col = jnp.concatenate([pad, b_col], 0)
            at = lane == b * block + j
            m_acc = jnp.where(at, a_col, m_acc)
            b_acc = jnp.where(at, b_col, b_acc)
        m_rows.append(m_acc)
        b_rows.append(b_acc)
        yield
    m_diag = jnp.concatenate(m_rows, 0)

    # the blocks below: products through the reading block's start
    from_block = jnp.exp(gl)
    lk, lq = k * from_block, q * from_block
    below = [jnp.zeros((block, C), f32)]
    for b in range(1, nb):
        r = jnp.concatenate(
            [kb[rows] * jnp.exp(left[rows] + span(j + 1, b))
             for j, rows in enumerate(blocks[:b])]
            + [jnp.zeros((C - b * block, dk), f32)], 0)
        p = _dot(jnp.concatenate([lk[blocks[b]], lq[blocks[b]]], 0), r,
                 ((1,), (1,)))                                 # (2 block, C)
        yield
        below.append(p[:block])
        b_rows[b] = b_rows[b] + p[block:]
    m_below = jnp.concatenate(below, 0)
    b_all = jnp.concatenate(b_rows, 0)

    # T = (I + m_diag)^-1, the blocks side by side
    p = -m_diag
    t = jnp.where(ri == ci, 1.0, 0.0).astype(f32) + p
    for _ in range(shift - 1):          # (-m)^n, n < block = 2^shift
        p = _dot(p, p)
        yield
        t = t + _dot(t, p)
        yield

    from_start = jnp.exp(G)
    ks = _dot(jnp.concatenate([k * from_start, q * from_start], 0), s0)
    yield
    w = _dot(t, v - ks[:C])
    if nb > 1:
        p = -_dot(t, m_below)
        yield
        for i in range(nb.bit_length() - 1):    # N^nb = 0, nb = 2^levels
            if i:
                p = _dot(p, p)
            w = w + _dot(p, w)
            yield
    o = ks[C:] + _dot(b_all, w)
    whole = jnp.transpose(jnp.broadcast_to(jnp.exp(span(0, nb)), (dk, dk)))
    s1 = whole * s0 + _dot(kb * jnp.exp(to_end), w, ((0,), (0,)))
    return o, s1


def _kernel(len_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref, o_ref,
            so_ref, *, heads: int, block: int):
    c = pl.program_id(2)
    n = len_ref[pl.program_id(0)] - c * CHUNK

    @pl.when(c == 0)
    def _():
        so_ref[...] = s_ref[...]

    @pl.when(n <= 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n > 0)
    def _():
        lanes = [slice(h * 128, (h + 1) * 128) for h in range(heads)]
        done = _in_step([_head(n, q_ref[0, :, at], k_ref[0, :, at],
                               g_ref[0, :, at], v_ref[0, :, at],
                               beta_ref[0, 0, :, h:h + 1], so_ref[0, :, at],
                               block) for h, at in enumerate(lanes)])
        for at, (o, s1) in zip(lanes, done):
            o_ref[0, :, at] = o
            so_ref[0, :, at] = s1


def _in_step(heads):
    """Run the generators ``heads`` a stage at a time, each in turn, and
    return their values: one head's chunk is a chain of some twenty
    products, each waiting for the one before it, and the heads of a
    step are independent — side by side in the program the matrix
    units take one head's product while another's drains."""
    values = [None] * len(heads)
    while any(v is None for v in values):
        for h, head in enumerate(heads):
            try:
                next(head)
            except StopIteration as end:
                values[h] = end.value
    return values


# One function under ``jit``: a program traces and lowers the kernel
# once, not once a layer (``kda_update_pallas``).
@functools.partial(jax.jit, static_argnames=("block", "heads", "interpret"))
def kda_scan_pallas(state: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
                    v: jnp.ndarray, g: jnp.ndarray, beta: jnp.ndarray,
                    lengths: jnp.ndarray, *, block: int, heads: int = 0,
                    interpret: bool = False):
    """``ops/kda.kda_scan`` with its arguments: ``state`` (S, d_k,
    H*d_v); ``q``, ``k``, ``g`` (S, T, H, d_k); ``v`` (S, T, H, d_v);
    ``beta`` (S, T, H); ``lengths`` (S,); ``block`` its ``chunk``.
    ``heads`` a grid step (0: :func:`kda_scan_heads`). Returns ``(o (S,
    T, H, d_v) float32 — zeros in a chunk wholly past its slice's
    length, of no use at any position past it —, the state behind each
    slice's last valid token in ``state.dtype``)``."""
    S, T, H, dk = k.shape
    dv = v.shape[-1]
    if not kda_scan_viable(dk, H, dv, T, block):
        raise ValueError(f"kda scan kernel: k {k.shape}, v {v.shape}, "
                         f"blocks of {block}")
    n = heads or kda_scan_heads(H)
    f32 = jnp.float32

    def flat(x):                        # (S, T, H, d) -> (S, T, H d)
        return x.astype(f32).reshape(S, T, H * x.shape[-1])

    def tokens(s, hb, c, lens):
        # a chunk past the length stays at the last live one: no fetch
        last = jnp.maximum((lens[s] + CHUNK - 1) // CHUNK - 1, 0)
        return s, jnp.minimum(c, last), hb

    def betas(s, hb, c, lens):
        s, c, hb = tokens(s, hb, c, lens)
        return s, hb, c, 0

    wide = pl.BlockSpec((1, CHUNK, 128 * n), tokens)
    whole = pl.BlockSpec((1, dk, 128 * n), lambda s, hb, c, lens: (s, 0, hb))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(S, H // n, T // CHUNK),
        in_specs=[wide, wide, wide, wide,
                  pl.BlockSpec((1, 1, CHUNK, n), betas), whole],
        out_specs=[pl.BlockSpec((1, CHUNK, 128 * n),
                                lambda s, hb, c, lens: (s, c, hb)), whole])
    blocks = (5 * CHUNK + 2 * dk) * 128 * n * 4 + CHUNK * 128 * 4
    o, last = pl.pallas_call(
        functools.partial(_kernel, heads=n, block=block),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, T, H * dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands: lengths, q, k, g, v, beta, state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * blocks + (16 << 20)),
        interpret=interpret,
    )(lengths.astype(jnp.int32), flat(q), flat(k), flat(g), flat(v),
      jnp.swapaxes(beta.astype(f32).reshape(S, T, H // n, n), 1, 2),
      state.astype(f32))
    return o.reshape(S, T, H, dv), last.astype(state.dtype)

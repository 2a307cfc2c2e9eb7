"""State-space operations of a Mamba-2 mixer (``models/nemotron_h.py``): the
causal depthwise conv with its tail, the selective state update in its two
forms, and the gated grouped norm. Plain XLA, float32 throughout: a request's
recurrent state is a float32 array of FIXED size (``[heads, head_dim, state]``
a layer, beside the conv's last ``K - 1`` inputs) that thousands of decode
steps multiply through, so nothing here rounds it to a narrower type.

The recurrence, per head ``h`` (``A_h < 0`` a scalar, ``D_h`` a skip gain;
``B_t``, ``C_t [state]`` shared by the heads of a group)::

    S_t = exp(dt_t A) S_{t-1} + dt_t (x_t ⊗ B_t)        S [head_dim, state]
    y_t = S_t C_t + D x_t

**One position a row** (``ssm_step``, ``conv_step``: a decode step) is that
line as written — elementwise over the state, which is read and written once.

**A chunk of positions** (``ssm_chunk``, ``conv_chunk``: chunked prefill) is
the BLOCK form over blocks of ``block`` positions (the published
``chunk_size``): with ``a_t = dt_t A`` and ``c_i = Σ_{t<=i} a_t`` inside a
block, the block's own positions see each other through one masked product
``y_i += Σ_{j<=i} exp(c_i - c_j) (C_i·B_j) dt_j x_j``, the state entering the
block adds ``exp(c_i) C_i·S_in``, and the state leaving it is ``exp(c_last)
S_in + Σ_j exp(c_last - c_j) dt_j x_j ⊗ B_j`` — matrix products inside a
block, the recurrence only from block to block, the row's stored state the
carry in and out. No token-by-token scan.

**Pads.** A position that is no real token has ``dt = 0`` (the caller forces
it): ``exp(0) = 1`` and ``0·(x ⊗ B) = 0``, so it leaves the state EXACTLY as
it was, and a right-padded chunk ends in the state of its last real token.
The conv's tail is taken at the row's last real position (``n_real``), not at
the chunk's end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
f32 = jnp.float32


# ------------------------------------------------------------------- the conv

def conv_step(tail, x, w, b):
    """One position a row. ``tail [B, K-1, C]`` (the last inputs, oldest
    first), ``x [B, C]``, ``w [K, C]`` (tap ``k`` meets the input ``K-1-k``
    positions back), ``b [C]`` → ``(silu(conv) [B, C], new tail)``."""
    win = jnp.concatenate([tail, x[:, None].astype(f32)], axis=1)  # [B, K, C]
    y = jnp.sum(win * w.astype(f32)[None], axis=1) + b.astype(f32)
    return jax.nn.silu(y), win[:, 1:]


def conv_chunk(tail, x, n_real, w, b):
    """A chunk. ``x [B, S, C]``; the row's first ``n_real [B]`` positions are
    real. Returns ``(silu(conv) [B, S, C], new tail)``: the tail is the
    ``K - 1`` inputs that END at the row's last real position (the old tail's
    where the chunk holds fewer; unchanged where it holds none)."""
    K = w.shape[0]
    S = x.shape[1]
    xin = jnp.concatenate([tail, x.astype(f32)], axis=1)  # [B, S + K - 1, C]
    wf = w.astype(f32)
    y = b.astype(f32)
    for k in range(K):
        y = y + xin[:, k:k + S] * wf[k]
    at = n_real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]  # [B, K-1]
    new_tail = jnp.take_along_axis(xin, at[:, :, None], axis=1)
    return jax.nn.silu(y), new_tail


# ------------------------------------------------------------ the state update

def ssm_step(state, x, dt, A, Bm, Cm, D):
    """One position a row. ``state [B, nh, hd, ds]`` f32, ``x [B, nh, hd]``,
    ``dt [B, nh]`` (after softplus; 0 for a row that must not advance), ``A``,
    ``D [nh]``, ``Bm``, ``Cm [B, g, ds]`` → ``(y [B, nh, hd] f32, state)``."""
    Bn, nh, hd, ds = state.shape
    g = Bm.shape[1]
    r = nh // g
    x, dt = x.astype(f32), dt.astype(f32)
    dA = jnp.exp(dt * A.astype(f32))  # [B, nh]
    s = state.reshape(Bn, g, r, hd, ds)
    xdt = (x * dt[..., None]).reshape(Bn, g, r, hd)
    s = s * dA.reshape(Bn, g, r, 1, 1) + (
        xdt[..., None] * Bm.astype(f32)[:, :, None, None, :]
    )
    y = jnp.sum(s * Cm.astype(f32)[:, :, None, None, :], axis=-1)  # [B,g,r,hd]
    y = y.reshape(Bn, nh, hd) + D.astype(f32)[None, :, None] * x
    return y, s.reshape(Bn, nh, hd, ds)


def ssm_chunk(state, x, dt, A, Bm, Cm, D, block: int):
    """A chunk in block form. ``state [B, nh, hd, ds]`` f32 (the carry in),
    ``x [B, S, nh, hd]``, ``dt [B, S, nh]`` (0 at every position that is no
    real token), ``Bm``, ``Cm [B, S, g, ds]`` → ``(y [B, S, nh, hd] f32,
    state)``. ``S`` is padded up to whole blocks with ``dt = 0``."""
    Bn, S, nh, hd = x.shape
    g, ds = Bm.shape[2], Bm.shape[3]
    r = nh // g
    pad = -S % block
    if pad:
        def padded(a):
            return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

        x, dt, Bm, Cm = padded(x), padded(dt), padded(Bm), padded(Cm)
    nc, Q = (S + pad) // block, block
    x = x.astype(f32).reshape(Bn, nc, Q, g, r, hd)
    dt = dt.astype(f32).reshape(Bn, nc, Q, g, r)
    Bm = Bm.astype(f32).reshape(Bn, nc, Q, g, ds)
    Cm = Cm.astype(f32).reshape(Bn, nc, Q, g, ds)
    a = dt * A.astype(f32).reshape(g, r)  # [B, nc, Q, g, r]  (<= 0)
    cum = jnp.cumsum(a, axis=2)
    xdt = x * dt[..., None]
    # inside a block: position i sees j <= i through exp(c_i - c_j) (C_i·B_j)
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cm, Bm, precision=_HI)
    diff = cum[:, :, :, None] - cum[:, :, None]  # [B, nc, i, j, g, r]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    y = jnp.einsum(
        "bcgij,bcijgr,bcjgrp->bcigrp", scores, decay, xdt, precision=_HI
    )
    # what each block adds to the state that leaves it
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, nc, Q, g, r]
    added = jnp.einsum(
        "bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, xdt, Bm, precision=_HI
    )
    total = jnp.exp(cum[:, :, -1])  # [B, nc, g, r] a block's whole decay
    from_in = jnp.exp(cum)  # [B, nc, Q, g, r]
    s = state.reshape(Bn, g, r, hd, ds)
    y_in = []
    for c in range(nc):  # the recurrence, block to block
        y_in.append(jnp.einsum(
            "bign,bgrpn->bigrp", Cm[:, c], s, precision=_HI
        ) * from_in[:, c][..., None])
        s = s * total[:, c][..., None, None] + added[:, c]
    y = y + jnp.stack(y_in, axis=1)
    y = y + D.astype(f32).reshape(g, r)[..., None] * x
    y = y.reshape(Bn, nc * Q, nh, hd)[:, :S]
    return y, s.reshape(Bn, nh, hd, ds)


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """The mixer's output norm, gate FIRST: ``RMSNorm_per_group(y ·
    silu(z)) · gain`` over ``groups`` groups of the last dim, in float32."""
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    shape = v.shape
    v = v.reshape(*shape[:-1], groups, shape[-1] // groups)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return v.reshape(shape) * gain.astype(f32)

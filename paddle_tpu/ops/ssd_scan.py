"""Chunked selective scan of a Mamba-2 (SSD) mixer, one B/C group.

The recurrence, a head at a time (state ``H`` is ``[P, N]``, fp32):

    H_t = exp(A * dt_t) * H_{t-1} + dt_t * x_t (x) B_t,     y_t = H_t . C_t

is evaluated a chunk of ``Q`` positions at a time (Dao & Gu 2024).  With
``cum`` the running sum of ``A * dt`` inside a chunk:

  * within a chunk, the masked decay product ``y_i += sum_{j<=i} (C_i .
    B_j) exp(cum_i - cum_j) dt_j x_j``, the only part that would hold a
    ``[Q, Q]`` matrix a head;
  * a chunk's own end state ``sum_j exp(cum_end - cum_j) dt_j x_j (x)
    B_j`` and what the state that enters a chunk adds to its outputs,
    ``exp(cum_i) (S_entering . C_i)``;
  * across a row's chunks the state recurrence ``S_c = exp(cum_end_c)
    S_{c-1} + own_c``.

Where the shapes fit their tiles all three are the Pallas kernels of
``ops/pallas/ssd_scan.py`` (forward and backward, a ``custom_vjp``: the
``[Q, Q]`` matrices live and die in VMEM, the state rides in scratch
along the row); else :func:`ssd_chunked_xla`, the same mathematics in
``jnp`` under autodiff, which does hold those matrices — chosen from
shapes alone.  A row that is no whole number of chunks is padded at its
end with ``dt = 0`` (no decay, no input), which changes no earlier
output.  :func:`ssd_scan_xbc` takes x, B and C in the one array a
Mamba-2 convolution leaves them in; the kernels then read them there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "ssd_scan_xbc", "ssd_chunked_xla", "ssd_recurrence"]

F32 = jnp.float32


def ssd_chunked_xla(x, dt, cum, B, C):
    """The chunked form in plain ``jnp`` — the fallback, and the
    kernels' yardstick.  x ``[b, c, Q, H, P]``; dt, cum ``[b, c, Q, H]``
    fp32; B, C ``[b, c, Q, N]`` -> y like x."""
    b, _, q, h, p = x.shape
    cb = jnp.einsum("bcin,bcjn->bcij", C, B, preferred_element_type=F32)
    seen = jnp.tril(jnp.ones((q, q), bool))[..., None]
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # [b,c,i,j,H]
    m = cb[..., None] * jnp.exp(jnp.where(seen, seg, -jnp.inf)) \
        * dt[:, :, None, :, :]
    y = jnp.einsum("bcijh,bcjhp->bcihp", m.astype(x.dtype), x,
                   preferred_element_type=F32)
    # a chunk's own end state, [b, c, H, P, N] fp32
    to_end = jnp.exp(cum[:, :, -1:, :] - cum) * dt
    own = jnp.einsum("bcqhp,bcqn->bchpn",
                     (x * to_end[..., None]).astype(x.dtype), B,
                     preferred_element_type=F32)
    keep = jnp.exp(cum[:, :, -1, :])                        # [b,c,H]

    def step(state, inp):
        k, o = inp
        return state * k[..., None, None] + o, state
    _, entering = jax.lax.scan(
        step, jnp.zeros((b, h, p, B.shape[-1]), F32),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(own, 1, 0)))
    carried = jnp.einsum("bcqn,cbhpn->bcqhp", C, entering.astype(x.dtype),
                         preferred_element_type=F32)
    return (y + carried * jnp.exp(cum)[..., None]).astype(x.dtype)


def ssd_scan(x, dt, A, B, C, chunk: int):
    """x ``[b, s, H, P]``; dt ``[b, s, H]`` fp32, already positive; A
    ``[H]`` fp32, negative; B, C ``[b, s, N]`` -> y ``[b, s, H, P]`` in
    x's dtype (the skip ``D * x`` is the caller's)."""
    from .pallas import ssd_scan as kernel
    b, s, h, p = x.shape
    pad = -s % chunk
    if pad:
        ext = lambda a: jnp.pad(a, [(0, 0), (0, pad)] +
                                [(0, 0)] * (a.ndim - 2))
        x, dt, B, C = ext(x), ext(dt), ext(B), ext(C)
    x, dt, B, C = (_cut(a, chunk) for a in (x, dt.astype(F32), B, C))
    cum = jnp.cumsum(dt * A.astype(F32), axis=2)            # [b,c,Q,H]
    form = kernel.ssd_chunked if kernel.takes(x, B) else ssd_chunked_xla
    return form(x, dt, cum, B, C).reshape(b, s + pad, h, p)[:, :s]


def _cut(a, chunk):
    """A row ``[b, s, ...]`` cut into its chunks ``[b, c, Q, ...]``."""
    return a.reshape(a.shape[0], a.shape[1] // chunk, chunk, *a.shape[2:])


def ssd_scan_xbc(xbc, dt, A, state: int, chunk: int):
    """:func:`ssd_scan` on x, B and C as the convolution before the scan
    leaves them, side by side in ONE array ``[b, s, H*P + 2N]`` -> y
    and x, ``[b, s, H*P]`` each.  Where the kernels take them there
    (``takes_xbc``: whole chunks, the state one lane tile) nothing is
    sliced out for them, and the backward gives xbc's cotangent whole,
    what is owed to the x returned here (the caller's skip reads it)
    included; else the three are sliced and go the way of
    :func:`ssd_scan`."""
    from .pallas import ssd_scan as kernel
    b, s, width = xbc.shape
    h, d = dt.shape[-1], width - 2 * state
    if s % chunk == 0 and kernel.takes_xbc(_cut(xbc, chunk), h, state):
        dt = _cut(dt.astype(F32), chunk)
        cum = jnp.cumsum(dt * A.astype(F32), axis=2)
        return kernel.ssd_chunked_xbc(xbc, dt, cum, state)
    x = xbc[..., :d]
    y = ssd_scan(x.reshape(b, s, h, d // h), dt, A, xbc[..., d:d + state],
                 xbc[..., d + state:], chunk)
    return y.reshape(b, s, d), x


def ssd_recurrence(x, dt, A, B, C):
    """The recurrence itself, token by token in fp32: what the chunked
    forms are held to by the tests.  Shapes as :func:`ssd_scan`."""
    b, s, h, p = x.shape
    x, dt, A, B, C = (a.astype(F32) for a in (x, dt, A, B, C))

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = state * jnp.exp(dtt * A)[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, ct)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1]), F32),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)

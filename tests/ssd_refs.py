"""The plain reference the chunked state-space recurrence (ops/ssd.py) is
held to: the recurrence of its module text token by token, a ``lax.scan`` in
float32, with the same arguments and results as ``ssd.ssd_chunked``."""

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, a, b, c, d=None, initial_state=None, *,
                  return_state: bool = False):
    """Token by token, in float32 (module text). Returns y (B, S, H, P) in
    ``x``'s type, and the last state (B, H, P, N) with ``return_state``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    f32 = jnp.float32
    state = (jnp.zeros((bsz, h, p, n), f32) if initial_state is None
             else initial_state.astype(f32))
    rep = h // g

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs                  # (B,H,P) (B,H) (B,G,N)
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(v.astype(f32), 1, 0) for v in (x, dt, b, c))
    state, y = jax.lax.scan(step, state, xs)
    y = jnp.moveaxis(y, 0, 1)
    if d is not None:
        y = y + x.astype(f32) * d.astype(f32)[:, None]
    y = y.astype(x.dtype)
    return (y, state) if return_state else y

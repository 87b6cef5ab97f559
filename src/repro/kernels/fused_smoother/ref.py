"""Pure-jnp oracle for the fused smoother recurrence step."""
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("accum_dtype",))
def smoother_step_ref(indices: jax.Array, data: jax.Array, dinv: jax.Array,
                      b_blocks: jax.Array, x_blocks: jax.Array,
                      d_blocks: jax.Array, coef: jax.Array, *,
                      accum_dtype=None):
    """Same contract as the kernel: one step of

        d' = c1 * d + c2 * D^{-1}(b - A x),   x' = x + d'

    over (nbr, bs[, k]) block vectors, A in padded BlockELL form.
    ``accum_dtype`` mirrors the kernel's accumulator rule (None = native);
    results round back to ``data.dtype``.
    """
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else data.dtype
    xg = x_blocks[indices].astype(acc)            # (nbr, kmax, bs[, k])
    ax = jnp.einsum("rkab,rkb...->ra...", data.astype(acc), xg,
                    preferred_element_type=acc)
    r = b_blocks.astype(acc) - ax
    z = jnp.einsum("rab,rb...->ra...", dinv.astype(acc), r,
                   preferred_element_type=acc)
    d_new = (coef[0].astype(acc) * d_blocks.astype(acc)
             + coef[1].astype(acc) * z)
    x_new = x_blocks.astype(acc) + d_new
    return x_new.astype(data.dtype), d_new.astype(data.dtype)


@functools.partial(jax.jit, static_argnames=("accum_dtype",))
def smoother_step_seq(indices: jax.Array, data: jax.Array, dinv: jax.Array,
                      b_blocks: jax.Array, x_blocks: jax.Array,
                      d_blocks: jax.Array, coef: jax.Array, *,
                      accum_dtype=None):
    """``smoother_step_ref`` summed in the kernel's order, for bitwise f64
    comparisons; written on its own, from a plain ``x[indices]`` gather.

    Order: for each output component ``a``, the per-slot products
    ``sum_b A[a, b] x[b]`` sequential in ``b``, then the sum over slots;
    ``z = D^{-1} r`` sequential in the column.
    """
    acc = jnp.dtype(accum_dtype) if accum_dtype is not None else data.dtype
    panel = b_blocks.ndim == 3
    b3, x3, d3 = (v if panel else v[..., None]
                  for v in (b_blocks, x_blocks, d_blocks))
    bs = data.shape[2]
    a_ = data.astype(acc)
    xg = x3[indices].astype(acc)                  # (nbr, kmax, bs, k)
    r = []
    for a in range(bs):
        t = a_[:, :, a, 0, None] * xg[:, :, 0]    # (nbr, kmax, k)
        for b in range(1, bs):
            t = t + a_[:, :, a, b, None] * xg[:, :, b]
        r.append(b3[:, a].astype(acc)
                 - jnp.sum(jnp.moveaxis(t, 1, 0), axis=0))
    dv = dinv.astype(acc)
    d_new, x_new = [], []
    for a in range(bs):
        z = dv[:, a, 0, None] * r[0]
        for c in range(1, bs):
            z = z + dv[:, a, c, None] * r[c]
        dn = coef[0].astype(acc) * d3[:, a].astype(acc) \
            + coef[1].astype(acc) * z
        d_new.append(dn)
        x_new.append(x3[:, a].astype(acc) + dn)
    x_new, d_new = (jnp.stack(v, axis=1).astype(data.dtype)
                    for v in (x_new, d_new))
    if not panel:
        x_new, d_new = x_new[..., 0], d_new[..., 0]
    return x_new, d_new

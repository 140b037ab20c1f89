"""What the files of document boundaries in the ops share
(``tests/test_ops_ssm_segments*.py``: the scan, the convolution, flash with
ids): a row's ids from its documents' lengths, the scan's arguments, each
document run alone as a row of its own, and the comparison.  This module
holds no test."""

import jax
import jax.numpy as jnp
import numpy as np

#: at a chunk / tile of 128: a boundary on the edge (128), one token after
#: the next (257), several inside one chunk (260, 263, 300), a long tail
LENGTHS = (128, 129, 3, 3, 37, 212)


def _ids(lengths, rows=1):
    return jnp.asarray(np.tile(np.repeat(np.arange(len(lengths)), lengths),
                               (rows, 1)), jnp.int32)


def _scan_args(S, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(k[0], (1, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (1, S, H)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.0)),
            0.3 * jax.random.normal(k[3], (1, S, G, N)),
            0.3 * jax.random.normal(k[4], (1, S, G, N)),
            jax.random.normal(k[5], (H,))), jax.random.normal(
                k[6], (1, S, H, P))


def _alone(fn, args, weight, lengths, by_token, shared):
    """(outputs laid end to end, gradients) of ``fn`` run on each document
    as a row of its own: ``by_token`` the indices of the arguments that lie
    [1, S, ...], ``shared`` of those whose gradients add up.  A document's
    output and gradients are one jitted program, so a length costs one
    compilation and not one an operation of ``fn`` and of its backward."""

    @jax.jit
    def one(weight, *mine):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out * weight), out
        (_, out), g = jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True)(*mine)
        return out, g

    outs, grads, at = [], [jnp.zeros_like(a) for a in args], 0
    for n in lengths:
        part = slice(at, at + n)
        mine = [a[:, part] if i in by_token else a
                for i, a in enumerate(args)]
        out, g = one(weight[:, part], *mine)
        outs.append(out)
        for i in by_token:
            grads[i] = grads[i].at[:, part].set(g[i])
        for i in shared:
            grads[i] = grads[i] + g[i]
        at += n
    return jnp.concatenate(outs, 1), grads


def _close(got, want, rtol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=rtol, atol=rtol * float(jnp.max(jnp.abs(w))) + 1e-6)

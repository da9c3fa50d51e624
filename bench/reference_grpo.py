"""GRPO steps of a plain reference model whose forward pass is given,
for models other than ``bench/reference.py``'s dense one (the LFM2
configuration: ``bench/reference_lfm2.py``).

``bench.reference.GRPOReference``'s steps, Adam update and readings;
only the forward pass differs.  Parameters are stored in their dtype
between steps; every computation is float32 at the highest precision
(``prec="fp8"``: the float8 control).  Step t follows the program's step
t, whose rollouts the current parameters generated.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from bench import reference


class GRPOReference(reference.GRPOReference):
    """``forward(theta32, consts, tokens, prec) -> logits``; ``theta0``:
    the trained tensors, all of one dtype; ``consts``: fixed arrays the
    forward pass reads (neither trained nor differentiated; passed as
    arguments, so that one compiled program serves every seed)."""

    def __init__(self, forward: Callable, theta0, *, lr, clip_eps,
                 kl_beta, group, consts=None, beta1=0.9, beta2=0.95,
                 eps=1e-8, clip_norm=1.0, prec="f32"):
        self.cfg, self.embed_scale = None, None     # the dense model's
        self.forward, self.consts, self.prec = forward, consts, prec
        self.lr, self.clip_eps, self.kl_beta = lr, clip_eps, kl_beta
        self.group, self.b1, self.b2, self.eps = group, beta1, beta2, eps
        self.clip_norm = clip_norm
        dtypes = {x.dtype for x in jax.tree_util.tree_leaves(theta0)}
        assert len(dtypes) == 1, dtypes
        self.dtype = dtypes.pop()
        self.theta0 = theta0
        self.theta = jax.tree_util.tree_map(jnp.copy, theta0)
        zeros = lambda a: jnp.zeros(a.shape, jnp.float32)  # noqa: E731
        self.m = jax.tree_util.tree_map(zeros, theta0)
        self.v = jax.tree_util.tree_map(zeros, theta0)
        self.t = 0
        self._build()

    def _build(self):
        super()._build()                # the update; log-probs and loss:
        forward, prec, consts = self.forward, self.prec, self.consts

        def seq_logp(theta32, consts, seqs, gen_start):
            lg = forward(theta32, consts, seqs, prec)[:, gen_start - 1:-1]
            lp = jax.nn.log_softmax(lg, -1)
            return jnp.take_along_axis(lp, seqs[:, gen_start:, None],
                                       -1)[..., 0]

        def logp(theta, consts, seqs, gen_start):
            with jax.default_matmul_precision("highest"):
                return seq_logp(reference._f32(theta), consts, seqs,
                                gen_start)

        def loss(theta32, consts, seqs, lp_old, adv, mask, n, gen_start):
            r = jnp.exp(seq_logp(theta32, consts, seqs, gen_start)
                        - lp_old)
            per = -jnp.minimum(r * adv, jnp.clip(r, 1 - self.clip_eps,
                                                 1 + self.clip_eps) * adv)
            return (per * mask).sum() / n

        def grad(theta, consts, seqs, lp_old, adv, mask, n, gen_start):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(loss)(
                    reference._f32(theta), consts, seqs, lp_old, adv, mask,
                    n, gen_start)

        logp, grad = (jax.jit(logp, static_argnums=3),
                      jax.jit(grad, static_argnums=7))
        self._logp = lambda theta, seqs, g: logp(theta, consts, seqs, g)
        self._grad = lambda theta, *a: grad(theta, consts, *a)

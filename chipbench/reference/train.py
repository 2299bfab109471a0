"""Plain float32 reference of a training step: the SALO-masked decoder's
mean next-token loss, its gradient, and AdamW as a traffic file states it.

The layers are ``decoder._layer`` (recomputed in the backward pass, one
layer at a time); the loss takes the output head in blocks of rows. AdamW:
the gradient is clipped to a global norm, then

  m = b1 m + (1 - b1) g;  v = b2 v + (1 - b2) g^2      (t = step + 1)
  p -= lr(step) * (m / (1 - b1^t) / (sqrt(v / (1 - b2^t)) + eps) + wd p)

with lr(step) = lr * min(step / warmup, 1) * cosine decay to
``min_lr_ratio`` over ``total_steps``. ``mode="fp8"`` is the control, as in
``decoder``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import decoder

LOSS_ROWS = 1024     # output-head rows per block of the loss


def _stack(params) -> dict:
    seg = params["seg0_attn_mlp"]
    return {"ln1": seg["ln1"]["scale"], "ln2": seg["ln2"]["scale"],
            **seg["attn"], **seg["mlp"]}


def _seq_loss(m, params, tokens, labels, mode):
    """Summed next-token loss of one sequence."""
    x = jnp.take(params["embed"]["w"], tokens, axis=0) * float(
        np.sqrt(m["d"]))
    layer = jax.checkpoint(
        lambda x, lp: decoder._layer.__wrapped__(x, lp, m, mode))
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x,
                        _stack(params))
    table = params["embed"]["w"] if m["tied"] else params["lm_head"]["w"]

    @jax.checkpoint
    def block(args):
        xb, lb = args
        lg = decoder._ein("nd,vd->nv",
                          decoder._rms(xb, params["ln_f"]["scale"], m["eps"]),
                          table, mode)
        gold = jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

    return jnp.sum(jax.lax.map(block, (x.reshape(-1, LOSS_ROWS, m["d"]),
                                       labels.reshape(-1, LOSS_ROWS))))


@functools.partial(jax.jit, static_argnames=("m", "mode"))
def loss_and_grad(params, tokens, m, mode="f32"):
    """Mean loss over every predicted token of a (batch, seq + 1) array of
    ids, and its gradient, all in float32."""
    def loss(p):
        per_seq = jax.vmap(lambda t: _seq_loss(m, p, t[:-1], t[1:], mode))(
            tokens)
        return jnp.sum(per_seq) / (tokens.shape[0] * (tokens.shape[1] - 1))

    return jax.value_and_grad(loss)(params)


def lr_at(o: dict, step: int) -> float:
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    frac = min(max((step - o["warmup_steps"])
                   / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    decay = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (
        1 + math.cos(math.pi * frac))
    return o["lr"] * warm * decay


@jax.jit
def _adamw(params, m, v, grads, lr, t, b1, b2, eps, wd, clip):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9)),
        grads)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1 / (jnp.sqrt(v_ / c2) + eps)
                                    + wd * p), params, m, v)
    return params, m, v, grads


def follow(m: dict, params, batches: list, o: dict, mode: str = "f32"):
    """Run the first ``len(batches)`` steps from ``params``. Returns (the
    loss of each step, the clipped gradient of the first step, the
    parameters after the last step)."""
    m = decoder._M(m)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    mom = jax.tree.map(jnp.zeros_like, p)
    vel = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    for step, tokens in enumerate(batches):
        loss, grads = loss_and_grad(p, jnp.asarray(tokens), m, mode)
        losses.append(float(loss))
        p, mom, vel, clipped = _adamw(
            p, mom, vel, grads, lr_at(o, step), step + 1, o["b1"], o["b2"],
            o["eps"], o["weight_decay"], o["grad_clip"])
        if first is None:
            first = clipped
    return losses, first, p

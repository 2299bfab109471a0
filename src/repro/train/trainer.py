"""Training step factory: loss -> grads -> (optionally compressed) psum ->
AdamW, with microbatch gradient accumulation and LR schedule.

``make_train_step`` returns a pure jittable function
``(params, opt_state, batch, ef_state=None) -> (params, opt_state,
metrics, ef_state)`` suitable for ``jax.jit(..., donate_argnums=(0, 1))``
under a mesh. The arity is FIXED: ``ef_state`` (the int8 error-feedback
residual) is always threaded — ``None`` unless gradient compression is
active — so callers and donation plumbing never switch shapes on a config
flag.

Gradient compression (``compress_grads=True``) is wired into the WIRE, not
just the values: when an ambient mesh maps any of ``compress_axes`` to
real devices, the gradient computation runs under ``shard_map`` over those
axes (batch sharded, params replicated) and the cross-device reduce is
:func:`repro.dist.compression.compressed_psum_with_residual` — each
participant ships int8 + one f32 scale per tensor instead of fp32 grads,
with the per-participant quantization residual carried in ``ef_state``
(leading axis = participant). The previous implementation
quantize-dequantized AFTER pjit's implicit fp32 all-reduce, moving exactly
as many bytes as the uncompressed step. Without a live mesh the step
degrades to the local quantize-dequantize (numerics-faithful, nothing to
compress on one device).

Note: inside the compressed region the loss/metrics are per-shard means
combined by ``pmean`` — exact for the equal-sized shards the batch axis
splitter produces; masked losses with unequal per-shard mask counts would
bias slightly (the synthetic pipeline emits no mask).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim import adamw
from repro.optim.schedule import Schedule


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: adamw.AdamWConfig = adamw.AdamWConfig()
    schedule: Schedule = Schedule()
    microbatches: int = 1            # gradient accumulation
    compress_grads: bool = False     # int8 all-reduce w/ error feedback
    # mesh axes whose reduce rides the compressed wire (the DCN-crossing
    # pod axis and the data axis — whichever exist on the ambient mesh)
    compress_axes: Tuple[str, ...] = ("pod", "data")


def make_train_step(model, tcfg: TrainConfig) -> Callable:
    def loss_fn(params, batch):
        return model.loss(params, batch)

    def grads_and_metrics(params, batch):
        """(grads, loss, metrics) with f32 grads on BOTH microbatch paths
        (the mb > 1 accumulator is f32; mb == 1 used to hand param-dtype
        grads — the optimizer/wire dtype must not depend on mb) and
        metrics averaged across microbatches (``m[-1]`` used to report
        only the LAST microbatch while the loss was averaged)."""
        mb = tcfg.microbatches
        if mb == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            return grads, loss, metrics

        # Microbatch accumulation: split the batch axis and scan.
        # (M-RoPE "positions" carries batch on axis 1, everything else
        # on axis 0.)
        def slice_mb(i, key, x):
            axis = 1 if key == "positions" else 0
            b = x.shape[axis] // mb
            return jax.lax.dynamic_slice_in_dim(x, i * b, b, axis=axis)

        def body(carry, i):
            acc_g, acc_l = carry
            mbatch = {k: slice_mb(i, k, v) for k, v in batch.items()}
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mbatch)
            return (jax.tree.map(jnp.add, acc_g, g), acc_l + l), m

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), metrics = jax.lax.scan(
            body, (zeros, 0.0), jnp.arange(mb))
        grads = jax.tree.map(lambda g: g / mb, grads)
        loss = loss / mb
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
        return grads, loss, metrics

    def _compress_axes():
        """(mesh, live compress axes, participant count) — the axes from
        tcfg.compress_axes present on the ambient mesh, i.e. the
        participants of the compressed wire. axes == () = nothing to
        shard. The single place mesh sizes are read."""
        from repro.dist.sharding import _ambient_mesh

        mesh = _ambient_mesh()
        if mesh is None or mesh.empty:
            return None, (), 1
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        axes = tuple(a for a in tcfg.compress_axes if sizes.get(a, 1) > 1)
        return mesh, axes, math.prod(sizes[a] for a in axes)

    def compressed_grads(mesh, axes, n, params, batch, ef_state):
        """Grad computation under shard_map over ``axes`` (``n``
        participants): batch sharded, params replicated, the reduce a
        compressed psum + error feedback."""
        from repro.dist import compression
        from repro.dist import sharding as shlib

        if ef_state is None:
            ef_state = jax.tree.map(
                lambda p: jnp.zeros((n,) + p.shape, jnp.float32), params)
        bspec = {k: P(None, axes) if k == "positions" else P(axes)
                 for k in batch}

        def local(params, batch, ef):
            ef = jax.tree.map(lambda e: e[0], ef)
            # constrain() is a no-op inside the shard_map region (arrays
            # are device-local); neutralize the ambient rules.
            with shlib.axis_rules({}):
                g, loss, metrics = grads_and_metrics(params, batch)

            def one(g_, e_):
                tot, resid = compression.compressed_psum_with_residual(
                    g_ + e_, axes)
                return tot / n, resid

            pairs = jax.tree.map(one, g, ef)
            is_pair = lambda t: isinstance(t, tuple)  # noqa: E731
            g = jax.tree.map(lambda t: t[0], pairs, is_leaf=is_pair)
            ef = jax.tree.map(lambda t: t[1], pairs, is_leaf=is_pair)
            loss = jax.lax.pmean(loss, axes)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axes),
                                   metrics)
            return g, loss, metrics, jax.tree.map(lambda e: e[None], ef)

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(), bspec, P(axes)),
                           out_specs=(P(), P(), P(), P(axes)),
                           check_vma=False)
        return fn(params, batch, ef_state)

    def train_step(params, opt_state, batch, ef_state=None):
        if tcfg.compress_grads:
            mesh, axes, n = _compress_axes()
            if axes:
                if any(v.shape[1 if k == "positions" else 0] % n
                       for k, v in batch.items()):
                    raise ValueError(
                        f"compress_grads: batch axis must divide the "
                        f"compress mesh axes {axes} (x{n})")
                grads, loss, metrics, ef_state = compressed_grads(
                    mesh, axes, n, params, batch, ef_state)
            else:
                # single participant: nothing on the wire; keep the
                # quantization numerics + error feedback locally so the
                # step is faithful to the distributed one
                from repro.dist import compression
                grads, loss, metrics = grads_and_metrics(params, batch)
                grads, ef_state = compression.compress_decompress(
                    grads, ef_state)
        else:
            grads, loss, metrics = grads_and_metrics(params, batch)

        lr_scale = tcfg.schedule(opt_state.step)
        params, opt_state, opt_metrics = adamw.update(
            tcfg.optimizer, opt_state, params, grads, lr_scale)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return params, opt_state, metrics, ef_state

    return train_step


def make_eval_step(model) -> Callable:
    def eval_step(params, batch):
        _, metrics = model.loss(params, batch)
        return metrics
    return eval_step

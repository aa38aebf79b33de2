"""Gradient compression for cross-replica reduction (int8 + error feedback).

The data-parallel gradient all-reduce is the collective that crosses pods
(DCI) at 1000-node scale, so its wire bytes are the lever. We compress by
quantizing each shard's gradient to int8 with a per-tensor fp32 scale, then
``all_gather``-ing the quantized tensors and reducing locally in fp32:

    wire bytes/device ≈ (N-1)/N · B     (int8 gather)
    vs. ring all-reduce bf16 ≈ 2 · (N-1)/N · 2B

≈ 4× fewer bytes on the wire. Error feedback (the residual between the true
and quantized gradient is carried into the next step) restores convergence —
``tests/test_compression.py`` checks both the bytes model and convergence on
a quadratic.

Exposed as (a) primitives usable inside ``shard_map`` and (b)
``make_dp_train_step`` — a pure data-parallel training step used by the
multi-replica integration tests and the elastic-training example.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def quantize_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def int8_all_reduce_mean(x, axis_name: str):
    """Inside shard_map: mean over ``axis_name`` with int8 wire format."""
    q, scale = quantize_int8(x.astype(jnp.float32))
    qg = jax.lax.all_gather(q, axis_name)  # (N, ...) int8 on the wire
    sg = jax.lax.all_gather(scale, axis_name)  # (N,) fp32 (negligible)
    shape = (-1,) + (1,) * x.ndim
    full = qg.astype(jnp.float32) * sg.reshape(shape)
    return full.mean(axis=0)


def tree_int8_all_reduce_mean(grads, axis_name: str, error):
    """Error-feedback compressed mean-reduce over a gradient pytree.

    ``error`` carries each tensor's quantization residual; returns
    (reduced_grads, new_error).
    """

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        q, scale = quantize_int8(g32)
        new_e = g32 - dequantize_int8(q, scale)
        qg = jax.lax.all_gather(q, axis_name)
        sg = jax.lax.all_gather(scale, axis_name)
        shape = (-1,) + (1,) * g.ndim
        red = (qg.astype(jnp.float32) * sg.reshape(shape)).mean(axis=0)
        return red, new_e

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_e = tdef.flatten_up_to(error)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (
        tdef.unflatten([o[0] for o in out]),
        tdef.unflatten([o[1] for o in out]),
    )


def error_init(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params
    )


def make_dp_train_step(cfg, opt_cfg, mesh: Mesh, axis: str = "data",
                       compress: bool = True):
    """Pure data-parallel train step under shard_map (params replicated,
    batch sharded over ``axis``), with optional int8+EF gradient reduce."""
    from repro.models import train_loss
    from repro.optim import adamw_update

    def dp_step(state, batch):
        def inner(params, opt, err, local_batch):
            (loss, _m), grads = jax.value_and_grad(
                train_loss, has_aux=True
            )(params, cfg, local_batch)
            if compress:
                grads, err = tree_int8_all_reduce_mean(grads, axis, err)
            else:
                grads = jax.lax.pmean(grads, axis)
            loss = jax.lax.pmean(loss, axis)
            new_p, new_opt, _om = adamw_update(params, grads, opt, opt_cfg)
            return new_p, new_opt, err, loss

        batch_specs = jax.tree_util.tree_map(
            lambda _: P(axis), batch
        )
        fn = shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), P(), P(), batch_specs),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        new_p, new_opt, err, loss = fn(
            state["params"], state["opt"], state["error"], batch
        )
        return {"params": new_p, "opt": new_opt, "error": err}, loss

    return dp_step

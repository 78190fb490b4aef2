"""GPipe-style pipeline parallelism for the dense transformer (PP axis).

jax-native formulation (DESIGN.md §5): stages are a shard_map over the
"pp" mesh axis; the classic GPipe schedule (M microbatches through S
stages in M+S−1 ticks) is a lax.scan whose carry is the inter-stage
activation buffer, moved stage-to-stage with lax.ppermute. Backward is
automatic: ppermute transposes to the reverse permute, so jax.grad of the
pipelined forward IS the GPipe backward schedule (bubble included).

Layout: layer-stacked params (L, ...) reshape to (S, L/S, ...) and shard
P("pp") on the stage dim — each device owns only its stage's weights.
Embedding/head run replicated outside the pipelined trunk (they are not
layer-stacked). Intended composition: pp × data (DP) × model (TP) —
the test exercises pp alone on virtual devices.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models import layers as L


def stack_stages(layer_params: dict, n_stages: int) -> dict:
    """(L, ...) layer-stacked tree -> (S, L/S, ...)."""
    def split(x):
        l = x.shape[0]
        assert l % n_stages == 0, f"{l} layers not divisible by {n_stages}"
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(split, layer_params)


def _stage_forward(cfg: T.TransformerConfig, stage_layers, x, positions):
    def body(carry, lp):
        y, _, _ = T._layer(cfg, lp, carry, positions)
        return y, None
    out, _ = jax.lax.scan(body, x, stage_layers)
    return out


def pipeline_forward(cfg: T.TransformerConfig, params, tokens, *,
                     mesh: Mesh, n_microbatches: int, pp_axis: str = "pp"):
    """Training/prefill forward with the trunk pipelined over `pp_axis`.

    params: dict with 'embed', 'layers' STAGE-STACKED (S, L/S, ...),
    'ln_final' (+ optional 'lm_head'). tokens: (B, S_seq) with
    B % n_microbatches == 0. Returns fp32 logits (B, S_seq, V).
    """
    n_stages = mesh.shape[pp_axis]
    dt = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    m = n_microbatches
    assert b % m == 0, "batch must divide into microbatches"
    mb = b // m
    x = params["embed"][tokens].astype(dt)              # (B, S, D)
    x_mbs = x.reshape(m, mb, s, -1)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                 (mb, s))

    def pipe(stage_layers, xs):
        sid = jax.lax.axis_index(pp_axis)
        stage_layers = jax.tree.map(lambda t: t[0], stage_layers)
        n_ticks = m + n_stages - 1
        out0 = jnp.zeros_like(xs)
        buf0 = jnp.zeros_like(xs[0])

        def tick(carry, t):
            buf, out = carry
            inject = xs[jnp.clip(t, 0, m - 1)]
            xin = jnp.where(sid == 0, inject, buf)
            y = _stage_forward(cfg, stage_layers, xin, positions)
            recv = jax.lax.ppermute(
                y, pp_axis, [(i, (i + 1) % n_stages)
                             for i in range(n_stages)])
            idx = t - (n_stages - 1)
            keep = (sid == n_stages - 1) & (idx >= 0)
            upd = out.at[jnp.clip(idx, 0, m - 1)].set(y)
            out = jnp.where(keep, upd, out)
            return (recv, out), None

        (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                   jnp.arange(n_ticks))
        # only the last stage holds real outputs; replicate via psum
        out = jax.lax.psum(
            jnp.where(sid == n_stages - 1, out, jnp.zeros_like(out)),
            pp_axis)
        return out

    specs_layers = jax.tree.map(lambda _: P(pp_axis), params["layers"])
    pipe_fn = jax.shard_map(
        pipe, mesh=mesh, in_specs=(specs_layers, P()), out_specs=P(),
        check_vma=False)
    y = pipe_fn(params["layers"], x_mbs)
    y = y.reshape(b, s, -1)
    y = L.rms_norm(y, params["ln_final"], cfg.norm_eps)
    head = params.get("lm_head",
                      params["embed"].T if cfg.tie_embeddings else None)
    logits = jnp.einsum("bsd,dv->bsv", y, head.astype(dt))
    return logits.astype(jnp.float32)


def pipeline_loss(cfg, params, tokens, targets, *, mesh, n_microbatches,
                  pp_axis: str = "pp"):
    logits = pipeline_forward(cfg, params, tokens, mesh=mesh,
                              n_microbatches=n_microbatches, pp_axis=pp_axis)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                         axis=-1))


def make_pipeline_train_step(cfg, mesh, n_microbatches: int,
                             pp_axis: str = "pp", lr: float = 1e-3):
    """GPipe training step (params stage-stacked, stage-sharded)."""
    from repro.optim import AdamWConfig, adamw_update

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: pipeline_loss(cfg, p, tokens, targets, mesh=mesh,
                                    n_microbatches=n_microbatches,
                                    pp_axis=pp_axis))(params)
        params, opt_state = adamw_update(
            params, grads, opt_state, jnp.float32(lr),
            AdamWConfig(weight_decay=0.0))
        return params, opt_state, loss

    return step

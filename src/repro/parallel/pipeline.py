"""Pipeline parallelism with SEALED stage boundaries.

The multi-pod mesh's 'pod' axis doubles as a pipeline axis: each pod owns a
contiguous slice of the layer stack, and the activations crossing the
pod-to-pod DCN hop — the paper's untrusted-bus analogue — are CTR-sealed
before `ppermute` and unsealed on arrival (Rule 1 applied to the pipeline
boundary).  Because counter mode is exact bitwise XOR, pipelined loss and
gradients match the unpipelined model bit-for-bit.

Schedule: classic SPMD GPipe fill-drain.  With S stages and M microbatches,
the scan runs M + S - 1 ticks; at tick t, stage s processes microbatch
t - s (if in range).  Backward flows through the transpose of ppermute
automatically (jax.grad of the shard_mapped function), so one
``make_pipelined_loss`` value_and_grad's like any other loss.

This is a working reference implementation for the dense family (the other
families follow the same recipe via their block functions); it is exercised
at smoke scale on a host-device mesh in tests/test_pipeline.py.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import cipher
from ..models import layers as L
from ..models import transformer as TF


def stack_params_by_stage(params, n_stages: int):
    """Re-group a dense LM param tree: layers split into per-stage slices.

    Returns a tree whose 'layers' leaves have leading dim [n_stages,
    layers_per_stage, ...]; embed lives on stage 0, unembed/final_norm on the
    last stage (replicated here for simplicity — they are small).
    """
    def regroup(a):
        nl = a.shape[0]
        assert nl % n_stages == 0, (nl, n_stages)
        return a.reshape(n_stages, nl // n_stages, *a.shape[1:])
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(regroup, params["layers"])
    return out


def make_pipelined_loss(cfg, mesh, n_stages: int, n_micro: int,
                        seal_key=None, axis: str = "pod"):
    """Returns loss(params_staged, batch) running under shard_map over
    ``axis`` (manual), with in-stage data/model axes left automatic.

    batch: tokens/labels [n_micro, B_micro, S].  seal_key: uint32[2] or None
    — when given, stage-boundary activations are sealed across the hop.
    """
    sealed = seal_key is not None
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]

    def _hop(x, tick, perm, domain, src_offset):
        """One sealed hop: seal with a (tick, sender, direction)-unique nonce,
        permute, unseal with the recomputed sender nonce.
        src_offset: sender stage relative to the receiver (-1 fwd, +1 bwd)."""
        me = jax.lax.axis_index(axis).astype(jnp.uint32)
        S = jnp.uint32(n_stages)
        nonce = (tick.astype(jnp.uint32) * jnp.uint32(16) + me
                 + jnp.uint32(domain))
        ct = cipher.seal_bits(x, seal_key, nonce)
        ct = jax.lax.ppermute(ct, axis, perm)
        src = (me + S + jnp.uint32(src_offset % n_stages)) % S
        nonce_rx = (tick.astype(jnp.uint32) * jnp.uint32(16) + src
                    + jnp.uint32(domain))
        return cipher.unseal_bits(ct, seal_key, nonce_rx, x.dtype)

    @jax.custom_vjp
    def _send(x, tick):
        if sealed:
            return _hop(x, tick, fwd_perm, 0, -1)
        return jax.lax.ppermute(x, axis, fwd_perm)

    def _send_fwd(x, tick):
        return _send(x, tick), tick

    def _send_bwd(tick, g):
        # activation COTANGENTS also cross the untrusted link: sealed reverse
        # hop (autodiff cannot see through bitcast/XOR, and must not — the
        # backward channel needs Rule-1 protection exactly like the forward)
        if sealed:
            return _hop(g, tick, bwd_perm, 8, +1), None
        return jax.lax.ppermute(g, axis, bwd_perm), None

    _send.defvjp(_send_fwd, _send_bwd)

    def staged_loss(params_staged, batch, reduce=True):
        sid = jax.lax.axis_index(axis)
        my_layers = jax.tree_util.tree_map(lambda a: a[0],
                                           params_staged["layers"])
        # params_staged['layers'] arrives sliced per stage by shard_map
        tokens, labels = batch["tokens"], batch["labels"]
        M, Bm, S = tokens.shape
        positions = jnp.arange(S)
        D = cfg.d_model

        def stage_fn(x):
            def body(c, lp):
                y, _ = TF._block(lp, cfg, c, positions)
                return y, None
            y, _ = jax.lax.scan(body, x, my_layers)
            return y

        n_ticks = M + n_stages - 1
        buf = jnp.zeros((Bm, S, D), cfg.act_dtype)
        loss_acc = jnp.zeros((), jnp.float32)

        def tick(carry, t):
            buf, loss_acc = carry
            mb_in = t                      # microbatch entering stage 0
            mb_out = t - (n_stages - 1)    # microbatch leaving the last stage
            # stage 0 injects the embedded microbatch
            tok_t = jax.lax.dynamic_index_in_dim(
                tokens, jnp.clip(mb_in, 0, M - 1), 0, keepdims=False)
            x0 = jnp.take(params_staged["embed"], tok_t, axis=0
                          ).astype(cfg.act_dtype)
            x = jnp.where((sid == 0) & (mb_in < M), x0.astype(buf.dtype), buf)
            y = stage_fn(x)
            # last stage computes loss for the microbatch draining now
            logits = TF.logits_of(params_staged, cfg, y)
            lab_t = jax.lax.dynamic_index_in_dim(
                labels, jnp.clip(mb_out, 0, M - 1), 0, keepdims=False)
            mb_loss = L.softmax_xent(logits, jnp.maximum(lab_t, 0),
                                     mask=lab_t >= 0)
            take = (sid == n_stages - 1) & (mb_out >= 0) & (mb_out < M)
            loss_acc = loss_acc + jnp.where(take, mb_loss, 0.0)
            # rotate activations to the next stage (sealed hop)
            buf = _send(y, t)
            return (buf, loss_acc), None

        (buf, loss_acc), _ = jax.lax.scan(tick, (buf, loss_acc),
                                          jnp.arange(n_ticks))
        if not reduce:
            # per-stage local loss (only the last stage's is nonzero) — used
            # by the grad path: seeding every device's own scalar with 1
            # differentiates the SUM of local losses, avoiding the
            # psum-self-transpose double count under check_vma=False.
            return loss_acc / M
        # all stages must return the same value: sum over the stage axis
        return jax.lax.psum(loss_acc, axis) / M

    staged = jax.shard_map(
        staged_loss, mesh=mesh,
        in_specs=(_param_specs_staged(), P()),
        out_specs=P(), axis_names={axis}, check_vma=False)

    def staged_value_and_grad(params_staged, batch):
        """Grad computed INSIDE the shard_map (per-stage), then combined:
        stage-sliced leaves keep their slice, replicated leaves are psum'd.

        Full-manual shard_map here: jax 0.8's partial-auto transpose rejects
        replicated out_specs for the cotangents; the pipeline body only uses
        the 'pod' axis, so full-manual is semantically identical for it.
        """
        def body(p, b):
            l, g = jax.value_and_grad(
                lambda pp: staged_loss(pp, b, reduce=False))(p)
            l = jax.lax.psum(l, axis)
            g = {k: (v if k == "layers" else
                     jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis), v))
                 for k, v in g.items()}
            return l, g
        specs = _param_specs_staged()
        return jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P()),
            out_specs=(P(), specs), check_vma=False
        )(params_staged, batch)

    staged.value_and_grad = staged_value_and_grad
    return staged


def _param_specs_staged():
    # layers sliced along the stage axis; embed/norm/unembed replicated
    return {"embed": P(), "layers": P("pod"), "final_norm": P(),
            "unembed": P()}

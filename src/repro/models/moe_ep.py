"""Expert-parallel MoE dispatch under manual shard_map — the fix for the
GSPMD-opacity problem measured in EXPERIMENTS §Perf A.

Observation: with Megatron-style TP, the token activations entering the MoE
layer are model-axis-REPLICATED (each model shard sees every token of its
data shard).  Expert parallelism therefore needs NO all-to-all at all: model
shard m selects the tokens routed to ITS local experts (a purely local
capacity-scatter over E/mn experts), runs its expert FFNs, and contributes a
partial [T_local, D] output; one psum over the model axis — the same
collective a TP MLP already pays — completes the combine.

Per layer/microbatch collective cost: one all-reduce of [T_l, D] activations
(~33 MB for moonshot) instead of GSPMD's replicated expert-buffer all-reduce
(~1.5 GB) — the napkin math behind the §Perf A fix.

Gradient note: vma tracking must stay ON (check_vma defaults True) — the
shard_map transpose then inserts the correct cotangent psums for the
replicated router and the data-replicated expert weights.  With
check_vma=False those sums are silently dropped (we measured exactly that
as a 0.31 max-grad error before enabling it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def moe_ffn_ep(p, cfg, x, mesh, data_axis="data", model_axis="model"):
    """Drop-in EP replacement for moe.moe_ffn. x: [B, S, D] -> [B, S, D].

    Requires: E % model_shards == 0, (B*S) % data_shards == 0, activations
    model-replicated on entry (the TP-standard layout this codebase uses).
    """
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    dn = mesh.shape[data_axis]
    mn = mesh.shape[model_axis]
    assert E % mn == 0
    E_l = E // mn
    T = B * S
    T_l = T // dn
    import math
    C_l = max(8, -(-int(math.ceil(T_l * k / E * m.capacity_factor)) // 8) * 8)

    def body(x_l, router, wg, wu, wd):
        # x_l: [T_l, D] (this data shard, model-replicated)
        # router: [D, E] replicated; wg/wu: [E_l, D, F]; wd: [E_l, F, D]
        mid = jax.lax.axis_index(model_axis)
        e0 = mid * E_l
        gates = jax.nn.softmax(x_l.astype(jnp.float32) @ router, axis=-1)
        gv, gi = jax.lax.top_k(gates, k)                       # [T_l, k]
        gv = gv / jnp.maximum(gv.sum(-1, keepdims=True), 1e-9)
        flat_e = gi.reshape(-1)                                # [T_l*k]
        mine = (flat_e >= e0) & (flat_e < e0 + E_l)
        loc_e = jnp.where(mine, flat_e - e0, 0)
        onehot = (loc_e[:, None] == jnp.arange(E_l)[None, :]) & mine[:, None]
        pos = (jnp.cumsum(onehot.astype(jnp.int32), axis=0)
               * onehot.astype(jnp.int32)).sum(-1) - 1
        keep = mine & (pos < C_l) & (pos >= 0)
        slot_e = jnp.where(keep, loc_e, 0)
        slot_c = jnp.where(keep, pos, 0)
        x_rep = jnp.repeat(x_l, k, axis=0) * keep[:, None].astype(x_l.dtype)
        buf = jnp.zeros((E_l, C_l, D), x_l.dtype).at[slot_e, slot_c].add(x_rep)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg)) \
            * jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        y_rep = out_buf[slot_e, slot_c] \
            * (gv.reshape(-1) * keep)[:, None].astype(x_l.dtype)
        y_partial = y_rep.reshape(T_l, k, D).sum(axis=1)       # my experts only
        return jax.lax.psum(y_partial, model_axis)             # TP-style combine

    xt = x.reshape(T, D)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(data_axis), P(), P(model_axis), P(model_axis),
                  P(model_axis)),
        out_specs=P(data_axis),
        # vma tracking ON: shard_map's transpose then inserts the correct
        # cotangent psums for the replicated router / data-replicated expert
        # weights (with check_vma=False those sums are silently dropped).
    )(xt, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out.reshape(B, S, D)

"""Mixture-of-Experts FFN — the paper's *fully partitioned* state access
pattern (§4.2) inside the model: the router is the hash ``h`` mapping each
token (task) to expert slots (state partitions), and expert parallelism
routes tokens to the shard owning the expert.

TPU-native realization (DESIGN §8): instead of CUDA scatter/atomics we use a
sort-based capacity dispatch per sequence —

  1. top-k router probs -> (expert, weight) per token
  2. argsort by expert id within each sequence (batch dims stay sharded over
     the data axes, so the sort is shard-local)
  3. positions-within-expert via a sorted segment cumsum; tokens beyond the
     per-expert capacity are dropped (standard capacity-factor semantics)
  4. gather tokens into a dense [B, E, C, d] buffer: E is sharded over the
     "model"/expert mesh axis, so each shard FFNs only its own experts
  5. weighted scatter-add back to [B, S, d] (GSPMD emits the partial-sum +
     all-reduce over the expert axis — exactly one TP-style collective)

Router load-balance (the paper's hash-fairness condition for S2 speedup) is
handled by an auxiliary load-balancing loss and, for kimi-k2, an
aux-loss-free learned bias added to routing logits (router_bias).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers
from repro.models.config import ModelConfig, MoEConfig


def init_moe(key, d: int, moe: MoEConfig, dtype) -> dict:
    kr, ke1, ke2, ke3, ks = jax.random.split(key, 5)
    E, ff = moe.num_experts, moe.d_ff_expert
    p = {
        "router": layers.truncated_normal(kr, (d, E), jnp.float32, d**-0.5),
        "w_gate": layers.truncated_normal(ke1, (E, d, ff), dtype, d**-0.5),
        "w_up": layers.truncated_normal(ke2, (E, d, ff), dtype, d**-0.5),
        "w_down": layers.truncated_normal(ke3, (E, ff, d), dtype, ff**-0.5),
    }
    if moe.router_bias:
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    if moe.num_shared:
        p["shared"] = layers.init_mlp(ks, d, ff * moe.num_shared, dtype)
    return p


def capacity(seq_len: int, moe: MoEConfig) -> int:
    c = int(math.ceil(seq_len * moe.top_k * moe.capacity_factor / moe.num_experts))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def route(x, params, moe: MoEConfig) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (expert_ids [B,S,k], weights [B,S,k] fp32, aux_loss scalar)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    select_from = logits + params.get("router_bias", 0.0)
    _, expert_ids = lax.top_k(select_from, moe.top_k)
    weights = jnp.take_along_axis(probs, expert_ids, axis=-1)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (fairness of the S2 hash): E * mean(f_e * p_e)
    E = moe.num_experts
    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)  # [B,S,k,E]
    frac_tokens = onehot.sum(axis=2).mean(axis=1)              # [B,E]
    mean_probs = probs.mean(axis=1)                            # [B,E]
    aux = E * (frac_tokens * mean_probs).sum(-1).mean()
    return expert_ids, weights.astype(jnp.float32), aux


def dispatch_indices(expert_ids, weights, moe: MoEConfig, cap: int):
    """Per-sequence sort-based capacity packing.

    expert_ids/weights: [B, S, k].  Returns
      buf_token  [B, E*C]   source token index per buffer row (or S = dummy)
      buf_weight [B, E*C]   combine weight per buffer row (0 for dummies)
    """
    B, S, k = expert_ids.shape
    E = moe.num_experts
    flat_e = expert_ids.reshape(B, S * k)
    flat_w = weights.reshape(B, S * k)
    flat_tok = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[:, None], (S, k)
    ).reshape(1, S * k)
    flat_tok = jnp.broadcast_to(flat_tok, (B, S * k))

    order = jnp.argsort(flat_e, axis=-1, stable=True)  # group by expert
    e_sorted = jnp.take_along_axis(flat_e, order, axis=-1)
    w_sorted = jnp.take_along_axis(flat_w, order, axis=-1)
    t_sorted = jnp.take_along_axis(flat_tok, order, axis=-1)

    # position within expert = index - first index of this expert's run
    idx = jnp.arange(S * k, dtype=jnp.int32)
    onehot_counts = jnp.zeros((B, E), jnp.int32).at[
        jnp.arange(B)[:, None], e_sorted
    ].add(1)
    run_start = jnp.cumsum(onehot_counts, axis=-1) - onehot_counts  # [B,E]
    pos_in_e = idx[None, :] - jnp.take_along_axis(run_start, e_sorted, axis=-1)

    keep = pos_in_e < cap
    slot = e_sorted * cap + jnp.where(keep, pos_in_e, 0)  # [B, S*k]

    bidx = jnp.arange(B)[:, None]
    # rows whose token overflowed capacity are parked on slot 0 of their
    # expert with weight 0 via the masked set below (keep=False writes are
    # redirected out of range and dropped)
    slot_or_oob = jnp.where(keep, slot, E * cap)  # E*cap is out of range
    buf_token = jnp.full((B, E * cap), S, jnp.int32).at[bidx, slot_or_oob].set(
        t_sorted, mode="drop"
    )
    buf_weight = jnp.zeros((B, E * cap), jnp.float32).at[bidx, slot_or_oob].set(
        w_sorted, mode="drop"
    )
    return buf_token, buf_weight


def moe_ffn(
    x, params, moe: MoEConfig, *, activation: str = "silu"
) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,d] -> (out [B,S,d], aux_loss).  See module docstring.

    Dispatches to the expert-parallel all_to_all path when the active
    sharding rules request it (ShardingRules.moe_a2a)."""
    from repro.launch.sharding import active_rules

    rules = active_rules()
    if rules is not None and getattr(rules, "moe_a2a", False):
        return moe_ffn_a2a(x, params, moe, activation=activation, rules=rules)
    B, S, d = x.shape
    E, k = moe.num_experts, moe.top_k
    cap = capacity(S, moe)

    expert_ids, weights, aux = route(x, params, moe)
    buf_token, buf_weight = dispatch_indices(expert_ids, weights, moe, cap)

    # gather tokens -> [B, E, C, d]; dummy rows (index S) read zeros
    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
    buf = jnp.take_along_axis(
        x_pad, buf_token[..., None].astype(jnp.int32), axis=1
    ).reshape(B, E, cap, d)

    # expert FFN (E sharded over the expert/model axis => shard-local einsum)
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    gate = act(jnp.einsum("becd,edf->becf", buf, params["w_gate"].astype(x.dtype)))
    up = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(x.dtype))
    out_buf = jnp.einsum(
        "becf,efd->becd", gate * up, params["w_down"].astype(x.dtype)
    )

    # weighted combine: scatter-add back to [B, S, d]
    out_buf = out_buf * buf_weight.reshape(B, E, cap, 1).astype(out_buf.dtype)
    flat = out_buf.reshape(B, E * cap, d)
    out = jnp.zeros((B, S + 1, d), x.dtype).at[
        jnp.arange(B)[:, None], buf_token
    ].add(flat, mode="drop")[:, :S]

    if moe.num_shared:
        out = out + layers.mlp(x, params["shared"], activation)
    return out, aux


def _flat_dispatch(flat_e, flat_w, E: int, cap: int, k: int = 1):
    """1-D sort-based capacity packing.  flat_e/flat_w [T*k] -> row tables
    (buf_token [E*cap] source TOKEN index (flat//k) or T=dummy,
    buf_weight [E*cap])."""
    R0 = flat_e.shape[0]
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    w_sorted = flat_w[order]
    t_sorted = (order // k).astype(jnp.int32)  # token index, not flat index
    counts = jnp.zeros((E,), jnp.int32).at[e_sorted].add(1)
    run_start = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(R0, dtype=jnp.int32) - run_start[e_sorted]
    keep = pos_in_e < cap
    slot = e_sorted * cap + jnp.where(keep, pos_in_e, 0)
    slot_or_oob = jnp.where(keep, slot, E * cap)
    buf_token = jnp.full((E * cap,), R0 // k, jnp.int32).at[slot_or_oob].set(
        t_sorted, mode="drop"
    )
    buf_weight = jnp.zeros((E * cap,), jnp.float32).at[slot_or_oob].set(
        w_sorted, mode="drop"
    )
    return buf_token, buf_weight


def moe_ffn_a2a(
    x, params, moe: MoEConfig, *, activation: str, rules,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE with all_to_all token routing — the paper's S2
    dispatch at production scale (§Perf beyond-paper optimization).

    Experts are sharded over the "data" axis (the partition owners); tokens
    are packed per destination shard and exchanged with ONE all_to_all each
    way (the emitter routing of §4.2), instead of GSPMD's activation
    all-reduce.  Expert-FFN hidden dim is TP-sharded over "model" (one psum).
    Cross-pod stays pure DP (hierarchical S3) — experts are replicated over
    the pod axis.

    Weight layout (see launch.sharding): w_gate/w_up [E("data"), d, ff("model")],
    w_down [E("data"), ff("model"), d]; router replicated.
    """
    mesh = rules.mesh
    ep = "data"
    tp = rules.tp_axis
    dp_spec = rules.dp
    n_ep = mesh.shape[ep]
    E, k = moe.num_experts, moe.top_k
    E_l = E // n_ep
    B, S, d = x.shape
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu

    def body(x_l, router_w, router_b, wg_l, wu_l, wd_l):
        B_l = x_l.shape[0]
        T = B_l * S
        xf = x_l.reshape(T, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        _, ids = lax.top_k(logits + router_b, k)
        w = jnp.take_along_axis(probs, ids, axis=-1)
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        # load-balance aux (hash fairness), averaged over the dp axes
        onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)
        aux = E * (onehot.sum(1).mean(0) * probs.mean(0)).sum()
        aux = lax.pmean(aux, ep)
        if "pod" in mesh.axis_names:
            aux = lax.pmean(aux, "pod")

        cap = max(4, -(-int(T * k * moe.capacity_factor / E) // 4) * 4)
        buf_token, buf_w = _flat_dispatch(
            ids.reshape(T * k), w.reshape(T * k).astype(jnp.float32), E, cap, k=k
        )
        xf_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
        send = xf_pad[buf_token]                         # [E*cap, d]
        send = send.reshape(n_ep, E_l * cap, d)
        recv = lax.all_to_all(send, ep, split_axis=0, concat_axis=0, tiled=False)
        # recv [n_ep(source), E_l*cap, d] -> [E_l, n_ep*cap, d]
        recv = recv.reshape(n_ep, E_l, cap, d).transpose(1, 0, 2, 3).reshape(
            E_l, n_ep * cap, d
        )
        gate = act(jnp.einsum("erd,edf->erf", recv, wg_l.astype(recv.dtype)))
        up = jnp.einsum("erd,edf->erf", recv, wu_l.astype(recv.dtype))
        out = jnp.einsum("erf,efd->erd", gate * up, wd_l.astype(recv.dtype))
        # out is a PARTIAL sum over the TP-sharded ff dim; combining first and
        # psum-ing the [T, d] result moves ~k*cf x fewer bytes than psum-ing
        # the [E_l, R, d] expert buffer (measured: §Perf deepseek i1->i2)
        back = out.reshape(E_l, n_ep, cap, d).transpose(1, 0, 2, 3).reshape(
            n_ep, E_l * cap, d
        )
        rows = lax.all_to_all(back, ep, split_axis=0, concat_axis=0, tiled=False)
        rows = rows.reshape(E * cap, d) * buf_w[:, None].astype(x_l.dtype)
        y = jnp.zeros((T + 1, d), x_l.dtype).at[buf_token].add(rows)[:T]
        y = lax.psum(y, tp)  # single [T, d] TP reduction after combine
        return y.reshape(B_l, S, d), aux

    from jax.sharding import PartitionSpec as P

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp_spec, None, None),
            P(None, None),
            P(None,),
            P(ep, None, tp),
            P(ep, None, tp),
            P(ep, tp, None),
        ),
        out_specs=(P(dp_spec, None, None), P()),
        check_vma=False,
    )(
        x,
        params["router"],
        params.get("router_bias", jnp.zeros((E,), jnp.float32)),
        params["w_gate"],
        params["w_up"],
        params["w_down"],
    )
    if moe.num_shared:
        y = y + layers.mlp(x, params["shared"], activation)
    return y, aux


def moe_ffn_dense_oracle(x, params, moe: MoEConfig, *, activation: str = "silu"):
    """O(B*S*E) oracle: every expert on every token, masked by the router's
    top-k weights, *without* capacity drops.  Matches moe_ffn exactly when
    capacity_factor is large enough that nothing is dropped."""
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    expert_ids, weights, aux = route(x, params, moe)
    gate = act(jnp.einsum("bsd,edf->bsef", x, params["w_gate"].astype(x.dtype)))
    up = jnp.einsum("bsd,edf->bsef", x, params["w_up"].astype(x.dtype))
    per_expert = jnp.einsum(
        "bsef,efd->bsed", gate * up, params["w_down"].astype(x.dtype)
    )
    E = moe.num_experts
    w_dense = jnp.zeros(weights.shape[:2] + (E,), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None, None],
        jnp.arange(x.shape[1])[None, :, None],
        expert_ids,
    ].add(weights)
    out = jnp.einsum("bsed,bse->bsd", per_expert, w_dense.astype(x.dtype))
    if moe.num_shared:
        out = out + layers.mlp(x, params["shared"], activation)
    return out, aux

"""Token-choice MoE FFN with capacity dropping — scatter-based dispatch.

Why not GShard dense-dispatch einsums: at DeepSeek-V3 scale (1M tokens,
E=256, C≈40k) the (tokens, experts, capacity) one-hot costs O(T²·k/E) FLOPs —
hundreds of times the useful expert compute. Instead we:

  1. route: top-k over router logits,
  2. compute each (token, slot) pair's *position inside its expert* with a
     hierarchical cumsum (local cumsum within ``n_groups`` groups + tiny
     cross-group offset) so nothing materializes beyond (T·k, E_onehot-free),
  3. scatter-add tokens into an (E·C, d) buffer (XLA scatter; under GSPMD the
     buffer is sharded experts->model, capacity->data),
  4. run the expert FFNs as one batched einsum over the expert axis,
  5. gather results back to token order and combine with router weights.

Tokens that overflow an expert's capacity are dropped (standard GShard/Switch
semantics; capacity_factor controls the drop rate).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.sharding import ShardingRules, constrain, single_device_rules


EXPERT_PAD = 16  # expert count padded to a multiple of the TP axis


def pad_experts(n: int) -> int:
    return ((n + EXPERT_PAD - 1) // EXPERT_PAD) * EXPERT_PAD


def init_moe(key, n_layers: int, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.bfloat16, n_shared: int = 0, shared_d_ff: int = 0):
    """Stacked-per-layer MoE params. Returns (params, axes).

    Expert weights are padded to a multiple of EXPERT_PAD so the expert dim
    shards evenly (granite: 40 -> 48). Padding experts are never routed to —
    router logits beyond ``n_experts`` are masked to -inf at dispatch."""
    n_experts = pad_experts(n_experts)
    ks = jax.random.split(key, 8)

    def w(kk, *shape, fan_in):
        return (jax.random.normal(kk, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    def stack(kk, *shape, fan_in):
        kl = jax.random.split(kk, n_layers)
        return jax.vmap(lambda k_: w(k_, *shape, fan_in=fan_in))(kl)

    params = {
        "router": stack(ks[0], n_experts, d_model, fan_in=d_model).transpose(0, 2, 1),
        "w_gate": stack(ks[1], n_experts, d_model, d_ff, fan_in=d_model),
        "w_up": stack(ks[2], n_experts, d_model, d_ff, fan_in=d_model),
        "w_down": stack(ks[3], n_experts, d_ff, d_model, fan_in=d_ff),
    }
    axes = {
        # experts shard on the model axis (EP); the per-expert ffn dim stays
        # unsharded — 'experts' and 'mlp' both map to `model` otherwise
        "router": ("layers", "embed", "experts"),
        "w_gate": ("layers", "experts", "embed", None),
        "w_up": ("layers", "experts", "embed", None),
        "w_down": ("layers", "experts", None, "embed"),
    }
    if n_shared > 0:
        params["shared_gate"] = stack(ks[4], d_model, shared_d_ff, fan_in=d_model)
        params["shared_up"] = stack(ks[5], d_model, shared_d_ff, fan_in=d_model)
        params["shared_down"] = stack(ks[6], shared_d_ff, d_model, fan_in=shared_d_ff)
        axes["shared_gate"] = ("layers", "embed", "mlp")
        axes["shared_up"] = ("layers", "embed", "mlp")
        axes["shared_down"] = ("layers", "mlp", "embed")
    return params, axes


def _positions_in_expert(expert_idx: jax.Array, n_experts: int, n_groups: int
                         ) -> jax.Array:
    """expert_idx: (Tk,) int32 — flat (token, slot) -> expert assignments.

    Returns (Tk,) int32: each assignment's arrival position within its expert.
    Hierarchical: exact cumsum, but reshaped to (n_groups, Tk/n_groups) so the
    big cumsum stays *local* to a data shard under SPMD; only the (G, E)
    per-group counts cross shards."""
    Tk = expert_idx.shape[0]
    G = n_groups if Tk % n_groups == 0 else 1
    eg = expert_idx.reshape(G, Tk // G)
    onehot = jax.nn.one_hot(eg, n_experts, dtype=jnp.int32)        # (G, T/G, E)
    local_pos = jnp.cumsum(onehot, axis=1) - onehot                # exclusive
    group_counts = jnp.sum(onehot, axis=1)                         # (G, E)
    group_offsets = jnp.cumsum(group_counts, axis=0) - group_counts
    pos = local_pos + group_offsets[:, None, :]                    # (G, T/G, E)
    pos_flat = jnp.take_along_axis(
        pos.reshape(Tk, n_experts), expert_idx[:, None], axis=1)[:, 0]
    return pos_flat


def mask_pad_experts(logits: jax.Array, n_experts: int) -> jax.Array:
    """-inf the padded expert columns so routing never selects them."""
    if logits.shape[-1] == n_experts:
        return logits
    ok = jnp.arange(logits.shape[-1]) < n_experts
    return jnp.where(ok, logits, -1e30)


def route(router_logits: jax.Array, top_k: int, router_type: str = "softmax"
          ) -> Tuple[jax.Array, jax.Array]:
    """(T, E) logits -> (weights (T, k) fp32, expert_idx (T, k) int32)."""
    if router_type == "sigmoid":  # deepseek-v3 style: sigmoid affinity, normalized
        scores = jax.nn.sigmoid(router_logits.astype(jnp.float32))
        w, idx = jax.lax.top_k(scores, top_k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    else:
        probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
        w, idx = jax.lax.top_k(probs, top_k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w, idx


def load_balance_loss(router_logits: jax.Array, expert_idx: jax.Array,
                      n_experts: int) -> jax.Array:
    """Switch-style aux loss: E * mean(frac_tokens_e * frac_prob_e)."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    frac_prob = jnp.mean(probs, axis=0)
    onehot = jax.nn.one_hot(expert_idx[:, 0], n_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(onehot, axis=0)
    return n_experts * jnp.sum(frac_prob * frac_tokens)


def moe_ffn_ep(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
               capacity_factor: float = 1.25,
               rules: Optional[ShardingRules] = None,
               router_type: str = "softmax") -> jax.Array:
    """Expert-parallel MoE with EXPLICIT all-to-all dispatch (shard_map).

    Why: under pure pjit, GSPMD lowers the dispatch scatter by replicating
    the token buffer across the expert shards — at DeepSeek scale that is
    ~120 GB of temp per device. Inside shard_map everything is local except
    two all-to-alls of (D, E_local·Ce, d) send/recv buffers — the textbook
    EP dispatch (DeepSeek-V3 §3.2's all-to-all, TPU-ICI flavored).

    Layout contract (matches the framework's default rules):
      x: (B, S, d) with B sharded over batch axes (pod,data), S over model
         when S > 1 — every device in the EP group holds distinct tokens;
      experts: padded to a multiple of the EP group size D and sharded over
         the group (router logits of padding experts are masked to -inf);
      EP group = ('data','model') when E >= |data|x|model| else ('model',);
         the pod axis stays pure DP (all-to-all never crosses pods).
    """
    assert rules is not None and rules.mesh is not None
    mesh = rules.mesh
    B, S, d = x.shape
    E, K = n_experts, top_k
    E_w = p["w_gate"].shape[-3]          # weights are EXPERT_PAD-padded
    dm, dd = mesh.shape["model"], mesh.shape["data"]
    ep_axes = ("data", "model") if E_w >= dm * dd else ("model",)
    D = int(np.prod([mesh.shape[a] for a in ep_axes]))
    E_pad = ((E_w + D - 1) // D) * D
    E_local = E_pad // D
    # x keeps the framework-default layout: batch over (pod,data), seq over
    # model (sequence parallelism). Each EP-group member therefore holds a
    # distinct token block; the group reuses whichever axes it spans.
    bb = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    seq_axis = "model" if S > 1 else None
    x_spec = P(bb, seq_axis, None)
    e_spec = P(ep_axes, None, None)
    has_shared = "shared_gate" in p
    repl = P(None, None)

    def local_fn(w_router, w_gate, w_up, w_down, shared, xb):
        Bl, Sl, _ = xb.shape
        t = xb.reshape(-1, d)
        Tl = t.shape[0]
        logits = t.astype(jnp.float32) @ w_router.astype(jnp.float32)
        if E_pad > E_w:
            logits = jnp.pad(logits, ((0, 0), (0, E_pad - E_w)),
                             constant_values=-1e30)
        logits = mask_pad_experts(logits, E)
        weights, expert_idx = route(logits, K, router_type)
        Ce = max(1, int(capacity_factor * Tl * K / E_pad))

        flat_e = expert_idx.reshape(-1)
        pos = _positions_in_expert(flat_e, E_pad, 1)
        keep = pos < Ce
        slot = flat_e * Ce + jnp.where(keep, pos, 0)

        xk = jnp.repeat(t[:, None, :], K, axis=1).reshape(Tl * K, d)
        xk = jnp.where(keep[:, None], xk, 0)
        send = jnp.zeros((E_pad * Ce, d), t.dtype).at[slot].add(xk, mode="drop")
        send = send.reshape(D, E_local * Ce, d)
        recv = jax.lax.all_to_all(send, ep_axes, 0, 0, tiled=True)

        toks = (recv.reshape(D, E_local, Ce, d)
                .transpose(1, 0, 2, 3).reshape(E_local, D * Ce, d))
        h = jax.nn.silu(jnp.einsum("end,edf->enf", toks, w_gate)) * \
            jnp.einsum("end,edf->enf", toks, w_up)
        out = jnp.einsum("enf,efd->end", h, w_down)
        back = (out.reshape(E_local, D, Ce, d)
                .transpose(1, 0, 2, 3).reshape(D, E_local * Ce, d))
        ret = jax.lax.all_to_all(back, ep_axes, 0, 0, tiled=True)

        y = ret.reshape(E_pad * Ce, d)[slot]
        y = y * (weights.reshape(-1)[:, None] * keep[:, None]).astype(y.dtype)
        y = y.reshape(Tl, K, d).sum(axis=1)
        if has_shared:
            sg, su, sd = shared
            hs = jax.nn.silu(t @ sg) * (t @ su)
            y = y + hs @ sd
        return y.reshape(Bl, Sl, d)

    # pad expert weights to E_pad (dummy experts receive ~no tokens)
    def padE(w):
        return jnp.pad(w, ((0, E_pad - E_w), (0, 0), (0, 0))) if E_pad > E_w else w

    shared = ((p["shared_gate"], p["shared_up"], p["shared_down"])
              if has_shared else (jnp.zeros((0,)),) * 3)
    shared_specs = tuple(P(*(None,) * a.ndim) for a in shared)
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(repl, e_spec, e_spec, e_spec, shared_specs, x_spec),
        out_specs=x_spec, check_vma=False)
    return fn(p["router"], padE(p["w_gate"]), padE(p["w_up"]),
              padE(p["w_down"]), shared, x)


def moe_ffn(p: dict, x: jax.Array, *, n_experts: int, top_k: int,
            capacity_factor: float = 1.25, n_groups: int = 16,
            rules: Optional[ShardingRules] = None,
            router_type: str = "softmax") -> jax.Array:
    """x: (B, S, d) or (T, d). Returns same shape."""
    rules = rules or single_device_rules()
    orig_shape = x.shape
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    K = top_k
    E = p["w_gate"].shape[-3]            # weights are EXPERT_PAD-padded
    C = max(K, int(capacity_factor * T * K / E))
    # pad capacity to a multiple of n_groups so the buffer can shard on data
    C = ((C + n_groups - 1) // n_groups) * n_groups

    router_logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    router_logits = mask_pad_experts(router_logits, n_experts)
    weights, expert_idx = route(router_logits, K, router_type)       # (T,K)

    flat_e = expert_idx.reshape(-1)                                  # (T*K,)
    pos = _positions_in_expert(flat_e, E, n_groups)                  # (T*K,)
    keep = (pos < C)
    slot = flat_e * C + jnp.where(keep, pos, 0)                      # (T*K,)

    # dispatch: scatter tokens into the (E*C, d) buffer
    xk = jnp.repeat(xt[:, None, :], K, axis=1).reshape(T * K, d)
    xk = jnp.where(keep[:, None], xk, 0)
    buf = jnp.zeros((E * C, d), xt.dtype)
    buf = buf.at[slot].add(xk, mode="drop")
    buf = buf.reshape(E, C, d)
    buf = constrain(buf, rules, "experts", "capacity", None)

    # expert FFN (swiglu), batched over experts
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    h = constrain(h, rules, "experts", "capacity", None)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = constrain(out, rules, "experts", "capacity", None)

    # combine: gather back to token order, weighted sum over the K slots
    y = out.reshape(E * C, d)[slot]                                  # (T*K, d)
    y = y * (weights.reshape(-1)[:, None] * keep[:, None]).astype(y.dtype)
    y = y.reshape(T, K, d).sum(axis=1)

    if "shared_gate" in p:
        hs = jax.nn.silu(xt @ p["shared_gate"]) * (xt @ p["shared_up"])
        y = y + hs @ p["shared_down"]
    return y.reshape(orig_shape)

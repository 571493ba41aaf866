"""Top-k routed mixture-of-experts MLP with capacity-based dispatch.

Two execution paths, same math:

* **local** (single device / no mesh rules): sort-based capacity dispatch —
  token→expert assignments ranked per expert (bincount + exclusive offsets),
  scattered into a dense (E, cap, d) buffer, grouped GEMMs, gathered back.

* **shard_map** (production meshes): GSPMD cannot partition the dispatch
  scatter (it replicates the buffer and all-reduces it every layer — measured
  at ~16 GB of all-reduce per MoE invocation on grok before this path
  existed).  The explicit formulation exploits that activations are
  *replicated over the model axis* under DP×TP: every model shard already
  holds all local tokens, so each shard dispatches only to the experts it
  owns ('expert' mode: E/model_size experts; 'ff' mode: the f/model_size
  slice of every expert) entirely locally, and one ``psum`` over the model
  axis combines partial outputs — the same wire cost as a dense TP MLP.
  FSDP-sharded expert weights are all-gathered over the data axis first
  (ZeRO-3 semantics).

Compute is ∝ top_k (active params) either way; tokens overflowing an
expert's capacity are dropped (GShard semantics).  Tests compare both paths
against the dense-dispatch oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..dist.sharding import constrain, current_mesh, current_rules
from .config import ModelConfig
from .layers import dense_init


def moe_init(key, cfg: ModelConfig) -> dict:
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    dt = cfg.pdtype()
    ks = jax.random.split(key, 5)
    k1, k2, k3 = jax.random.split(ks[0], 3)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": dense_init(ks[1], d, e.num_experts, dt),
        "wi": (jax.random.normal(k1, (e.num_experts, d, f), jnp.float32)
               * scale_in).astype(dt),
        "wg": (jax.random.normal(k2, (e.num_experts, d, f), jnp.float32)
               * scale_in).astype(dt),
        "wo": (jax.random.normal(k3, (e.num_experts, f, d), jnp.float32)
               * scale_out).astype(dt),
    }
    if e.num_shared_experts:
        fs = f * e.num_shared_experts
        p["shared_wi"] = dense_init(ks[2], d, fs, dt)
        p["shared_wg"] = dense_init(ks[3], d, fs, dt)
        p["shared_wo"] = dense_init(ks[4], fs, d, dt)
    return p


# ---------------------------------------------------------------------------
# Local capacity dispatch (single shard; also the body of the shard_map path)
# ---------------------------------------------------------------------------

def _dispatch_compute(x_flat, top_w, top_idx, wi, wg, wo, num_experts: int,
                      expert_offset, cap: int, compute_dtype):
    """Capacity-dispatch x_flat (T,d) for experts [offset, offset+E_local).

    top_idx are GLOBAL expert ids; assignments outside this shard's expert
    range are dropped locally (they're handled by the owning shard).
    Returns (T, d) partial output (zeros for tokens fully routed elsewhere)."""
    T, d = x_flat.shape
    K = top_w.shape[-1]
    e_local = wi.shape[0]

    expert_flat = top_idx.reshape(T * K) - expert_offset
    weight_flat = top_w.reshape(T * K)
    mine = (expert_flat >= 0) & (expert_flat < e_local)
    expert_key = jnp.where(mine, expert_flat, e_local)   # sort strangers last
    token_flat = jnp.arange(T * K, dtype=jnp.int32) // K

    order = jnp.argsort(expert_key, stable=True)
    sorted_e = expert_key[order]
    counts = jnp.bincount(expert_key, length=e_local + 1)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(T * K, dtype=jnp.int32) - offsets[sorted_e]

    x_gathered = x_flat[token_flat[order]].astype(compute_dtype)
    buf = jnp.zeros((e_local, cap, d), compute_dtype)
    ok = sorted_e < e_local
    se = jnp.where(ok, sorted_e, e_local)                # row e_local dropped
    buf = buf.at[se, rank_sorted].set(
        jnp.where(ok[:, None], x_gathered, 0), mode="drop")

    h = jnp.einsum("ecd,edf->ecf", buf, wi,
                   preferred_element_type=jnp.float32)
    g = jnp.einsum("ecd,edf->ecf", buf, wg,
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * h).astype(compute_dtype)
    y_e = jnp.einsum("ecf,efd->ecd", h, wo,
                     preferred_element_type=jnp.float32).astype(compute_dtype)

    in_cap = ok & (rank_sorted < cap)
    y_sorted = jnp.where(in_cap[:, None],
                         y_e[jnp.minimum(se, e_local - 1),
                             jnp.minimum(rank_sorted, cap - 1)], 0.0)
    inv = jnp.argsort(order, stable=True)
    y_assign = y_sorted[inv]
    contrib = y_assign.astype(jnp.float32) * weight_flat[:, None]
    return jax.ops.segment_sum(contrib, token_flat, num_segments=T)


def _route(x_flat, router, K: int):
    logits = (x_flat @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return probs, top_w, top_idx


def _aux_loss(e, probs, top_idx, dp_axes=()):
    """Switch aux loss.  Under shard_map the per-expert density and router
    probability are pmean'd over the DP axes BEFORE the (nonlinear) product —
    mean-of-shard-aux is not the global aux."""
    T = probs.shape[0]
    onehot_density = jnp.bincount(
        top_idx.reshape(-1), length=e.num_experts).astype(jnp.float32) \
        / (T * e.top_k)
    mean_prob = jnp.mean(probs, axis=0)
    if dp_axes:
        onehot_density = jax.lax.pmean(onehot_density, dp_axes)
        mean_prob = jax.lax.pmean(mean_prob, dp_axes)
    return e.num_experts * jnp.sum(onehot_density * mean_prob) \
        * e.router_aux_coef


def _moe_local(p, cfg: ModelConfig, x, capacity_factor: float):
    e = cfg.moe
    b, s, d = x.shape
    T = b * s
    cap = max(8, int(capacity_factor * T * e.top_k / e.num_experts))
    x_flat = x.reshape(T, d)
    probs, top_w, top_idx = _route(x_flat, p["router"], e.top_k)
    out_flat = _dispatch_compute(x_flat, top_w, top_idx, p["wi"], p["wg"],
                                 p["wo"], e.num_experts, 0, cap, cfg.cdtype())
    out = out_flat.reshape(b, s, d)
    if e.num_shared_experts:
        xe = x_flat.astype(cfg.cdtype())
        hs = jax.nn.silu(xe @ p["shared_wg"]) * (xe @ p["shared_wi"])
        out = out + (hs @ p["shared_wo"]).reshape(b, s, d).astype(out.dtype)
    return out.astype(x.dtype), _aux_loss(e, probs, top_idx)


# ---------------------------------------------------------------------------
# shard_map path (production meshes)
# ---------------------------------------------------------------------------

def _weight_specs(e, rules):
    """PartitionSpecs of the MoE weights under the active rules."""
    def ax(name):
        v = rules.get(name)
        return v

    if e.shard_mode == "expert" and ax("experts"):
        wi = P(ax("experts"), ax("expert_ff_in"), ax("moe_ff"))
        wo = P(ax("experts"), ax("moe_ff"), ax("expert_ff_in"))
    else:
        wi = P(None, ax("expert_ff_in"), ax("moe_ff"))
        wo = P(None, ax("moe_ff"), ax("expert_ff_in"))
    return wi, wo


def _moe_shard_map(p, cfg: ModelConfig, x, capacity_factor: float):
    e = cfg.moe
    mesh = current_mesh()
    rules = current_rules()
    dp = rules.get("batch")
    dp_axes = tuple(dp) if isinstance(dp, (tuple, list)) else (
        (dp,) if dp else ())
    model_ax = "model"
    b, s, d = x.shape
    wi_spec, wo_spec = _weight_specs(e, rules)
    x_spec = P(dp if dp else None, None, None)
    expert_mode = e.shard_mode == "expert" and rules.get("experts")
    model_size = mesh.shape[model_ax]
    e_local = e.num_experts // model_size if expert_mode else e.num_experts
    fsdp_axis = rules.get("mlp_embed")

    def body(x_l, router, wi, wg, wo, *shared):
        bl, sl, _ = x_l.shape
        T = bl * sl
        cap = max(8, int(capacity_factor * T * e.top_k
                         / max(e.num_experts, 1)))
        # ZeRO-3: reassemble the weight shards held on the DP axis
        if fsdp_axis is not None:
            axes = (fsdp_axis,) if isinstance(fsdp_axis, str) else fsdp_axis
            for a in axes:
                router = jax.lax.all_gather(router, a, axis=0, tiled=True)
                wi = jax.lax.all_gather(wi, a, axis=1, tiled=True)
                wg = jax.lax.all_gather(wg, a, axis=1, tiled=True)
                wo = jax.lax.all_gather(wo, a, axis=2, tiled=True)
        x_flat = x_l.reshape(T, d)
        probs, top_w, top_idx = _route(x_flat, router, e.top_k)
        if expert_mode:
            offset = jax.lax.axis_index(model_ax) * e_local
        else:
            offset = jnp.int32(0)
        out_flat = _dispatch_compute(x_flat, top_w, top_idx, wi, wg, wo,
                                     e.num_experts, offset, cap,
                                     cfg.cdtype())
        # partial outputs: expert mode sums shards' disjoint expert sets;
        # ff mode sums the f-slices — one psum either way
        out_flat = jax.lax.psum(out_flat, model_ax)
        out = out_flat.reshape(bl, sl, d).astype(x_l.dtype)
        if e.num_shared_experts:
            swi, swg, swo = shared
            if fsdp_axis is not None:
                axes = (fsdp_axis,) if isinstance(fsdp_axis, str) \
                    else fsdp_axis
                for a in axes:
                    swi = jax.lax.all_gather(swi, a, axis=0, tiled=True)
                    swg = jax.lax.all_gather(swg, a, axis=0, tiled=True)
                    swo = jax.lax.all_gather(swo, a, axis=1, tiled=True)
            xe = x_flat.astype(cfg.cdtype())
            hs = jax.nn.silu(xe @ swg) * (xe @ swi)
            hs = jax.lax.psum(hs @ swo, model_ax) if swo.shape[0] != \
                e.d_ff_expert * e.num_shared_experts else hs @ swo
            out = out + hs.reshape(bl, sl, d).astype(out.dtype)
        aux = _aux_loss(e, probs, top_idx, dp_axes)
        return out, aux

    mlp_spec = P(rules.get("mlp_embed"), rules.get("ff"))
    mlp_spec_o = P(rules.get("ff"), rules.get("mlp_embed"))
    in_specs = [x_spec, P(rules.get("embed"), None), wi_spec, wi_spec,
                wo_spec]
    args = [x, p["router"], p["wi"], p["wg"], p["wo"]]
    if e.num_shared_experts:
        in_specs += [mlp_spec, mlp_spec, mlp_spec_o]
        args += [p["shared_wi"], p["shared_wg"], p["shared_wo"]]
    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(*args)


def moe_apply(p: dict, cfg: ModelConfig, x: jnp.ndarray,
              capacity_factor: float = 1.25):
    """x: (B, S, d) -> (out, aux_loss)."""
    mesh = current_mesh()
    rules = current_rules()
    if mesh is not None and rules is not None and "model" in mesh.axis_names:
        return _moe_shard_map(p, cfg, x, capacity_factor)
    return _moe_local(p, cfg, x, capacity_factor)


def moe_apply_dense(p: dict, cfg: ModelConfig, x: jnp.ndarray):
    """Dense-dispatch oracle (every expert computes every token): O(E) FLOPs,
    used only by tests to validate the capacity dispatch above."""
    e = cfg.moe
    b, s, d = x.shape
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, e.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(top_idx, e.num_experts, dtype=jnp.float32)
    combine = jnp.einsum("bske,bsk->bse", onehot, top_w)
    xe = x.astype(jnp.float32)
    h = jnp.einsum("bsd,edf->bsef", xe, p["wi"].astype(jnp.float32))
    g = jnp.einsum("bsd,edf->bsef", xe, p["wg"].astype(jnp.float32))
    h = jax.nn.silu(g) * h
    y = jnp.einsum("bsef,efd->bsed", h, p["wo"].astype(jnp.float32))
    out = jnp.einsum("bsed,bse->bsd", y, combine)
    if e.num_shared_experts:
        hs = jax.nn.silu(xe @ p["shared_wg"].astype(jnp.float32)) \
            * (xe @ p["shared_wi"].astype(jnp.float32))
        out = out + hs @ p["shared_wo"].astype(jnp.float32)
    return out.astype(x.dtype)

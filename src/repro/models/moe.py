"""Mixture-of-Experts layer: shared + routed experts, top-k dispatch with
capacity, expert parallelism over the ``model`` mesh axis.

Router options: ``topk`` (standard softmax) or ``sinkhorn`` — the paper's
Sinkhorn-Knopp solver as a balanced-assignment router (repro.core.router).

Dispatch is scatter-based (Megatron/MaxText-style capacity buffers): tokens
are scattered into an (E, C, d) buffer by (expert, rank-within-expert),
experts run as one batched einsum over the E dim (shardable over ``model``),
and results gather back. Tokens past capacity are dropped (standard); with
the Sinkhorn router drops are rare because assignment is balanced by
construction — this is the measurable benefit of the paper's technique here
(see benchmarks/moe_router.py).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.router import route

Params = dict[str, Any]


def padded_experts(n_experts: int, tp: int) -> int:
    """Experts shard over 'model' (EP): pad count up to a tp multiple
    (qwen2-moe: 60 -> 64 at TP=16). Padded experts are router-masked and
    carry zero Sinkhorn column marginal -> never receive tokens."""
    return -(-n_experts // tp) * tp


def init_moe(key, d_model: int, d_ff: int, n_experts: int, n_shared: int,
             top_k: int, tp: int = 1, dtype=jnp.float32) -> Params:
    n_experts = padded_experts(n_experts, tp)
    kr, ke, ks = jax.random.split(key, 3)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    k1, k2, k3 = jax.random.split(ke, 3)
    p = {
        "router": jax.random.normal(kr, (d_model, n_experts), dtype) * s_in,
        "w_gate": jax.random.normal(k1, (n_experts, d_model, d_ff), dtype) * s_in,
        "w_up": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s_in,
        "w_down": jax.random.normal(k3, (n_experts, d_ff, d_model), dtype) * s_out,
    }
    if n_shared > 0:
        ff_sh = n_shared * d_ff
        s1, s2, s3 = jax.random.split(ks, 3)
        p["shared"] = {
            "w_gate": jax.random.normal(s1, (d_model, ff_sh), dtype) * s_in,
            "w_up": jax.random.normal(s2, (d_model, ff_sh), dtype) * s_in,
            "w_down": jax.random.normal(s3, (ff_sh, d_model), dtype) * (ff_sh ** -0.5),
        }
    return p


def moe_apply(p: Params, x: jax.Array, top_k: int, router_kind: str = "topk",
              capacity_factor: float = 1.25, router_iters: int = 6,
              n_real: int | None = None) -> tuple[jax.Array, jax.Array]:
    """x (B, T, d) -> (out (B, T, d), aux load-balance loss scalar)."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    n = b * t
    e = p["router"].shape[1]
    cap = int(capacity_factor * top_k * n / (n_real or e) + 1)

    logits = (flat @ p["router"]).astype(jnp.float32)
    probs = route(logits, router_kind, n_iter=router_iters,
                  n_real=n_real)                                # (n, E)
    topw, topi = lax.top_k(probs, top_k)                        # (n, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # rank of each assignment within its expert (static-shape scatter dispatch)
    eid = topi.reshape(-1)                                      # (n*k,)
    oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)                # (n*k, E)
    rank = (jnp.cumsum(oh, axis=0) - oh)
    rank = jnp.take_along_axis(rank, eid[:, None], axis=1)[:, 0]
    keep = (rank < cap).astype(x.dtype)
    rankc = jnp.minimum(rank, cap - 1)

    tok = jnp.arange(n).repeat(top_k)
    buf = jnp.zeros((e, cap, d), x.dtype)
    buf = buf.at[eid, rankc].add(flat[tok] * keep[:, None])     # (E, C, d)

    # expert FFN (swiglu), batched over E — shard E over 'model'
    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    hu = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * hu, p["w_down"])

    gathered = out_buf[eid, rankc] \
        * (keep * topw.reshape(-1).astype(x.dtype))[:, None]
    out = gathered.reshape(n, top_k, d).sum(axis=1)

    if "shared" in p:
        sp = p["shared"]
        out = out + (jax.nn.silu(flat @ sp["w_gate"]) * (flat @ sp["w_up"])) \
            @ sp["w_down"]

    # switch-style aux loss: E * sum_e fraction_tokens_e * mean_prob_e
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return out.reshape(b, t, d), aux.astype(x.dtype)


def moe_apply_ep(p: Params, x: jax.Array, top_k: int,
                 router_kind: str, capacity_factor: float,
                 router_iters: int, n_real: int, mesh, dp_axes: tuple,
                 tp_axis: str) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map (the production path).

    The pjit scatter formulation computes token ranks with a GLOBAL cumsum
    and all-reduces the whole (E, C, d) buffer across data shards (measured
    966 GB + 773 GB of per-layer ARs on qwen3-moe; EXPERIMENTS.md §Perf #4).
    Here instead, per (data x model) chip:

      - route + rank LOCALLY (tokens are data-sharded; activations are
        replicated over the model axis, so every model chip sees the same
        tokens and routes identically). NOTE: the Sinkhorn router therefore
        balances load PER DATA SHARD rather than globally — the scalable
        semantics (global balancing would need a cross-shard solve); top-k
        routing is bitwise identical to the single-device layer;
      - scatter into a LOCAL (E, C_loc, d) buffer (C_loc = capacity of the
        shard's own tokens — the paper's per-thread disjoint-nnz ownership);
      - each model chip slices ITS E/tp experts and runs their FFNs with
        its local expert weights;
      - combine with ONE psum of (n_loc, d) over the model axis — the same
        collective a dense TP layer pays. No global cumsum, no buffer AR.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    e = p["router"].shape[1]
    tp_size = dict(zip(mesh.axis_names, mesh.devices.shape))[tp_axis]
    e_loc = e // tp_size

    x_spec = P(dp_axes, None, None)
    w_specs = {
        "router": P(), "w_gate": P(tp_axis, None, None),
        "w_up": P(tp_axis, None, None), "w_down": P(tp_axis, None, None),
    }
    if "shared" in p:
        w_specs["shared"] = {"w_gate": P(None, tp_axis),
                             "w_up": P(None, tp_axis),
                             "w_down": P(tp_axis, None)}
    p_specs = {k: w_specs[k] for k in p}

    def body(p_loc, x_loc):
        bl, tl, _ = x_loc.shape
        n = bl * tl
        flat = x_loc.reshape(n, d)
        cap = int(capacity_factor * top_k * n / n_real + 1)
        logits = (flat @ p_loc["router"]).astype(jnp.float32)
        probs = route(logits, router_kind, n_iter=router_iters,
                      n_real=n_real)
        topw, topi = lax.top_k(probs, top_k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
        eid = topi.reshape(-1)
        oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - oh,
                                   eid[:, None], axis=1)[:, 0]
        keep = (rank < cap).astype(x_loc.dtype)
        rankc = jnp.minimum(rank, cap - 1)
        tok = jnp.arange(n).repeat(top_k)
        buf = jnp.zeros((e, cap, d), x_loc.dtype)
        buf = buf.at[eid, rankc].add(flat[tok] * keep[:, None])

        midx = lax.axis_index(tp_axis)
        my = lax.dynamic_slice_in_dim(buf, midx * e_loc, e_loc, axis=0)
        h = jnp.einsum("ecd,edf->ecf", my, p_loc["w_gate"])
        hu = jnp.einsum("ecd,edf->ecf", my, p_loc["w_up"])
        outb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * hu,
                          p_loc["w_down"])

        rel = eid - midx * e_loc
        mine = (rel >= 0) & (rel < e_loc)
        relc = jnp.where(mine, rel, 0)
        gathered = jnp.where(
            mine[:, None], outb[relc, rankc], 0.0) \
            * (keep * topw.reshape(-1).astype(x_loc.dtype))[:, None]
        out = gathered.reshape(n, top_k, d).sum(axis=1)

        if "shared" in p_loc:
            sp = p_loc["shared"]       # ff dim tp-sharded -> partial sums
            out = out + (jax.nn.silu(flat @ sp["w_gate"])
                         * (flat @ sp["w_up"])) @ sp["w_down"]
        out = lax.psum(out, tp_axis)   # ONE collective per MoE layer

        frac = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32),
                        axis=0)
        aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
        aux = lax.pmean(aux, dp_axes)    # identical across tp already
        return out.reshape(bl, tl, d), aux.astype(x_loc.dtype)

    out, aux = shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                         out_specs=(x_spec, P()))(p, x)
    return out, aux


def moe_dropped_fraction(p: Params, x: jax.Array, top_k: int,
                         router_kind: str, capacity_factor: float = 1.25,
                         router_iters: int = 6) -> jax.Array:
    """Fraction of (token, expert) assignments dropped at capacity — the
    router-quality metric the Sinkhorn router improves."""
    b, t, d = x.shape
    flat = x.reshape(b * t, d)
    n = b * t
    e = p["router"].shape[1]
    cap = int(capacity_factor * top_k * n / e + 1)
    logits = (flat @ p["router"]).astype(jnp.float32)
    probs = route(logits, router_kind, n_iter=router_iters)
    _, topi = lax.top_k(probs, top_k)
    eid = topi.reshape(-1)
    oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)
    rank = (jnp.cumsum(oh, axis=0) - oh)
    rank = jnp.take_along_axis(rank, eid[:, None], axis=1)[:, 0]
    return jnp.mean((rank >= cap).astype(jnp.float32))

"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Dispatch is the GShard/Switch capacity scheme expressed with sort +
scatter instead of the (tokens, experts, capacity) one-hot einsum, so
compiled FLOPs stay ~= useful expert FLOPs (the dispatch itself is
gather/scatter, not matmul).  Experts shard over the "model" mesh axis
(EP == TP axis); GSPMD inserts the token all-to-all at the dispatch and
combine reshards.

Semantics (tested against a dense per-token loop oracle):
  * router logits fp32, softmax over the top-k logits, renormalized;
  * capacity C = ceil(T * k / E * capacity_factor); tokens beyond an
    expert's capacity are dropped (contribute 0 for that expert slot);
  * load-balancing aux loss: E * sum_e f_e * p_e (Switch).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.distributed.sharding import constrain
from jax.sharding import PartitionSpec as P


def moe_ffn(
    cfg: ModelConfig, p: dict, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (B, T, D), aux_loss scalar.  Dispatches to the
    configured implementation ("gspmd" global dispatch vs "local"
    shard_map dispatch)."""
    from repro.distributed.sharding import get_current_mesh

    mesh = get_current_mesh()
    if (
        cfg.moe_impl == "local"
        and mesh is not None
        and "model" in mesh.axis_names
        and cfg.moe_experts % mesh.shape["model"] == 0
    ):
        return _moe_ffn_local(cfg, p, x, mesh)
    return _moe_ffn_gspmd(cfg, p, x)


def _moe_ffn_gspmd(
    cfg: ModelConfig, p: dict, x: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (B, T, D), aux_loss scalar."""
    b, t, d = x.shape
    dt = x.dtype
    e, k = cfg.moe_experts, cfg.moe_topk
    tokens = x.reshape(b * t, d)
    n_tok = b * t
    # capacity floor matters at decode (n_tok == batch): ceil(B*k/E*cf)
    # rounds to ~1 and hot experts would drop live traffic
    capacity = max(
        int(math.ceil(n_tok * k / e * cfg.moe_capacity)), min(n_tok, 16)
    )

    # --- routing (fp32) --------------------------------------------------
    logits = (tokens.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_p, top_e = jax.lax.top_k(probs, k)  # (T, k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch): fraction routed vs mean prob
    f_e = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (n_tok * k)
    aux = e * jnp.sum(f_e * probs.mean(0)) * cfg.moe_aux_coef

    # --- sort-based dispatch ---------------------------------------------
    flat_e = top_e.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n_tok * k, dtype=jnp.int32) - offsets[sorted_e]
    keep = rank < capacity
    dest = jnp.where(keep, sorted_e * capacity + rank, e * capacity)  # drop slot

    src_tok = order // k  # flat token index per sorted assignment
    gathered = tokens[src_tok]  # (T*k, D)
    buf = jnp.zeros((e * capacity + 1, d), dt).at[dest].set(gathered)
    xs = buf[: e * capacity].reshape(e, capacity, d)
    xs = constrain(xs, P("model", None, None))  # expert-parallel layout

    # --- expert computation (grouped matmul) ------------------------------
    gate = jnp.einsum("ecd,edf->ecf", xs, p["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xs, p["w_up"].astype(dt))
    hidden = jax.nn.silu(gate) * up
    out = jnp.einsum("ecf,efd->ecd", hidden, p["w_down"].astype(dt))
    out = constrain(out, P("model", None, None))

    # --- combine -----------------------------------------------------------
    out_flat = out.reshape(e * capacity, d)
    out_flat = jnp.concatenate([out_flat, jnp.zeros((1, d), dt)], axis=0)
    per_assign = out_flat[dest]  # (T*k, D), dropped -> 0 row
    unsorted = jnp.zeros((n_tok * k, d), dt).at[order].set(per_assign)
    combined = (
        unsorted.reshape(n_tok, k, d) * weights[..., None].astype(dt)
    ).sum(axis=1)
    return combined.reshape(b, t, d), aux


def _dispatch_local(cfg: ModelConfig, tokens: jax.Array, logits: jax.Array,
                    capacity: int):
    """Capacity dispatch of local tokens -> ((E, C, D) buffer, combine info).

    Pure local computation (no collectives): used per-shard inside the
    shard_map path and globally by the gspmd path's tests.
    """
    n_tok, d = tokens.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n_tok * k, dtype=jnp.int32) - offsets[sorted_e]
    keep = rank < capacity
    dest = jnp.where(keep, sorted_e * capacity + rank, e * capacity)
    gathered = tokens[order // k]
    buf = jnp.zeros((e * capacity + 1, d), tokens.dtype).at[dest].set(gathered)
    xs = buf[: e * capacity].reshape(e, capacity, d)
    aux_f = counts.astype(jnp.float32) / (n_tok * k)
    aux = e * jnp.sum(aux_f * probs.mean(0)) * cfg.moe_aux_coef
    return xs, (order, dest, weights), aux


def _combine_local(cfg: ModelConfig, out_ecd: jax.Array, info, n_tok: int):
    order, dest, weights = info
    e, c = out_ecd.shape[0], out_ecd.shape[1]
    d = out_ecd.shape[-1]
    k = cfg.moe_topk
    out_flat = jnp.concatenate(
        [out_ecd.reshape(e * c, d), jnp.zeros((1, d), out_ecd.dtype)], axis=0
    )
    per_assign = out_flat[dest]
    unsorted = jnp.zeros((n_tok * k, d), out_ecd.dtype).at[order].set(per_assign)
    return (unsorted.reshape(n_tok, k, d) * weights[..., None].astype(out_ecd.dtype)).sum(1)


def _moe_ffn_local(
    cfg: ModelConfig, p: dict, x: jax.Array, mesh
) -> tuple[jax.Array, jax.Array]:
    """shard_map MoE: local dispatch + all-to-all over the model axis.

    Tokens stay in their (pod, data) shard end-to-end; the only
    cross-device traffic is two all-to-alls of the (E, C_local, D)
    dispatch buffer along "model" (experts' owner axis).  This replaces
    the GSPMD global argsort/scatter, which was measured to all-reduce
    the full dispatch buffer across the data axis (EXPERIMENTS.md
    section Perf, moonshot train_4k iteration 1).
    """
    import math as _math

    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    e, k = cfg.moe_experts, cfg.moe_topk
    m_size = mesh.shape["model"]
    el = e // m_size
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_shards = _math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    if batch_axes and b % n_shards:
        return _moe_ffn_gspmd(cfg, p, x)  # non-divisible batch: fall back
    tl = (b // n_shards) * t
    cap = max(int(_math.ceil(tl * k / e * cfg.moe_capacity)), min(tl, 16))

    def local(xs, router, w_gate, w_up, w_down):
        # xs: (Bl, T, D) local tokens; experts local: (El, D, F)
        bl = xs.shape[0]
        tokens = xs.reshape(bl * t, d)
        logits = tokens.astype(jnp.float32) @ router.astype(jnp.float32)
        buf, info, aux = _dispatch_local(cfg, tokens, logits, cap)
        # (E, C, D) -> (M, El, C, D) -> exchange over "model"
        send = buf.reshape(m_size, el, cap, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (M, El, C, D) — rows from every peer for MY experts
        xs_e = recv.transpose(1, 0, 2, 3).reshape(el, m_size * cap, d)
        dt = xs_e.dtype
        gate = jnp.einsum("ecd,edf->ecf", xs_e, w_gate.astype(dt))
        up = jnp.einsum("ecd,edf->ecf", xs_e, w_up.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, w_down.astype(dt))
        # send results back: (El, M, C, D) -> (M, El, C, D) -> all_to_all
        back = out.reshape(el, m_size, cap, d).transpose(1, 0, 2, 3)
        got = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                                 tiled=False)
        out_buf = got.reshape(e, cap, d)
        y = _combine_local(cfg, out_buf, info, bl * t).reshape(bl, t, d)
        aux = jax.lax.pmean(aux, ("model",) + batch_axes if batch_axes else ("model",))
        return y, aux

    bspec = (batch_axes if len(batch_axes) > 1 else batch_axes[0]) if batch_axes else None
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_ffn_dense_oracle(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Reference: loop over experts densely, no capacity drops.

    Used by tests (with capacity_factor large enough that the fast path
    drops nothing, the two must agree).
    """
    b, t, d = x.shape
    tokens = x.reshape(b * t, d).astype(jnp.float32)
    logits = tokens @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_topk)
    weights = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    out = jnp.zeros_like(tokens)
    for ei in range(cfg.moe_experts):
        gate = tokens @ p["w_gate"][ei].astype(jnp.float32)
        up = tokens @ p["w_up"][ei].astype(jnp.float32)
        y = (jax.nn.silu(gate) * up) @ p["w_down"][ei].astype(jnp.float32)
        w_e = jnp.where(top_e == ei, weights, 0.0).sum(-1)  # (T,)
        out += y * w_e[:, None]
    return out.reshape(b, t, d).astype(x.dtype)

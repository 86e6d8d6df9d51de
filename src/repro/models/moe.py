"""Placement-aware Mixture-of-Experts layer (the paper's technique as a
first-class JAX feature).

Two implementations with identical math:

* ``dense`` — reference oracle: every expert computed for every token,
  combined with the routing weights. Used for correctness tests and tiny
  training runs.
* ``ep`` — expert-parallel SPMD. Every EP rank (the TPU analogue of the
  paper's *edge server*) holds ``S`` expert **slots**; a static
  ``slot_to_expert`` table (produced by the DanceMoE placement algorithms,
  including replication of hot experts) defines what lives where, and
  ``expert_to_target`` routes each source rank's tokens to its *nearest
  replica* by mesh distance. Tokens whose chosen expert is resident at their
  source rank never cross the interconnect — the paper's "local compute
  ratio" becomes the fraction of a2a traffic that stays on-chip.

  Two dispatch modes:
  - ``dispatch`` (train/prefill): capacity-bounded ``all_to_all`` exchange,
    tokens row-sharded over the EP axes.
  - ``gather`` (decode): token counts are tiny (batch <= 128), so tokens are
    all-gathered, each rank computes the (token, expert) pairs assigned to
    it, and a psum combines — far cheaper than a ragged a2a at that scale.

Mesh convention: the tensor/expert-parallel axis is named ``model``; all
other axes (``pod``, ``data``) shard the batch.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, rms_norm

@dataclasses.dataclass(frozen=True)
class EPSpec:
    """Static expert-parallel geometry."""
    axes: tuple[str, ...]        # mesh axes forming the EP dimension
    mesh_axes: tuple[str, ...]   # all mesh axis names, in order
    n_ep: int                    # number of EP ranks (product of axes sizes)
    slots: int                   # S: expert slots per rank
    # Both capacities are upper bounds: each call clamps them to what its
    # own rows can fill (``_row_bound``), so a step never computes buffer
    # rows that no input reaches; a value below that bound drops as set.
    capacity: int                # C: per (src->dst) a2a send capacity
    slot_capacity: int           # C2: per-slot compute capacity (recv side)

    @property
    def batch_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.mesh_axes if a != "model")

    @property
    def dispatch_row_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.mesh_axes if a not in self.axes) + self.axes

    @staticmethod
    def build(mesh, cfg, *, ep_axes=("model",), capacity_factor: float = 2.0,
              rows_per_rank: int = 4096, slots: int | None = None,
              capacity: int | None = None, slot_capacity: int | None = None):
        n_ep = int(np.prod([mesh.shape[a] for a in ep_axes]))
        S = slots if slots is not None else (
            -(-cfg.num_experts // n_ep) + (1 if n_ep > 1 else 0))
        C = capacity if capacity is not None else max(
            8, int(np.ceil(rows_per_rank * cfg.top_k / n_ep
                           * capacity_factor)))
        C2 = slot_capacity if slot_capacity is not None else max(
            8, int(np.ceil(n_ep * C / S)))
        return EPSpec(tuple(ep_axes), tuple(mesh.axis_names), n_ep, S, C, C2)


class EPPlacement(NamedTuple):
    """Device arrays derived from a placement plan. They are jit *arguments*
    (not compile-time constants), so a migration — adopting a new plan —
    does NOT trigger recompilation."""
    slot_to_expert: jax.Array    # [n_ep, S] int32 (-1 = empty slot)
    expert_to_slot: jax.Array    # [n_ep, E] int32 (-1 = not resident)
    expert_to_target: jax.Array  # [n_ep, E] int32 (src rank -> replica rank)


def uniform_placement(n_ep: int, S: int, E: int) -> EPPlacement:
    """Megatron-style uniform EP layout (the paper's `Uniform` baseline):
    expert e lives on rank e % n_ep; no replication."""
    s2e = -np.ones((n_ep, S), np.int32)
    for e in range(E):
        r, s = e % n_ep, e // n_ep
        if s < S:
            s2e[r, s] = e
    return placement_from_tables(s2e, num_experts=E)


def placement_from_tables(s2e: np.ndarray, mesh_distance=None,
                          num_experts: int | None = None) -> EPPlacement:
    """Build runtime tables from a slot_to_expert matrix [n_ep, S]
    (output of the placement algorithms; -1 = empty slot).

    ``expert_to_target`` picks, per source rank, the nearest replica by
    ``mesh_distance[src, dst]`` (default: ring distance over EP ranks — the
    ICI-hop analogue of the paper's cross-server latency matrix).
    """
    n_ep, S = s2e.shape
    E = num_experts if num_experts is not None else int(s2e.max()) + 1
    e2s = -np.ones((n_ep, E), np.int32)
    for r in range(n_ep):
        for s in range(S):
            e = int(s2e[r, s])
            if e >= 0:
                e2s[r, e] = s
    if mesh_distance is None:
        idx = np.arange(n_ep)
        mesh_distance = np.minimum(np.abs(idx[:, None] - idx[None, :]),
                                   n_ep - np.abs(idx[:, None] - idx[None, :]))
    e2t = np.zeros((n_ep, E), np.int32)
    for e in range(E):
        holders = np.where(e2s[:, e] >= 0)[0]
        if len(holders) == 0:
            raise ValueError(f"expert {e} unplaced (coverage violated)")
        d = mesh_distance[:, holders]                  # [n_ep, n_holders]
        e2t[:, e] = holders[np.argmin(d, axis=1)]
    return EPPlacement(jnp.asarray(s2e.astype(np.int32)),
                       jnp.asarray(e2s), jnp.asarray(e2t))


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def moe_params_dense(key, cfg, dtype=jnp.float32) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((d,), dtype),
        "router": dense_init(ks[0], (d, E), 0, dtype),
        "w1": dense_init(ks[1], (E, d, f), 1, dtype),
        "w3": dense_init(ks[2], (E, d, f), 1, dtype),
        "w2": dense_init(ks[3], (E, f, d), 1, dtype),
    }


def moe_params_ep(key, cfg, spec: EPSpec, dtype=jnp.float32) -> dict:
    """EP-layout params: expert weights stored per (rank, slot)."""
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "norm": jnp.ones((d,), dtype),
        "router": dense_init(ks[0], (d, cfg.num_experts), 0, dtype),
        "w1": dense_init(ks[1], (spec.n_ep, spec.slots, d, f), 2, dtype),
        "w3": dense_init(ks[2], (spec.n_ep, spec.slots, d, f), 2, dtype),
        "w2": dense_init(ks[3], (spec.n_ep, spec.slots, f, d), 2, dtype),
    }


def dense_to_ep(dense_p: dict, placement: EPPlacement) -> dict:
    """Materialise EP-layout weights from dense weights + a placement
    (also the migration primitive: a new placement is just a new gather)."""
    s2e = jnp.maximum(placement.slot_to_expert, 0)
    out = {k: dense_p[k] for k in ("norm", "router")}
    for k in ("w1", "w3", "w2"):
        out[k] = dense_p[k][s2e]        # [n_ep, S, ...]
    return out


def _gather_slots(w, s2e):
    """w: stacked expert weights [G, E, ...]; s2e: stacked slot tables
    [G, n_ep, S] -> [G, n_ep, S, ...] (empty slots hold expert 0)."""
    g = jnp.arange(w.shape[0])[:, None, None]
    return w[g, jnp.maximum(s2e, 0)]


def gather_expert_slots(w, slot_to_expert, sharding=None):
    """One stacked expert weight of the dense master ([G, E, ...]) gathered
    into EP slot layout ([G, n_ep, S, ...]) by the stacked placement table,
    in one program for all layers. ``sharding``: where the result lives
    (e.g. slots sharded over the EP axis); None leaves it to the inputs."""
    return jax.jit(_gather_slots, out_shardings=sharding)(w, slot_to_expert)


def regather_ep_groups(dense_groups: dict, placement_stacked,
                       n_groups: int, sharding=None) -> dict:
    """``dense_to_ep`` for every layer group at once: dense master group
    params (stacked [G, E, ...]) + stacked placement tables -> EP-layout
    groups. Non-MoE groups pass through unchanged; ``sharding`` places the
    gathered expert slots (see ``gather_expert_slots``)."""
    s2e = placement_stacked.slot_to_expert
    assert s2e.shape[0] == n_groups, (s2e.shape, n_groups)
    out = {}
    for k, v in dense_groups.items():
        if "router" in v:
            out[k] = {**v, **{w: gather_expert_slots(v[w], s2e, sharding)
                              for w in ("w1", "w3", "w2")}}
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# Routing (shared by both impls — guarantees identical math)
# ---------------------------------------------------------------------------

def route(router_w, h2d, top_k):
    logits = (h2d @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return probs, topv, topi


def aux_load_balance_loss(probs, topi, E):
    """Switch-transformer load-balance loss."""
    onehot = jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(-2)  # [T, E]
    frac = onehot.mean(0)
    mean_prob = probs.mean(0)
    return E * jnp.sum(frac * mean_prob)


def grouped_ffn(x, w1, w3, w2, use_kernel: bool = False):
    """Batched expert FFN: x [S, C, D] x weights [S, D, F] -> [S, C, D]."""
    if use_kernel:
        from repro.kernels.ops import moe_gmm
        return moe_gmm(x, w1, w3, w2)
    a = jnp.einsum("scd,sdf->scf", x, w1)
    b = jnp.einsum("scd,sdf->scf", x, w3)
    hmid = (jax.nn.silu(a) * b).astype(x.dtype)
    return jnp.einsum("scf,sfd->scd", hmid, w2)


# ---------------------------------------------------------------------------
# Dense reference
# ---------------------------------------------------------------------------

def moe_apply_dense(p, cfg, x, *, norm_eps: float = 1e-5):
    """x: [B, T, D]. Returns (out, stats)."""
    B, T, D = x.shape
    h = rms_norm(x, p["norm"], norm_eps).reshape(B * T, D)
    probs, topv, topi = route(p["router"], h, cfg.top_k)
    a = jnp.einsum("td,edf->tef", h, p["w1"])
    b = jnp.einsum("td,edf->tef", h, p["w3"])
    y_all = jnp.einsum("tef,efd->ted", jax.nn.silu(a) * b, p["w2"])
    sel = jnp.take_along_axis(y_all, topi[..., None], axis=1)   # [T, K, D]
    y = jnp.einsum("tkd,tk->td", sel, topv.astype(y_all.dtype))
    counts = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32).sum((0, 1))
    stats = {"counts": counts,
             "counts_per_rank": counts[None],
             "aux_loss": aux_load_balance_loss(probs, topi, cfg.num_experts),
             "local_frac": jnp.float32(1.0)}
    return x + y.reshape(B, T, D).astype(x.dtype), stats


# ---------------------------------------------------------------------------
# Expert-parallel implementation
# ---------------------------------------------------------------------------

def _bucket(keys, n_buckets, capacity):
    """Sort-based capacity bucketing. keys: [N] int in [0, n_buckets]
    (== n_buckets means invalid). Returns (order, pos-in-bucket, keep),
    all aligned with sorted order."""
    order = jnp.argsort(keys, stable=True)
    sk = keys[order]
    starts = jnp.searchsorted(sk, jnp.arange(n_buckets))
    pos = jnp.arange(keys.shape[0]) - starts[jnp.clip(sk, 0, n_buckets - 1)]
    keep = (sk < n_buckets) & (pos < capacity)
    return order, pos, keep


def _dropped(keys, n_buckets, keep):
    """Entries with a bucket (``keys < n_buckets``) that ``_bucket`` left
    out for want of capacity."""
    return (jnp.sum(keys < n_buckets, dtype=jnp.int32)
            - jnp.sum(keep, dtype=jnp.int32))


def _row_bound(cap: int, pairs: int) -> int:
    """A capacity clamped to the most (row, expert) pairs a call can put in
    one bucket: ``pairs`` rounded up to the sublane tile (8), never past
    ``cap``."""
    return min(cap, -(-pairs // 8) * 8)


def _ep_counters(dropped, filled, S, C2):
    """This rank's pairs dropped, slot rows filled with a live pair and
    slot rows computed (S x C2), as one vector: one psum carries all
    three."""
    return jnp.stack([dropped, filled.astype(jnp.int32),
                      jnp.int32(S * C2)])


def _local_slots(p):
    """Per-device slice of the EP weights inside shard_map ([1,S,...]→[S,...])."""
    return {k: p[k][0] for k in ("w1", "w3", "w2")}


def _ep_dispatch_local(h_loc, p, placement, cfg, spec: EPSpec,
                       use_kernel: bool, m_loc=None, o_loc=None):
    """Per-device body (inside shard_map) — a2a dispatch mode.
    h_loc: [R, D] this rank's rows. m_loc: optional [R] float validity —
    0-rows (chunked-prefill padding) are excluded from the gating counts.
    o_loc: optional [R] int32 — the EP rank each row's *request* originated
    at. Gating counts are attributed to it; without it they fall back to
    the physical row-sharding rank (which mis-credits mixed-origin batches
    — the serving runtime always passes the true origin)."""
    R, D = h_loc.shape
    E, K = cfg.num_experts, cfg.top_k
    n_ep, S = spec.n_ep, spec.slots
    # a source row sends at most K pairs to one rank, and a slot (one
    # expert) takes at most one pair of each row that reaches it
    C = _row_bound(spec.capacity, R * K)
    C2 = _row_bound(spec.slot_capacity, n_ep * min(C, R))
    my = lax.axis_index(spec.axes)
    with jax.named_scope("moe.route"):
        probs, topv, topi = route(p["router"], h_loc, K)

    with jax.named_scope("moe.dispatch"):
        flat_e = topi.reshape(R * K)
        flat_w = topv.reshape(R * K)
        flat_src = jnp.repeat(jnp.arange(R), K)
        tgt = placement.expert_to_target[my, flat_e]              # [RK]
        order, pos, keep = _bucket(tgt, n_ep, C)
        dest = jnp.where(keep, tgt[order] * C + pos, n_ep * C)    # OOB = drop
        buf_x = jnp.zeros((n_ep * C, D), h_loc.dtype).at[dest].set(
            h_loc[flat_src[order]], mode="drop")
        # a pair of a masked-out row travels as expert + E, so the
        # receiver can tell the live pairs it computes
        code = flat_e.astype(jnp.int32)
        if m_loc is not None:
            code = jnp.where(m_loc[flat_src] > 0, code, code + E)
        buf_e = jnp.full((n_ep * C,), -1, jnp.int32).at[dest].set(
            code[order], mode="drop")

        recv_x = lax.all_to_all(buf_x.reshape(n_ep, C, D), spec.axes, 0, 0,
                                tiled=False)
        recv_e = lax.all_to_all(buf_e.reshape(n_ep, C), spec.axes, 0, 0,
                                tiled=False)

        # --- receiver: slot bucketing into the slot buffer ---
        xs = recv_x.reshape(n_ep * C, D)
        es = recv_e.reshape(n_ep * C)
        live = es < E
        es = jnp.where(live, es, es - E)
        slot = jnp.where(es >= 0,
                         placement.expert_to_slot[my, jnp.maximum(es, 0)], -1)
        slot_key = jnp.where(slot >= 0, slot, S).astype(jnp.int32)
        order2, pos2, keep2 = _bucket(slot_key, S, C2)
        dest2 = jnp.where(keep2, slot_key[order2] * C2 + pos2, S * C2)
        sbuf = jnp.zeros((S * C2, D), h_loc.dtype).at[dest2].set(
            xs[order2], mode="drop")
    w = _local_slots(p)
    with jax.named_scope("moe.expert_ffn"):
        y = grouped_ffn(sbuf.reshape(S, C2, D), w["w1"], w["w3"], w["w2"],
                        use_kernel).reshape(S * C2, D)
    with jax.named_scope("moe.combine"):
        # scatter expert outputs back into recv-buffer order
        got = jnp.where(keep2[:, None], y[jnp.clip(dest2, 0, S * C2 - 1)],
                        0).astype(h_loc.dtype)
        out_tok = jnp.zeros((n_ep * C, D), h_loc.dtype).at[order2].set(got)

        back = lax.all_to_all(out_tok.reshape(n_ep, C, D), spec.axes, 0, 0,
                              tiled=False).reshape(n_ep * C, D)
        contrib = jnp.where(keep[:, None],
                            back[jnp.clip(dest, 0, n_ep * C - 1)], 0)
        contrib = contrib * flat_w[order][:, None].astype(h_loc.dtype)
        out = jnp.zeros((R, D), h_loc.dtype).at[flat_src[order]].add(contrib)

    # --- stats: f_n(e) per *originating* server. Every row scatter-adds
    # its expert choices into its origin's row of an [n_ep, E] matrix; the
    # full-mesh psum (rows are sharded over every axis) then yields the
    # replicated global attribution — identical totals to the old
    # stacked-per-physical-rank output, but credited correctly under
    # mixed-origin batches. Scalars are pmean'd over the whole mesh. ---
    hot = jax.nn.one_hot(topi, E, dtype=jnp.float32)
    if m_loc is not None:
        hot = hot * m_loc[:, None, None]
    org = o_loc if o_loc is not None else jnp.full((R,), my, jnp.int32)
    counts = jnp.zeros((n_ep, E), jnp.float32).at[org].add(
        hot.sum(1), mode="drop")
    counts = lax.psum(counts, spec.mesh_axes)
    local = lax.pmean(jnp.mean((tgt == my).astype(jnp.float32)),
                      spec.mesh_axes)
    aux = lax.pmean(aux_load_balance_loss(probs, topi, E), spec.mesh_axes)
    # (row, expert) pairs dropped at either capacity: C on the send side,
    # C2 on the receive side (padding rows included)
    ep = lax.psum(_ep_counters(
        _dropped(tgt, n_ep, keep) + _dropped(slot_key, S, keep2),
        jnp.sum(keep2 & live[order2]), S, C2), spec.mesh_axes)
    return out, counts, local, aux, ep


def _ep_gather_local(h_loc, m_loc, p, placement, cfg, spec: EPSpec,
                     use_kernel: bool, gather_axes: tuple[str, ...],
                     o_loc=None):
    """Per-device body — decode gather mode. h_loc: [R, D] rows sharded over
    the batch axes only (replicated over `model`). m_loc: [R] float row
    validity mask — vacant slots in a continuous-batching pool carry 0 and
    are excluded from the activation statistics (their compute is discarded
    by the caller anyway). o_loc: optional [R] int32 originating EP rank
    per row — stats and the local ratio are attributed to it; without it
    requests "arrive at" the first EP rank of their batch shard."""
    R, D = h_loc.shape
    E, K = cfg.num_experts, cfg.top_k
    n_ep, S = spec.n_ep, spec.slots
    my = lax.axis_index(spec.axes)
    with jax.named_scope("moe.dispatch"):
        h_all = (lax.all_gather(h_loc, gather_axes, tiled=True)
                 if gather_axes else h_loc)                    # [Btok, D]
        m_all = (lax.all_gather(m_loc, gather_axes, tiled=True)
                 if gather_axes else m_loc)                    # [Btok]
    Btok = h_all.shape[0]
    # a row's top-k experts are distinct and each resolves to one slot of
    # one rank, so a slot takes at most one pair of each row
    C2 = _row_bound(spec.slot_capacity, Btok)
    with jax.named_scope("moe.route"):
        probs, topv, topi = route(p["router"], h_all, K)
    with jax.named_scope("moe.dispatch"):
        if o_loc is not None:
            # explicit origin: the edge server each request arrived at
            src_ep = (lax.all_gather(o_loc, gather_axes, tiled=True)
                      if gather_axes else o_loc)               # [Btok]
        else:
            # positional fallback: requests "arrive at" the first EP rank
            # of their batch shard (the paper's server identity)
            n_gather = max(Btok // R, 1)
            span = max(n_ep // n_gather, 1)
            src_ep = (jnp.arange(Btok) // R) * span            # [Btok]
        flat_e = topi.reshape(-1)
        flat_src = jnp.repeat(jnp.arange(Btok), K)
        tgt = placement.expert_to_target[src_ep[flat_src], flat_e]
        mine = tgt == my
        slot = jnp.where(mine, placement.expert_to_slot[my, flat_e], -1)
        slot_key = jnp.where(slot >= 0, slot, S).astype(jnp.int32)
        order2, pos2, keep2 = _bucket(slot_key, S, C2)
        dest2 = jnp.where(keep2, slot_key[order2] * C2 + pos2, S * C2)
        sbuf = jnp.zeros((S * C2, D), h_loc.dtype).at[dest2].set(
            h_all[flat_src[order2]], mode="drop")
    w = _local_slots(p)
    with jax.named_scope("moe.expert_ffn"):
        y = grouped_ffn(sbuf.reshape(S, C2, D), w["w1"], w["w3"], w["w2"],
                        use_kernel).reshape(S * C2, D)
    with jax.named_scope("moe.combine"):
        yw = jnp.where(keep2[:, None], y[jnp.clip(dest2, 0, S * C2 - 1)],
                       0).astype(h_loc.dtype)
        yw = yw * topv.reshape(-1)[order2][:, None].astype(h_loc.dtype)
        out_all = jnp.zeros((Btok, D), h_loc.dtype).at[
            flat_src[order2]].add(yw)
        out_all = lax.psum(out_all, spec.axes)
        if gather_axes:
            g_idx = lax.axis_index(gather_axes)
            out = lax.dynamic_slice_in_dim(out_all, g_idx * R, R, 0)
        else:
            out = out_all

    # stats: every EP rank sees the same gathered tokens, so the per-origin
    # [n_ep, E] matrix is computed identically everywhere (replicated over
    # the EP axes); only batch axes outside the gather still shard tokens
    # and need a psum
    valid = m_all[flat_src].astype(jnp.float32)
    counts = jnp.zeros((n_ep, E), jnp.float32).at[src_ep[flat_src]].add(
        jax.nn.one_hot(flat_e, E, dtype=jnp.float32) * valid[:, None],
        mode="drop")
    non_ep = tuple(a for a in spec.mesh_axes
                   if a not in spec.axes and a not in gather_axes)
    # each EP rank buckets only the pairs bound for it: their C2 drops
    # add up over the EP axes (padding rows included)
    ep = lax.psum(_ep_counters(_dropped(slot_key, S, keep2),
                               jnp.sum(keep2 & (valid[order2] > 0)), S, C2),
                  spec.axes)
    if non_ep:
        counts = lax.psum(counts, non_ep)
        ep = lax.psum(ep, non_ep)
    local = lax.pmean(
        jnp.sum((tgt == src_ep[flat_src]).astype(jnp.float32) * valid)
        / jnp.maximum(jnp.sum(valid), 1.0), spec.mesh_axes)
    aux = lax.pmean(aux_load_balance_loss(probs, topi, E), spec.mesh_axes)
    return out, counts, local, aux, ep


def _ep_stats(counts, local, aux, ep):
    """The layer's stats dict. ``dropped``: (row, expert) pairs left out
    for want of capacity; ``rows_filled``: kept pairs of live rows;
    ``rows_computed``: the slot-buffer rows the expert FFN ran (S x C2,
    summed over ranks)."""
    return {"counts": counts.sum(0), "counts_per_rank": counts,
            "aux_loss": aux, "local_frac": local, "dropped": ep[0],
            "rows_filled": ep[1], "rows_computed": ep[2]}


def moe_apply_ep(p, cfg, x, *, mesh, spec: EPSpec, placement: EPPlacement,
                 mode: str, use_kernel: bool = False,
                 norm_eps: float = 1e-5, seq_sharded_out: bool = False,
                 token_mask=None, origin=None):
    """Placement-aware EP MoE. x: [B, T, D]. Returns (out, stats).

    token_mask: [B] float validity per batch row (decode: vacant
    continuous-batching slots) or [B, T] per token (chunked prefill:
    prompt padding); 0-entries are excluded from the gating statistics.
    origin: [B] or [B, T] int32 — the EP rank each token's *request*
    originated at. ``counts_per_rank[r]`` then holds the gating counts of
    traffic that arrived at server ``r`` regardless of how the rows were
    sharded for compute; without it attribution falls back to the physical
    rank (row-sharding rank in dispatch mode, batch-shard position in
    decode mode), which mis-credits mixed-origin batches."""
    B, T, D = x.shape
    h = rms_norm(x, p["norm"], norm_eps)
    wspec = {
        "router": P(),
        "w1": P(spec.axes, None, None, None),
        "w3": P(spec.axes, None, None, None),
        "w2": P(spec.axes, None, None, None),
    }
    pl_spec = EPPlacement(P(), P(), P())      # tiny tables: replicate
    p_in = {k: p[k] for k in wspec}
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_batch = int(np.prod([sizes[a] for a in spec.batch_axes])) \
        if spec.batch_axes else 1
    rows_shardable = (B * T) % max(n_batch, 1) == 0 and B * T >= n_batch
    batch_row_axes = spec.batch_axes if rows_shardable else ()
    use_origin = origin is not None

    if mode == "decode":
        rows_spec = P(batch_row_axes if batch_row_axes else None, None)
        gather_axes = tuple(a for a in spec.axes if a in batch_row_axes)

        def body(h_loc, m_loc, o_loc, p_loc, pl_loc):
            return _ep_gather_local(h_loc, m_loc, p_loc, pl_loc, cfg, spec,
                                    use_kernel, gather_axes,
                                    o_loc=o_loc if use_origin else None)
    elif seq_sharded_out and T % sizes.get("model", 1) == 0:
        # sequence-parallel residual: h is [B(batch axes), T(model), D].
        # NOTE: flattening two sharded dims globally is NOT a free reshape
        # (block tiling vs b-major order mismatch — measured as a hidden
        # all-gather per MoE layer). Keep the 3-D sharding into shard_map and
        # reshape LOCALLY per device: genuinely free, and the EP rank index
        # (data-major, model-minor) matches the token ownership exactly.
        rows_spec3 = P(batch_row_axes or None, "model", None)

        def body3(h3, p_loc, pl_loc):
            b_, t_, d_ = h3.shape
            o, c, l, a, ep = _ep_dispatch_local(
                h3.reshape(b_ * t_, d_), p_loc, pl_loc, cfg, spec,
                use_kernel)
            return o.reshape(b_, t_, d_), c, l, a, ep

        fn = jax.shard_map(body3, mesh=mesh,
                           in_specs=(rows_spec3, wspec, pl_spec),
                           out_specs=(rows_spec3, P(), P(), P(), P()),
                           check_vma=False)
        out, counts, local, aux, ep = fn(h, p_in, placement)
        return x + out.astype(x.dtype), _ep_stats(counts, local, aux, ep)
    else:
        rows_spec = P(spec.dispatch_row_axes, None)

        def body(h_loc, m_loc, o_loc, p_loc, pl_loc):
            # mask excludes chunked-prefill padding from the gating counts
            return _ep_dispatch_local(h_loc, p_loc, pl_loc, cfg, spec,
                                      use_kernel, m_loc=m_loc,
                                      o_loc=o_loc if use_origin else None)

    # counts leave both bodies as a replicated [n_ep, E] per-origin matrix
    out_specs = (rows_spec, P(), P(), P(), P())
    mask_spec = P(rows_spec[0])
    # the row axis must divide evenly over its mesh axes; short batches
    # (e.g. a chunked-prefill geometry whose max_slots * block_size is not
    # a device-count multiple) are padded with masked zero rows instead of
    # pushing a divisibility constraint onto every serving caller. Padding
    # rows route like chunk-padding rows always have (they consume a2a
    # capacity but are masked out of the gating statistics).
    row_axes = rows_spec[0]
    if row_axes:
        axes = row_axes if isinstance(row_axes, tuple) else (row_axes,)
        n_shards = int(np.prod([sizes[a] for a in axes]))
    else:
        n_shards = 1
    pad = (-(B * T)) % n_shards
    rows = h.reshape(B * T, D)
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, D), rows.dtype)])
    rows = lax.with_sharding_constraint(rows, NamedSharding(mesh, rows_spec))

    def to_rows(v, dtype, pad_value=0):
        vv = v.astype(dtype)
        vv = (vv if vv.ndim == 2 else
              jnp.broadcast_to(vv[:, None], (B, T)))
        vv = vv.reshape(B * T)
        if pad:
            vv = jnp.concatenate(
                [vv, jnp.full((pad,), pad_value, dtype)])
        return lax.with_sharding_constraint(
            vv, NamedSharding(mesh, mask_spec))

    mask_rows = to_rows(token_mask if token_mask is not None
                        else jnp.ones((B, T)), jnp.float32)
    origin_rows = to_rows(origin if use_origin
                          else jnp.zeros((B, T), jnp.int32), jnp.int32)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(rows_spec, mask_spec, mask_spec, wspec,
                                 pl_spec),
                       out_specs=out_specs, check_vma=False)
    out_rows, counts, local, aux, ep = fn(rows, mask_rows, origin_rows,
                                          p_in, placement)
    out = out_rows[:B * T].reshape(B, T, D)
    if batch_row_axes and B % n_batch == 0:
        out = lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(batch_row_axes, None, None)))
    return x + out.astype(x.dtype), _ep_stats(counts, local, aux, ep)

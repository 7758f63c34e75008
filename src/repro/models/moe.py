"""Mixture-of-Experts block with expert-parallel all-to-all dispatch.

TPU-native design (DESIGN.md §3): inside shard_map, each device holds
E/ep_size experts and a token shard. Routing is capacity-based (tokens
over capacity are dropped — their residual passes through, the standard
TPU MoE formulation), dispatch uses sorted scatter into fixed-size
per-destination buffers, and the exchange is a single
jax.lax.all_to_all each way. Local expert compute is a capacity-
bucketed batched matmul (e_local, ECAP, D) @ (e_local, D, F) that keeps
the MXU dims dense — no (tokens, experts, capacity) one-hot einsum,
whose dispatch tensor would be TBs at the assigned shapes.

The same code runs without a mesh (ep_axis=None -> ep_size=1, the
all_to_all degenerates to identity) for CPU smoke tests.

Serving on one device takes the dropless layer instead
(:func:`moe_held`): no capacity, so a request's output never depends on
its batch-mates, over the experts the chip holds (all of them, or one
expert-parallel rank's share).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, init_mlp, mlp


def init_moe(key, cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    mc = cfg.moe
    d = cfg.d_model
    f = mc.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    std = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, mc.num_experts, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (mc.num_experts, d, f)) * std
                   ).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (mc.num_experts, d, f)) * std
                 ).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (mc.num_experts, f, d))
                   * std * 0.5).astype(dtype),
    }
    if mc.num_shared_experts:
        import dataclasses
        shared_cfg = dataclasses.replace(
            cfg, d_ff=f * mc.num_shared_experts, activation="silu")
        p["shared"] = init_mlp(ks[4], shared_cfg, dtype=dtype)
    return p


def moe_block(p: Dict, cfg: ModelConfig, x, ep_axis: Optional[str] = None
              ) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, S, D) *local* shard (inside shard_map) or global (no mesh).

    Returns (y, aux) with aux = {"lb_loss": load-balance loss,
    "router_fraction": per-expert dispatch fraction}."""
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    y, aux = _moe_tokens(p, cfg, tokens, ep_axis)
    if "shared" in p:
        y = y + mlp(p["shared"], cfg, tokens)
    return y.reshape(b, s, d), aux


def moe_block_sharded(p: Dict, cfg: ModelConfig, x, parallel,
                      mode: str = "a2a") -> Tuple[jnp.ndarray, Dict]:
    """shard_map wrapper for pjit contexts (dry-run / real meshes).

    mode="a2a"  (train/prefill): tokens are split over the model axis
                (sequence sharding) and dispatched to expert shards with
                all_to_all — the bandwidth-optimal exchange for T >> B.
    mode="psum" (decode): tokens stay data-sharded/replicated over the
                model axis; each shard computes its local experts and
                the outputs are psum-combined (no dispatch for tiny T).
    The shared experts (DeepSeek/Llama-4) are replicated over the model
    axis and computed on local tokens either way.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mx = parallel.model_axis
    p_specs = {"router": P(None, None),
               "w_gate": P(mx, None, None),
               "w_up": P(mx, None, None),
               "w_down": P(mx, None, None)}
    if "shared" in p:
        p_specs["shared"] = jax.tree.map(lambda _: P(), p["shared"])
    # divisibility-guarded token specs (shard_map demands exact splits):
    # drop the batch axes if B doesn't divide; fall back from a2a
    # (sequence split over the model axis) to psum if S doesn't divide.
    b_sz, s_sz = x.shape[0], x.shape[1]
    dpn = 1
    for a in parallel.data_axes:
        dpn *= parallel.mesh.shape[a]
    dp_axes = parallel.data_axes if b_sz % dpn == 0 else None
    if mode == "a2a" and s_sz % parallel.mesh.shape[mx] != 0:
        mode = "psum"
    x_spec = P(dp_axes, mx, None) if mode == "a2a" \
        else P(dp_axes, None, None)
    all_axes = parallel.all_axes

    def fn(pl, xl):
        b, s, d = xl.shape
        toks = xl.reshape(-1, d)
        if mode == "a2a":
            y, aux = _moe_tokens(pl, cfg, toks, mx)
        else:
            y3, aux = moe_block_psum(pl, cfg, xl, mx)
            y = y3.reshape(-1, d)
        if "shared" in pl:
            y = y + mlp(pl["shared"], cfg, toks)
        lb = aux["lb_loss"]
        for ax in all_axes:
            lb = jax.lax.pmean(lb, ax)
        return y.reshape(b, s, d), lb

    y, lb = shard_map(fn, mesh=parallel.mesh, in_specs=(p_specs, x_spec),
                      out_specs=(x_spec, P()))(p, x)
    return y, {"lb_loss": lb}


def moe_block_psum(p: Dict, cfg: ModelConfig, x, ep_axis: str
                   ) -> Tuple[jnp.ndarray, Dict]:
    """Decode-path MoE: tokens are replicated across the expert axis
    (B is sharded over data only); every shard routes all its tokens,
    computes the pairs owned by its local experts, and the outputs are
    combined with a psum. For T = batch-size tokens this moves 2*T*D
    bytes (ring) and needs no all-to-all — cheaper than dispatch when
    T is tiny and avoids gathering expert weights."""
    mc = cfg.moe
    b, s, d = x.shape
    tokens = x.reshape(-1, d)
    t = tokens.shape[0]
    e, k = mc.num_experts, mc.top_k
    ep = jax.lax.psum(1, ep_axis)
    e_loc = e // ep
    my = jax.lax.axis_index(ep_axis)

    logits = tokens.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)
    flat_g = gate.reshape(-1).astype(tokens.dtype)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    mine = (flat_e // e_loc) == my
    local_e = jnp.where(mine, flat_e % e_loc, e_loc)     # e_loc = drop bucket
    ecap = int(math.ceil(t * k / max(e_loc, 1) * mc.capacity_factor))
    ecap = max(ecap, 8)
    y_pairs = _expert_apply(tokens[flat_tok], local_e, p, e_loc, ecap,
                            valid=mine)
    y = jnp.zeros((t, d), tokens.dtype)
    y = y.at[flat_tok].add(y_pairs * flat_g[:, None])
    y = jax.lax.psum(y, ep_axis)
    aux = {"lb_loss": jnp.float32(0.0), "router_fraction": None}
    return y.reshape(b, s, d), aux


def _expert_apply(toks, eids, p, e_loc: int, ecap: int, valid=None):
    """Capacity-bucketed batched expert MLP. toks: (N, D); eids: (N,)
    in [0, e_loc) or >= e_loc for dropped/foreign entries. Returns
    per-input outputs (zeros for dropped)."""
    n, d = toks.shape
    if valid is None:
        valid = eids < e_loc
    key = jnp.where(valid, eids, e_loc)
    order = jnp.argsort(key, stable=True)
    eid_s = key[order]
    counts = jnp.zeros(e_loc + 1, jnp.int32).at[eid_s].add(1)
    start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                             jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n) - start[eid_s]
    ok = (eid_s < e_loc) & (rank < ecap)
    row = jnp.where(ok, eid_s, 0)
    col = jnp.where(ok, rank, ecap)
    buf = jnp.zeros((e_loc, ecap + 1, d), toks.dtype)
    buf = buf.at[row, col].set(toks[order] * ok[:, None].astype(toks.dtype))
    buf = buf[:, :ecap]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    y_sorted = jnp.where(ok[:, None],
                         out_buf[row, jnp.minimum(col, ecap - 1)], 0.0)
    return jnp.zeros((n, d), toks.dtype).at[order].set(y_sorted)


def _moe_tokens(p: Dict, cfg: ModelConfig, tokens, ep_axis: Optional[str]):
    mc = cfg.moe
    t, d = tokens.shape
    e, k = mc.num_experts, mc.top_k
    ep = jax.lax.psum(1, ep_axis) if ep_axis else 1
    e_loc = e // ep
    assert e % ep == 0, f"{e} experts not divisible by ep={ep}"

    # ---- routing -----------------------------------------------------------
    logits = tokens.astype(jnp.float32) @ p["router"]           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                        # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    pe = probs.mean(0)
    fe = jnp.zeros(e).at[eidx.reshape(-1)].add(1.0) / (t * k)
    lb_loss = e * jnp.sum(fe * pe)

    # ---- dispatch to per-destination-shard buffers -------------------------
    n_pairs = t * k
    flat_e = eidx.reshape(-1)                                   # (T*k,)
    flat_g = gate.reshape(-1).astype(tokens.dtype)
    flat_tok = jnp.repeat(jnp.arange(t), k)
    dest = flat_e // e_loc                                      # target shard
    cap = int(math.ceil(n_pairs / ep * mc.capacity_factor))
    cap = max(cap, 8)

    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    counts = jnp.zeros(ep, jnp.int32).at[dest_s].add(1)
    bucket_start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                    jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(n_pairs) - bucket_start[dest_s]           # pos in bucket
    keep = rank < cap
    # coordinates of each kept pair in the send buffer
    rows, cols = dest_s, jnp.where(keep, rank, cap)             # cap = scratch
    send_tok = jnp.zeros((ep, cap + 1, d), tokens.dtype)
    send_tok = send_tok.at[rows, cols].set(tokens[flat_tok[order]])
    send_eid = jnp.full((ep, cap + 1), -1, jnp.int32) \
        .at[rows, cols].set(flat_e[order] % e_loc)
    send_tok, send_eid = send_tok[:, :cap], send_eid[:, :cap]

    # ---- exchange ----------------------------------------------------------
    if ep_axis:
        recv_tok = jax.lax.all_to_all(send_tok, ep_axis, 0, 0, tiled=False)
        recv_eid = jax.lax.all_to_all(send_eid, ep_axis, 0, 0, tiled=False)
    else:
        recv_tok, recv_eid = send_tok, send_eid

    # ---- local expert compute (capacity-bucketed) --------------------------
    # Expert weights arrive pre-sharded by shard_map's in_specs
    # (P("model") on the expert axis): local shape (e_loc, D, F).
    assert p["w_gate"].shape[0] == e_loc, (
        f"expert weights {p['w_gate'].shape[0]} != local experts {e_loc}; "
        "check shard_map in_specs")
    n_recv = ep * cap
    r_tok = recv_tok.reshape(n_recv, d)
    r_eid = recv_eid.reshape(n_recv)                            # -1 = empty
    ecap = int(math.ceil(n_recv / max(e_loc, 1) * mc.capacity_factor))
    ecap = max(ecap, 8)
    y_flat = _expert_apply(r_tok, jnp.where(r_eid < 0, e_loc, r_eid),
                           p, e_loc, ecap)
    y_recv = y_flat.reshape(ep, cap, d)
    if ep_axis:
        y_send = jax.lax.all_to_all(y_recv, ep_axis, 0, 0, tiled=False)
    else:
        y_send = y_recv
    # back at the source: y_send[dest, rank] is the expert output for the
    # pair that was sent there; combine with gates.
    pair_out = jnp.where(keep[:, None],
                         y_send[rows, jnp.minimum(cols, cap - 1)], 0.0)
    y = jnp.zeros((t, d), tokens.dtype)
    y = y.at[flat_tok[order]].add(pair_out * flat_g[order][:, None])
    aux = {"lb_loss": lb_loss, "router_fraction": fe}
    return y, aux


# ------------------------------------------------------------ dropless
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def moe_held(p: Dict, cfg: ModelConfig, x, valid=None):
    """One chip's share of a dropless expert layer. The router scores
    every token over all ``num_experts`` experts at its published width
    (f32, ``softmax`` or ``sigmoid``), keeps the top ``top_k``,
    normalises their gates to sum 1 and scales them
    (``routed_scale``); the chip then computes only the (token, expert)
    pairs whose expert it holds, ``first_expert`` onwards
    (``experts_held``, or all), with no capacity: nothing is dropped,
    so a row's output never depends on the other rows. The held experts
    run as one grouped matmul over the pairs sorted by expert
    (``kernels/expert_gmm.py``); the shared expert is added once. What
    experts held elsewhere would add is left out. ``valid``: optional
    bool mask over x's leading dims; rows outside it (a bucketed
    chunk's padding, idle slots) are routed to no expert.

    p: "router" (D, E) f32; "w_gate", "w_up" (held, D, F), "w_down"
    (held, F, D), or each of these stacked over layers with "layer"
    this layer's index; optional "shared" MLP. x: (..., D)."""
    mc = cfg.moe
    held = mc.experts_held or mc.num_experts
    assert p["w_up"].shape[-3] == held, (p["w_up"].shape, held)
    shape = x.shape
    h = x.reshape(-1, shape[-1])
    with jax.named_scope("moe.route"):
        logits = jnp.dot(h.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        score = (jax.nn.sigmoid(logits) if mc.scoring == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        gate, idx = jax.lax.top_k(score, mc.top_k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9) \
            * mc.routed_scale
        local = idx - mc.first_expert
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine &= valid.reshape(-1, 1)
    with jax.named_scope("moe.experts"):
        # one expert axis over all stacked layers: this layer's experts
        # start at layer * held
        w = {n: p[n].reshape(-1, *p[n].shape[-2:]) for n in EXPERT_LEAVES}
        y = _held_experts(w, p.get("layer", 0) * held, h,
                          jnp.where(mine, local, held), gate, held)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + mlp(p["shared"], cfg, h).astype(jnp.float32)
    return y.astype(x.dtype).reshape(shape)


def _held_experts(w: Dict, first, h, local, gate, held: int):
    """Sum over each token's held experts of gate * expert(h), f32.
    w: expert matrices on one expert axis, this layer's from ``first``
    on; local: (T, k) held expert index of each pair, ``held`` for a
    pair this chip does not compute. The pairs are laid out by expert
    in row tiles of ``tm``, each expert's rows starting a tile, in a
    buffer sized for the most pairs T rows can bring (each token meets
    an expert at most once), so no pair is ever dropped."""
    from repro.kernels import ops as kops
    t, k = local.shape
    tm = min(128, -(-t // 16) * 16)
    tiles = min(held * -(-t // tm), -(-t * min(k, held) // tm) + held)
    m = tiles * tm
    e = local.reshape(-1)                                  # (T*k,)
    hit = e[:, None] == jnp.arange(held)[None, :]          # (T*k, held)
    count = hit.sum(0)
    rank = jnp.cumsum(hit, 0) - 1
    e_c = jnp.minimum(e, held - 1)
    tile_end = jnp.cumsum(-(-count // tm))                 # per expert
    start = (tile_end - (-(-count // tm))) * tm            # first row
    row = jnp.where(e < held, start[e_c]
                    + jnp.take_along_axis(rank, e_c[:, None], 1)[:, 0], m)
    tok = jnp.arange(t * k) // k
    # each buffer row's token and gate; rows holding no pair get 0
    src = jnp.zeros(m, jnp.int32).at[row].set(tok, mode="drop")
    g = jnp.zeros(m, jnp.float32).at[row].set(gate.reshape(-1),
                                              mode="drop")
    live = jnp.zeros(m, bool).at[row].set(True, mode="drop")
    tile_expert = first + jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(tiles), side="right"),
        held - 1)
    live_tiles = tile_end[-1]
    xs = jnp.take(h, src, axis=0)

    def gmm(a, wts, out_dtype):
        return kops.expert_gmm(a, wts, tile_expert, live_tiles, tm,
                               out_dtype)
    a = jax.nn.silu(gmm(xs, w["w_gate"], jnp.float32)) \
        * gmm(xs, w["w_up"], jnp.float32)
    o = gmm(a.astype(h.dtype), w["w_down"], jnp.float32)
    # rows of tiles past the live ones are unset: select, never scale
    contrib = jnp.where(live[:, None], o * g[:, None], 0.0)
    return jnp.zeros((t, h.shape[1]), jnp.float32).at[src].add(contrib)

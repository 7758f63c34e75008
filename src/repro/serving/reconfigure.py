"""Live fleet re-provisioning + fault injection (DESIGN.md §Live
re-provisioning & fault injection).

The re-planner closed the loop in software (boundary moves); this
module closes it in HARDWARE shape: ``reprovision`` tears a loaded
engine down and rebuilds it with a different slot count / context
window / tp submesh **without dropping an in-flight request** —

    quiesce -> checkpoint -> rebuild -> restore

1. quiesce: ``drain_checkpoint`` preempts every occupied slot through
   the PR-8 host-offload tier (swap vs recompute by the cold-suffix
   threshold; mid-prefill slots checkpoint onto the recompute path) and
   requeues them in slot order AHEAD of already-waiting arrivals.
2. checkpoint: each ``_PreemptedState`` carries the emitted-token
   prefix (so gateway SSE cursors survive), the replay token list, and
   — on the swap path — the slot's exact KV bits as host numpy arrays.
3. rebuild: a fresh ``InferenceEngine`` on the (possibly different)
   submesh, built from the runtime's pristine host params.
4. restore: checkpointed requests transplant ahead of queued ones;
   ``_adopt_state`` adapts swap-path KV to the new geometry (dense rows
   pad/truncate along the seq axis — zero padding is bitwise-safe, the
   attention mask ends at pos; paged blocks move unchanged, block size
   is fleet-uniform) and falls back to recompute when it cannot.

Resume is BITWISE identical to an uninterrupted run: the masked no-op
invariant makes a slot's tokens independent of its co-tenants, the
swap path restores exact KV bits, and the recompute path replays the
exact tokens whose KV sat at positions 0..pos-1 (PR 8), all of which
holds across engines because every pool shares one set of params and
one prefill chunking.

The same machinery survives UNPLANNED teardown: ``FaultInjector`` can
kill an engine (device state lost, host bookkeeping survives), exhaust
its paged allocator, or wedge ``step()``; ``HealthPolicy`` detects the
stall, and ``recover_pool`` salvages every accepted request from host
mirrors ONLY (the dead engine's allocator counters may be mid-update —
salvage never touches them) and re-routes them one pool up, which
preserves the no-OOM guarantee (band_i requests fit pool i+1's larger
context by construction).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving.engine import (InferenceEngine, ServeRequest,
                                  _PreemptedState)


class PoolDownError(RuntimeError):
    """Submission refused: the target pool is inside a re-provisioning
    / crash-recovery blackout window. Carries the seconds a client
    should wait (the gateway maps this to 503 + Retry-After)."""

    def __init__(self, pool: str, retry_after: float):
        super().__init__(f"pool {pool} is re-provisioning; "
                         f"retry after {retry_after:.2f}s")
        self.pool = pool
        self.retry_after = retry_after


# --------------------------------------------------------------- migration
def _fits(req: ServeRequest, c_max: int, paged: bool, block_size: int,
          num_blocks: int) -> bool:
    """Would ``req`` (fresh OR resumed — the replay list plus remaining
    budget sums to the same len(tokens) + max_new_tokens positions) fit
    an engine of this geometry at all?"""
    total = len(req.tokens) + req.max_new_tokens
    if total > c_max:
        return False
    if paged and math.ceil(total / block_size) > num_blocks:
        return False
    return True


def _fit_seq(h: np.ndarray, axis: int, n: int) -> np.ndarray:
    """Pad (zeros) or truncate a dense host KV row to ``n`` positions
    along its seq axis. Bitwise-safe either way: positions >= pos are
    never attended (the mask ends at pos), and pos <= the fit-checked
    len(tokens) + max_new_tokens <= n on the truncation path."""
    if h.shape[axis] == n:
        return h
    if h.shape[axis] > n:
        sl = [slice(None)] * h.ndim
        sl[axis] = slice(0, n)
        return np.ascontiguousarray(h[tuple(sl)])
    pad = [(0, 0)] * h.ndim
    pad[axis] = (0, n - h.shape[axis])
    return np.pad(h, pad)


def _adopt_state(state: _PreemptedState, src: InferenceEngine,
                 dst: InferenceEngine) -> _PreemptedState:
    """Adapt a host checkpoint taken on ``src`` to ``dst``'s cache
    geometry. Paged blocks move unchanged (block size is fleet-uniform
    and the kv-head sharding never changes the logical shape, so a
    host copy scatters into ANY paged engine, whatever its submesh);
    dense rows pad/truncate along the seq axis. Any mismatch the swap
    tier cannot follow falls back to the recompute path — replay and
    last_tok are computed on BOTH preemption paths exactly so this
    conversion is always available."""
    if state.host_kv is None:
        return state
    if src.paged and dst.paged and src.block_size == dst.block_size \
            and state.n_blocks <= dst.blocks_per_slot:
        return state
    if not src.paged and not dst.paged:
        if src.c_max == dst.c_max:
            return state
        # removing the batch axis leaves the seq axis at the SAME index
        # (seq immediately follows batch in every cache layout)
        kv = jax.tree.map(
            lambda c, h: _fit_seq(h, src._batch_axis(c), dst.c_max),
            src.cache, state.host_kv)
        return dataclasses.replace(state, host_kv=kv)
    return dataclasses.replace(state, host_kv=None, n_blocks=0, pos=0)


def _move_request(src: InferenceEngine, dst: InferenceEngine,
                  req: ServeRequest,
                  state: Optional[_PreemptedState]) -> None:
    """Transplant one queued/checkpointed request from ``src`` to the
    tail of ``dst``'s queue, carrying its accounting. The enqueue
    timestamp is re-keyed to ``dst``'s iteration clock (carrying the
    old engine's would make queue_iters negative or absurd)."""
    rid = req.rid
    if state is not None:
        dst._preempted[rid] = _adopt_state(state, src, dst)
    dst.waiting.append(req)
    dst._mark_queued(rid)
    src._enqueued_at.pop(rid, None)
    for attr in ("_queue_iters", "_prefill_iters", "_rid_preemptions"):
        v = getattr(src, attr).pop(rid, None)
        if v is not None:
            d = getattr(dst, attr)
            d[rid] = d.get(rid, 0) + v
    src._preempted.pop(rid, None)
    src._req_hashes.pop(rid, None)
    src._hol_bypassed.pop(rid, None)
    src._resume_last_tok.pop(rid, None)


def reprovision(runtime, pool: str, *, n_max: Optional[int] = None,
                c_max: Optional[int] = None,
                tp: Optional[int] = None) -> Dict[str, object]:
    """Rebuild ``runtime.engines[pool]`` with a new slot count /
    context window / tp submesh, migrating every in-flight and queued
    request. Zero-drop and bitwise: resumed outputs are identical to an
    uninterrupted run (test- and bench-pinned).

    In-flight requests the new geometry cannot hold at all re-route one
    pool up (their band fits the larger pool by construction); shrinking
    the TOP pool below an in-flight request's footprint is refused
    up front, before any state is touched."""
    names = list(runtime.engines)
    if pool not in runtime.engines:
        raise KeyError(f"unknown pool {pool!r} (have {names})")
    i = names.index(pool)
    old = runtime.engines[pool]
    new_n = old.n_max if n_max is None else int(n_max)
    new_c = old.c_max if c_max is None else int(c_max)
    if new_n < 1:
        raise ValueError(f"n_max must be >= 1, got {new_n}")
    bounds = runtime.router.boundaries
    if i < len(bounds) and new_c < bounds[i]:
        raise ValueError(
            f"pool {pool} context {new_c} < its routing boundary "
            f"{bounds[i]}: compressed requests could overflow the KV "
            "cache (shrink the boundary first)")
    ecfg = old.config
    if tp is not None:
        if runtime.config.mesh is None:
            raise ValueError("tp re-provisioning needs a fleet mesh")
        from repro.launch.mesh import make_submeshes
        subs = make_submeshes(runtime.config.mesh, int(tp))
        ecfg = ecfg.replace(mesh=subs[i % len(subs)])
    # misfit scan BEFORE any mutation: a request the new geometry can
    # never hold must have somewhere to go
    block = ecfg.block_size
    nb = ecfg.num_blocks if ecfg.num_blocks is not None \
        else new_n * math.ceil(new_c / block)
    inflight = [r for r in old.slot_req if r is not None] \
        + list(old.waiting)
    misfits = {r.rid for r in inflight
               if not _fits(r, new_c, ecfg.paged, block, nb)}
    if misfits and i + 1 >= len(names):
        raise ValueError(
            f"shrinking top pool {pool} to c_max={new_c} would orphan "
            f"{len(misfits)} in-flight request(s); drain them first")
    # quiesce: checkpoint every occupied slot into the host tier,
    # requeued in slot order ahead of already-waiting arrivals
    checkpointed = old.drain_checkpoint()
    new_eng = InferenceEngine(runtime.cfg, runtime.params, new_n, new_c,
                              config=ecfg, name=pool, tracer=old.tracer)
    up = runtime.engines[names[i + 1]] if i + 1 < len(names) else None
    migrated = rerouted = 0
    for req in list(old.waiting):
        state = old._preempted.get(req.rid)
        if req.rid in misfits:
            _move_request(old, up, req, state)
            rerouted += 1
            d = runtime._decisions.get(req.rid)
            if d is not None:
                d.pool = names[i + 1]
        else:
            _move_request(old, new_eng, req, state)
        migrated += 1
    old.waiting.clear()
    # unconsumed finished results follow the pool name
    new_eng.results.update(old.results)
    old.results.clear()
    # atomic swap: the router/gateway mapping points at the new engine
    # from the next submit/step on
    runtime.engines[pool] = new_eng
    stats = runtime.reprovision_stats
    stats["rebuilds"] += 1
    stats["migrated_requests"] += migrated
    stats["rerouted_requests"] += rerouted
    return {"pool": pool, "checkpointed": checkpointed,
            "migrated": migrated, "rerouted": rerouted,
            "n_max": new_n, "c_max": new_c}


# ----------------------------------------------------------- fault recovery
def salvage_states(
        eng: InferenceEngine,
) -> List[Tuple[ServeRequest, Optional[_PreemptedState]]]:
    """Read every accepted request out of a DEAD engine, from host
    mirrors ONLY — device KV is gone and the allocator counters may be
    mid-update (the oom fault raises from INSIDE ``_alloc_block``,
    after the caller decremented its reservation), so nothing here
    calls into the engine or trusts its paged bookkeeping.

    Slot occupants come out first in slot order as recompute-path
    checkpoints (their device KV is lost; the replay list and last fed
    token are reconstructed exactly as ``preempt_slot`` would have),
    then the queue in order — already-checkpointed requests keep their
    host-RAM swap copies, which survived the crash."""
    out: List[Tuple[ServeRequest, Optional[_PreemptedState]]] = []
    for s in range(eng.n_max):
        req = eng.slot_req[s]
        if req is None:
            continue
        emitted = list(eng.slot_out[s])
        replay = list(req.tokens) if not emitted else \
            list(req.tokens) + [req.tokens[-1]] + emitted[:-1]
        if eng.slot_prefill_left[s]:
            # mid-prefill: a resumed replay parked the true next fed
            # token in _resume_last_tok; a fresh prefill feeds the last
            # prompt token, which is replay[-1] either way
            last = eng._resume_last_tok.get(req.rid)
            if last is None:
                last = int(replay[-1]) if replay else 0
        else:
            last = int(eng.slot_last_tok[s])
        out.append((req, _PreemptedState(
            req=req, out=emitted, pos=0, last_tok=int(last),
            replay=replay, host_kv=None, n_blocks=0)))
    for req in eng.waiting:
        out.append((req, eng._preempted.get(req.rid)))
    return out


def recover_pool(runtime, pool: str, *,
                 blackout_s: float = 0.0) -> Dict[str, object]:
    """Crash recovery for ``pool``: salvage every accepted request from
    the dead engine's host mirrors, rebuild the engine at its
    provisioned shape (fresh device state), and re-route the salvaged
    requests ONE POOL UP — band_i requests fit pool i+1's larger
    context, so the no-OOM guarantee survives the migration. The top
    pool (nothing above it) restores into its own rebuilt engine.
    New submissions to the pool are refused with ``PoolDownError``
    until ``blackout_s`` elapses."""
    names = list(runtime.engines)
    if pool not in runtime.engines:
        raise KeyError(f"unknown pool {pool!r} (have {names})")
    i = names.index(pool)
    old = runtime.engines[pool]
    salvaged = salvage_states(old)
    new_eng = InferenceEngine(runtime.cfg, runtime.params, old.n_max,
                              old.c_max, config=old.config, name=pool,
                              tracer=old.tracer)
    up_name = names[i + 1] if i + 1 < len(names) else pool
    migrated = 0
    for req, state in salvaged:
        dst = new_eng if up_name == pool else runtime.engines[up_name]
        _move_request(old, dst, req, state)
        migrated += 1
        if up_name != pool:
            d = runtime._decisions.get(req.rid)
            if d is not None:
                d.pool = up_name
    # finished-but-unconsumed results survived on the host; keep them
    # reachable under the pool's name
    new_eng.results.update(old.results)
    old.results.clear()
    runtime.engines[pool] = new_eng
    runtime.pool_down_until[pool] = time.monotonic() + blackout_s
    runtime.reprovision_stats["engine_restarts"] += 1
    runtime.reprovision_stats["migrated_requests"] += migrated
    return {"pool": pool, "migrated": migrated, "rerouted_to": up_name,
            "blackout_s": blackout_s}


class FaultInjector:
    """Inject faults into a live pool's engine (tests / chaos smoke).

    * ``kill``: the device state is lost; the next ``step()`` raises
      ``EngineDead``. Host bookkeeping (queue, emitted-token mirrors,
      host-offload KV tier) survives for salvage.
    * ``exhaust_allocator``: the next paged block allocation raises
      ``EngineDead`` from INSIDE the allocator — deliberately leaving
      its counters inconsistent, which is exactly why salvage reads
      host mirrors only.
    * ``wedge``: ``step()`` returns without advancing the iteration
      clock — the stall signature ``HealthPolicy`` detects.
    """

    def __init__(self, runtime):
        self.runtime = runtime

    def kill(self, pool: str) -> None:
        self.runtime.engines[pool]._fault = "killed"

    def exhaust_allocator(self, pool: str) -> None:
        eng = self.runtime.engines[pool]
        if not eng.paged:
            raise ValueError("allocator-exhaustion fault needs paged mode")
        eng._fault = "oom"

    def wedge(self, pool: str) -> None:
        self.runtime.engines[pool]._fault = "wedged"

    def clear(self, pool: str) -> None:
        self.runtime.engines[pool]._fault = None


class HealthPolicy:
    """Stall detector for the gateway drive loop: an engine that is
    busy and being stepped but whose iteration clock has not advanced
    for ``patience`` consecutive checks is wedged (a healthy ``step()``
    ALWAYS advances the clock). Crashes don't need this — they raise
    ``EngineDead`` synchronously; the wedge fault is the silent-failure
    mode this catches."""

    def __init__(self, patience: int = 3):
        self.patience = max(1, int(patience))
        self._seen: Dict[str, Tuple[int, int]] = {}

    def check(self, runtime) -> List[str]:
        """Call once per drive pass, AFTER stepping busy engines;
        returns the pools judged wedged (their strike state resets so a
        recovered pool gets a fresh budget)."""
        wedged = []
        for name, eng in runtime.engines.items():
            if not eng.busy():
                self._seen.pop(name, None)
                continue
            last_it, strikes = self._seen.get(name, (-1, 0))
            strikes = strikes + 1 if eng.iteration == last_it else 0
            if strikes >= self.patience:
                wedged.append(name)
                self._seen.pop(name, None)
            else:
                self._seen[name] = (eng.iteration, strikes)
        return wedged

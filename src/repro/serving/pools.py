"""K-pool serving runtime: the FleetOpt plan made executable.

Wires together:
  * the planner's boundary vector / gamma vector / per-pool sizing,
  * the gateway router with the extractive compressor (C&R),
  * one InferenceEngine per pool (pool i sized for its boundary's
    token budget, the top pool for c_max_long).

This is the end-to-end "implementation mechanism" of the paper: the
boundary vector B* is enforced in software at the gateway, and the
hard OOM guarantee (Eq. 15) means no compressed request can overflow
its target pool's KV cache.  ``TwoPoolRuntime`` is the paper's K=2
special case; ``FleetRuntime.from_plan`` spins up N engines straight
from a :class:`~repro.core.planner.FleetPlan`.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Union

from repro.configs.base import ModelConfig
from repro.core.compression import ExtractiveCompressor
from repro.core.naming import pool_names
from repro.core.planner import FleetPlan
from repro.core.router import GatewayRouter, RoutingDecision
from repro.core.workload import OutputLenPredictor, Request, get_workload
from repro.serving.config import ServingConfig
from repro.serving.engine import InferenceEngine, ServeRequest, ServeResult
from repro.serving.tokenizer import ByteChunkTokenizer
from repro.serving.tracing import Tracer


@dataclasses.dataclass
class GatewayRequest:
    rid: int
    text: str
    max_output_tokens: int
    category: str = "prose"
    # opaque multi-turn session id: turns of one session share a prompt
    # prefix, so the gateway pins them to the pool whose engine caches
    # their KV blocks (router session affinity; None = stateless)
    session: Optional[str] = None


@dataclasses.dataclass
class GatewayResponse:
    rid: int
    pool: str
    compressed: bool
    compression_ms: float
    output_tokens: List[int]
    prefill_iters: int
    decode_iters: int
    queue_iters: int
    shed: bool = False             # refused by stability-aware admission
    preemptions: int = 0
    # still in flight when run() hit its iteration cap: output_tokens
    # holds the partial prefix emitted so far, and the request stays
    # live on its engine (a later run() can still finish it)
    timed_out: bool = False


class FleetRuntime:
    """N-pool gateway + engines.

    ``boundaries`` (tokens, strictly increasing) and ``gammas`` define
    the routing bands; ``n_maxes``/``c_maxes`` give each engine's slot
    count and context window — pool i's ``c_maxes[i]`` must be at
    least ``boundaries[i]`` so the no-OOM guarantee holds.
    """

    def __init__(self, cfg: ModelConfig, params,
                 boundaries: Sequence[int], gammas: Sequence[float],
                 n_maxes: Sequence[int], c_maxes: Sequence[int],
                 c_chunk: Optional[int] = None, *,
                 config: Optional[ServingConfig] = None,
                 lout_predictor: Optional[OutputLenPredictor] = None,
                 **overrides):
        # -- ServingConfig shim (DESIGN.md §Serving API) -------------------
        # One config object reaches EVERY engine — this is what closed
        # the dropped-knob bugs (TwoPoolRuntime losing the overload
        # kwargs, FleetRuntime never forwarding hol_window); the
        # field-reach regression test in tests/test_serving_config.py
        # keeps it closed. Legacy kwargs (incl. kv_block_size) fold in
        # via ServingConfig.replace.
        scfg = config if config is not None else ServingConfig()
        if c_chunk is not None:
            overrides = dict(overrides, c_chunk=c_chunk)
        if overrides:
            scfg = scfg.replace(**overrides)
        self.config = scfg
        k = len(boundaries) + 1
        if len(n_maxes) != k or len(c_maxes) != k:
            raise ValueError(f"need {k} n_maxes/c_maxes for "
                             f"{len(boundaries)} boundaries")
        for i, b in enumerate(boundaries):
            if c_maxes[i] < b:
                raise ValueError(
                    f"pool {i} context {c_maxes[i]} < its boundary {b}: "
                    "compressed requests could overflow the KV cache")
        # -- multi-device placement (DESIGN.md §Sharded serving) -----------
        # mesh + tp_degree place each pool's engine replica on its own
        # submesh of tp_degree devices (launch/mesh.make_submeshes);
        # with fewer submeshes than pools, placement wraps round-robin
        # (pools then time-share devices — fine on a CPU smoke host,
        # a real fleet provisions enough devices per plan).
        if scfg.mesh is not None:
            from repro.launch.mesh import make_submeshes
            subs = make_submeshes(scfg.mesh, scfg.tp_degree)
            self._submeshes = [subs[i % len(subs)] for i in range(k)]
        else:
            self._submeshes = [None] * k
        self.tp_degree = scfg.tp_degree
        self.cfg = cfg
        self.tokenizer = ByteChunkTokenizer(cfg.vocab_size)
        # -- output-length awareness (DESIGN.md §Serving API) --------------
        # lout_routing / lout_reservation need a calibrated predictor;
        # callers pass one built from their workload
        # (OutputLenPredictor.from_workload), else the chat-shaped
        # lmsys calibration is the default. The predictor's per-
        # category bias EMA is fed by record_completion.
        self.lout_predictor = lout_predictor
        if self.lout_predictor is None and (scfg.lout_routing
                                            or scfg.lout_reservation):
            self.lout_predictor = OutputLenPredictor.from_workload(
                get_workload("lmsys"))
        self.router = GatewayRouter(
            boundaries=boundaries, gammas=gammas,
            compressor=ExtractiveCompressor(),
            lout_predictor=(self.lout_predictor
                            if scfg.lout_routing else None))
        names = pool_names(k)
        # spans of the whole fleet: this runtime's routing and C&R, its
        # engines and the gateway over it (off until started)
        self.tracer = Tracer()
        # The whole serving feature surface (paged / prefix_cache /
        # decode_k / spec_k / overload survival / lout reservation) is
        # configured per-engine by ONE ServingConfig; see its docstring
        # for the field-by-field DESIGN.md map. Each engine gets the
        # shared config with only its submesh swapped in.
        self.engines: Dict[str, InferenceEngine] = {
            names[i]: InferenceEngine(
                cfg, params, n_maxes[i], c_maxes[i],
                config=scfg.replace(mesh=self._submeshes[i],
                                    tp_degree=1),
                name=names[i], tracer=self.tracer)
            for i in range(k)}
        # pristine host params, kept for live re-provisioning: engine
        # rebuilds re-shard from these instead of re-gathering a dead
        # or differently-sharded engine's device copy
        self.params = params
        self._decisions: Dict[int, RoutingDecision] = {}
        self._categories: Dict[int, str] = {}
        # -- live re-provisioning (DESIGN.md §Live re-provisioning) --------
        self.reprovision_stats = {"rebuilds": 0, "engine_restarts": 0,
                                  "migrated_requests": 0,
                                  "rerouted_requests": 0,
                                  "autoscale_actions": 0}
        # pool -> monotonic deadline while crash recovery blacks it out
        self.pool_down_until: Dict[str, float] = {}
        # per-pool GPU counts of the plan this fleet was provisioned
        # from (from_plan sets it); the autoscaler's hysteresis baseline
        self.plan_pool_gpus: Optional[List[int]] = None
        # demo-tokens per datacenter-token when from_plan shrank the
        # boundaries onto a reduced model (1.0 = native scale); the
        # re-planner uses it to plan at datacenter scale where the
        # hardware profiles are calibrated
        self.ctx_scale = 1.0

    def device_placement(self) -> Dict[str, List[int]]:
        """pool name -> device ids its engine replica spans (one id on
        a single-device runtime)."""
        return {name: [d.id for d in eng.devices()]
                for name, eng in self.engines.items()}

    @classmethod
    def from_plan(cls, cfg: ModelConfig, params, plan: FleetPlan,
                  slots_per_pool: Union[int, Sequence[int]] = 4,
                  c_chunk: int = 64,
                  ctx_scale: Optional[float] = None, *,
                  config: Optional[ServingConfig] = None,
                  lout_predictor: Optional[OutputLenPredictor] = None,
                  **overrides) -> "FleetRuntime":
        """Build a runtime with the plan's boundary/gamma structure.

        The plan's per-GPU slot counts target datacenter hardware; a
        local runtime caps each pool at ``slots_per_pool`` engine
        slots (one cap for every pool, or one per pool).
        ``ctx_scale`` shrinks the token boundaries (e.g.
        ``512 / 65536`` to demo a 64K plan on a reduced model with a
        512-token cache); boundaries are kept >= 2 * c_chunk so the
        chunked prefill path stays exercised.  Serving knobs come from
        ``config`` (a :class:`ServingConfig`) or legacy kwargs, same
        shim as the constructor.
        """
        scale = ctx_scale if ctx_scale is not None else 1.0
        bounds = []
        for b in plan.boundaries:
            bounds.append(max(int(b * scale), 2 * c_chunk,
                              (bounds or [0])[-1] + 1))
        c_top = max(int(plan.pools[-1].c_max * scale),
                    (bounds[-1] if bounds else 2 * c_chunk) * 2)
        c_maxes = tuple(bounds) + (c_top,)
        caps = ([slots_per_pool] * len(plan.pools)
                if isinstance(slots_per_pool, int) else list(slots_per_pool))
        if len(caps) != len(plan.pools):
            raise ValueError(f"{len(caps)} slot caps for "
                             f"{len(plan.pools)} pools")
        n_maxes = tuple(min(cap, max(1, pp.n_max))
                        for cap, pp in zip(caps, plan.pools))
        rt = cls(cfg, params, tuple(bounds), plan.gammas, n_maxes,
                 c_maxes, c_chunk, config=config,
                 lout_predictor=lout_predictor, **overrides)
        rt.ctx_scale = scale
        rt.plan_pool_gpus = [pp.n_gpus for pp in plan.pools]
        return rt

    def submit(self, req: GatewayRequest) -> RoutingDecision:
        """Route one request through the gateway and enqueue it on the
        chosen pool's engine.  Returns the routing decision.

        With ``lout_routing`` the router banded by PREDICTED output
        length, so the chosen pool's context may be smaller than
        prompt + max_output_tokens; the generation budget is clamped
        to what the pool can hold (token-budget routing — the no-OOM
        guarantee moves from the worst case to an enforced budget).
        With ``lout_reservation`` the engine-side ServeRequest carries
        the prediction as its KV reservation hint."""
        prompt_tokens = self.tokenizer.count(req.text)
        r = Request(l_total=prompt_tokens + req.max_output_tokens,
                    l_in=prompt_tokens, l_out=req.max_output_tokens,
                    category=req.category,
                    prompt_bytes=len(req.text.encode("utf-8")))
        tr, st = self.tracer, self.router.stats
        ms0 = st.compression_ms_sum
        with tr.span("router.route", rid=req.rid) as sp:
            decision = self.router.route(r, prompt_text=req.text,
                                         session=req.session)
            if sp is not None and st.compression_ms_sum > ms0:
                # C&R ran inside route(): its span is the time that fed
                # compression_ms_sum (one clock), ending about now
                t1 = time.perf_counter_ns()
                tr.interval("router.compress",
                            t1 - int((st.compression_ms_sum - ms0) * 1e6),
                            t1, rid=req.rid)
        if decision.pool in self.pool_down_until:
            left = self.pool_down_until[decision.pool] - time.monotonic()
            if left > 0:
                # crash-recovery blackout: refuse with the wait the
                # gateway maps to 503 + Retry-After
                from repro.serving.reconfigure import PoolDownError
                raise PoolDownError(decision.pool, left)
            del self.pool_down_until[decision.pool]
        text = decision.compressed_text if decision.compressed else req.text
        ids = self.tokenizer.encode(text)
        max_new = req.max_output_tokens
        if self.config.lout_routing:
            budget = self.engines[decision.pool].c_max - len(ids)
            max_new = max(1, min(max_new, budget))
        hint = None
        if self.config.lout_reservation:
            hint = self.lout_predictor.predict(len(ids),
                                               category=req.category,
                                               cap=max_new)
        self.engines[decision.pool].submit(ServeRequest(
            rid=req.rid, tokens=ids, max_new_tokens=max_new,
            category=req.category, l_out_hint=hint))
        self._decisions[req.rid] = decision
        self._categories[req.rid] = req.category
        # feed the bytes-per-token EMA with the true tokenizer count
        self.router.ema.update(req.category, len(text.encode("utf-8")),
                               len(ids))
        return decision

    def record_completion(self, rid: int, res: ServeResult) -> None:
        """Feed a finished request's ACTUAL output length back into the
        output-length model (per-category bias EMA). No-op without a
        predictor or for shed/empty results."""
        if self.lout_predictor is None or res.shed \
                or not res.output_tokens:
            return
        d = self._decisions.get(rid)
        if d is not None:
            self.lout_predictor.update(d.l_in_effective,
                                       len(res.output_tokens),
                                       category=self._categories.get(rid))

    def reprovision(self, pool: str, *, n_max: Optional[int] = None,
                    c_max: Optional[int] = None,
                    tp: Optional[int] = None) -> Dict[str, object]:
        """Live-rebuild one pool's engine with a new slot count /
        context / tp submesh, migrating every in-flight request through
        the host-offload tier — zero-drop, bitwise-identical resume
        (DESIGN.md §Live re-provisioning)."""
        from repro.serving import reconfigure
        return reconfigure.reprovision(self, pool, n_max=n_max,
                                       c_max=c_max, tp=tp)

    def release(self, rid: int) -> None:
        """Drop every host-side record of a CONSUMED request — the
        engine's result entry and the gateway's routing/category
        entries. Without this a days-long serving process leaks one
        dict entry per request served (ISSUE 10); the gateway calls it
        after flushing a result, run() after building its response."""
        for eng in self.engines.values():
            eng.results.pop(rid, None)
        self._decisions.pop(rid, None)
        self._categories.pop(rid, None)

    def _response(self, rid: int, res: ServeResult,
                  timed_out: bool = False) -> GatewayResponse:
        d = self._decisions[rid]
        return GatewayResponse(
            rid=rid, pool=d.pool, compressed=d.compressed,
            compression_ms=d.compression_ms,
            output_tokens=res.output_tokens,
            prefill_iters=res.prefill_iters,
            decode_iters=res.decode_iters, queue_iters=res.queue_iters,
            shed=res.shed, preemptions=res.preemptions,
            timed_out=timed_out)

    def run(self, max_iters: int = 100_000) -> Dict[int, GatewayResponse]:
        """Drive all pools to completion, interleaving their lockstep
        iterations (the pools are independent engines, so interleaving
        cannot change any request's tokens — but it models the real
        deployment, where all pools serve concurrently, and keeps
        per-pool iteration clocks comparable).

        Finished requests are consumed (their host-dict entries evicted
        via :meth:`release`, so repeated waves don't grow host memory).
        Requests still in flight when the iteration cap hits are
        surfaced as ``timed_out=True`` responses carrying their partial
        tokens — previously they silently vanished from the returned
        dict — and stay live on their engines, so a later ``run()`` can
        still finish them."""
        out: Dict[int, GatewayResponse] = {}
        busy = True
        while busy:
            busy = False
            for eng in self.engines.values():
                if eng.busy() and eng.iteration < max_iters:
                    eng.step()
                    busy = True
        for eng in self.engines.values():
            for rid, res in list(eng.results.items()):
                self.record_completion(rid, res)
                out[rid] = self._response(rid, res)
                self.release(rid)
        # iteration cap hit with work still in flight (overload, a
        # wedged engine, or a too-small max_iters): report the partial
        # state honestly instead of dropping the requests on the floor
        for eng in self.engines.values():
            for s in range(eng.n_max):
                req = eng.slot_req[s]
                if req is None or req.rid in out:
                    continue
                out[req.rid] = self._response(req.rid, ServeResult(
                    rid=req.rid, output_tokens=list(eng.slot_out[s]),
                    prefill_iters=eng._prefill_iters.get(req.rid, 0),
                    decode_iters=len(eng.slot_out[s]),
                    queue_iters=eng._queue_iters.get(req.rid, 0),
                    preemptions=eng._rid_preemptions.get(req.rid, 0)),
                    timed_out=True)
            for req in eng.waiting:
                if req.rid in out:
                    continue
                st = eng._preempted.get(req.rid)
                out[req.rid] = self._response(req.rid, ServeResult(
                    rid=req.rid,
                    output_tokens=list(st.out) if st else [],
                    prefill_iters=eng._prefill_iters.get(req.rid, 0),
                    decode_iters=len(st.out) if st else 0,
                    queue_iters=eng._queue_iters.get(req.rid, 0),
                    preemptions=eng._rid_preemptions.get(req.rid, 0)),
                    timed_out=True)
        return out


class TwoPoolRuntime(FleetRuntime):
    """The paper's two-pool runtime (K=2 view of :class:`FleetRuntime`)."""

    def __init__(self, cfg: ModelConfig, params, b_short: int, gamma: float,
                 n_max_short: int, n_max_long: int, c_max_long: int,
                 c_chunk: Optional[int] = None, *,
                 config: Optional[ServingConfig] = None,
                 lout_predictor: Optional[OutputLenPredictor] = None,
                 **overrides):
        # the shared ServingConfig shim forwards EVERY serving knob —
        # this constructor used to silently drop the overload-survival
        # kwargs (preemption / max_queue_wait / swap_threshold) by
        # re-declaring a stale subset of the parent signature
        super().__init__(cfg, params, boundaries=(b_short,),
                         gammas=(gamma,),
                         n_maxes=(n_max_short, n_max_long),
                         c_maxes=(b_short, c_max_long), c_chunk=c_chunk,
                         config=config, lout_predictor=lout_predictor,
                         **overrides)

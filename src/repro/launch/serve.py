"""Batched serving launcher: prefill + decode loop under pjit on the
available devices (the serve-side analog of launch/train.py), plus a
``--fleet K`` mode that plans a K-pool fleet with the FleetOpt planner
and spins up one gateway-routed engine per pool.

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.serve --arch minitron-8b --reduced \
      --mesh 4x2 --batch 8 --prompt-len 64 --new-tokens 16

  # plan a 3-pool azure fleet and serve a mixed prompt batch through it
  PYTHONPATH=src python -m repro.launch.serve --fleet 3 --workload azure \
      --reduced --new-tokens 8

  # same fleet behind the asyncio HTTP gateway (OpenAI-compatible
  # /v1/completions with SSE streaming, /health, Prometheus /metrics,
  # closed-loop re-planner on /admin/replan)
  PYTHONPATH=src python -m repro.launch.serve --fleet 2 --reduced \
      --decode-k 4 --http 8000

  # CI smoke: ephemeral port, in-process client, exit nonzero on failure
  PYTHONPATH=src python -m repro.launch.serve --fleet 2 --reduced \
      --decode-k 4 --smoke

  # record program spans while serving, written as JSON lines on exit
  PYTHONPATH=src python -m repro.launch.serve --fleet 2 --reduced \
      --decode-k 4 --http 8000 --spans spans.jsonl
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.distributed import sharding as SH
from repro.distributed.context import make_context
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M


def build_fleet_runtime(args):
    """Plan K pools from the workload CDF and make the plan
    executable: one InferenceEngine per pool behind the C&R gateway
    (serving/pools.py). The plan's token boundaries are scaled so the
    top pool's context is ``args.top_context`` tokens; the defaults
    (512-token top pool, 2 slots per pool, 16-token chunks) size the
    CPU demo, and a chip run passes its own sizes through the same
    flags. All serving knobs travel as ONE ServingConfig (DESIGN.md
    §Serving API)."""
    from repro.core.planner import plan_k_pool
    from repro.core.workload import get_workload
    from repro.serving.config import ServingConfig
    from repro.serving.pools import FleetRuntime

    w = get_workload(args.workload)
    plan = plan_k_pool(w, lam=args.lam, t_slo=0.5, k=args.fleet)
    print(f"plan: {plan.summary()}")
    for pp in plan.pools:
        print(f"  {pp.name}: c_max={pp.c_max} n_gpus={pp.n_gpus} "
              f"rho={pp.utilization:.3f} ttft_p99={pp.ttft_p99_s*1e3:.0f}ms")

    cfg = model_config(args)
    params = init_params(cfg)
    mesh = None
    if args.tp > 1:
        # tp>1 shards every pool engine over a tp-device submesh
        # (DESIGN.md §Sharded serving); --mesh DxM picks the global
        # mesh shape, else one flat row over all devices.
        from repro.launch.mesh import make_smoke_mesh
        if args.mesh:
            d, m = (int(x) for x in args.mesh.split("x"))
            mesh = make_mesh((d, m), ("data", "model"))
        else:
            mesh = make_smoke_mesh()
    scfg = ServingConfig(
        paged=args.paged or args.prefix_cache or args.preemption,
        prefix_cache=args.prefix_cache, decode_k=args.decode_k,
        spec_k=args.spec_k, mesh=mesh, tp_degree=args.tp,
        decode_impl=args.decode_impl,
        preemption=args.preemption, max_queue_wait=args.max_queue_wait,
        autoscale=getattr(args, "autoscale", False))
    slots = [int(x) for x in str(args.slots).split(",")]
    rt = FleetRuntime.from_plan(
        cfg, params, plan,
        slots_per_pool=slots[0] if len(slots) == 1 else slots,
        c_chunk=args.c_chunk,
        ctx_scale=args.top_context / plan.pools[-1].c_max, config=scfg)
    for name, eng in rt.engines.items():
        print(f"  {name} engine: n_max={eng.n_max} c_max={eng.c_max} "
              f"decode={eng.decode_impl} paged={eng.paged} "
              f"devices={[d.id for d in eng.devices()]}")
    return rt, plan


def model_config(args):
    """The served model: ``--reduced`` is the 2-layer float32 CPU toy;
    otherwise the published config, with ``--layers N`` cutting only
    its depth (the cut layers would sit on further pipeline stages)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), dtype="float32")
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    return cfg


def init_params(cfg):
    """Random weights from seed 0, drawn inside one jit so that only
    the config's dtype reaches device memory (an eager init would
    materialize every float32 draw before its cast)."""
    return jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0))


def serve_fleet(args) -> None:
    """Offline fleet demo: plan, route a mixed prompt batch, drain."""
    from repro.serving.pools import GatewayRequest

    rt, plan = build_fleet_runtime(args)
    bounds = rt.router.boundaries
    print(f"runtime pools: boundaries={bounds} "
          f"gammas={rt.router.gammas} "
          f"contexts={[e.c_max for e in rt.engines.values()]}")
    for name, ids in rt.device_placement().items():
        print(f"  {name}: tp={rt.tp_degree} devices={ids}")

    def prompt(n_words: int, topic: str) -> str:
        return " ".join(f"{topic} fact {i}: fleets split by context length."
                        for i in range(n_words))

    # one prompt per pool band + one borderline C&R candidate per boundary
    reqs, rid = [], 0
    for i, eng in enumerate(rt.engines.values()):
        lo = bounds[i - 1] if i else 0
        words = max(2, (lo + (bounds[i] if i < len(bounds) else eng.c_max))
                    // 2 // 8)
        reqs.append(GatewayRequest(rid, prompt(words, f"band{i}"),
                                   args.new_tokens))
        rid += 1
    for i, b in enumerate(bounds):
        reqs.append(GatewayRequest(
            rid, prompt(max(2, int(b * 1.2) // 8), f"borderline{i}"),
            args.new_tokens, category="rag"))
        rid += 1

    t0 = time.time()
    for r in reqs:
        d = rt.submit(r)
        print(f"  req {r.rid}: {r.category:5s} -> {d.pool:6s}"
              f"{' [C&R]' if d.compressed else ''} "
              f"L_eff={d.l_total_effective}")
    results = rt.run(max_iters=20_000)
    if args.prefix_cache:
        # a two-turn agent session, turn 2 AFTER turn 1 completes: it
        # resubmits turn 1's prompt plus new text — the gateway pins it
        # to the same pool (session affinity) and the engine's prefix
        # cache skips the shared full blocks' prefill
        b0 = bounds[0] if bounds else \
            next(iter(rt.engines.values())).c_max // 2   # K=1: no bounds
        base = prompt(max(2, b0 // 4 // 8), "session")
        for i, text in enumerate((base,
                                  base + " follow-up resubmits history.")):
            d = rt.submit(GatewayRequest(rid, text, args.new_tokens,
                                         session="demo"))
            print(f"  req {rid}: turn{i + 1:2d} -> {d.pool:6s} "
                  f"L_eff={d.l_total_effective}")
            rid += 1
            results.update(rt.run(max_iters=20_000))
    dt = time.time() - t0
    done = sum(len(res.output_tokens) for res in results.values())
    s = rt.router.stats
    print(f"served {len(results)} requests / {done} tokens in {dt:.1f}s; "
          f"gateway: borderline={s.borderline} "
          f"compressed={s.compressed_ok} pinned={s.affinity_pinned} "
          f"per_pool={s.per_pool}")
    disp = sum(e.dispatches for e in rt.engines.values())
    dtok = sum(e.decode_tokens_emitted for e in rt.engines.values())
    print(f"engine hot path: decode_k={args.decode_k} "
          f"{disp} dispatches / {dtok} decode tokens "
          f"({disp / max(1, dtok):.3f} dispatches/token)")
    if args.spec_k > 1:
        for name, eng in rt.engines.items():
            st = eng.spec_stats
            if st["verify_windows"]:
                print(f"  {name}: spec_k={args.spec_k} "
                      f"kappa={eng.spec_kappa():.2f} "
                      f"acceptance={eng.spec_acceptance_rate():.2f} "
                      f"({st['accepted_tokens']}/{st['proposed_tokens']} "
                      f"draft tokens over {st['verify_windows']} windows)")
    if args.prefix_cache:
        for name, eng in rt.engines.items():
            st = eng.prefix_stats
            if st["lookups"]:
                print(f"  {name}: prefix hits {st['hit_blocks']} blocks "
                      f"({st['hit_tokens']} tokens), "
                      f"{st['allocated_blocks']} allocated, "
                      f"{st['registered_blocks']} registered")
    # overload survival (DESIGN.md §Overload survival): always printed
    # when the knobs are on, so shed/preempt behavior is observable
    if args.preemption or args.max_queue_wait is not None:
        for name, eng in rt.engines.items():
            snap = eng.utilization_snapshot(detail=True)
            print(f"  {name}: overload preempted={snap['preempted']} "
                  f"(swap={snap['swapped_out']} "
                  f"recompute={snap['recomputed']}) shed={snap['shed']} "
                  f"hol_bypass={snap['hol_bypass']} "
                  f"queue_wait_est={snap['queue_wait_est_iters']:.1f} it "
                  f"mu={snap['service_rate_per_iter']:.3f}/it")


async def http_call(host, port, method, path, body=None,
                     timeout: float = 120.0):
    """Minimal raw HTTP/1.1 client (stdlib only) for the in-process
    smoke: returns (status, header dict, body bytes). The gateway
    always closes the connection, so read-to-EOF is the framing."""
    import asyncio
    reader, writer = await asyncio.open_connection(host, port)
    payload = body if body is not None else b""
    writer.write(f"{method} {path} HTTP/1.1\r\nHost: smoke\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n"
                 .encode() + payload)
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), timeout=timeout)
    writer.close()
    head, _, rest = raw.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        if ":" in ln:
            k, v = ln.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return status, headers, rest


def parse_sse(body: bytes):
    """data: events -> (list of JSON chunks, saw [DONE])."""
    import json
    chunks, done = [], False
    for ev in body.split(b"\n\n"):
        if not ev.startswith(b"data: "):
            continue
        if ev == b"data: [DONE]":
            done = True
        else:
            chunks.append(json.loads(ev[6:]))
    return chunks, done


async def _smoke_client(gw) -> None:
    """Exercise every endpoint against a live gateway and assert the
    PR's acceptance behaviors: >1 SSE flush, streamed == offline token
    ids, parsable Prometheus text with per-pool series, a forced
    re-plan tick that moves the live boundary on short-shifted
    traffic, structured 4xx."""
    import json
    import re
    host, port = gw.host, gw.port
    prompt = "smoke fleet serving demo " * 6

    status, _, body = await http_call(host, port, "GET", "/health")
    h = json.loads(body)
    assert status == 200 and h["status"] == "ok", (status, h)
    print(f"smoke /health ok: pools={list(h['pools'])} "
          f"boundaries={h['boundaries']}")

    req = json.dumps({"prompt": prompt, "max_tokens": 12,
                      "stream": True}).encode()
    status, headers, body = await http_call(host, port, "POST",
                                            "/v1/completions", req)
    assert status == 200, body[:200]
    assert headers.get("content-type") == "text/event-stream", headers
    chunks, done = parse_sse(body)
    token_chunks = [c for c in chunks
                    if c["choices"][0]["finish_reason"] is None]
    streamed = [t for c in token_chunks
                for t in c["choices"][0]["token_ids"]]
    assert done and len(token_chunks) > 1, \
        f"want >1 flush + [DONE], got {len(token_chunks)} flushes"
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    print(f"smoke SSE ok: {len(token_chunks)} flushes / "
          f"{len(streamed)} tokens from pool "
          f"{chunks[-1]['fleetopt']['pool']}")

    # same prompt through the non-streaming path: decode is
    # deterministic argmax, so the ids must match bitwise
    req = json.dumps({"prompt": prompt, "max_tokens": 12}).encode()
    status, _, body = await http_call(host, port, "POST",
                                      "/v1/completions", req)
    offline = json.loads(body)["choices"][0]["token_ids"]
    assert status == 200 and offline == streamed, (streamed, offline)
    print("smoke parity ok: streamed ids == offline drain ids")

    status, _, body = await http_call(host, port, "POST",
                                      "/v1/completions", b"{not json")
    err = json.loads(body)
    assert status == 400 and err["error"]["type"] \
        == "invalid_request_error", (status, err)

    # a short-prompt burst so the re-planner's window is clearly
    # short-shifted relative to the provisioned boundaries
    for i in range(6):
        req = json.dumps({"prompt": f"short {i} " * 3,
                          "max_tokens": 8}).encode()
        status, _, _ = await http_call(host, port, "POST",
                                       "/v1/completions", req)
        assert status == 200
    b_before = list(gw.runtime.router.boundaries)
    status, _, body = await http_call(host, port, "POST",
                                      "/admin/replan")
    rep = json.loads(body)
    assert status == 200 and rep["tick"] >= 1, rep
    assert rep["applied"], f"re-plan did not move boundaries: {rep}"
    b_after = list(gw.runtime.router.boundaries)
    assert b_after == rep["boundaries_after"]
    assert all(a <= b for a, b in zip(b_after, b_before)), \
        (b_before, b_after)
    print(f"smoke re-plan ok: boundaries {b_before} -> {b_after} "
          f"(reason: {rep['reason']})")

    status, _, body = await http_call(host, port, "GET", "/metrics")
    text = body.decode()
    assert status == 200
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9eE+.in-]+$')
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert sample_re.match(line), f"bad metric line: {line!r}"
    for needle in ('fleetopt_dispatches_total{pool="short"}',
                   'fleetopt_boundary_tokens{index="0"}',
                   "fleetopt_replan_applied_total",
                   "fleetopt_stream_flushes_total"):
        assert needle in text, f"missing metric {needle}"
    gauge = float([ln for ln in text.splitlines()
                   if ln.startswith('fleetopt_boundary_tokens{index="0"}')
                   ][0].split()[-1])
    assert int(gauge) == b_after[0], (gauge, b_after)
    print("smoke /metrics ok: Prometheus text parses, boundary gauge "
          "tracks the applied re-plan")


async def _chaos_client(gw) -> None:
    """Fault-injection smoke (DESIGN.md §Live re-provisioning): kill an
    engine mid-stream and assert the live stream completes with tokens
    bitwise identical to an unfaulted run (crash recovery migrates the
    checkpointed request one pool up, SSE cursor intact), the dead pool
    503s with Retry-After during its blackout, and a post-blackout
    retry serves the same tokens again."""
    import asyncio
    import json
    from repro.serving.reconfigure import FaultInjector
    host, port = gw.host, gw.port
    prompt = "chaos smoke fleet serving " * 4
    max_tokens = 32

    # unfaulted reference: which pool serves this prompt + its tokens
    req = json.dumps({"prompt": prompt,
                      "max_tokens": max_tokens}).encode()
    status, _, body = await http_call(host, port, "POST",
                                      "/v1/completions", req)
    ref = json.loads(body)
    assert status == 200, body[:200]
    ref_ids = ref["choices"][0]["token_ids"]
    victim = ref["fleetopt"]["pool"]

    # live stream on the victim pool, killed after its first flush
    sreq = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": True}).encode()
    stream_task = asyncio.ensure_future(
        http_call(host, port, "POST", "/v1/completions", sreq))
    tok0, done0 = gw.tokens_streamed, gw.completions_done
    for _ in range(20_000):
        if gw.tokens_streamed > tok0:
            break
        await asyncio.sleep(0.001)
    assert gw.tokens_streamed > tok0, "stream never flushed"
    assert gw.completions_done == done0, "stream finished before kill"
    async with gw._lock:
        FaultInjector(gw.runtime).kill(victim)
    print(f"chaos: killed pool {victim!r} mid-stream")

    # the driver hits EngineDead on its next step and recovers inline;
    # probe the blackout 503 as soon as the restart counter ticks
    for _ in range(20_000):
        if gw.runtime.reprovision_stats["engine_restarts"] >= 1:
            break
        await asyncio.sleep(0.001)
    assert gw.runtime.reprovision_stats["engine_restarts"] >= 1, \
        "driver never recovered the killed engine"
    status, headers, body = await http_call(host, port, "POST",
                                            "/v1/completions", req)
    assert status == 503, (status, body[:200])
    retry_after = int(headers["retry-after"])
    assert retry_after >= 1, headers
    err = json.loads(body)["error"]
    assert err["type"] == "overloaded_error", err
    print(f"chaos: blackout 503 ok (Retry-After: {retry_after}s)")

    # the killed stream must still deliver EVERY token, bitwise
    status, _, body = await stream_task
    assert status == 200, body[:200]
    chunks, done = parse_sse(body)
    streamed = [t for c in chunks
                if c["choices"][0]["finish_reason"] is None
                for t in c["choices"][0]["token_ids"]]
    assert done and streamed == ref_ids, (streamed, ref_ids)
    final = [c for c in chunks
             if c["choices"][0]["finish_reason"] is not None][-1]
    print(f"chaos: killed stream completed bitwise on pool "
          f"{final['fleetopt']['pool']!r} ({len(streamed)} tokens)")

    # after the blackout the pool serves again — same tokens
    await asyncio.sleep(retry_after)
    status, _, body = await http_call(host, port, "POST",
                                      "/v1/completions", req)
    assert status == 200, (status, body[:200])
    retry_ids = json.loads(body)["choices"][0]["token_ids"]
    assert retry_ids == ref_ids, (retry_ids, ref_ids)
    print("chaos: post-blackout retry ok (tokens bitwise identical)")

    status, _, body = await http_call(host, port, "GET", "/metrics")
    text = body.decode()
    for needle in ("fleetopt_engine_restarts_total",
                   "fleetopt_migrated_requests_total"):
        line = [ln for ln in text.splitlines()
                if ln.startswith(needle)][0]
        assert float(line.split()[-1]) >= 1, line
    print("chaos: /metrics ok (restart + migration counters visible)")


def serve_http(args) -> None:
    """Run the asyncio gateway over a planned fleet: ``--http PORT``
    serves until killed; ``--smoke`` binds an ephemeral port, runs the
    in-process client against it and exits nonzero on any failure
    (``--chaos`` adds the fault-injection pass)."""
    import asyncio

    from repro.serving.replanner import Replanner
    from repro.serving.server import ServingGateway

    rt, plan = build_fleet_runtime(args)
    print(f"runtime pools: boundaries={rt.router.boundaries} "
          f"gammas={rt.router.gammas} "
          f"contexts={[e.c_max for e in rt.engines.values()]}")
    rp = Replanner(rt, min_observed=4, n_samples=2048)
    # chaos needs a blackout window long enough for the in-process
    # client to observe the 503 between recovery and its probe
    gw = ServingGateway(rt, replanner=rp, port=0 if args.smoke
                        else args.http,
                        replan_interval_s=args.replan_interval,
                        blackout_s=3.0 if args.chaos else 0.25)

    async def smoke():
        await gw.start()
        print(f"smoke gateway on {gw.host}:{gw.port}")
        try:
            await _smoke_client(gw)
            if args.chaos:
                await _chaos_client(gw)
        finally:
            await gw.stop()

    async def forever():
        await gw.start()
        print(f"gateway listening on http://{gw.host}:{gw.port} "
              f"(POST /v1/completions, GET /health, GET /metrics, "
              f"POST /admin/replan)")
        assert gw._server is not None
        async with gw._server:
            await gw._server.serve_forever()

    if args.spans:
        rt.tracer.start()
    try:
        if args.smoke:
            t0 = time.time()
            asyncio.run(smoke())
            print(f"serve smoke passed in {time.time() - t0:.1f}s")
            return
        try:
            asyncio.run(forever())
        except KeyboardInterrupt:
            pass
    finally:
        if args.spans:
            write_spans(rt.tracer, args.spans)


def write_spans(tracer, path: str) -> None:
    """Stop ``tracer`` and write its spans to ``path``, one JSON object
    a line (times in ``time.perf_counter_ns`` nanoseconds)."""
    tracer.stop()
    spans = tracer.take()
    with open(path, "w") as f:
        for sp in spans:
            f.write(json.dumps(sp._asdict()) + "\n")
    print(f"{len(spans)} spans written to {path}"
          + (f" ({tracer.dropped} dropped: the list was full)"
             if tracer.dropped else ""))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=0, metavar="N",
                    help="serve only the first N layers of --arch "
                         "(0 = its published depth); widths stay")
    ap.add_argument("--mesh", default="")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--int8-kv", action="store_true")
    ap.add_argument("--fleet", type=int, default=0, metavar="K",
                    help="plan a K-pool fleet and serve through the "
                         "gateway (K engines) instead of the raw "
                         "pjit decode loop")
    ap.add_argument("--workload", default="azure",
                    choices=["azure", "lmsys", "agent-heavy"],
                    help="workload CDF for --fleet planning")
    ap.add_argument("--lam", type=float, default=1000.0,
                    help="arrival rate (req/s) for --fleet planning")
    ap.add_argument("--slots", default="2", metavar="N[,N...]",
                    help="--fleet engine slots per pool (capped by the "
                         "plan's per-GPU slot count): one number for "
                         "every pool, or one per pool")
    ap.add_argument("--top-context", type=int, default=512,
                    metavar="TOKENS",
                    help="--fleet top pool's context; every planned "
                         "boundary scales by TOKENS / the plan's top "
                         "context (default 512: the CPU demo)")
    ap.add_argument("--c-chunk", type=int, default=16, metavar="TOKENS",
                    help="--fleet engines' prefill chunk length")
    ap.add_argument("--decode-impl", default="xla",
                    choices=["xla", "pallas"],
                    help="--fleet engines' decode attention: the XLA "
                         "gather path, or the Pallas paged/flash "
                         "kernel (one device per engine: not with "
                         "--tp > 1)")
    ap.add_argument("--paged", action="store_true",
                    help="--fleet engines use the paged KV cache "
                         "(block-table allocator; same output tokens)")
    ap.add_argument("--decode-k", type=int, default=1, metavar="K",
                    help="--fleet engines run K decode iterations per "
                         "host dispatch (on-device lax.scan micro-loop; "
                         "same output tokens, ~K-fold fewer host "
                         "round-trips in decode-only steady state)")
    ap.add_argument("--spec-k", type=int, default=1, metavar="W",
                    help="--fleet engines self-speculate with verify "
                         "windows of W tokens (n-gram prompt-lookup "
                         "drafts checked by the model's own argmax in "
                         "the decode scan; bitwise-same output tokens, "
                         ">1 of them per iteration on repetitive "
                         "traffic)")
    ap.add_argument("--tp", type=int, default=1, metavar="D",
                    help="--fleet engines run tensor-parallel over D "
                         "devices each (submeshes of --mesh or of a "
                         "flat mesh over all devices; same output "
                         "tokens, 1/D per-device KV)")
    ap.add_argument("--preemption", action="store_true",
                    help="--fleet engines survive overload by LIFO "
                         "preemption with a host-offload KV tier "
                         "(implies --paged): admission pressure swaps "
                         "a decoding slot's blocks to host RAM (or "
                         "discards for recompute) and resumes it "
                         "bitwise-identically ahead of new arrivals")
    ap.add_argument("--max-queue-wait", type=float, default=None,
                    metavar="ITERS",
                    help="--fleet engines shed new requests once the "
                         "rolling queue-wait estimate exceeds this many "
                         "iterations (stability-aware admission; "
                         "bounded queue instead of TTFT collapse)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="--fleet engines share full prompt blocks via "
                         "the ref-counted prefix cache (implies --paged) "
                         "and demo a two-turn session with gateway "
                         "affinity")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the --fleet runtime over the asyncio "
                         "HTTP gateway (OpenAI-compatible "
                         "/v1/completions with SSE streaming, /health, "
                         "Prometheus /metrics, /admin/replan) instead "
                         "of the offline demo batch")
    ap.add_argument("--smoke", action="store_true",
                    help="with --fleet: bind an ephemeral port, run the "
                         "in-process smoke client against every "
                         "endpoint (streaming parity, metrics parse, "
                         "forced re-plan) and exit nonzero on failure")
    ap.add_argument("--chaos", action="store_true",
                    help="with --smoke: also kill one engine mid-stream "
                         "via the fault injector and assert the stream "
                         "completes bitwise after crash recovery, the "
                         "dead pool 503s with Retry-After during its "
                         "blackout, and a post-blackout retry matches")
    ap.add_argument("--autoscale", action="store_true",
                    help="--fleet engines may be LIVE-REBUILT by the "
                         "re-planner when a tick's context/GPU-count "
                         "delta exceeds its hysteresis (zero-drop KV "
                         "migration; DESIGN.md §Live re-provisioning)")
    ap.add_argument("--spans", default=None, metavar="FILE",
                    help="with --http or --smoke: record the gateway's, "
                         "router's and engines' program spans (each also "
                         "a jax.profiler annotation) and write them to "
                         "FILE as JSON lines on exit")
    ap.add_argument("--replan-interval", type=float, default=None,
                    metavar="SECONDS",
                    help="run a re-planner tick every S seconds "
                         "(--http mode; /admin/replan always works)")
    return ap.parse_args(argv)


def main():
    args = parse_args()
    enable_compile_cache()

    if args.fleet:
        if args.http is not None or args.smoke:
            serve_http(args)
        else:
            serve_fleet(args)
        return

    cfg = model_config(args)
    if args.int8_kv:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = jax.device_count(), 1
    mesh = make_mesh((d, m), ("data", "model"))
    ctx = make_context(mesh)
    cache_len = args.prompt_len + args.new_tokens

    key = jax.random.PRNGKey(0)
    params = init_params(cfg)
    toks = jax.random.randint(key, (args.batch, args.prompt_len), 1,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.frontend_tokens:
        batch["frontend"] = jnp.ones(
            (args.batch, cfg.frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.01

    pspecs = SH.param_specs(jax.eval_shape(lambda: params), ctx)
    params = jax.device_put(params, SH.to_named(pspecs, mesh))

    def prefill(params, batch):
        # cache_len is a static python int (closure), not a traced value
        return M.prefill(params, cfg, dict(batch, cache_len=cache_len),
                         parallel=ctx)

    def decode(params, tok, cache, pos):
        return M.decode_step(params, cfg, tok, cache, pos, parallel=ctx)

    t0 = time.time()
    logits, cache = jax.jit(prefill)(params, batch)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    out = [nxt]
    dstep = jax.jit(decode)
    for t in range(args.new_tokens - 1):
        logits, cache = dstep(params, nxt, cache, args.prompt_len + t)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        out.append(nxt)
    out = jnp.concatenate(out, axis=1)
    dt = time.time() - t0
    print(f"arch={cfg.name} mesh {d}x{m} batch={args.batch} "
          f"prompt={args.prompt_len} -> {args.new_tokens} new tokens "
          f"in {dt:.1f}s ({args.batch * args.new_tokens / dt:.1f} tok/s)")
    print("first sequence:", out[0].tolist())


if __name__ == "__main__":
    main()

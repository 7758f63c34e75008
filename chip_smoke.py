#!/usr/bin/env python3
"""Bring-up check of the FleetOpt served path on one TPU.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # tensor-parallel pool vs one chip

One chip: plans the ``azure`` fleet with two pools, builds the runtime
through ``repro.launch.serve`` (the launcher's own flags, at chip
sizes), and serves real HTTP completions through ``ServingGateway``:
HTTP gateway -> router with compress-and-route (C&R) -> two-pool
``FleetRuntime`` -> paged ``InferenceEngine``s -> compiled Pallas paged
decode. The model is Minitron-8B at its published widths with random
weights from seed 0; only its depth is cut (``--layers``), so weights,
two full KV pools and a step's temporaries fit one chip's HBM. It then
checks, at the same widths and dtype, that the engine's step functions
(``paged_prefill_chunk`` then ``paged_decode_step``, Pallas and XLA)
give the logits of the teacher-forced ``forward`` over one served
request's tokens, and that the decode step lowers to a Mosaic kernel.

Four chips (``--four-chips``): one pool tensor-parallel over the four
chips against the same configuration on one chip, both on the same
requests and tokens: logits within tolerance, KV bytes per device at
1/4.

Every phase runs in this one process, which holds the chip(s). Any
failure raises; the last line of standard output is the JSON result
only when every phase passed.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Minitron-8B has 32 layers. One layer is ~176 M parameters (0.33 GiB
# in bf16), the untied 256k-vocab embedding plus head ~3.9 GiB, so all
# 32 layers (~14.4 GiB) leave no room for a KV cache on a 16 GiB v5e.
# 12 layers hold 7.8 GiB of weights; KV is 12 x 2 x 8 x 128 x 2 B =
# 48 KiB per token, so each 32k-token pool below takes 1.5 GiB, and the
# largest step (a fused prefill chunk + decode over the short pool)
# needs ~3 GiB of temporaries (compiled.memory_analysis() for v5e).
# The 20 layers cut here would sit on further pipeline stages.
PUBLISHED_LAYERS = 32
LAYERS = 12
MAX_TOKENS = 24

# The launcher's flags at chip sizes. The plan's boundary (8192) and
# top context (65536) scale by 16384 / 65536: a short pool of 16 slots
# at 2048 tokens and a long pool of 2 slots at 16384 tokens.
ONE_CHIP_FLEET = [
    "--arch", "minitron-8b", "--layers", str(LAYERS), "--fleet", "2",
    "--workload", "azure", "--paged", "--decode-k", "4",
    "--decode-impl", "pallas", "--slots", "16,2",
    "--top-context", "16384", "--c-chunk", "64"]

# One pool of 16 slots at 2048 tokens, XLA decode (the Pallas kernels
# run on one device), on one chip and tensor-parallel over four.
FOUR_CHIP_POOL = [
    "--arch", "minitron-8b", "--layers", str(LAYERS), "--fleet", "1",
    "--workload", "azure", "--paged", "--decode-k", "4",
    "--decode-impl", "xla", "--slots", "16", "--top-context", "2048",
    "--c-chunk", "64"]

# Logit tolerances, as max |a - b| over max |reference|. bf16 keeps 8
# significand bits (relative step 2^-8 ~ 0.4%). The two sides of each
# comparison round at different points: the step path attends over
# gathered KV pages and, with Pallas, keeps the softmax and P @ V in
# float32 before one cast, while forward() and the XLA decode round the
# attention weights to bf16; each layer's difference of a few bf16
# steps then feeds the next layer's residual stream. On the CPU backend
# in bf16 at d_model 512 and 1024 (12 layers) these errors measure
# 0.013-0.020 for every pair below, at any width tried. 2^-4 is about
# three times that, and still far below what a wrong mask, position or
# block table gives (errors of order 1).
TOL_STEP_VS_FORWARD = 2.0 ** -4
# Pallas vs XLA decode on the same cache: they differ only inside
# attention, but by as much as either differs from forward().
TOL_PALLAS_VS_XLA = 2.0 ** -4
# Tensor parallelism sums each row-parallel matmul's partial products
# across chips in another order than one chip does, which moves bf16
# results by rounding steps in every layer: the same budget.
TOL_TP4_VS_ONE = 2.0 ** -4


def log(msg: str) -> None:
    print(msg, flush=True)


def prompt_text(n_tokens: int, topic: str) -> str:
    """Distinct sentences (the C&R compressor ranks sentences), about
    ``n_tokens`` tokens under the 4-bytes-per-token tokenizer."""
    out, i = [], 0
    while len(" ".join(out).encode()) < 4 * n_tokens:
        out.append(f"Note {i} on the {topic}: pool {i % 7} serves "
                   f"requests of length band {i % 13} under load {i}.")
        i += 1
    return " ".join(out)


class CompileTimes:
    """Backend compile seconds per jitted function, from JAX's
    monitoring events."""

    def __init__(self):
        self.secs = defaultdict(float)
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs[kw.get("fun_name", "?")] += duration

    def report(self, min_secs: float = 0.5) -> None:
        for name, s in sorted(self.secs.items(), key=lambda kv: -kv[1]):
            if s >= min_secs:
                log(f"  compile {s:7.2f}s  {name}")


def memory_report(tag: str) -> None:
    import jax
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        log(f"memory[{tag}] device {d.id}: peak_bytes_in_use="
            f"{st.get('peak_bytes_in_use')} bytes_in_use="
            f"{st.get('bytes_in_use')} bytes_limit={st.get('bytes_limit')}")


# ---------------------------------------------------------------- serving
async def serve_requests(gw, requests):
    """Send each (label, body) completion to the live gateway, in
    order, and return the parsed responses by label."""
    from repro.launch.serve import http_call, parse_sse
    out = {}
    for label, body in requests:
        t0 = time.time()
        status, _, raw = await http_call(gw.host, gw.port, "POST",
                                         "/v1/completions",
                                         json.dumps(body).encode(),
                                         timeout=gw.request_timeout_s)
        assert status == 200, (label, status, raw[:300])
        if body.get("stream"):
            chunks, done = parse_sse(raw)
            assert done, f"{label}: stream ended without [DONE]"
            ids = [t for c in chunks
                   if c["choices"][0]["finish_reason"] is None
                   for t in c["choices"][0]["token_ids"]]
            final = chunks[-1]
        else:
            final = json.loads(raw)
            ids = final["choices"][0]["token_ids"]
        finish = final["choices"][0]["finish_reason"]
        ann = final["fleetopt"]
        out[label] = dict(ids=ids, pool=ann["pool"],
                          compressed=ann["compressed"], finish=finish)
        log(f"request {label:10s}: pool={ann['pool']:5s} "
            f"C&R={'yes' if ann['compressed'] else 'no '} "
            f"L_eff={ann['l_total_effective']:5d} "
            f"prefill_iters={ann['prefill_iters']:3d} tokens={len(ids)} "
            f"finish={finish} ({time.time() - t0:.2f}s)")
        assert len(ids) == body["max_tokens"] or finish == "stop", \
            f"{label}: {len(ids)} tokens of {body['max_tokens']}, " \
            f"finish={finish}"
    return out


def serve_phase(rt):
    """Real HTTP completions through the gateway: short, borderline
    (C&R into the short pool), long, and the short prompt streamed."""
    from repro.serving.server import ServingGateway
    bounds = rt.router.boundaries
    short = prompt_text(bounds[0] // 8, "short pool")
    border = prompt_text(int(bounds[0] * 1.25), "borderline retrieval")
    long = prompt_text(int(bounds[0] * rt.router.gammas[0] * 1.2),
                       "long document")
    requests = [
        ("short", dict(prompt=short, max_tokens=MAX_TOKENS)),
        ("borderline", dict(prompt=border, max_tokens=MAX_TOKENS,
                            category="rag")),
        ("long", dict(prompt=long, max_tokens=MAX_TOKENS)),
        ("stream", dict(prompt=short, max_tokens=MAX_TOKENS,
                        stream=True)),
    ]
    gw = ServingGateway(rt, port=0, request_timeout_s=900.0)

    async def run():
        await gw.start()
        log(f"gateway on {gw.host}:{gw.port}")
        try:
            return await serve_requests(gw, requests)
        finally:
            await gw.stop()

    t0 = time.time()
    res = asyncio.run(run())
    served = sum(len(r["ids"]) for r in res.values())
    log(f"served {len(res)} requests, {served} tokens in "
        f"{time.time() - t0:.1f}s (bring-up observation, not a benchmark)")
    assert res["short"]["pool"] == "short" and not res["short"]["compressed"]
    assert res["borderline"]["pool"] == "short" \
        and res["borderline"]["compressed"], res["borderline"]
    assert res["long"]["pool"] == "long", res["long"]
    assert res["stream"]["ids"] == res["short"]["ids"], \
        (res["stream"]["ids"], res["short"]["ids"])
    log("stream ids == non-stream ids for the same request")
    return rt.tokenizer.encode(short), res["short"]["ids"]


# ----------------------------------------------------------- correctness
def rel_err(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def step_logits(cfg, params, prompt, gen, cache_shardings, impls):
    """Logits of the engine's paged step functions over ``prompt`` then
    ``gen``: one ``paged_prefill_chunk`` over the prompt, then one
    ``paged_decode_step`` per generated token, per decode impl.
    Returns {impl: (len(gen) + 1, V) float32 logits}; row i predicts
    token len(prompt) + i."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.profiles import DEFAULT_KV_BLOCK as BS
    from repro.models import model as M
    n_tok = len(prompt) + len(gen)
    nb = -(-n_tok // BS)
    cache = M.init_paged_cache(cfg, nb, BS, shardings=cache_shardings)
    bt = jnp.arange(nb, dtype=jnp.int32)[None]
    prefill = jax.jit(lambda p, c, t, bt, l: M.paged_prefill_chunk(
        p, cfg, t, c, bt, jnp.zeros((1,), jnp.int32), l))
    first, cache = prefill(params, cache, jnp.asarray([prompt], jnp.int32),
                           bt, jnp.asarray([len(prompt)], jnp.int32))
    out = {}
    for impl in impls:
        decode = jax.jit(lambda p, c, t, bt, pos, impl=impl:
                         M.paged_decode_step(p, cfg, t, c, bt, pos,
                                             decode_impl=impl))
        rows, c = [first], cache
        for i, tok in enumerate(gen):
            lg, c = decode(params, c, jnp.asarray([[tok]], jnp.int32), bt,
                           jnp.asarray([len(prompt) + i], jnp.int32))
            rows.append(lg)
        out[impl] = np.concatenate([np.asarray(r, np.float32)
                                    for r in rows])
    return out


def logits_phase(cfg, params, prompt, gen):
    """Engine step functions vs teacher-forced forward, Pallas vs XLA,
    and the Pallas kernel compiled into the lowered decode step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.profiles import DEFAULT_KV_BLOCK as BS
    from repro.models import model as M
    toks = jnp.asarray([list(prompt) + list(gen)], jnp.int32)
    ref, _ = jax.jit(lambda p, t: M.forward(p, cfg, {"tokens": t}))(
        params, toks)
    ref = np.asarray(ref[0, len(prompt) - 1:], np.float32)
    got = step_logits(cfg, params, prompt, gen, None, ("pallas", "xla"))
    e_pal = rel_err(got["pallas"], ref[:len(gen) + 1])
    e_xla = rel_err(got["xla"], ref[:len(gen) + 1])
    e_px = rel_err(got["pallas"], got["xla"])
    agree = float(np.mean(got["pallas"].argmax(-1)
                          == ref[:len(gen) + 1].argmax(-1)))
    log(f"logits over {len(prompt)} prompt + {len(gen)} decoded tokens "
        f"(max|ref|={np.max(np.abs(ref)):.3f}): "
        f"pallas-vs-forward={e_pal:.5f} xla-vs-forward={e_xla:.5f} "
        f"(tol {TOL_STEP_VS_FORWARD}), pallas-vs-xla={e_px:.5f} "
        f"(tol {TOL_PALLAS_VS_XLA}); argmax agreement {agree:.3f}")
    assert e_pal <= TOL_STEP_VS_FORWARD, e_pal
    assert e_xla <= TOL_STEP_VS_FORWARD, e_xla
    assert e_px <= TOL_PALLAS_VS_XLA, e_px

    # the decode step the engine jits, lowered for this device
    nb = 4
    cache = jax.eval_shape(lambda: M.init_paged_cache(cfg, nb, BS))
    lowered = jax.jit(lambda p, c, t, bt, pos: M.paged_decode_step(
        p, cfg, t, c, bt, pos, decode_impl="pallas")).lower(
        params, cache, jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, nb), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    assert "tpu_custom_call" in lowered.as_text(), \
        "the Pallas decode kernel did not lower to a Mosaic custom call"
    log("paged_decode_step(decode_impl='pallas') lowers to tpu_custom_call")


# ------------------------------------------------------------ the phases
def one_chip(fleet_argv) -> None:
    from repro.launch import serve
    args = serve.parse_args(fleet_argv)
    cfg = serve.model_config(args)
    log(f"model {cfg.name}: d_model={cfg.d_model} heads={cfg.num_heads} "
        f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} ({cfg.activation}, no gate) "
        f"vocab={cfg.vocab_size} untied_head={not cfg.tie_embeddings} "
        f"dtype={cfg.dtype}; depth {cfg.num_layers} of "
        f"{PUBLISHED_LAYERS} published layers (widths as published; the "
        f"cut layers would sit on further pipeline stages)")
    t0 = time.time()
    rt, _ = serve.build_fleet_runtime(args)
    log(f"runtime built in {time.time() - t0:.1f}s; KV pool bytes: "
        + ", ".join(f"{n}={e.cache_bytes_per_device()}"
                    for n, e in rt.engines.items()))
    assert [e.decode_impl for e in rt.engines.values()] == ["pallas"] * 2
    memory_report("runtime built")
    prompt, gen = serve_phase(rt)
    memory_report("after serving")
    params = rt.params
    del rt
    gc.collect()
    logits_phase(cfg, params, prompt, gen[:8])


def four_chips(pool_argv) -> None:
    import jax
    import numpy as np
    from repro.distributed import sharding as SH
    from repro.launch import serve
    from repro.models import model as M
    from repro.serving.pools import GatewayRequest
    from repro.core.profiles import DEFAULT_KV_BLOCK as BS
    results = {}
    for tp in (1, 4):
        args = serve.parse_args(pool_argv + ["--tp", str(tp)])
        cfg = serve.model_config(args)
        rt, _ = serve.build_fleet_runtime(args)
        (eng,) = rt.engines.values()
        prompts = [prompt_text(eng.c_max // d, f"tp request {d}")
                   for d in (16, 4, 2)]
        for rid, text in enumerate(prompts):
            rt.submit(GatewayRequest(rid, text, MAX_TOKENS))
        out = rt.run(max_iters=20_000)
        toks = {rid: r.output_tokens for rid, r in sorted(out.items())}
        assert all(len(t) == MAX_TOKENS for t in toks.values()), toks
        shard = None
        if eng.mesh is not None:
            n_tok = len(rt.tokenizer.encode(prompts[0])) + 8
            struct = jax.eval_shape(lambda: M.init_paged_cache(
                cfg, -(-n_tok // BS), BS))
            shard = SH.to_named(SH.serving_cache_specs(
                struct, eng.parallel, paged=True), eng.mesh)
        prompt = rt.tokenizer.encode(prompts[0])
        gen = results[1]["tokens"][0][:8] if tp == 4 else toks[0][:8]
        lg = step_logits(cfg, eng.params, prompt, gen, shard, ("xla",))
        results[tp] = dict(tokens=toks, logits=lg["xla"],
                           kv_bytes=eng.cache_bytes_per_device(),
                           devices=[d.id for d in eng.devices()])
        log(f"tp={tp}: devices={results[tp]['devices']} "
            f"kv_bytes_per_device={results[tp]['kv_bytes']}")
        memory_report(f"tp={tp}")
        del rt, eng
        gc.collect()
    err = rel_err(results[4]["logits"], results[1]["logits"])
    same = sum(int(np.array_equal(results[4]["tokens"][r],
                                  results[1]["tokens"][r]))
               for r in results[1]["tokens"])
    log(f"tp=4 vs one chip: logits rel err {err:.5f} (tol "
        f"{TOL_TP4_VS_ONE}); {same}/{len(prompts)} requests emitted "
        f"identical tokens")
    assert err <= TOL_TP4_VS_ONE, err
    assert results[4]["kv_bytes"] * 4 == results[1]["kv_bytes"], \
        (results[4]["kv_bytes"], results[1]["kv_bytes"])
    assert len(results[4]["devices"]) == 4
    log("KV bytes per device at tp=4 are 1/4 of one chip's")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="compare a pool tensor-parallel over 4 chips "
                         "with the same pool on one chip (and nothing "
                         "else)")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{dev.platform!r}")
    if args.four_chips and len(jax.devices()) != 4:
        raise SystemExit(f"--four-chips needs 4 chips, found "
                         f"{len(jax.devices())}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    compiles = CompileTimes()
    t0 = time.time()
    if args.four_chips:
        four_chips(FOUR_CHIP_POOL)
    else:
        one_chip(ONE_CHIP_FLEET)
    log("compile seconds per jitted function (>= 0.5 s):")
    compiles.report()
    memory_report("end")
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

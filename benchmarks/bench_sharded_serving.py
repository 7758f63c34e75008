"""Mesh-sharded serving engine: tp-degree sweep (beyond-paper;
DESIGN.md §Sharded serving).

For tp in {1, 2, 4}, runs the SAME ragged request stream through a
tiny-model engine whose KV cache + params shard over a tp-device
submesh (faked on CPU via XLA's host-platform device count), and
records:

1. **Decode-only steps/s** — the best-of-N steady-state window
   protocol shared with bench_engine_hotpath. On a faked CPU mesh the
   collectives are emulated in-process, so ABSOLUTE throughput drops
   with tp and is reported for trajectory only, never gated.
2. **Per-device KV bytes** — ``engine.cache_bytes_per_device()``;
   must scale as 1/tp (the kv-head-sharded pool really splits), the
   deterministic ``hbm_scaling_ok`` flag.
3. **Output-token parity** — every tp must emit bitwise the tp=1
   engine's tokens (``token_parity``; the gate's hard invariant, same
   contract tests/test_decode_consistency.py pins).

The sweep needs >= 4 devices but benchmarks.run imports jax with
whatever the host has, and XLA_FLAGS is read at first jax import — so
``run()`` re-execs this file in a SUBPROCESS with
``--xla_force_host_platform_device_count=8`` appended when the current
process sees fewer, then reads the record back.

Writes benchmarks/results/sharded_serving.csv and the repo-root
``BENCH_sharded_serving.json`` (gated on the deterministic flags by
benchmarks/check_regression.py).
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np                                               # noqa: E402

ROOT_JSON = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_sharded_serving.json")

TP_SWEEP = (1, 2, 4)
N_MAX, C_MAX, C_CHUNK, BLOCK = 4, 128, 16, 16


def _tiny_cfg():
    """bench_engine_hotpath's dispatch-bound tiny model, with 4 kv
    heads so the serving cache's HEAD-dim sharding rule (not the seq
    fallback) is what the tp=2/4 rows exercise."""
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config("llama3-70b").reduced(), dtype="float32",
        d_model=64, d_ff=128, num_heads=4, num_kv_heads=4, head_dim=16,
        vocab_size=256)


def _mesh_for(tp):
    if tp == 1:
        return None
    from repro.launch.mesh import make_smoke_mesh, make_submeshes
    return make_submeshes(make_smoke_mesh(), tp)[0]


def _fresh(cfg, params, tp):
    from repro.serving.engine import InferenceEngine
    return InferenceEngine(cfg, params, n_max=N_MAX, c_max=C_MAX,
                           c_chunk=C_CHUNK, paged=True, block_size=BLOCK,
                           mesh=_mesh_for(tp))


def _fill(eng, rng, rep):
    from repro.serving.engine import ServeRequest
    for rid in range(N_MAX):
        eng.submit(ServeRequest(
            rid=rep * 100 + rid,
            tokens=[int(t) for t in rng.integers(1, 200, 8)],
            max_new_tokens=100))
    while any(eng.slot_prefill_left[s] for s in range(eng.n_max)
              if eng.slot_req[s] is not None) or eng.waiting:
        eng.step()
    eng.step()


def _steady_steps_per_s(cfg, params, tp, quick):
    rng = np.random.default_rng(0)
    eng = _fresh(cfg, params, tp)
    reps = 2 if quick else 4
    n_disp = 12 if quick else 32
    best = 0.0
    for rep in range(reps):
        _fill(eng, rng, rep)
        it0, t0 = eng.iteration, time.perf_counter()
        for _ in range(n_disp):
            eng.step()
        dt = time.perf_counter() - t0
        assert not eng.results, "a request finished inside the window"
        best = max(best, (eng.iteration - it0) / dt)
        eng.run_to_completion(100_000)
        eng.results.clear()
    return best, eng


def _token_stream(cfg, params, tp):
    """Deterministic ragged stream -> {rid: output_tokens} at this tp."""
    from repro.serving.engine import ServeRequest
    rng = np.random.default_rng(7)
    eng = _fresh(cfg, params, tp)
    for rid in range(6):
        eng.submit(ServeRequest(
            rid=rid,
            tokens=[int(t) for t in rng.integers(1, 200,
                                                 int(rng.integers(3, 40)))],
            max_new_tokens=int(rng.integers(2, 10))))
    res = eng.run_to_completion(100_000)
    return {rid: r.output_tokens for rid, r in sorted(res.items())}


def _run_local(quick: bool) -> dict:
    import jax
    from benchmarks.common import emit
    from repro.models import model as M
    cfg = _tiny_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    rows, tokens = [], {}
    for tp in TP_SWEEP:
        steps, eng = _steady_steps_per_s(cfg, params, tp, quick)
        tokens[tp] = _token_stream(cfg, params, tp)
        rows.append({"tp": tp,
                     "devices": len(eng.devices()),
                     "steps_per_s": round(steps, 1),
                     "kv_bytes_per_device": eng.cache_bytes_per_device()})
    emit("sharded_serving", rows)

    base_bytes = rows[0]["kv_bytes_per_device"]
    hbm_ok = all(r["kv_bytes_per_device"] * r["tp"] == base_bytes
                 for r in rows)
    parity = all(tokens[tp] == tokens[1] for tp in TP_SWEEP)
    by_tp = {r["tp"]: r for r in rows}
    record = {
        "rows": rows,
        "token_parity": bool(parity),
        "hbm_scaling_ok": bool(hbm_ok),
        # trajectory only (CPU-emulated collectives), never gated
        "steps_ratio_tp4_vs_tp1": round(
            by_tp[4]["steps_per_s"] / by_tp[1]["steps_per_s"], 3),
        "hbm_ratio_tp4_vs_tp1": round(
            by_tp[4]["kv_bytes_per_device"] / base_bytes, 4),
        "quick": quick,
    }
    with open(ROOT_JSON, "w") as f:
        json.dump(record, f, indent=2)
    print(f"# sharded serving: token_parity={parity} hbm_ok={hbm_ok} "
          f"bytes/dev {[r['kv_bytes_per_device'] for r in rows]} "
          f"steps/s {[r['steps_per_s'] for r in rows]} "
          f"-> {os.path.basename(ROOT_JSON)}")
    return record


def run(quick: bool = False) -> dict:
    """Entry point for benchmarks.run: a CPU parent that sees fewer
    than 4 devices re-execs in a subprocess with 8 faked CPU devices
    (XLA_FLAGS is consumed at first jax import, too late to set here).
    An accelerator parent with fewer than 4 devices raises: it holds
    the chip, and a CPU child would quietly benchmark the host."""
    import jax
    if jax.device_count() >= 4:
        return _run_local(quick)
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the sharded-serving sweep needs >= 4 "
            f"{jax.default_backend()} devices, found {jax.device_count()}")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__)]
    if quick:
        cmd.append("--quick")
    r = subprocess.run(cmd, env=env)
    if r.returncode:
        raise RuntimeError(
            f"sharded-serving bench subprocess failed (exit {r.returncode})")
    with open(ROOT_JSON) as f:
        return json.load(f)


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)

"""End-to-end driver: serve batched requests through the gateway with
Compress-and-Route on a small model (the paper's kind of system, at
laptop scale).

Builds the pool engines from a boundary vector (the generalized
FleetRuntime API — TwoPoolRuntime is its K=2 special case), pushes a
mixed batch of short / borderline / long prompts through the gateway,
and prints per-request routing + serving outcomes.

Run: PYTHONPATH=src python examples/serve_two_pool.py [--pools 3]

Multi-device (each pool engine tensor-parallel over 2 devices, faked
on a CPU host):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python examples/serve_two_pool.py --tp 2
"""
import argparse
import dataclasses
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.configs.base import get_config                       # noqa: E402
from repro.models import model as M                             # noqa: E402
from repro.serving.pools import FleetRuntime, GatewayRequest    # noqa: E402

B_SHORT, GAMMA = 256, 1.5


def make_prompt(n_sentences: int, topic: str) -> str:
    return " ".join(
        f"{topic} point {i}: systems provision fleets by context length "
        f"and queueing behaviour, with detail {i % 7}."
        for i in range(n_sentences))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pools", type=int, default=2, choices=(2, 3),
                    help="2 = the paper's short/long split; 3 adds a "
                         "mid-context pool (generalized boundary vector)")
    ap.add_argument("--tp", type=int, default=1, metavar="D",
                    help="tensor-parallel degree per pool engine "
                         "(needs D*pools devices for distinct "
                         "submeshes; same output tokens)")
    ap.add_argument("--mesh", default="", metavar="DxM",
                    help="global mesh shape to carve submeshes from "
                         "(default: one flat row over all devices)")
    args = ap.parse_args()

    mesh = None
    if args.tp > 1 or args.mesh:
        from repro.launch.mesh import make_mesh, make_smoke_mesh
        if args.mesh:
            d, m = (int(x) for x in args.mesh.split("x"))
            mesh = make_mesh((d, m), ("data", "model"))
        else:
            mesh = make_smoke_mesh()

    cfg = dataclasses.replace(get_config("llama3-70b").reduced(),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # The boundary vector is software only (enforced at the gateway):
    # pool i's engine provisions exactly its boundary's KV budget, the
    # top pool the worst case.  gamma_j widens boundary j's virtual
    # capacity via C&R with no hardware change (paper §5.1).
    if args.pools == 2:
        boundaries, gammas = (B_SHORT,), (GAMMA,)
        n_maxes, c_maxes = (4, 2), (B_SHORT, 4096)
    else:
        boundaries, gammas = (B_SHORT, 1024), (GAMMA, GAMMA)
        n_maxes, c_maxes = (4, 3, 2), (B_SHORT, 1024, 4096)
    rt = FleetRuntime(cfg, params, boundaries, gammas, n_maxes, c_maxes,
                      c_chunk=64, mesh=mesh, tp_degree=args.tp)
    if mesh is not None:
        for name, ids in rt.device_placement().items():
            print(f"  {name}: tp={args.tp} devices={ids}")
    requests = [
        GatewayRequest(0, "What is the cost cliff?", 8),
        GatewayRequest(1, make_prompt(3, "short"), 8),
        GatewayRequest(2, make_prompt(14, "borderline-rag"), 8,
                       category="rag"),
        GatewayRequest(3, make_prompt(14, "borderline-code"), 8,
                       category="code"),     # safety gate -> next pool up
        GatewayRequest(4, make_prompt(60, "long"), 8),
        GatewayRequest(5, make_prompt(13, "borderline-prose"), 8),
    ]
    print(f"{args.pools}-pool runtime: boundaries={boundaries} "
          f"gammas={gammas} (virtual capacities "
          f"{tuple(int(g * b) for b, g in zip(boundaries, gammas))})")
    for r in requests:
        d = rt.submit(r)
        print(f"  req {r.rid}: {r.category:5s} -> {d.pool:6s} "
              f"{'[C&R ' + format(d.compression_ms, '.1f') + 'ms]' if d.compressed else '':14s}"
              f" L_eff={d.l_total_effective}")
    results = rt.run(max_iters=5000)
    print("\nserved:")
    for rid in sorted(results):
        res = results[rid]
        print(f"  req {rid}: pool={res.pool:6s} out={len(res.output_tokens)}"
              f" prefill_iters={res.prefill_iters} queue={res.queue_iters}")
    s = rt.router.stats
    print(f"\ngateway stats: alpha_obs={s.alpha_observed:.2f} "
          f"borderline={s.borderline} compressed={s.compressed_ok} "
          f"per_pool={s.per_pool} "
          f"mean_overhead={s.mean_overhead_ms:.2f}ms/req")


if __name__ == "__main__":
    main()

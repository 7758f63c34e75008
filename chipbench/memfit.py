#!/usr/bin/env python3
"""Does a configuration fit one v5e? Compiles the served step programs
for a described (not attached) chip and sums what they hold.

    JAX_PLATFORMS=cpu python chipbench/memfit.py --config minitron-8b \
        --layers 11 --short-context 4096

For each pool (the configuration's slots; the short pool at
``--short-context`` tokens, the long one at its top context) it builds
the engine on abstract weights and KV pools, compiles the decode scan
and the mixed step at the largest prefill chunk, and prints each
program's temporaries from ``compiled.memory_analysis()``. The last
line is weights + both KV pools + the largest temporaries against the
chip's 15.75 GiB. Nothing runs and nothing is allocated at full size.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

GiB = 2 ** 30
LIMIT_GIB = 15.75


def _bytes(tree) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--short-context", type=int, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness as H
    from repro.models import model as M
    from repro.serving.config import ServingConfig
    from repro.serving.engine import InferenceEngine

    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    cfgj = H.load_config(args.config)
    cfgj["num_hidden_layers"] = args.layers
    model = H.load_model(cfgj)
    sv = cfgj["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def abstract(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = abstract(jax.eval_shape(
        lambda: model.program_params(cfgj, 0)))
    init_paged_cache = M.init_paged_cache
    M.init_paged_cache = lambda *a, **k: abstract(
        jax.eval_shape(lambda: init_paged_cache(*a, **k)))
    cfg = model.program_config(cfgj)
    weights = _bytes(params)
    print(f"{args.config}, {args.layers} layers: weights "
          f"{weights / GiB:.3f} GiB")
    kv, temps = 0, []
    try:
        for n_max, c_max in ((sv["slots"][0], args.short_context),
                             (sv["slots"][1], sv["top_context"])):
            scfg = ServingConfig(paged=True, decode_k=sv["decode_k"],
                                 decode_impl=sv["decode_impl"],
                                 block_size=sv["block_size"],
                                 num_blocks=sv["num_blocks"])
            eng = InferenceEngine(cfg, params, n_max, c_max, sv["c_chunk"],
                                  config=scfg)
            kv += _bytes(eng.cache)
            rows = spec((n_max,), jnp.int32)
            live = spec((n_max,), jnp.bool_)
            table = spec(eng.block_tables.shape, jnp.int32)
            scan = eng._decode_scan.lower(params, eng.cache, rows, table,
                                          rows, live, rows).compile()
            chunk = max(eng.buckets)
            mixed = eng._mixed.lower(
                params, eng.cache, spec((n_max, chunk), jnp.int32), table,
                rows, rows, spec((n_max, 1), jnp.int32), live).compile()
            for what, c in (("decode scan", scan),
                            (f"mixed step, {chunk}-token chunk", mixed)):
                t = c.memory_analysis().temp_size_in_bytes
                temps.append(t)
                print(f"  pool {n_max} x {c_max}: {what} temporaries "
                      f"{t / GiB:.3f} GiB")
    finally:
        M.init_paged_cache = init_paged_cache
    total = (weights + kv + max(temps)) / GiB
    print(f"weights + KV pools {kv / GiB:.3f} GiB + largest temporaries: "
          f"{total:.3f} of {LIMIT_GIB} GiB")


if __name__ == "__main__":
    main()

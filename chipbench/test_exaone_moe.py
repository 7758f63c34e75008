"""K-EXAONE-236B-A23B's model module (``models/exaone_moe.py``) at a
small size of the same shape, on the CPU: d 64, 8 query / 2 KV heads x
16, a window of 8 keys, 16-token pages; a dense layer 0, then one LLLG
period of expert layers; 16 experts scored by a sigmoid router, top 4,
x2.5, 4 held here, 1 shared. It is served through ``run_cell`` and
comes out correct while its int8 control fails; faults planted in the
served program (softmax for sigmoid, the x2.5 left out, the window one
off) are caught by the same comparison; the four expert shares with the
shared expert counted once add up to the uncut layer; a row's expert
output does not depend on its batch-mates; the expected expert work
that ``expert_cost`` counts is what the seeded router routes; and the
engine's expert-row and page counters are exact and on /metrics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import reference as R
from chipbench import weights as W
from chipbench.test_drive import bench, tiny_mix
from repro.models import layers as L
from repro.models import moe as MOE
from repro.serving.config import ServingConfig
from repro.serving.engine import InferenceEngine, ServeRequest
from repro.serving.metrics import fleet_metrics, render_prometheus
from repro.serving.pools import FleetRuntime

SEED = 2 ** 33 + 41
WINDOW = 8
CELL = {"name": "tiny.exaone", "config": "tiny", "traffic": "tiny",
        "chips": 1}


def tiny(layers: int = 5, **serving):
    """The configuration file at a small size of the same shape."""
    cfg = H.load_config("k-exaone-236b-a23b")
    cfg.update(num_hidden_layers=layers, hidden_size=64,
               num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               vocab_size=1024, num_experts=4, num_experts_per_tok=4,
               sliding_window=WINDOW, dtype="float32",
               sliding_windows=[WINDOW if t == "sliding_attention" else 0
                                for t in cfg["layer_types"]])
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["serving"] = dict(cfg["serving"], slots=[4, 2], top_context=512,
                          num_blocks=None, c_chunk=16, **serving)
    # float32 on both sides: the served tokens are the reference's own
    cfg["check"] = {"logit_gap_mean": 1e-4, "min_compared_tokens": 40}
    return cfg


@pytest.fixture(scope="module")
def model():
    return H.load_model(tiny())


def test_the_module_gives_the_contract_and_the_cut(model):
    assert all(hasattr(model, n) for n in H.MODEL_API)
    assert model.KERNELS["expert_gmm"] == "expert_gmm"
    full = H.load_config("k-exaone-236b-a23b")
    assert model.published_layers(full) == [0, 4, 5, 6, 7]
    assert model.windows(full) == [128, 128, 128, 128, 0]
    pc = model.program_config(full)
    assert (pc.dense_layers, pc.dense_d_ff, pc.layer_windows) == \
        (1, 18432, (128, 128, 128, 128, 0))
    assert (pc.moe.num_experts, pc.moe.top_k, pc.moe.experts_held,
            pc.moe.first_expert, pc.moe.scoring, pc.moe.routed_scale) == \
        (128, 8, 8, 0, "sigmoid", 2.5)
    assert (pc.d_model, pc.num_heads, pc.num_kv_heads, pc.head_dim,
            pc.vocab_size, pc.rope_theta) == (6144, 64, 8, 128, 153600,
                                              1e6)


# ------------------------------------------- served, checked, controlled
@pytest.fixture(scope="module")
def sound():
    b = bench()
    b["workloads"] = [CELL]
    return H.run_cell(CELL, SEED, 3.0, False, b,
                      cfgj=tiny(decode_impl="pallas"), mix=tiny_mix(),
                      log=lambda m: None, control=True)


def test_served_through_run_cell_is_correct(sound):
    e2e, chk = sound["e2e"], sound["check"]
    assert e2e["attempted"] == 18 and e2e["failed"] == 0
    assert chk["ok"], chk["checks"]
    assert chk["checks"]["compared_tokens"][0] >= 40
    assert {r["pool"] for r in chk["requests"]} == {"short", "long"}
    # prompts run past the window, so windowed layers drop keys
    assert max(r["prompt"] for r in chk["requests"]) > 4 * WINDOW


def test_int8_control_fails_the_check(sound):
    chk = sound["check"]
    assert chk["control_ok"] is False
    gap, limit = chk["control_checks"]["logit_gap_mean"]
    assert gap > limit


# ------------------------------------------------------ planted faults
def _serve(cfgj, model, program):
    """Serve three prompts through one paged engine built on
    ``program`` (a ModelConfig) and the module's weights; returns the
    mean logit gap of the served tokens against the reference."""
    params = model.program_params(cfgj, SEED)
    eng = InferenceEngine(program, params, 2, 128, 16,
                          config=ServingConfig(paged=True, block_size=16))
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 1000, n)]
               for n in (40, 23, 9)]
    for rid, p in enumerate(prompts):
        eng.submit(ServeRequest(rid=rid, tokens=p, max_new_tokens=12))
    res = eng.run_to_completion(500)
    ref = model.Forward(cfgj, SEED)
    gaps = []
    for rid, p in enumerate(prompts):
        out = res[rid].output_tokens
        toks, first = R.served_sequence(p, out)
        gaps.extend(R.gaps(ref.logits(toks, first), out))
    return float(np.mean(gaps))


def _windows_off(pc, d):
    return dataclasses.replace(pc, layer_windows=tuple(
        w + d if w else 0 for w in pc.layer_windows))


FAULTS = {
    "none": lambda pc: pc,
    "softmax_router": lambda pc: dataclasses.replace(
        pc, moe=dataclasses.replace(pc.moe, scoring="softmax")),
    "no_routed_scale": lambda pc: dataclasses.replace(
        pc, moe=dataclasses.replace(pc.moe, routed_scale=1.0)),
    "window_plus_one": lambda pc: _windows_off(pc, 1),
    "window_minus_one": lambda pc: _windows_off(pc, -1),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_planted_in_the_served_program_is_caught(model, fault):
    cfgj = tiny()
    gap = _serve(cfgj, model, FAULTS[fault](model.program_config(cfgj)))
    if fault == "none":
        assert gap <= cfgj["check"]["logit_gap_mean"]
    else:
        assert gap > cfgj["check"]["logit_gap_mean"]


def test_two_periods_scanned_are_served_correctly(model):
    """Nine layers: the expert layers run as a scan over two LLLG
    periods (five are one period, unrolled)."""
    cfgj = tiny(layers=9)
    assert _serve(cfgj, model, model.program_config(cfgj)) <= \
        cfgj["check"]["logit_gap_mean"]


# ------------------------------------------- the chip's share of experts
def _share(first: int, held: int):
    cfg = tiny()
    cfg.update(first_expert=first, num_experts=held)
    return cfg


def _program_moe(model, cfg, i: int):
    """Expert layer ``i``'s leaves in the program's tree, float32."""
    w = model.layer_params(cfg, W.root_key(SEED), i, jnp.float32)
    return {"router": w["router"], "w_gate": w["e_gate"],
            "w_up": w["e_up"], "w_down": w["e_down"],
            "shared": {"gate": w["s_gate"], "up": w["s_up"],
                       "down": w["s_down"]}}


def _hidden(rows: int, seed: int = 5):
    return jax.random.normal(jax.random.PRNGKey(seed), (rows, 64),
                             jnp.float32)


def test_expert_shares_add_up_to_the_uncut_layer(model):
    h = _hidden(24)
    uncut = _share(0, 16)
    w = model.layer_params(uncut, W.root_key(SEED), 2, jnp.float32)
    c = model.dims(uncut)
    shared = model._glu(h, w["s_gate"], w["s_up"], w["s_down"], False)
    want = model.experts(c, w, h, False) + shared
    total = -3 * shared
    with jax.default_matmul_precision("highest"):
        for first in (0, 4, 8, 12):
            cfg = _share(first, 4)
            total = total + MOE.moe_held(_program_moe(model, cfg, 2),
                                         model.program_config(cfg), h)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a_rows_output_does_not_depend_on_its_batch_mates(model):
    cfg = tiny()
    p, pc = _program_moe(model, cfg, 1), model.program_config(cfg)
    h = _hidden(40)
    crowd = jnp.concatenate([h, jnp.repeat(h[:1], 64, axis=0)])
    with jax.default_matmul_precision("highest"):
        all_rows = np.asarray(MOE.moe_held(p, pc, crowd))
        for i in (0, 7, 39):
            alone = np.asarray(MOE.moe_held(p, pc, h[i:i + 1]))
            np.testing.assert_allclose(all_rows[i], alone[0], rtol=1e-6,
                                       atol=1e-6)
        # 65 rows that choose the same experts: no capacity drops any
        np.testing.assert_allclose(all_rows[40:],
                                   np.repeat(all_rows[:1], 64, axis=0),
                                   rtol=1e-6, atol=1e-6)
        # rows outside ``valid`` reach no expert: the shared one only
        valid = np.arange(40) % 3 > 0
        masked = np.asarray(MOE.moe_held(p, pc, h, jnp.asarray(valid)))
        shared = np.asarray(L.mlp(p["shared"], pc, h))
    np.testing.assert_allclose(masked[valid], all_rows[:40][valid],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(masked[~valid], shared[~valid], rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("rows", [16, 1024])
def test_expert_cost_counts_what_the_seeded_router_routes(rows):
    """At the cell's widths: the held pairs and held experts chosen by
    the seeded router of an expert layer, over inputs of unit rms,
    against ``expected_pairs_and_hits``, within 5%."""
    cfg = H.load_config("k-exaone-236b-a23b")
    mod = H.load_model(cfg)
    router = mod._layer(cfg, W.root_key(SEED), 4, ("router",),
                        jnp.float32)["router"]
    calls = 8192 // rows
    h = jax.random.normal(jax.random.PRNGKey(9), (calls, rows,
                                                  cfg["hidden_size"]))
    _, idx = jax.lax.top_k(jax.nn.sigmoid(h @ router),
                           cfg["num_experts_per_tok"])
    held = np.asarray(idx) < cfg["num_experts"]
    pairs = held.sum() / calls
    hits = np.mean([len(np.unique(np.asarray(idx)[c][held[c]]))
                    for c in range(calls)])
    want_pairs, want_hits = mod.expected_pairs_and_hits(cfg, rows)
    assert pairs == pytest.approx(want_pairs, rel=0.05)
    assert hits == pytest.approx(want_hits, rel=0.05)
    flops, byts = mod.expert_cost(cfg, rows)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert flops == 6 * d * f * want_pairs
    assert byts == (want_hits * 3 * d * f + want_pairs * 2 * d) * 2


# ------------------------------------------------------------ counters
@pytest.mark.parametrize("window,lens", [(WINDOW, (30, 11, 7)),
                                         (128, (300, 150, 40))])
def test_expert_rows_and_swept_pages_are_counted_and_exported(model, window,
                                                              lens):
    """A scripted run: the counters are exact, and a windowed row's
    sweep copies at most ceil((window + 15) / 16) pages a layer, 9 at
    the cell's window of 128 keys."""
    cfgj = tiny()
    cfgj["sliding_windows"] = [w and window for w in cfgj["sliding_windows"]]
    pc = model.program_config(cfgj)
    params = model.program_params(cfgj, SEED)
    rt = FleetRuntime(pc, params, boundaries=(64,), gammas=(1.4,),
                      n_maxes=(2, 1), c_maxes=(512, 512), c_chunk=16,
                      config=ServingConfig(paged=True, decode_k=4,
                                           block_size=16,
                                           decode_impl="pallas"))
    eng = rt.engines["short"]
    for rid, n in enumerate(lens):
        eng.submit(ServeRequest(rid=rid, tokens=list(range(1, n + 1)),
                                max_new_tokens=9))
    res = eng.run_to_completion(500)
    moe_layers = cfgj["num_hidden_layers"] - 1
    rows = sum(lens) + sum(len(res[r].output_tokens) for r in res)
    assert eng.moe_rows_total == moe_layers * rows
    swept, full = [], 0
    for rid, n in enumerate(lens):
        for pos in range(n, n + len(res[rid].output_tokens)):
            end = -(-(pos + 1) // 16)
            full += end                                 # one global layer
            swept.append(end - max(pos + 1 - window, 0) // 16)
    assert max(swept) == -(-(window + 15) // 16)        # 2, or 9 at 128
    window_pages = 4 * sum(swept)                       # four windowed
    assert eng.attn_pages_swept == {"window": window_pages, "full": full}
    text = render_prometheus(fleet_metrics(rt))
    assert f'fleetopt_moe_rows_total{{pool="short"}} {moe_layers * rows}' \
        in text
    assert 'fleetopt_moe_rows_total{pool="long"} 0' in text
    assert "fleetopt_attn_pages_swept_total{kind=\"window\",pool=\"short\"}" \
        f" {window_pages}" in text
    assert "fleetopt_attn_pages_swept_total{kind=\"full\",pool=\"short\"}" \
        f" {full}" in text


# -------------------------------------------------------------- reader
def test_expert_gmm_roofline_reads_the_traced_calls():
    from chipbench import flops as F
    read = H.load_reader("expert_gmm_roofline")
    scan_passes = read.__globals__["scan_passes"]
    # three rows decode 4, 2 and 4 positions in a 4-pass scan
    assert scan_passes([10, 11, 12, 13, 50, 51, 7, 8, 9, 10], 4) == \
        [3, 3, 2, 2]
    # two rows at adjacent positions: runs are cut at k passes
    assert scan_passes([1, 2, 3, 4, 5, 6], 4) == [2, 2, 1, 1]
    cfg = H.load_config("k-exaone-236b-a23b")
    peak = H.peaks_for("TPU v5 lite")
    rec = H.Record(cfg=cfg, peak=peak, model=H.load_model(cfg))
    assert read(rec) is None
    rec.trace_t0, rec.trace_t1 = 1.0, 2.0
    rec.trace = {"kernel_s": {"expert_gmm": 0.01}}
    rec.spans = [
        {"name": "step.short", "t0": 1.1, "t1": 1.2, "kind": "mixed",
         "prefill": [(0, 64), (128, 64)], "decode": [300, 301, 302]},
        {"name": "step.short", "t0": 1.3, "t1": 1.4, "kind": "decode",
         "prefill": [], "decode": [40, 41, 42, 43, 90, 91]},
        {"name": "step.long", "t0": 2.5, "t1": 2.6, "kind": "decode",
         "prefill": [], "decode": [5]}]               # after the trace
    least = sum(4 * F.roofline_seconds(*rec.model.expert_cost(cfg, r),
                                       peak) for r in (128, 3, 2, 2, 1, 1))
    assert read(rec) == pytest.approx(100 * least / 0.01)
    dense = H.load_config("minitron-8b")
    assert read(H.Record(cfg=dense, peak=peak, model=H.load_model(dense),
                         trace=rec.trace, trace_t0=1.0, trace_t1=2.0,
                         spans=rec.spans)) is None

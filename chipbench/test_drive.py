"""One CPU drive of the client + gateway loop at a 2-layer reduced
model through the harness's inner function, the int8 control in the
program's place, and a fault planted in the timed path that the check
must catch. The command itself refuses the CPU (test_discovery)."""
import json

import pytest

from chipbench import harness as H
from chipbench import run as RUN
from chipbench import traffic as T

SEED = 2 ** 33 + 29
CELL = {"name": "tiny.agent", "config": "tiny", "traffic": "tiny",
        "chips": 1}


def tiny_cfg():
    cfg = H.load_config("minitron-8b")
    cfg.update(num_hidden_layers=2, hidden_size=256, num_attention_heads=4,
               num_key_value_heads=2, head_dim=64, intermediate_size=512,
               vocab_size=1024, dtype="float32")
    cfg["serving"].update(slots=[4, 2], top_context=512, num_blocks=None,
                          c_chunk=16, decode_impl="xla")
    # float32 on both sides: the served tokens are the reference's own
    cfg["check"] = {"logit_gap_mean": 1e-4, "min_compared_tokens": 40}
    return cfg


def tiny_mix():
    mix = T.load("agent-heavy.r1")
    # follow every request due in the window to its first token, so a
    # loaded test machine changes the timings and not the counts
    mix.update(rate_per_s=6.0, warmup_s=1.0, follow_s=60.0,
               scale=512 / 65536)
    return mix


def bench():
    b = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    b["workloads"] = [CELL]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    return b


def drive(seconds=3.0, **kw):
    return H.run_cell(CELL, SEED, seconds, False, bench(), cfgj=tiny_cfg(),
                      mix=tiny_mix(), log=lambda m: None, **kw)


@pytest.fixture(scope="module")
def sound():
    return drive(control=True)


def test_drive_serves_the_window_and_checks_correct(sound):
    e2e = sound["e2e"]
    assert e2e["attempted"] == 18 and e2e["failed"] == 0
    assert len(e2e["ttft_ms"]) == 18 and e2e["output_tok_s"] > 0
    chk = sound["check"]
    assert chk["ok"], chk["checks"]
    assert chk["checks"]["compared_tokens"][0] >= 40
    pools = {r["pool"] for r in chk["requests"]}
    assert pools == {"short", "long"}
    assert sound["routed"]["to_long"] + sound["routed"]["to_short"] \
        == sound["routed"]["total"]


def test_result_line_carries_the_contract_keys(sound):
    line = RUN.result_line(CELL, bench(), sound, False, 3.0)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in bench()["end_to_end"]}
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(line)


def test_int8_control_in_the_programs_place_fails(sound):
    chk = sound["check"]
    assert chk["control_ok"] is False
    gap, limit = chk["control_checks"]["logit_gap_mean"]
    assert gap > limit
    # the control changes only the model: every other number is the
    # program's own
    for k in ("route_faults", "compress_faults", "prompt_faults",
              "compared_tokens"):
        assert chk["control_checks"][k] == chk["checks"][k]


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    build = H.build

    def broken(cfgj, mix, seed, model):
        rt, plan = build(cfgj, mix, seed, model)
        for eng in rt.engines.values():
            orig = eng._append_token

            def append(s, tok, orig=orig, eng=eng):
                if len(eng.slot_out[s]) == 3:
                    tok = (tok + 1) % eng.cfg.vocab_size
                return orig(s, tok)
            eng._append_token = append
        return rt, plan
    monkeypatch.setattr(H, "build", broken)
    res = drive()
    assert not res["check"]["ok"]
    assert res["check"]["checks"]["logit_gap_mean"][0] > \
        res["check"]["checks"]["logit_gap_mean"][1]

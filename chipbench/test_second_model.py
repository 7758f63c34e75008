"""A second block served end to end through the harness with no edit to
it: the gated-SiLU decoder of ``test_models/`` (its module and its
configuration are the only files that name it), driven through
``run_cell`` on the CPU as ``test_drive.py`` drives the dense
squared-ReLU model. Its window checks correct, its int8 control fails
the check, and a fault planted in its served weights is caught."""
import json

from chipbench import harness as H
from chipbench.test_drive import bench, tiny_mix

SEED = 2 ** 33 + 31
HERE = H.HERE / "test_models"
CELL = {"name": "tiny.silu", "config": "dense-silu-gated",
        "traffic": "tiny", "chips": 1}


def config():
    return json.loads((HERE / "dense-silu-gated.json").read_text())


def drive(monkeypatch, **kw):
    monkeypatch.setattr(H, "MODELS", HERE)
    b = bench()
    b["workloads"] = [CELL]
    return H.run_cell(CELL, SEED, 3.0, False, b, cfgj=config(),
                      mix=tiny_mix(), log=lambda m: None, **kw)


def test_the_harness_names_no_second_model():
    # the contract is the only way in: no shared file names the module
    shared = [p for p in H.HERE.rglob("*")
              if p.is_file() and p.suffix in (".py", ".json")
              and HERE not in p.parents
              and not p.name.startswith("test_")]
    assert shared
    for p in shared:
        text = p.read_text()
        assert "dense_silu_gated" not in text and \
            "dense-silu-gated" not in text, p


def test_gated_silu_model_is_served_checked_and_controlled(monkeypatch):
    res = drive(monkeypatch, control=True)
    e2e, chk = res["e2e"], res["check"]
    assert e2e["attempted"] == 18 and e2e["failed"] == 0
    assert chk["ok"], chk["checks"]
    assert chk["checks"]["compared_tokens"][0] >= 40
    assert {r["pool"] for r in chk["requests"]} == {"short", "long"}
    assert chk["control_ok"] is False
    gap, limit = chk["control_checks"]["logit_gap_mean"]
    assert gap > limit


def test_gate_and_up_swapped_in_the_served_weights_is_caught(monkeypatch):
    load = H.load_model

    def swapped(cfgj):
        model = load(cfgj)
        make = model.program_params

        def params(cfg, seed):
            p = make(cfg, seed)
            mlp = p["layers"]["mlp"]
            mlp["gate"], mlp["up"] = mlp["up"], mlp["gate"]
            return p
        model.program_params = params
        return model
    monkeypatch.setattr(H, "load_model", swapped)
    chk = drive(monkeypatch)["check"]
    assert not chk["ok"]
    gap, limit = chk["checks"]["logit_gap_mean"]
    assert gap > limit

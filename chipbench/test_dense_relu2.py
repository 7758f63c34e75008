"""The dense squared-ReLU model module (``models/dense_relu2.py``): its
seeded weights against the program's tree, its reference against the
program's own forward pass at a small size, the int8 control, and its
operation and byte counts against hand counts."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness as H
from chipbench import reference as R
from chipbench import weights as W

SEED = 2 ** 33 + 3
M = H.load_model(H.load_config("minitron-8b"))
CFG = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
       "head_dim": 2, "intermediate_size": 16, "vocab_size": 10,
       "num_hidden_layers": 3, "dtype": "bfloat16"}


def tiny(dtype="float32", layers=2):
    cfg = H.load_config("minitron-8b")
    cfg.update(num_hidden_layers=layers, hidden_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               intermediate_size=512, vocab_size=1024, dtype=dtype)
    return cfg


def test_layers_regenerate_bit_for_bit():
    cfg = tiny()
    params = M.program_params(cfg, SEED)
    key = W.root_key(SEED)
    for i in range(cfg["num_hidden_layers"]):
        one = M.layer_params(cfg, key, i, jnp.float32)
        np.testing.assert_array_equal(params["layers"]["attn"]["wq"][i],
                                      one["wq"])
        np.testing.assert_array_equal(params["layers"]["mlp"]["down"][i],
                                      one["down"])
        np.testing.assert_array_equal(params["layers"]["ln2"][i], one["ln2"])


def test_weights_depend_on_the_whole_seed():
    cfg = tiny()
    a = M.program_params(cfg, 5)["lm_head"]
    b = M.program_params(cfg, 5 + 2 ** 32)["lm_head"]
    assert not np.array_equal(a, b)


def test_program_tree_matches_the_program_init():
    from repro.models import model as PM
    cfg = tiny()
    mine = jax.tree.structure(M.program_params(cfg, 1))
    theirs = jax.tree.structure(jax.eval_shape(
        lambda: PM.init_params(M.program_config(cfg), jax.random.key(0))))
    assert mine == theirs


def test_reference_equals_program_forward_in_float32():
    from repro.models import model as PM
    cfg = tiny()
    params = M.program_params(cfg, SEED)
    toks = np.random.default_rng(0).integers(1, 1024, 300).tolist()
    prog, _ = PM.forward(params, M.program_config(cfg),
                         {"tokens": jnp.asarray([toks])})
    ref = M.Forward(cfg, SEED).logits(toks, 0)
    prog = np.asarray(prog[0])
    assert np.max(np.abs(prog - ref)) <= 1e-4 * np.max(np.abs(ref))
    np.testing.assert_array_equal(R.gaps(ref, prog.argmax(-1)), 0.0)


def test_int8_control_moves_tokens_the_reference_would_not():
    cfg = tiny()
    toks = np.random.default_rng(1).integers(1, 1024, 400).tolist()
    ref = M.Forward(cfg, SEED).logits(toks, 0)
    ctl = M.Forward(cfg, SEED, int8=True).logits(toks, 0)
    g = R.gaps(ref, ctl.argmax(-1))
    assert g.max() > 0.0
    assert np.mean(g > 0) > 0.01


def test_linear_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, up 8x16, down 16x8
    per_layer = 64 + 32 + 32 + 64 + 128 + 128
    assert M.linear_flops_per_token(CFG) == 2 * per_layer * 3


def test_attention_and_head_by_hand():
    # scores and values: 2 * 2 * heads * head_dim * ctx, every layer
    assert M.attention_flops(CFG, 5) == 4 * 4 * 2 * 5 * 3
    assert M.head_flops_per_token(CFG) == 2 * 8 * 10
    # a token decoded at position 4 attends 5 keys and needs the head
    assert M.decode_flops(CFG, 4) == (M.linear_flops_per_token(CFG)
                                      + 480 + 160)
    # a chunk of 3 at positions 2..4 attends 3 + 4 + 5 keys, no head
    assert M.prefill_flops(CFG, 2, 3) == (3 * M.linear_flops_per_token(CFG)
                                          + 4 * 4 * 2 * 12 * 3)


def test_decode_kernel_cost_counts_live_context_only():
    flops, byts = M.decode_kernel_cost(CFG, 6)
    assert flops == 4 * 4 * 2 * 7 * 3
    # K and V of 7 tokens x 2 kv heads x 2 dims, query and output of
    # 4 heads x 2 dims, bf16, 3 layers
    assert byts == (2 * 7 * 2 * 2 + 2 * 4 * 2) * 2 * 3


def test_a_gated_or_other_activation_is_refused():
    cfg = tiny()
    for change in ({"hidden_act": "silu"}, {"mlp_gate": True}):
        bad = dict(cfg, **change)
        for make in (M.program_config, lambda c: M.Forward(c, SEED)):
            try:
                make(bad)
            except ValueError as e:
                assert "dense_relu2" in str(e)
            else:
                raise AssertionError(f"{change} was accepted")

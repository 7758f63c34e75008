"""The plain reference against the program's own forward pass at a
small size, the seeded weights, and the int8 control."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness as H
from chipbench import reference as R
from chipbench import weights as W

SEED = 2 ** 33 + 3


def tiny(dtype="float32", layers=2):
    cfg = H.load_config("minitron-8b")
    cfg.update(num_hidden_layers=layers, hidden_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=64,
               intermediate_size=512, vocab_size=1024, dtype=dtype)
    return cfg


def test_layers_regenerate_bit_for_bit():
    cfg = tiny()
    params = W.program_params(cfg, SEED)
    key = W.root_key(SEED)
    for i in range(cfg["num_hidden_layers"]):
        one = W.layer(cfg, key, i, jnp.float32)
        np.testing.assert_array_equal(params["layers"]["attn"]["wq"][i],
                                      one["wq"])
        np.testing.assert_array_equal(params["layers"]["mlp"]["down"][i],
                                      one["down"])
        np.testing.assert_array_equal(params["layers"]["ln2"][i], one["ln2"])


def test_weights_depend_on_the_whole_seed():
    cfg = tiny()
    a = W.program_params(cfg, 5)["lm_head"]
    b = W.program_params(cfg, 5 + 2 ** 32)["lm_head"]
    assert not np.array_equal(a, b)


def test_program_tree_matches_the_program_init():
    from repro.models import model as M
    cfg = tiny()
    mine = jax.tree.structure(W.program_params(cfg, 1))
    theirs = jax.tree.structure(jax.eval_shape(
        lambda: M.init_params(H.program_config(cfg), jax.random.key(0))))
    assert mine == theirs


def test_reference_equals_program_forward_in_float32():
    from repro.models import model as M
    cfg = tiny()
    params = W.program_params(cfg, SEED)
    toks = np.random.default_rng(0).integers(1, 1024, 300).tolist()
    prog, _ = M.forward(params, H.program_config(cfg),
                        {"tokens": jnp.asarray([toks])})
    ref = R.Forward(cfg, SEED).logits(toks, 0)
    prog = np.asarray(prog[0])
    assert np.max(np.abs(prog - ref)) <= 1e-4 * np.max(np.abs(ref))
    np.testing.assert_array_equal(R.gaps(ref, prog.argmax(-1)), 0.0)


def test_int8_control_moves_tokens_the_reference_would_not():
    cfg = tiny()
    toks = np.random.default_rng(1).integers(1, 1024, 400).tolist()
    ref = R.Forward(cfg, SEED).logits(toks, 0)
    ctl = R.Forward(cfg, SEED, int8=True).logits(toks, 0)
    g = R.gaps(ref, ctl.argmax(-1))
    assert g.max() > 0.0
    assert np.mean(g > 0) > 0.01


def test_served_sequence_repeats_the_last_prompt_token():
    toks, first = R.served_sequence([5, 6, 7], [8, 9, 10])
    assert toks == [5, 6, 7, 7, 8, 9] and first == 3


def text(tokens, sentence_bytes=40):
    """ASCII sentences, exactly 4 * tokens bytes."""
    n = 4 * tokens
    s = " ".join(f"Item {i} " + "w" * (sentence_bytes - 9) + "."
                 for i in range(n // sentence_bytes + 2))
    return s[: n - 1] + "."


def test_routing_rule():
    b, g = 100, 2.0
    assert R.expected_pools(text(60), 20, "code", b, g, 4) == \
        {("short", False)}
    assert R.expected_pools(text(150), 10, "rag", b, g, 4) == \
        {("short", True)}
    assert R.expected_pools(text(150), 10, "code", b, g, 4) == \
        {("long", False)}
    assert R.expected_pools(text(250), 10, "prose", b, g, 4) == \
        {("long", False)}
    # within the margin of the edge either side stands
    assert R.expected_pools(text(97), 2, "code", b, g, 4) == \
        {("short", False), ("long", False)}
    # must-keep sentences (3 first, 2 last: 50 tokens here) over the
    # budget of 100 - 60: compression fails, the request goes long
    assert R.expected_pools(text(120), 60, "rag", b, g, 4) == \
        {("long", False)}


def test_compression_faults():
    sents = [f"Item {i} alpha beta gamma delta." for i in range(10)]
    prompt = " ".join(sents)
    kept = " ".join(sents[:3] + sents[5:6] + sents[8:])
    ids = R.tokenize(kept, 1024)
    assert R.compression_faults(prompt, kept, ids, 200, 1024) == []
    assert "first three or last two sentences dropped" in \
        R.compression_faults(prompt, " ".join(sents[1:4] + sents[8:]),
                             R.tokenize(" ".join(sents[1:4] + sents[8:]),
                                        1024), 200, 1024)
    assert "served tokens are not the compressed text" in \
        R.compression_faults(prompt, kept, ids[:-1], 200, 1024)
    assert "compressed prompt over its budget" in \
        R.compression_faults(prompt, kept, ids, 10, 1024)


def test_sample_takes_the_longest_each_pool_and_a_compressed_one():
    done = {i: {"prompt_ids": [0] * (10 + i), "served": [1] * 5,
                "pool": "short", "compressed": i == 3} for i in range(20)}
    done[50] = {"prompt_ids": [0] * 5, "served": [1] * 5, "pool": "long",
                "compressed": False}
    pick = R.sample_requests(done, SEED, 40, 6)
    assert pick[0] == 19 and 50 in pick and 3 in pick
    assert R.sample_requests(done, SEED, 40, 6) == pick
